"""Host sampling (`core/pipeline.py:plan_next`, `sampling/neighbor.py`):
wall time of the loader's `plan_next` stage spans in the window, per
window batch, in ms."""


def read(rec):
    d = [s for name, s in rec.spans if name == "plan_next"]
    return 1e3 * sum(d) / len(rec.steps) if d else None
