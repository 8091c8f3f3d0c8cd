"""Pricing (`core/pipeline.py:execute`): wall time of the loader's `price`
spans in the window, per window batch, in ms: the accumulator's update,
the storage model's price of the batch, the feedback controllers' tick
and, with tracing on, the priced span tree."""


def read(rec):
    d = [s for name, s in rec.spans if name == "price"]
    return 1e3 * sum(d) / len(rec.steps) if d else None
