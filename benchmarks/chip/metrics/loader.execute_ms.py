"""Tier fold and gather (`core/pipeline.py:execute`, `core/tiers.py`,
`core/feature_store.py`): wall time of the loader's `execute` stage spans
in the window, per window batch, in ms.  It holds host staging, the
transfers to and from the device and the wait on `device_gather`."""


def read(rec):
    d = [s for name, s in rec.spans if name == "execute"]
    return 1e3 * sum(d) / len(rec.steps) if d else None
