"""Device tier (`core/cache_jax.py`): set-parallel rounds of the HBM cache
lookup per window batch, from `GatherReport.access_rounds` (each probe's
largest count of ids in one cache set).  None where the program does not
count them."""


def read(rec):
    r = [getattr(s.report, "access_rounds", None) for s in rec.steps]
    if not r or None in r or not sum(r):
        return None
    return sum(r) / len(rec.steps)
