"""Device tier (`core/device_store.py`, `core/cache_jax.py`): device time
of the `device_gather` and `push_window` programs per profiled batch, in
ms, from the trace."""

PROGRAMS = ("device_gather", "push_window")


def read(rec):
    if rec.trace is None:
        return None
    ev = [rec.trace.program(fn) for fn in PROGRAMS]
    if not len(ev[0]):
        return None
    return 1e3 * sum(e.total_s() for e in ev) / len(rec.profiled)
