"""GNN step (`models/gnn.py:sgd_step`): device time of the jitted
`sgd_step` program per profiled step, in ms, from the trace."""


def read(rec):
    if rec.trace is None:
        return None
    ev = rec.trace.program("sgd_step")
    return 1e3 * ev.total_s() / len(rec.profiled) if len(ev) else None
