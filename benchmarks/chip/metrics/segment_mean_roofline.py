"""Kernels (`kernels/segment_mean.py`): the least time of the profiled
`segment_mean` calls over their device time, in %.  Each step makes one
call per hop level that layer 0 aggregates from the table."""
from gnnbench import arith


def read(rec):
    if rec.trace is None or rec.peak is None:
        return None
    ev = rec.trace.kernel("segment_mean")
    calls = arith.segment_mean_calls(rec.batch_size, rec.fanouts)
    if len(ev) != len(calls) * len(rec.profiled) or not ev.total_s():
        return None
    cfg = rec.cell.config
    per_step = sum(arith.least_time_s(*arith.segment_mean_cost(
        b, f, int(cfg["feature_dim"]), int(cfg["feature_itemsize"])),
        rec.peak) for b, f in calls)
    return 100.0 * per_step * len(rec.profiled) / ev.total_s()
