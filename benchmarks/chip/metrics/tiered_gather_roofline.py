"""Kernels (`kernels/tiered_gather.py`): the least time of the profiled
`tiered_gather` calls over their device time, in %.  One call per batch;
its least work is the batch's unique rows, each read once and written
once, so the padding of the table bucket counts as waste."""
from gnnbench import arith


def read(rec):
    if rec.trace is None or rec.peak is None:
        return None
    ev = rec.trace.kernel("tiered_gather")
    if len(ev) != len(rec.profiled) or not ev.total_s():
        return None
    cfg = rec.cell.config
    least = sum(arith.least_time_s(0.0, arith.tiered_gather_bytes(
        s.unique, int(cfg["feature_dim"]), int(cfg["feature_itemsize"])),
        rec.peak) for s in rec.profiled)
    return 100.0 * least / ev.total_s()
