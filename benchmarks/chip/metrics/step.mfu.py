"""GNN step: the whole step's share of the chip's bf16 peak, in %: the
GraphSAGE training step's matrix-multiply operations
(`arith.sage_step_flops`) times the window's steps, over the window's
host-clock length and the peak."""
from gnnbench import arith


def read(rec):
    if rec.peak is None or not rec.steps:
        return None
    cfg = rec.cell.config
    flops = arith.sage_step_flops(rec.batch_size, rec.fanouts,
                                  int(cfg["feature_dim"]),
                                  int(cfg["hidden_dim"]),
                                  int(cfg["num_classes"]))
    return 100.0 * flops * len(rec.steps) / rec.window_s \
        / rec.peak["bf16_flops"]
