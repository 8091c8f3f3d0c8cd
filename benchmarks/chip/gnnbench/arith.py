"""The yardstick's arithmetic: chip peaks, and the operations and bytes
that a kernel call or a training step needs, computed from shapes.

Peaks are keyed by `device_kind` as JAX reports it.  A kind that is not in
the table is an error, never a default.
"""
from __future__ import annotations

from typing import Sequence

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def level_sizes(batch: int, fanouts: Sequence[int]) -> list[int]:
    """Nodes per hop level of a fixed-fanout sample: B, B*f0, B*f0*f1..."""
    out = [batch]
    for f in fanouts:
        out.append(out[-1] * f)
    return out


def sage_step_flops(batch: int, fanouts: Sequence[int], in_dim: int,
                    hidden: int, classes: int) -> float:
    """Matrix-multiply operations of one GraphSAGE-mean training step
    (forward and backward), counted 2 per multiply-add.

    Layer t maps hop levels 0..L-1-t, each through two matrices (self and
    neighbour mean).  Each matrix product y = x @ W costs 2*n*in*out
    forward, as much again for dW, and as much again for dx when x
    depends on the weights.  Layer 0's inputs are features, which take no
    gradient, so it has no dx.  Aggregation adds, activations and the
    softmax are not counted."""
    n = level_sizes(batch, fanouts)
    L = len(fanouts)
    dims = [in_dim] + [hidden] * L
    total = 0.0
    for t in range(L):
        rows = sum(n[:L - t])
        fwd = 2 * (2.0 * rows * dims[t] * dims[t + 1])
        total += fwd * (2 if t == 0 else 3)
    total += 3 * 2.0 * batch * hidden * classes     # output head
    return total


def tiered_gather_bytes(rows: int, dim: int, itemsize: int) -> float:
    """One `tiered_gather` call: each requested row read once (from the
    cache or the staged buffer) and written once.  The 8-row band the
    kernel fetches for a cache hit is not counted."""
    return 2.0 * rows * dim * itemsize


def segment_mean_cost(dst_rows: int, fanout: int, dim: int,
                      itemsize: int) -> tuple[float, float]:
    """(flops, bytes) of one `segment_mean` call over a (B, F) index:
    B*F rows read and B rows written; B*F*D adds."""
    return (float(dst_rows * fanout * dim),
            float((dst_rows * fanout + dst_rows) * dim * itemsize))


def segment_mean_calls(batch: int, fanouts: Sequence[int]
                       ) -> list[tuple[int, int]]:
    """(B, F) of the `segment_mean` calls one GraphSAGE step makes: layer
    0 aggregates every level but the last straight from the table."""
    n = level_sizes(batch, fanouts)
    return [(n[l], f) for l, f in enumerate(fanouts)]
