"""What decides `correct`: the numbers compared with the plain reference,
each against a limit of its own from the cell's limits file.

- `feature_rows_wrong`: delivered feature rows that differ from the host
  table's row for that node, over the checked steps and a sample of the
  window's batches drawn from the seed.  Exact: limit 0.
- `sampled_edges_invalid`: sampled (destination, neighbour) pairs that are
  not an edge of the generated graph (a node without edges samples
  itself), seeds outside the train split or repeated, hop lengths that do
  not match the fanouts, and nodes missing from or added to the batch's
  node set.  Exact: limit 0.
- `window_losses_nonfinite`: window steps whose loss is not finite.
  Exact: limit 0.
- `loss_gap`: the largest relative gap between the program's loss and
  the reference's over the checked steps.
- `grad_norm_gap`: the worst leaf's gap between the norms of the first
  gradient, the program's worked out from its state after one step as
  (p0 - p1) / lr, measured against the reference's norm of that leaf or
  of the median leaf, whichever is larger.
- `update_norm_gap`: the same for the parameters' change over the checked
  steps, p3 - p0.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both norm gaps: they move by round-off alone.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

EXACT = ("feature_rows_wrong", "sampled_edges_invalid",
         "window_losses_nonfinite")
CALIBRATED = ("loss_gap", "grad_norm_gap", "update_norm_gap")
SMALL_LEAF = 1e-3


def feature_rows_wrong(feats: np.ndarray, all_nodes: np.ndarray,
                       delivered: np.ndarray) -> int:
    want = feats[np.asarray(all_nodes, np.int64)]
    if delivered.shape != want.shape:
        return len(all_nodes)
    return int((delivered != want).any(axis=1).sum())


class EdgeSet:
    """The generated graph's distinct edges, as sorted keys src * N + dst."""

    def __init__(self, keys: np.ndarray, num_nodes: int):
        """`keys`: sorted, distinct."""
        self.n = int(num_nodes)
        self.keys = keys
        self.has_out = np.zeros(self.n, bool)
        self.has_out[self.keys // self.n] = True

    def endpoints(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) of the distinct edges, sorted by source."""
        return self.keys // self.n, self.keys % self.n

    def contains(self, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
        k = src.astype(np.int64) * self.n + dst.astype(np.int64)
        pos = np.searchsorted(self.keys, k)
        pos = np.minimum(pos, len(self.keys) - 1)
        return self.keys[pos] == k

    @classmethod
    def of_pairs(cls, src: np.ndarray, dst: np.ndarray, num_nodes: int
                 ) -> "EdgeSet":
        return cls(np.unique(src.astype(np.int64) * num_nodes
                             + dst.astype(np.int64)), num_nodes)


def sampled_edges_invalid(edges: EdgeSet, train_ids: np.ndarray,
                          batch: int, fanouts, seeds, hops,
                          all_nodes) -> int:
    bad = 0
    seeds = np.asarray(seeds, np.int64)
    bad += abs(len(seeds) - batch)
    bad += int((~np.isin(seeds, train_ids)).sum())
    bad += len(seeds) - len(np.unique(seeds))
    if len(hops) != len(fanouts):
        return bad + abs(len(hops) - len(fanouts)) * batch
    frontier = seeds
    for f, nbr in zip(fanouts, hops):
        nbr = np.asarray(nbr, np.int64)
        if nbr.shape != (len(frontier) * f,):
            return bad + len(frontier) * f
        dst = np.repeat(frontier, f)
        ok = np.where(edges.has_out[dst], edges.contains(dst, nbr),
                      nbr == dst)
        bad += int((~ok).sum())
        frontier = nbr
    want = np.unique(np.concatenate([seeds, *[np.asarray(h, np.int64)
                                              for h in hops]]))
    got = np.asarray(all_nodes, np.int64)
    bad += len(np.setxor1d(want, got))
    return bad


def _leaf_norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(np.linalg.norm(
        np.asarray(v, np.float64))) for k, v in flat}


def _diff(a, b, scale=1.0):
    return jax.tree.map(lambda x, y: (np.asarray(x, np.float64)
                                      - np.asarray(y, np.float64)) * scale,
                        a, b)


def norm_gap(prog: dict, ref: dict, moving: set) -> float:
    """Worst leaf's |‖prog‖ - ‖ref‖| over max(‖ref leaf‖, median ‖ref‖)."""
    med = float(np.median([ref[k] for k in moving]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in moving)


def trajectory_gaps(p0, prog_losses, prog_states, ref_losses, ref_states,
                    lr: float) -> dict:
    """The three calibrated numbers of one trajectory (the program's, or
    a variant's in its place) against the reference's.  States are the
    parameters after each step; steps beyond the third are ignored."""
    n = min(3, len(ref_losses))
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog_losses[:n],
                                                   ref_losses[:n]))
    g_ref = _leaf_norms(_diff(p0, ref_states[0], 1 / lr))
    g_prog = _leaf_norms(_diff(p0, prog_states[0], 1 / lr))
    med = float(np.median(list(g_ref.values())))
    moving = {k for k, v in g_ref.items() if v >= SMALL_LEAF * med}
    d_ref = _leaf_norms(_diff(ref_states[n - 1], p0))
    d_prog = _leaf_norms(_diff(prog_states[n - 1], p0))
    return {"loss_gap": loss,
            "grad_norm_gap": norm_gap(g_prog, g_ref, moving),
            "update_norm_gap": norm_gap(d_prog, d_ref, moving)}


def load_limits(path: Path) -> dict:
    limits = json.loads(Path(path).read_text())["limits"]
    missing = [k for k in EXACT + CALIBRATED if k not in limits]
    if missing:
        raise KeyError(f"{path}: no limit for {missing}")
    return limits


def judge(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}); a number passes at or under its
    limit, and a missing or non-finite number fails."""
    out, ok = {}, True
    for name in EXACT + CALIBRATED:
        v = readings.get(name)
        good = v is not None and np.isfinite(v) and v <= limits[name]
        ok &= bool(good)
        out[name] = {"value": v, "limit": limits[name]}
    return ok, out
