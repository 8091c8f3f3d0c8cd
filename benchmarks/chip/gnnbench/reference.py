"""The plain reference: GraphSAGE-mean over a sampled batch in plain
`jax.numpy`, its cross-entropy loss, gradient and SGD step.  It imports
nothing of the program.

    h_dst' = relu(h_dst @ W_self + mean_{n in N(dst)} h_n @ W_nbr + b)

Inputs are node ids (seeds, then each hop's sampled neighbours in
destination order) and the benchmark's own host feature table.  The
default variant computes in float32 under `highest` matmul precision.
Two further variants stand in for the program to calibrate the limits:
`bf16` computes everything in bfloat16 (the control), and `half_batch`
takes the loss's mean over the first half of the seeds (a fault).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("reference", "bf16", "half_batch")


def batch_inputs(feats: np.ndarray, seeds: np.ndarray, hops) -> tuple:
    """(table, levels): the unique rows of this batch, padded to a power
    of two, and per level the row of each node in that table."""
    ids = np.concatenate([np.asarray(seeds, np.int64)]
                         + [np.asarray(h, np.int64) for h in hops])
    uniq, inv = np.unique(ids, return_inverse=True)
    size = 1 << max(3, (len(uniq) - 1).bit_length())
    table = np.zeros((size, feats.shape[1]), feats.dtype)
    table[:len(uniq)] = feats[uniq]
    levels, at = [], 0
    for n in [len(seeds)] + [len(h) for h in hops]:
        levels.append(inv[at:at + n].astype(np.int32))
        at += n
    return table, levels


def _forward(params, table, levels, fanouts, dtype):
    p = jax.tree.map(lambda x: x.astype(dtype), params)
    table = table.astype(dtype)
    L = len(fanouts)
    h = [table[idx] for idx in levels]
    for t in range(L):
        w = p[f"layer{t}"]
        nxt = []
        for lvl in range(L - t):
            n = h[lvl].shape[0]
            nbr = h[lvl + 1].reshape(n, fanouts[lvl], -1).mean(axis=1)
            nxt.append(jax.nn.relu(h[lvl] @ w["w_self"] + nbr @ w["w_nbr"]
                                   + w["b"]))
        h = nxt
    return h[0] @ p["head"]["w"] + p["head"]["b"]


def _loss(params, table, levels, labels, *, fanouts, dtype, half):
    logits = _forward(params, table, levels, fanouts, dtype)
    per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    if half:
        per = per[: per.shape[0] // 2]
    return jnp.mean(per)


@functools.lru_cache(maxsize=None)
def _step_fn(fanouts: tuple, variant: str):
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    half = variant == "half_batch"

    loss_fn = functools.partial(_loss, fanouts=fanouts, dtype=dtype,
                                half=half)

    @jax.jit
    def step(params, table, levels, labels, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, table, levels,
                                                  labels)
        # the state is kept in `dtype`: bfloat16 parameters lose updates
        # below their rounding, as a bfloat16 optimizer state would
        new = jax.tree.map(
            lambda p, g: (p.astype(dtype) - lr.astype(dtype) * g.astype(dtype)
                          ).astype(jnp.float32), params, grads)
        return new, loss.astype(jnp.float32)

    return step


def follow(params0, batches, fanouts, lr: float, variant: str = "reference"):
    """Run the steps of `batches` [(table, levels, labels)] from `params0`.
    Returns (losses, params after each step)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    step = _step_fn(tuple(fanouts), variant)
    prec = "highest" if variant != "bf16" else "default"
    params, losses, states = params0, [], []
    with jax.default_matmul_precision(prec):
        for table, levels, labels in batches:
            params, loss = step(params, jnp.asarray(table),
                                [jnp.asarray(l) for l in levels],
                                jnp.asarray(labels), jnp.float32(lr))
            losses.append(float(loss))
            states.append(params)
    return losses, states
