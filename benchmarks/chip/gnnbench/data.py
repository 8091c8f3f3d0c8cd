"""A cell's inputs, each a function of one seed and nothing else: the
graph and the train split of the harness's fixed structure seed, and the
feature table, the labels and the model's initial weights of `--seed`.

The generators are the benchmark's own, so a change to the program cannot
move the data.  The edge generator is the recursive-matrix (RMAT) model of
`repro.graph.synthetic.rmat_edges`.  Edges and the feature table are made
in a fixed number of chunks, each from a spawned stream of its own, which
a pool of threads works through: the data depends on the seed alone, not
on the host's cores.  NumPy releases the interpreter lock inside these
array operations, so the threads run in parallel.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

RMAT_ABC = (0.57, 0.19, 0.19)      # quadrant probabilities a, b, c
CHUNKS = 64                        # independent streams per generated array


def threads() -> int:
    return max(1, min(12, os.cpu_count() or 1))


def streams(seed: int, purpose: str, n: int) -> list[np.random.Generator]:
    """`n` independent generators for one purpose of one seed."""
    tag = int.from_bytes(purpose.encode(), "little")
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), tag])
    return [np.random.default_rng(s) for s in ss.spawn(n)]


def word(seed: int, purpose: str) -> int:
    """A 31-bit seed for an API that takes a small integer."""
    return int(streams(seed, purpose, 1)[0].integers(0, 2**31 - 1))


def _chunks(n: int) -> list[tuple[int, int]]:
    bounds = np.linspace(0, n, CHUNKS + 1).astype(np.int64)
    return [(int(a), int(b)) for a, b in zip(bounds[:-1], bounds[1:])]


def _each_chunk(fn, n: int, seed: int, purpose: str) -> list:
    """`fn(rng, lo, hi)` over the CHUNKS chunks of `range(n)`, each with a
    stream of its own, in a pool of threads; results in chunk order."""
    parts = _chunks(n)
    rngs = streams(seed, purpose, CHUNKS)
    with ThreadPoolExecutor(threads()) as ex:
        return list(ex.map(lambda i: fn(rngs[i], *parts[i]), range(CHUNKS)))


def rmat_keys(num_nodes: int, num_edges: int, seed: int) -> np.ndarray:
    """`num_edges` distinct RMAT edges without self-loops, as the sorted
    keys src * num_nodes + dst.  Edges are drawn in rounds until that many
    distinct ones are found; a seeded draw drops the surplus."""
    a, b, c = RMAT_ABC
    scale = int(np.ceil(np.log2(max(num_nodes, 2))))

    def block(rng, lo, hi):
        n = hi - lo
        src = np.zeros(n, np.int64)
        dst = np.zeros(n, np.int64)
        for _ in range(scale):
            r = rng.random(n)
            src <<= 1
            dst <<= 1
            src |= r >= a + b
            dst |= ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src %= num_nodes
        dst %= num_nodes
        keep = src != dst
        return src[keep] * num_nodes + dst[keep]

    have = np.empty(0, np.int64)       # distinct keys, sorted
    rnd = 0
    while len(have) < num_edges:
        more = int((num_edges - len(have)) * 1.08) + 1024
        have = np.unique(np.concatenate(
            [have] + _each_chunk(block, more, seed, f"rmat/{rnd}")))
        rnd += 1
    extra = streams(seed, "rmat/drop", 1)[0].choice(
        len(have), len(have) - num_edges, replace=False)
    return np.delete(have, extra)


def feature_table(num_nodes: int, dim: int, seed: int) -> np.ndarray:
    """(num_nodes, dim) float32, uniform in [-1, 1)."""
    out = np.empty((num_nodes, dim), np.float32)

    def fill(rng, lo, hi) -> None:
        block = out[lo:hi]
        rng.random(out=block, dtype=np.float32)
        block *= 2
        block -= 1

    _each_chunk(fill, num_nodes, seed, "features")
    return out


def labels(num_nodes: int, num_classes: int, seed: int) -> np.ndarray:
    return streams(seed, "labels", 1)[0].integers(
        0, num_classes, num_nodes).astype(np.int32)


def train_split(num_nodes: int, fraction: float, seed: int) -> np.ndarray:
    n = max(1, round(num_nodes * fraction))
    return np.sort(streams(seed, "split", 1)[0].permutation(num_nodes)[:n])


def init_params(seed: int, in_dim: int, hidden: int, classes: int,
                layers: int) -> dict:
    """GraphSAGE weights on the device, in one jitted call: LeCun-normal
    matrices and zero biases, in float32, the type they are trained in.
    The tree has the layout the program's `GNN` uses."""
    import jax
    import jax.numpy as jnp

    dims = [in_dim] + [hidden] * layers
    shapes = {}
    for l in range(layers):
        shapes[f"layer{l}"] = {"w_self": (dims[l], dims[l + 1]),
                               "w_nbr": (dims[l], dims[l + 1]),
                               "b": (dims[l + 1],)}
    shapes["head"] = {"w": (hidden, classes), "b": (classes,)}
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        vals = [jnp.zeros(s, jnp.float32) if len(s) == 1 else
                jax.random.normal(k, s, jnp.float32) / np.sqrt(s[0])
                for k, s in zip(keys, flat)]
        return jax.tree.unflatten(tree, vals)

    return make(jax.random.PRNGKey(word(seed, "params")))
