"""Device-resident GIDS feature tier: the fully-jittable composition of

    cache_jax (window-buffered cache metadata, HBM)      §3.4
  + an HBM row store (the BaM software cache's data)
  + the tiered_gather Pallas kernel (slot-indirect row DMA)

One `device_gather` call = lookup/fill metadata -> write missed rows from
the host-staged buffer into their assigned lines -> gather every requested
row from (cache | staged).  This is the TPU rendering of the paper's
GPU-thread gather loop: it fuses into the surrounding step, so cache
maintenance costs no host round-trip.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache_jax
from repro.kernels import ops


class DeviceStore(NamedTuple):
    cache: cache_jax.CacheState
    rows: jnp.ndarray               # (num_lines, D) HBM row storage


def bucket_rows(x: np.ndarray, fill=0) -> np.ndarray:
    """`x` padded with `fill` rows to a power-of-two row count (at least 8),
    so a jitted device call compiles once per bucket rather than once per
    batch: the ids of a device-store probe (padded with -1), a batch's
    feature table (padded with zero rows no hop index reaches)."""
    n = len(x)
    size = max(8, 1 << (n - 1).bit_length())
    if size == n:
        return x
    out = np.full((size,) + x.shape[1:], fill, x.dtype)
    out[:n] = x
    return out


def init_store(num_lines: int, dim: int, ways: int = 8,
               dtype=jnp.float32) -> DeviceStore:
    return DeviceStore(cache=cache_jax.init_cache(num_lines, ways),
                       rows=jnp.zeros((num_lines, dim), dtype))


def _fills(hits: jnp.ndarray, slots: jnp.ndarray, ids: jnp.ndarray,
           num_lines: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Row-store writes of one batch, from `cache_jax.access`'s per-set
    ordered outcome.  Returns (fill_slots, refilled):

    - fill_slots[i]: the line request i's staged row is written to, or
      `num_lines` (dropped).  A line filled twice keeps only its LAST
      filler, the tag the metadata holds: scatter leaves the winner among
      duplicate indices unspecified (TPU), so every kept slot is unique.
    - refilled[i]: request i hit a line an earlier request of this batch
      filled.  The hit id is then that filler's id, so its staged row is
      the line's content."""
    order = jnp.arange(ids.shape[0], dtype=jnp.int32)
    fill = jnp.where(~hits & (slots >= 0) & (ids >= 0), slots, num_lines)
    first = jnp.full(num_lines + 1, ids.shape[0], jnp.int32
                     ).at[fill].min(order)
    last = jnp.full(num_lines + 1, -1, jnp.int32).at[fill].max(order)
    fill_slots = jnp.where(last[fill] == order, fill, num_lines)
    refilled = hits & (first[jnp.where(hits, slots, num_lines)] < order)
    return fill_slots, refilled


@functools.partial(jax.jit, static_argnames=("use_pallas",),
                   donate_argnames=("store",))
def device_gather(store: DeviceStore, ids: jnp.ndarray,
                  staged: jnp.ndarray, future_counts: jnp.ndarray,
                  use_pallas: bool = True):
    """ids: (B,) node ids (-1 pad), repeats allowed; staged: (B, D) the
    host rows of `ids`, read for misses and for hits on lines this batch
    filled; future_counts: window-buffer reuse counts.

    Returns (new_store, rows (B, D), hit_mask).  `store` is donated: the
    fill writes the row store in place instead of copying it, so the
    caller must use the returned store from here on."""
    state, hits, slots = cache_jax.access(store.cache, ids, future_counts)
    fill_slots, refilled = _fills(hits, slots, ids, store.rows.shape[0])
    # serve: a hit reads the row store as it stood before this batch's
    # fills (its line may be evicted by a later fill), unless an earlier
    # request of the batch filled that line; misses come from staging
    gather_slots = jnp.where(hits & ~refilled, slots, -1)
    out = ops.tiered_gather(gather_slots, store.rows, staged,
                            use_pallas=use_pallas)
    rows_store = store.rows.at[fill_slots].set(
        staged.astype(store.rows.dtype), mode="drop")   # OOB -> dropped
    return DeviceStore(cache=state, rows=rows_store), out, hits


push_window = cache_jax.push_window       # re-export: same metadata
count_in_window = cache_jax.count_in_window
