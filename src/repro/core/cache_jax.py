"""Jittable (device-resident) twin of the window-buffered software cache.

TPU adaptation: on the GPU, BaM cache metadata lives in device memory and is
mutated by thousands of threads; on TPU the idiomatic equivalent is cache
metadata as jit-carried state (tags / reuse / slot arrays in HBM) updated by
a compiled step function, so cache maintenance fuses into the input pipeline
step and never round-trips to the host.

Set-parallel updates.  The cache semantics are those of a loop that takes
a batch's ids one at a time, in batch order.  But an id reads and writes
only the set it hashes to, so ids of different sets never interact: the
result depends only on the order of the ids *within* each set.  `access`
therefore runs in rounds.  Round r applies the per-id step, in every set
at once, to that set's r-th id in batch order (at most one id per set, so
the round's writes never collide).  Each set sees its ids in the loop's
order and ends in the loop's state; hit mask and slots are each id's own
outcome; the counters are sums.  So tags, reuse, counters, hit mask and
slots equal the loop's bit for bit, in as many rounds as the batch's
largest count of ids in one set (`CacheState.rounds`: about a dozen for
65,536 random ids over 32,768 sets; as many as the ids only if all share a
set).  `push_window` never changes tags, so its bumps commute: one compare
and one scatter-add.

Semantics match `software_cache.WindowBufferedCache(evict="first")` exactly
(property-tested).  Padding node id = -1 (ignored).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

_HASH_MULT = 0x9E3779B9  # 32-bit Fibonacci hash, matches the numpy twin


class CacheState(NamedTuple):
    tags: jnp.ndarray    # (num_sets, ways) int32 node id, -1 = empty
    reuse: jnp.ndarray   # (num_sets, ways) int32 future-reuse counter
    slots: jnp.ndarray   # (num_sets, ways) int32 backing-row index in the
                         # HBM feature cache (constant layout: set*ways+way)
    hits: jnp.ndarray    # () int64 running counters
    misses: jnp.ndarray  # ()
    bypasses: jnp.ndarray  # ()
    rounds: jnp.ndarray  # () int32 set-parallel rounds of the last `access`


def init_cache(num_lines: int, ways: int = 8) -> CacheState:
    assert num_lines % ways == 0
    num_sets = num_lines // ways
    return CacheState(
        tags=jnp.full((num_sets, ways), -1, jnp.int32),
        reuse=jnp.zeros((num_sets, ways), jnp.int32),
        slots=jnp.arange(num_lines, dtype=jnp.int32).reshape(num_sets, ways),
        hits=jnp.zeros((), jnp.int64),
        misses=jnp.zeros((), jnp.int64),
        bypasses=jnp.zeros((), jnp.int64),
        rounds=jnp.zeros((), jnp.int32),
    )


def _set_of(ids: jnp.ndarray, num_sets: int) -> jnp.ndarray:
    h = (ids.astype(jnp.uint32) * jnp.uint32(_HASH_MULT)) >> jnp.uint32(8)
    return (h % jnp.uint32(num_sets)).astype(jnp.int32)


@jax.jit
def push_window(state: CacheState, nodes: jnp.ndarray) -> CacheState:
    """Bump reuse counters for cached lines appearing in a future batch.
    `nodes` is deduplicated, padded with -1; a repeated node bumps its line
    once per occurrence."""
    sets = _set_of(nodes, state.tags.shape[0])
    match = (state.tags[sets] == nodes[:, None]) & (nodes >= 0)[:, None]
    return state._replace(
        reuse=state.reuse.at[sets].add(match.astype(state.reuse.dtype)))


def _round(tags, reuse, slots, n, fc):
    """One request per set, every set at once: the per-request step of the
    sequential cache, on `(ways, num_sets)` metadata.  `n` holds each set's
    request (-1 where a set has none this round), `fc` its future count.
    Returns the updated tags and reuse, the hit, slot (-1 unless hit or
    filled) and bypass of each set's request."""
    valid = n >= 0
    match = tags == n
    is_hit = valid & match.any(0)
    empty, safe = tags == -1, reuse == 0
    has_empty = empty.any(0)
    room = has_empty | safe.any(0)
    # fill path: first empty way, else first safe (reuse == 0) way
    way = jnp.where(is_hit, jnp.argmax(match, 0),
                    jnp.where(has_empty, jnp.argmax(empty, 0),
                              jnp.argmax(safe, 0)))
    can_fill = valid & ~is_hit & room
    at = jnp.arange(tags.shape[0], dtype=jnp.int32)[:, None] == way
    tags = jnp.where(at & can_fill, n, tags)
    # a hit consumes one reservation; a fill sets the window's count
    reuse = jnp.where(at & is_hit, jnp.maximum(reuse - 1, 0),
                      jnp.where(at & can_fill, fc, reuse))
    slot = jnp.where(is_hit | can_fill,
                     jnp.sum(jnp.where(at, slots, 0), axis=0), -1)
    return tags, reuse, is_hit, slot, valid & ~is_hit & ~room


@jax.jit
def access(state: CacheState, nodes: jnp.ndarray,
           future_counts: jnp.ndarray) -> tuple[CacheState, jnp.ndarray,
                                                jnp.ndarray]:
    """Lookup + fill for the current batch (already popped off the window).

    future_counts[i] = occurrences of nodes[i] in the remaining window
    (computed by the host pipeline or by `count_in_window`).  Returns
    (new_state, hit_mask, slot_or_minus1) where slot is the backing row in
    the HBM feature cache (for hits and successful fills).
    """
    num_sets, _ = state.tags.shape
    B = nodes.shape[0]
    # the batch grouped by set, batch order kept within a set; padding last
    key = jnp.where(nodes >= 0, _set_of(nodes, num_sets), num_sets)
    key, by_set, fc_by_set, order = jax.lax.sort(
        (key, nodes, future_counts, jnp.arange(B, dtype=jnp.int32)),
        num_keys=1, is_stable=True)
    count = jnp.zeros(num_sets, jnp.int32).at[key].add(1, mode="drop")
    first = jnp.cumsum(count) - count
    slots_t = state.slots.T

    def body(carry):
        r, tags, reuse, hits, misses, bypasses, hit_by_set, slot_by_set = carry
        pos = jnp.where(r < count, first + r, B)    # B: no request (dropped)
        n = jnp.take(by_set, pos, mode="fill", fill_value=-1)
        fc = jnp.take(fc_by_set, pos, mode="fill", fill_value=0)
        tags, reuse, is_hit, slot, bypass = _round(tags, reuse, slots_t, n, fc)
        hits += is_hit.sum(dtype=hits.dtype)
        misses += ((n >= 0) & ~is_hit).sum(dtype=misses.dtype)
        bypasses += bypass.sum(dtype=bypasses.dtype)
        hit_by_set = hit_by_set.at[pos].set(is_hit, mode="drop")
        slot_by_set = slot_by_set.at[pos].set(slot, mode="drop")
        return (r + 1, tags, reuse, hits, misses, bypasses, hit_by_set,
                slot_by_set)

    rounds = jnp.max(count)
    init = (jnp.zeros((), jnp.int32), state.tags.T, state.reuse.T,
            state.hits, state.misses, state.bypasses,
            jnp.zeros(B, bool), jnp.full(B, -1, jnp.int32))
    _, tags, reuse, hits, misses, bypasses, hit_by_set, slot_by_set = \
        jax.lax.while_loop(lambda c: c[0] < rounds, body, init)
    hit_mask = jnp.zeros(B, bool).at[order].set(hit_by_set,
                                                unique_indices=True)
    slot_out = jnp.full(B, -1, jnp.int32).at[order].set(slot_by_set,
                                                       unique_indices=True)
    new_state = state._replace(tags=tags.T, reuse=reuse.T, hits=hits,
                               misses=misses, bypasses=bypasses,
                               rounds=rounds)
    return new_state, hit_mask, slot_out


@jax.jit
def count_in_window(nodes: jnp.ndarray, window: jnp.ndarray) -> jnp.ndarray:
    """future_counts[i] = #occurrences of nodes[i] in `window` (W, B) of
    future batches (padded with -1)."""
    flat = window.reshape(-1)
    eq = nodes[:, None] == flat[None, :]
    eq &= (nodes >= 0)[:, None]
    return eq.sum(axis=1).astype(jnp.int32)
