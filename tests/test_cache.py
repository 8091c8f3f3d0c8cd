"""Window-buffered software cache: paper §3.4 semantics + invariants +
numpy/JAX twin agreement (property-based)."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.software_cache import WindowBufferedCache, run_trace


def zipf_trace(n_batches, batch, n_nodes, seed=0, a=1.3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        ids = rng.zipf(a, size=batch * 4) % n_nodes
        out.append(np.unique(ids)[:batch])
    return out


def test_stats_invariants():
    cache = WindowBufferedCache(256, ways=4, window_depth=4)
    trace = zipf_trace(30, 64, 2000)
    stats = run_trace(cache, trace)
    assert stats.hits + stats.misses == stats.accesses
    assert stats.fills <= stats.misses
    assert stats.fills + stats.bypasses == stats.misses
    assert 0.0 <= stats.hit_ratio <= 1.0


def test_window_buffering_beats_random_eviction():
    """Fig. 11: deeper windows raise the hit ratio on a skewed trace."""
    trace = zipf_trace(60, 128, 4000, seed=3)
    ratios = []
    for depth in (0, 4, 8):
        cache = WindowBufferedCache(512, ways=4, window_depth=depth, seed=7)
        ratios.append(run_trace(cache, trace).hit_ratio)
    assert ratios[1] >= ratios[0]
    assert ratios[2] >= ratios[0]
    assert ratios[2] > ratios[0] + 0.01  # depth 8 is materially better


def test_pinned_lines_never_evicted():
    """A line with positive future-reuse counter must survive until its
    reuses are consumed (the USE state of Fig. 6)."""
    cache = WindowBufferedCache(8, ways=2, window_depth=2, seed=0)
    hot = np.array([1])
    cold_batches = [np.array([9, 17, 25, 33]), np.array([41, 49, 57, 65])]
    cache.push_window(hot)       # future batch containing node 1
    cache.push_window(cold_batches[0])
    cache.access(np.array([1]))  # miss -> fill; window shows no future reuse
    # reinsert with future reuse: push window with node 1 again
    cache.push_window(hot)
    sets = cache.tags == 1
    assert sets.any()
    assert cache.reuse[sets][0] >= 1
    # storm of conflicting fills cannot evict node 1's line
    for b in cold_batches:
        cache.access(b)
        cache.push_window(b + 100)
    assert (cache.tags == 1).any(), "pinned line was evicted"


def test_window_zero_is_bam_baseline():
    cache = WindowBufferedCache(64, ways=4, window_depth=0)
    cache.access(np.array([1, 2, 3]))
    assert (cache.reuse == 0).all()
    assert len(cache.window) == 0


@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_numpy_jax_twins_agree(seed, depth):
    """The jittable cache (first-safe eviction) matches the numpy
    reference step for step on random traces."""
    import jax.numpy as jnp
    from repro.core import cache_jax

    rng = np.random.default_rng(seed)
    trace = [np.unique(rng.integers(0, 300, 24)) for _ in range(8)]
    B = max(len(b) for b in trace)
    npc = WindowBufferedCache(32, ways=4, window_depth=depth, evict="first")
    jc = cache_jax.init_cache(32, ways=4)

    W = depth
    window: list = []
    for b in trace[:W]:
        npc.push_window(b)
        pad = np.full(B, -1, np.int64)
        pad[:len(b)] = b
        jc = cache_jax.push_window(jc, jnp.asarray(pad, jnp.int32))
        window.append(pad)
    for i, b in enumerate(trace):
        pad = np.full(B, -1, np.int64)
        pad[:len(b)] = b
        if window:
            window.pop(0)
        rest = (np.stack(window) if window
                else np.full((1, B), -1, np.int64))
        fc = cache_jax.count_in_window(jnp.asarray(pad, jnp.int32),
                                       jnp.asarray(rest, jnp.int32))
        hits_np = npc.access(b)
        jc, hits_j, _ = cache_jax.access(jc, jnp.asarray(pad, jnp.int32),
                                         fc)
        np.testing.assert_array_equal(hits_np, np.asarray(hits_j)[:len(b)])
        nxt = i + W
        if W > 0 and nxt < len(trace):
            nb = trace[nxt]
            npc.push_window(nb)
            padn = np.full(B, -1, np.int64)
            padn[:len(nb)] = nb
            jc = cache_jax.push_window(jc, jnp.asarray(padn, jnp.int32))
            window.append(padn)
    assert int(jc.hits) == npc.stats.hits
    assert int(jc.misses) == npc.stats.misses
    np.testing.assert_array_equal(
        np.sort(np.asarray(jc.tags).ravel()),
        np.sort(npc.tags.ravel()).astype(np.int32))


def _access_sequential(state, nodes, future_counts):
    """The one-id-at-a-time cache step that `cache_jax.access` replaces:
    the oracle its set-parallel rounds must equal bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.core import cache_jax

    sets = cache_jax._set_of(nodes, state.tags.shape[0])

    def body(i, carry):
        tags, reuse, hits, misses, bypasses, hit_mask, slot_out = carry
        n, s, fc = nodes[i], sets[i], future_counts[i]
        valid = n >= 0
        row_tags, row_reuse = tags[s], reuse[s]
        match = row_tags == n
        is_hit = valid & jnp.any(match)
        way_hit = jnp.argmax(match)
        new_reuse_hit = row_reuse.at[way_hit].set(
            jnp.maximum(row_reuse[way_hit] - 1, 0))
        empty, safe = row_tags == -1, row_reuse == 0
        has_empty, has_safe = jnp.any(empty), jnp.any(safe)
        way_fill = jnp.where(has_empty, jnp.argmax(empty), jnp.argmax(safe))
        can_fill = valid & ~is_hit & (has_empty | has_safe)
        row_tags2 = jnp.where(can_fill, row_tags.at[way_fill].set(n),
                              row_tags)
        row_reuse2 = jnp.where(is_hit, new_reuse_hit,
                               jnp.where(can_fill,
                                         row_reuse.at[way_fill].set(fc),
                                         row_reuse))
        tags = tags.at[s].set(jnp.where(valid, row_tags2, row_tags))
        reuse = reuse.at[s].set(jnp.where(valid, row_reuse2, row_reuse))
        hits += is_hit.astype(hits.dtype)
        misses += (valid & ~is_hit).astype(misses.dtype)
        bypasses += (valid & ~is_hit
                     & ~(has_empty | has_safe)).astype(bypasses.dtype)
        hit_mask = hit_mask.at[i].set(is_hit)
        way = jnp.where(is_hit, way_hit, way_fill)
        slot_out = slot_out.at[i].set(
            jnp.where(valid & (is_hit | can_fill), state.slots[s, way], -1))
        return tags, reuse, hits, misses, bypasses, hit_mask, slot_out

    B = nodes.shape[0]
    init = (state.tags, state.reuse, state.hits, state.misses,
            state.bypasses, jnp.zeros(B, bool), jnp.full(B, -1, jnp.int32))
    tags, reuse, hits, misses, bypasses, hit_mask, slots = \
        jax.jit(lambda c: jax.lax.fori_loop(0, B, body, c))(init)
    return (state._replace(tags=tags, reuse=reuse, hits=hits, misses=misses,
                           bypasses=bypasses), hit_mask, slots)


def _push_sequential(state, nodes):
    """The one-id-at-a-time `push_window` (oracle)."""
    import jax
    from repro.core import cache_jax

    sets = cache_jax._set_of(nodes, state.tags.shape[0])

    def body(i, reuse):
        n, s = nodes[i], sets[i]
        return reuse.at[s].add(((state.tags[s] == n) & (n >= 0))
                               .astype(reuse.dtype))

    reuse = jax.jit(lambda r: jax.lax.fori_loop(0, nodes.shape[0], body,
                                                r))(state.reuse)
    return state._replace(reuse=reuse)


def _same_batch_set(num_sets, count, offset=0):
    """`count` distinct ids that all hash to one set."""
    from repro.core.software_cache import _hash_ids
    ids = np.arange(offset, offset + 200_000)
    ids = ids[_hash_ids(ids, num_sets) == _hash_ids(ids[:1], num_sets)[0]]
    return ids[:count]


def _exact_cases():
    """(name, lines, ways, batches): each batch a (B,) int array, -1 pad."""
    rng = np.random.default_rng(5)

    def pad(ids, B):
        out = np.full(B, -1, np.int64)
        out[:len(ids)] = ids
        return out

    crowded = [pad(np.unique(rng.integers(0, 300, 24)), 24)
               for _ in range(8)]
    wide = [pad(np.unique(rng.integers(0, 5000, 400)), 512)
            for _ in range(6)]
    dups = [rng.integers(-1, 60, 40) for _ in range(6)]
    padding = [np.full(16, -1, np.int64)] * 2
    one_set = [pad(_same_batch_set(8, 20), 32),
               pad(_same_batch_set(8, 12, 7), 16)]
    # a set filled with lines pinned by reuse > 0 (each id sits where the
    # test's future count is nonzero): the next ids of that set find no
    # room and bypass
    same = _same_batch_set(8, 10)
    pinned = [np.array([-1, same[0], same[1], -1, same[2], same[3]]),
              same[4:]]
    return [("crowded_32x4", 32, 4, crowded), ("wide_256x8", 256, 8, wide),
            ("duplicates", 32, 4, dups), ("padding_only", 32, 4, padding),
            ("one_set", 64, 8, one_set), ("pinned_bypass", 32, 4, pinned)]


@pytest.mark.parametrize("case", _exact_cases(), ids=lambda c: c[0])
def test_access_rounds_equal_sequential_loop(case):
    """Set-parallel rounds leave tags, reuse, counters, hit mask and slots
    exactly as the sequential loop does; `push_window` likewise; the round
    count is the batch's largest number of valid ids in one set."""
    import jax.numpy as jnp
    from repro.core import cache_jax
    from repro.core.software_cache import _hash_ids

    _, lines, ways, batches = case
    par = seq = cache_jax.init_cache(lines, ways)
    for b in batches:
        ids = jnp.asarray(b, jnp.int32)
        # look-ahead: announce the batch, so hits hold reservations
        par = cache_jax.push_window(par, ids)
        seq = _push_sequential(seq, ids)
        np.testing.assert_array_equal(np.asarray(par.reuse),
                                      np.asarray(seq.reuse))
        fc = jnp.asarray((np.arange(len(b)) % 3) * (b >= 0), jnp.int32)
        par, hit_p, slot_p = cache_jax.access(par, ids, fc)
        seq, hit_s, slot_s = _access_sequential(seq, ids, fc)
        for field in ("tags", "reuse", "slots", "hits", "misses",
                      "bypasses"):
            np.testing.assert_array_equal(np.asarray(getattr(par, field)),
                                          np.asarray(getattr(seq, field)),
                                          err_msg=field)
        np.testing.assert_array_equal(np.asarray(hit_p), np.asarray(hit_s))
        np.testing.assert_array_equal(np.asarray(slot_p), np.asarray(slot_s))
        valid = b[b >= 0]
        expect = (np.bincount(_hash_ids(valid, lines // ways)).max()
                  if len(valid) else 0)
        assert int(par.rounds) == expect
    if case[0] == "pinned_bypass":
        assert int(par.bypasses) > 0
    if case[0] == "one_set":
        assert int(par.rounds) == (batches[-1] >= 0).sum()
