"""Device-resident GIDS tier: jittable cache + Pallas gather end-to-end."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_store as ds


def test_device_gather_roundtrip_and_hits():
    rng = np.random.default_rng(0)
    N, D = 500, 64
    feats = rng.standard_normal((N, D)).astype(np.float32)

    store = ds.init_store(num_lines=128, dim=D, ways=4)
    ids1 = np.unique(rng.integers(0, N, 32)).astype(np.int32)
    B = len(ids1)
    staged1 = jnp.asarray(feats[ids1])
    fc = jnp.zeros(B, jnp.int32)
    store, rows1, hits1 = ds.device_gather(store, jnp.asarray(ids1),
                                           staged1, fc)
    np.testing.assert_allclose(rows1, feats[ids1])   # correct rows
    assert not bool(hits1.any())                     # cold cache

    # second access: same ids -> hits served from the device row store,
    # even with garbage staged rows (proves rows come from the cache)
    garbage = jnp.zeros((B, D), jnp.float32)
    store, rows2, hits2 = ds.device_gather(store, jnp.asarray(ids1),
                                           garbage, fc)
    assert bool(hits2.all())
    np.testing.assert_allclose(rows2, feats[ids1])


def test_device_gather_window_pinning():
    rng = np.random.default_rng(1)
    N, D = 200, 32
    feats = rng.standard_normal((N, D)).astype(np.float32)
    store = ds.init_store(num_lines=16, dim=D, ways=4)

    hot = np.array([7], dtype=np.int32)
    # access hot once (fills), then push a window announcing reuse
    store, _, _ = ds.device_gather(store, jnp.asarray(hot),
                                   jnp.asarray(feats[hot]),
                                   jnp.zeros(1, jnp.int32))
    store = store._replace(
        cache=ds.push_window(store.cache, jnp.asarray(hot)))
    # storm of conflicting ids cannot evict the pinned line
    for i in range(6):
        ids = (hot + 16 * (i + 1)).astype(np.int32)  # same set, diff tags
        store, _, _ = ds.device_gather(store, jnp.asarray(ids),
                                       jnp.asarray(feats[ids]),
                                       jnp.zeros(1, jnp.int32))
    store, rows, hits = ds.device_gather(store, jnp.asarray(hot),
                                         jnp.zeros((1, D), jnp.float32),
                                         jnp.zeros(1, jnp.int32))
    assert bool(hits[0]), "pinned hot line was evicted"
    np.testing.assert_allclose(rows, feats[hot])


def test_device_gather_same_batch_evictions():
    """Within one batch a line can be hit and then evicted by a later fill,
    or filled twice: the hit still gets its own row, and the line keeps the
    row of its last filler (the tag the metadata holds)."""
    D = 128
    feats = np.arange(12 * D, dtype=np.float32).reshape(12, D)
    store = ds.init_store(num_lines=8, dim=D, ways=8)     # one set, 8 ways
    warm = np.arange(8, dtype=np.int32)
    store, _, _ = ds.device_gather(store, jnp.asarray(warm),
                                   jnp.asarray(feats[warm]),
                                   jnp.zeros(8, jnp.int32))
    # 0 hits, then 8..11 each evict a zero-reuse way — the first of them
    # the line 0 was just served from, later ones the lines 8.. filled
    ids = np.array([0, 8, 9, 10, 11], np.int32)
    store, rows, hits = ds.device_gather(store, jnp.asarray(ids),
                                         jnp.asarray(feats[ids]),
                                         jnp.zeros(len(ids), jnp.int32))
    assert bool(hits[0]) and not bool(hits[1:].any())
    np.testing.assert_array_equal(rows, feats[ids])
    # every resident tag's line holds that node's row
    tags = np.asarray(store.cache.tags).ravel()
    lines = np.asarray(store.cache.slots).ravel()
    resident = tags >= 0
    np.testing.assert_array_equal(np.asarray(store.rows)[lines[resident]],
                                  feats[tags[resident]])
    again = np.unique(tags[resident]).astype(np.int32)
    _, rows, hits = ds.device_gather(store, jnp.asarray(again),
                                     jnp.zeros((len(again), D), jnp.float32),
                                     jnp.zeros(len(again), jnp.int32))
    assert bool(hits.all())
    np.testing.assert_array_equal(rows, feats[again])


def test_device_gather_repeated_ids():
    """An id that misses and fills a line, then hits that line later in the
    same batch, is served its own row — the line's pre-batch content would
    be another node's."""
    D = 128
    feats = np.arange(12 * D, dtype=np.float32).reshape(12, D)
    store = ds.init_store(num_lines=8, dim=D, ways=8)     # one set, 8 ways
    warm = np.arange(8, dtype=np.int32)
    store, _, _ = ds.device_gather(store, jnp.asarray(warm),
                                   jnp.asarray(feats[warm]),
                                   jnp.zeros(8, jnp.int32))
    # 9 fills the line 0 held and is hit there; 10 then takes that line
    # (the first zero-reuse way) and 9 and 10 evict each other
    ids = np.array([9, 3, 9, 10, 9, 10], np.int32)
    store, rows, hits = ds.device_gather(store, jnp.asarray(ids),
                                         jnp.asarray(feats[ids]),
                                         jnp.zeros(len(ids), jnp.int32))
    np.testing.assert_array_equal(np.asarray(hits),
                                  [False, True, True, False, False, False])
    np.testing.assert_array_equal(rows, feats[ids])


@pytest.mark.parametrize("seed", range(4))
def test_fills_keep_one_writer_per_line(seed):
    """Whatever order the backend applies duplicate scatter indices in, the
    row store ends up the same: after the dedup every kept fill line is
    unique, and it is the line's last filler in the batch."""
    rng = np.random.default_rng(seed)
    B, L = 64, 16
    ids = rng.integers(-1, 40, B).astype(np.int32)
    slots = rng.integers(-1, L, B).astype(np.int32)
    hits = (rng.random(B) < 0.3) & (slots >= 0) & (ids >= 0)
    fill_slots, refilled = ds._fills(jnp.asarray(hits), jnp.asarray(slots),
                                     jnp.asarray(ids), L)
    fill_slots, refilled = np.asarray(fill_slots), np.asarray(refilled)
    kept = fill_slots[fill_slots < L]
    assert len(kept) == len(np.unique(kept))
    fills = ~hits & (slots >= 0) & (ids >= 0)
    for line in range(L):
        writers = np.flatnonzero(fills & (slots == line))
        expect = writers[-1:]                      # the last filler, if any
        np.testing.assert_array_equal(np.flatnonzero(fill_slots == line),
                                      expect)
        for i in np.flatnonzero(hits & (slots == line)):
            assert refilled[i] == bool((writers < i).any())
    assert not refilled[~hits].any()


def test_access_rounds_reach_the_gather_report():
    """`GatherReport.access_rounds` is the device-store probe's round count,
    the batch's largest number of ids in one cache set, and 0 for a probe
    that holds only padding."""
    from repro.core.feature_store import TieredFeatureStore
    from repro.core.software_cache import _hash_ids
    from repro.core.tiers import DeviceStoreTier, StorageTier

    feats = np.random.default_rng(2).standard_normal((400, 16)) \
        .astype(np.float32)
    tier = DeviceStoreTier(feats, num_lines=64, ways=8)
    fs = TieredFeatureStore([tier, StorageTier(feats)])
    ids = np.unique(np.random.default_rng(3).integers(0, 400, 50))
    rows, report = fs.gather(ids)
    np.testing.assert_array_equal(rows, feats[ids])
    expect = int(np.bincount(_hash_ids(ids, 8)).max())
    assert report.access_rounds == tier.last_access_rounds == expect > 1
    assert report == dataclasses.replace(report, access_rounds=0)
    # an empty request probes a bucket of padding: no round runs
    assert tier.probe(np.array([], np.int64)).shape == (0,)
    assert tier.last_access_rounds == int(tier.store.cache.rounds) == 0
    rows, report = fs.gather(np.array([], np.int64))
    assert rows.shape == (0, 16) and report.access_rounds == 0
