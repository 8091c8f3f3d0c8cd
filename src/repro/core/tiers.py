"""Pluggable data-plane tiers — the open hierarchy behind the feature store.

The paper fixes three placements (GPU software cache §3.4, constant host
buffer §3.3, GPU-direct storage §3.1).  Related systems show the hierarchy
should be open: PyTorch-Direct's zero-copy host tier and Data Tiering's
reorder-and-score placement are each "just another tier".  This module
defines the `Tier` protocol every placement implements plus adapters for the
existing components:

  DeviceCacheTier    — wraps `WindowBufferedCache` (HBM metadata, numpy ref)
  DeviceStoreTier    — wraps `device_store.DeviceStore` (jittable HBM rows +
                       Pallas `tiered_gather`)
  ConstantBufferTier — wraps `ConstantBuffer` (pinned host memory)
  StorageTier        — the memmap/array storage backstop (always hits)
  ShardedStorageTier — the backstop partitioned across `n_shards` SSD queues
                       by a pluggable `PlacementPolicy` (core/sharding.py);
                       per-request shard ids feed the per-shard burst
                       pricing (`storage_sim.price_sharded_burst`)
  KVSlotTier         — a KV-cache slot pool for the serve engine (a request
                       "hits" while it holds a slot; retirement = evictable)

This module owns the *feature-row* namespace.  The *topology* namespace —
the CSR adjacency partitioned into page-granular GPU/host/storage tiers for
GPU-initiated sampling — mirrors the same ideas one level down in
`core/topology.py` (`TieredTopologyStore`, with admission policies
registered like `core/sharding.py` placements); its tier vocabulary reuses
`LATENCY_CLASSES` so telemetry reads the same across both planes.

`build_plan` folds an ordered tier stack over one batch of requests into a
`GatherPlan`: a per-request tier-assignment array that is, by construction, a
partition — every request is served by exactly one tier.  The plan feeds both
the `tiered_gather` Pallas kernel (slot array) and the storage-timeline
pricing (per-tier counts).  Requests a storage-class tier claims additionally
carry a shard id (`GatherPlan.shard`): the serving tier's placement decision
for a sharded backstop, 0 for a single-queue one, -1 for requests faster
tiers redirected off storage entirely.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Protocol, Sequence, runtime_checkable

import numpy as np

from repro.obs import NULL_TRACER

from .constant_buffer import ConstantBuffer
from .software_cache import WindowBufferedCache
from .storage_sim import IO_BYTES

#: Valid latency classes, fastest first.  The storage-timeline pricing keys
#: off the class, not the concrete tier, so user tiers slot into the model.
LATENCY_CLASSES = ("hbm", "host", "storage")


@runtime_checkable
class Tier(Protocol):
    """One placement in the data plane.

    `probe(node_ids)` returns a boolean hit mask over the requests that
    reached this tier (requests claimed by faster tiers are not offered).
    Probing MAY mutate tier state (a cache fills its lines on miss — the
    paper's access path does exactly that).  `admit(node_ids)` announces the
    node list of a *future* batch so the tier can pin / prefetch (window
    buffering); tiers without look-ahead treat it as a no-op.
    """

    name: str
    latency_class: str

    @property
    def capacity_bytes(self) -> int | None: ...      # None = unbounded

    def probe(self, node_ids: np.ndarray) -> np.ndarray: ...

    def admit(self, node_ids: np.ndarray) -> None: ...

    def reset(self) -> None: ...


class _TierBase:
    """Default no-op admit/reset so simple tiers stay two methods."""

    name = "tier"
    latency_class = "storage"

    @property
    def capacity_bytes(self) -> int | None:
        return None

    def admit(self, node_ids: np.ndarray) -> None:
        del node_ids

    def reset(self) -> None:
        pass


class DeviceCacheTier(_TierBase):
    """HBM tier backed by the window-buffered software cache (§3.4).

    The wrapped cache is metadata-only (the reference numpy twin); the HBM
    row store it implies can be materialized for the Pallas kernel via
    `TieredFeatureStore.device_rows`.
    """

    latency_class = "hbm"

    def __init__(self, cache: WindowBufferedCache, name: str = "hbm-cache",
                 line_bytes: int = IO_BYTES):
        self.cache = cache
        self.name = name
        self.line_bytes = line_bytes

    @property
    def capacity_bytes(self) -> int:
        return self.cache.num_sets * self.cache.ways * self.line_bytes

    @property
    def window_depth(self) -> int:
        return self.cache.window_depth

    @property
    def window(self) -> deque:
        return self.cache.window

    def probe(self, node_ids: np.ndarray) -> np.ndarray:
        return self.cache.access(node_ids)

    def probe_merged(self, node_ids: np.ndarray,
                     multiplicity: np.ndarray) -> np.ndarray:
        """One deduplicated probe for a whole merged window: each node
        consumes its full reuse multiplicity at once (see
        `WindowBufferedCache.access_merged`; the caller has already retired
        the consumed window entries and pushed the next window's)."""
        return self.cache.access_merged(node_ids, multiplicity)

    def admit(self, node_ids: np.ndarray) -> None:
        self.cache.push_window(node_ids)

    def lookup_slots(self, node_ids: np.ndarray) -> np.ndarray:
        """Resident cache line per node (post-probe), -1 if absent."""
        return self.cache.lookup(node_ids)

    def reset(self) -> None:
        self.cache.reset()


class DeviceStoreTier(_TierBase):
    """Fully-jittable HBM tier: cache_jax metadata + HBM row store + the
    `tiered_gather` Pallas kernel, via `device_store.device_gather`.

    Requests are padded to a power-of-two bucket so the jitted step re-uses
    compiled shapes across batches.  `last_rows` holds the device-gathered
    rows of the most recent probe (the real data path of this tier), and
    `last_h2d_bytes` / `last_d2h_bytes` the bytes that probe copied to and
    from the device: the padded ids, staged rows and reuse counts up, the
    padded rows and hit mask back.  `last_access_rounds` is the number of
    set-parallel rounds its cache lookup took (`CacheState.rounds`: the
    probe's largest count of ids in one cache set).  A probe runs as the
    wall stages `stage_rows`, `h2d`, `device_wait` and `d2h` of `tracer`,
    which the loader sets when its own tracer is enabled.
    """

    latency_class = "hbm"
    tracer = NULL_TRACER

    def __init__(self, features: np.ndarray, num_lines: int, ways: int = 8,
                 window_depth: int = 0, use_pallas: bool = True,
                 name: str = "device-store"):
        import jax.numpy as jnp                      # deferred: numpy-only
        from . import device_store                   # users never pay for jax
        self._jnp = jnp
        self._mod = device_store
        self._host_features = features
        self._init_args = (num_lines, features.shape[1], ways)
        self.store = device_store.init_store(num_lines, features.shape[1],
                                             ways)
        self.window_depth = window_depth
        self.window: deque[np.ndarray] = deque()
        self.use_pallas = use_pallas
        self.name = name
        self.last_rows = None
        self.last_h2d_bytes = self.last_d2h_bytes = 0
        self.last_access_rounds = 0

    @property
    def capacity_bytes(self) -> int:
        return int(self.store.rows.nbytes)

    def _future_counts(self, ids: np.ndarray) -> np.ndarray:
        """Per-id count of future window batches containing it, in one
        concatenated membership pass: each window entry contributes its
        unique ids once, the sorted concatenation is binary-searched from
        both sides, and the span width is the count."""
        if not self.window:
            return np.zeros(len(ids), np.int32)
        cat = np.sort(np.concatenate(
            [np.unique(np.asarray(w)) for w in self.window]))
        lo = np.searchsorted(cat, ids, side="left")
        hi = np.searchsorted(cat, ids, side="right")
        return (hi - lo).astype(np.int32)

    def probe(self, node_ids: np.ndarray) -> np.ndarray:
        if self.window_depth > 0 and self.window:
            self.window.popleft()
        return self._probe_rows(node_ids)

    def probe_merged(self, node_ids: np.ndarray,
                     multiplicity: np.ndarray) -> np.ndarray:
        """Merged-window probe: one deduplicated device gather for the whole
        window (the caller has already retired the consumed look-ahead
        entries).  The jittable cache metadata decrements one reservation
        per hit, not the full multiplicity — surplus reservations keep
        lines pinned a little longer than the reference cache would
        (conservative: capacity, not correctness)."""
        del multiplicity
        return self._probe_rows(node_ids)

    def _probe_rows(self, node_ids: np.ndarray) -> np.ndarray:
        n, tr, jnp = len(node_ids), self.tracer, self._jnp
        with tr.stage("stage_rows"):
            ids = self._mod.bucket_rows(np.asarray(node_ids, np.int32), -1)
            staged = self._host_features[np.maximum(ids, 0)]
            fc = np.zeros(len(ids), np.int32)
            fc[:n] = self._future_counts(node_ids)
        with tr.stage("h2d"):
            ids_d, staged_d, fc_d = (jnp.asarray(ids), jnp.asarray(staged),
                                     jnp.asarray(fc))
        with tr.stage("device_wait"):
            self.store, rows, hits = self._mod.device_gather(
                self.store, ids_d, staged_d, fc_d, use_pallas=self.use_pallas)
            rows.block_until_ready()
            hits.block_until_ready()
        with tr.stage("d2h"):
            self.last_rows = np.asarray(rows)[:n]
            hit_mask = np.asarray(hits)[:n]
            self.last_access_rounds = int(self.store.cache.rounds)
        self.last_h2d_bytes = ids.nbytes + staged.nbytes + fc.nbytes
        self.last_d2h_bytes = rows.nbytes + hits.nbytes
        return hit_mask

    def admit(self, node_ids: np.ndarray) -> None:
        if self.window_depth == 0:
            return
        self.window.append(np.asarray(node_ids))
        self.store = self.store._replace(cache=self._mod.push_window(
            self.store.cache, self._jnp.asarray(self._mod.bucket_rows(
                np.asarray(node_ids, np.int32), -1))))

    def lookup_slots(self, node_ids: np.ndarray) -> np.ndarray:
        """Resident HBM row per node from the jittable cache metadata, -1 if
        absent (read-only; mirrors `WindowBufferedCache.lookup`)."""
        from .software_cache import _hash_ids   # the shared Fibonacci hash —
        tags = np.asarray(self.store.cache.tags)  # must match cache_jax
        slots = np.asarray(self.store.cache.slots)  # bit-exactly
        ids = np.asarray(node_ids)
        sets = _hash_ids(ids, tags.shape[0])
        match = tags[sets] == ids[:, None]        # (n, ways) tag compare
        way = match.argmax(axis=1)                # first matching way
        return np.where(match.any(axis=1),
                        slots[sets, way], -1).astype(np.int32)

    def device_rows(self) -> np.ndarray:
        """The resident HBM row store (already materialized on device)."""
        return np.asarray(self.store.rows)

    def reset(self) -> None:
        self.store = self._mod.init_store(*self._init_args)
        self.window.clear()
        self.last_rows = None
        self.last_h2d_bytes = self.last_d2h_bytes = 0
        self.last_access_rounds = 0


class TenantCacheTier(_TierBase):
    """HBM software-cache tier partitioned per tenant with priced isolation.

    The serving twin of `DeviceCacheTier`: the line budget is split into
    per-tenant `WindowBufferedCache` partitions (window_depth=0 — serving
    has no epoch lookahead, so eviction is BaM-random within the partition).
    A request fills and evicts ONLY inside its own tenant's partition, so a
    noisy tenant scanning the whole graph cannot evict another tenant's hot
    set — isolation is by construction, and it is *priced*: misses the
    partition bound creates surface in the storage burst like any other
    miss, so the benchmark sees exactly what the quota costs and buys.

    The serving engine announces who is asking via `stage_tenants(tenant_of)`
    immediately before the gather: one tenant id per node offered to the
    next `probe`/`probe_merged`.  This tier must therefore sit FIRST in the
    stack (the fold offers the full request set to the first tier, keeping
    the staged array positionally aligned).  A node two tenants share is
    served from (and filled into) the first requester's partition for that
    window — the shared data plane still dedupes the fetch; quotas govern
    eviction, not bytes on the wire.  Un-staged probes default to tenant 0,
    the single-tenant degenerate case.
    """

    latency_class = "hbm"

    def __init__(self, num_lines: int, ways: int = 8, tenants: int = 1,
                 quotas: Sequence[float] | None = None, seed: int = 0,
                 line_bytes: int = IO_BYTES, name: str = "hbm-tenant-cache"):
        if tenants < 1:
            raise ValueError(f"need at least one tenant, got {tenants}")
        if quotas is None:
            quotas = (1.0 / tenants,) * tenants
        self.ways = ways
        self.line_bytes = line_bytes
        self.name = name
        self._num_lines = int(num_lines)
        self._seed = seed
        self._tenants = tenants
        self._init_quotas = self._check_quotas(quotas)
        self.quotas = self._init_quotas
        self.partitions = self._build_partitions(self.quotas)
        self._staged: np.ndarray | None = None

    def _check_quotas(self, quotas: Sequence[float]) -> tuple[float, ...]:
        quotas = tuple(float(q) for q in quotas)
        if len(quotas) != self._tenants:
            raise ValueError(
                f"{len(quotas)} quotas for {self._tenants} tenants — pass "
                "one capacity share per tenant")
        if any(q <= 0 for q in quotas):
            raise ValueError(f"quotas must be positive, got {quotas}")
        return quotas

    def _build_partitions(self, quotas: tuple[float, ...]
                          ) -> tuple[WindowBufferedCache, ...]:
        total = sum(quotas)
        # per-partition line budget: quota share rounded down to a whole
        # number of sets (the cache asserts num_lines % ways == 0), floored
        # at one set so every tenant owns at least `ways` lines; partition
        # seeds derive from the tenant index, so a tenant's hash placement
        # is stable across repartitions
        return tuple(
            WindowBufferedCache(
                max(self.ways,
                    (int(self._num_lines * q / total) // self.ways)
                    * self.ways),
                self.ways, window_depth=0, seed=self._seed + 17 * t)
            for t, q in enumerate(quotas))

    def repartition(self, quotas: Sequence[float]) -> None:
        """Online quota re-split (the `QuotaController`'s actuator,
        core/feedback.py): rebuild the per-tenant partitions at the new
        shares.  Rebuilt partitions start COLD — the refill is priced as
        ordinary misses in subsequent bursts, which is exactly why the
        controller repartitions sparingly — but each tenant's cumulative
        hit/access counters carry over, so `hit_ratio(tenant)` telemetry
        (and the `ServeResult` rollup) stays a run-long signal."""
        quotas = self._check_quotas(quotas)
        stats = [c.stats for c in self.partitions]
        self.partitions = self._build_partitions(quotas)
        for cache, old in zip(self.partitions, stats):
            cache.stats = old
        self.quotas = quotas

    @property
    def tenants(self) -> int:
        return len(self.partitions)

    @property
    def capacity_bytes(self) -> int:
        return sum(c.num_sets * c.ways for c in self.partitions) \
            * self.line_bytes

    def partition_lines(self, tenant: int) -> int:
        c = self.partitions[tenant]
        return c.num_sets * c.ways

    def stage_tenants(self, tenant_of: np.ndarray) -> None:
        """Announce the requesting tenant of each node in the NEXT probe —
        (n,) int array positionally aligned with the node list the fold
        will offer.  Consumed by that one probe."""
        t = np.asarray(tenant_of)
        if len(t) and (t.min() < 0 or t.max() >= self.tenants):
            raise ValueError(
                f"tenant ids in [{t.min()}, {t.max()}] out of range for "
                f"{self.tenants} partitions")
        self._staged = t

    def _take_staged(self, n: int) -> np.ndarray:
        t = self._staged
        self._staged = None
        if t is None:
            return np.zeros(n, np.int64)
        if len(t) != n:
            raise ValueError(
                f"staged {len(t)} tenant ids but the fold offered {n} "
                "nodes — the tenant tier must be first in the stack")
        return t

    def probe(self, node_ids: np.ndarray) -> np.ndarray:
        return self._probe(node_ids, None)

    def probe_merged(self, node_ids: np.ndarray,
                     multiplicity: np.ndarray) -> np.ndarray:
        return self._probe(node_ids, multiplicity)

    def _probe(self, node_ids: np.ndarray,
               multiplicity: np.ndarray | None) -> np.ndarray:
        tenant = self._take_staged(len(node_ids))
        hits = np.zeros(len(node_ids), dtype=bool)
        for tid, cache in enumerate(self.partitions):
            m = tenant == tid
            if not m.any():
                continue
            mult = None if multiplicity is None else multiplicity[m]
            hits[m] = cache.access(node_ids[m], multiplicity=mult)
        return hits

    def lookup_slots(self, node_ids: np.ndarray) -> np.ndarray:
        """Resident line per node across the concatenated partitions
        (partition t's lines offset by the budgets before it), -1 if the
        node is resident in no partition.  Read-only, tenant-agnostic: a
        row in HBM is a row in HBM regardless of whose quota pinned it."""
        out = np.full(len(node_ids), -1, np.int64)
        offset = 0
        for cache in self.partitions:
            slot = cache.lookup(np.asarray(node_ids))
            found = (out == -1) & (slot >= 0)
            out[found] = slot[found] + offset
            offset += cache.num_sets * cache.ways
        return out

    def hit_ratio(self, tenant: int) -> float:
        return self.partitions[tenant].stats.hit_ratio

    def hit_ratios(self) -> tuple[float, ...]:
        """Cumulative per-tenant hit ratios — the quota controller's input,
        rolled up into `ServeResult.tenant_hit_ratios`."""
        return tuple(c.stats.hit_ratio for c in self.partitions)

    def reset(self) -> None:
        # full post-construction state: construction-time quotas restored
        # (an adaptive run may have repartitioned), partitions cold, fresh
        # counters — so replays of the same stream are bit-reproducible
        self.quotas = self._init_quotas
        self.partitions = self._build_partitions(self.quotas)
        self._staged = None


class ConstantBufferTier(_TierBase):
    """Pinned-host tier backed by the constant CPU buffer (§3.3).  Stateless
    membership lookup — the PyTorch-Direct zero-copy tier has the same shape
    with a different selection policy."""

    latency_class = "host"

    def __init__(self, cbuf: ConstantBuffer, row_bytes: int | None = None,
                 name: str = "host-cbuf"):
        self.cbuf = cbuf
        self.row_bytes = row_bytes
        self.name = name

    @property
    def capacity_bytes(self) -> int | None:
        if self.cbuf.rows is not None:
            return int(self.cbuf.rows.nbytes)
        if self.row_bytes is not None:
            return self.cbuf.size * self.row_bytes
        return None

    def probe(self, node_ids: np.ndarray) -> np.ndarray:
        return self.cbuf.redirect_mask(node_ids)


class StorageTier(_TierBase):
    """The storage namespace backstop (memmap file or in-memory array).
    Always hits — a tier stack is valid iff it ends in a backstop."""

    latency_class = "storage"

    def __init__(self, features: np.ndarray, name: str = "storage"):
        self.features = features
        self.name = name

    @property
    def capacity_bytes(self) -> int:
        return int(self.features.nbytes)

    def probe(self, node_ids: np.ndarray) -> np.ndarray:
        return np.ones(len(node_ids), dtype=bool)

    def rows(self, node_ids: np.ndarray) -> np.ndarray:
        return np.asarray(self.features[node_ids])


class ShardedStorageTier(StorageTier):
    """The storage backstop partitioned across `n_shards` independent SSD
    queues by a `PlacementPolicy` (core/sharding.py).

    The *bytes* are unchanged — one logical feature namespace, every probe
    hits — but each storage-bound request now carries the shard whose queue
    it drains through (`shard_of`, threaded into `GatherPlan.shard` by
    `build_plan`).  Pricing then completes the batch at the MAX over shards
    (`storage_sim.price_sharded_burst`), which is what makes multi-SSD
    scaling and placement skew measurable.

    `specs` may be one `SSDSpec` (homogeneous array), a sequence of
    `n_shards` specs (heterogeneous — e.g. one Optane + three 980Pros, the
    straggler story), or None (every shard inherits the loader's device
    spec).
    """

    def __init__(self, features: np.ndarray, placement,
                 specs=None, name: str = "sharded-storage"):
        super().__init__(features, name=name)
        self.placement = placement
        if specs is not None and not isinstance(specs, (list, tuple)):
            specs = (specs,) * placement.n_shards
        if specs is not None:
            specs = tuple(specs)
            if len(specs) != placement.n_shards:
                raise ValueError(
                    f"{len(specs)} shard specs for {placement.n_shards} "
                    "shards — pass one spec per shard (or a single spec "
                    "to replicate)")
        self.specs = specs
        # fault plane: a FailoverRouter (core/faults.py) rewrites the
        # placement decision at plan time — reads off dead/degraded shards
        # go to a live replica.  None (the default) keeps shard_of the
        # bare placement, bit-identical to the unrouted plane.
        self.router = None

    @property
    def n_shards(self) -> int:
        return self.placement.n_shards

    def shard_of(self, node_ids: np.ndarray) -> np.ndarray:
        """Per-request shard id (the placement decision), (B,) int16.
        With a router wired, the decision is failover-adjusted — same
        bytes, healthier queue."""
        primary = np.asarray(self.placement.shard_of(node_ids), np.int16)
        if self.router is None:
            return primary
        return np.asarray(self.router.route(node_ids, primary), np.int16)

    def resolve_shard_specs(self, default_spec) -> tuple:
        """Per-shard `SSDSpec`s, falling back to `default_spec` (the
        loader's device) when the tier was built spec-less."""
        if self.specs is not None:
            return self.specs
        return (default_spec,) * self.n_shards

    # -- checkpoint -----------------------------------------------------------
    def state_dict(self) -> dict:
        """Shard-assignment state for checkpoint round-trip.  Built-in
        policies are deterministic, but the table-based ones (`degree`) are
        exactly what an online rebalancer would mutate — resume restores the
        assignment rather than trusting reconstruction."""
        return {"n_shards": self.n_shards,
                "placement": self.placement.state_dict()}

    def load_state_dict(self, state: dict) -> None:
        if state.get("n_shards", self.n_shards) != self.n_shards:
            raise ValueError(
                f"checkpoint has {state.get('n_shards')} shards, tier has "
                f"{self.n_shards} — shard count is namespace layout, not "
                "runtime state")
        self.placement.load_state_dict(state["placement"])


class KVSlotTier(_TierBase):
    """KV-cache slot pool as a data-plane tier (serve engine).

    A request "hits" while it holds a slot — its KV lines are resident and
    un-evictable, the serving analogue of the window cache's USE state.  A
    retired request's slot returns to safe-to-evict and is recycled for the
    next admission.
    """

    latency_class = "hbm"

    def __init__(self, slots: int, bytes_per_slot: int = 0,
                 name: str = "kv-slots"):
        self.num_slots = slots
        self.bytes_per_slot = bytes_per_slot
        self.name = name
        self._free: deque[int] = deque(range(slots))
        self._held: dict[int, int] = {}              # rid -> slot

    @property
    def capacity_bytes(self) -> int:
        return self.num_slots * self.bytes_per_slot

    @property
    def occupancy(self) -> float:
        return len(self._held) / self.num_slots if self.num_slots else 0.0

    def probe(self, request_ids: np.ndarray) -> np.ndarray:
        held = np.fromiter(self._held.keys(), dtype=np.int64,
                           count=len(self._held))
        return np.isin(np.asarray(request_ids, dtype=np.int64), held)

    def admit(self, request_ids: np.ndarray) -> None:
        """Best-effort bulk admission: ids beyond the free capacity are NOT
        admitted (no queueing at this layer).  Callers that must know the
        outcome use `acquire()` per id — the serve engine does, keeping its
        own queue for the overflow."""
        for r in request_ids:
            self.acquire(int(r))

    def acquire(self, rid: int) -> int | None:
        """Assign a free slot to `rid` (idempotent); None when full."""
        if rid in self._held:
            return self._held[rid]
        if not self._free:
            return None
        slot = self._free.popleft()
        self._held[rid] = slot
        return slot

    def release(self, rid: int) -> int:
        slot = self._held.pop(rid)
        self._free.append(slot)
        return slot

    def reset(self) -> None:
        self._free = deque(range(self.num_slots))
        self._held.clear()


# -- gather plan ---------------------------------------------------------------

@dataclasses.dataclass
class GatherPlan:
    """Per-request tier assignment for one batch: `assignment[i]` indexes the
    tier stack entry that serves request i.  Folding guarantees a partition
    (`is_partition`); `kernel_slots` renders the device-tier portion as the
    slot array the `tiered_gather` Pallas kernel consumes.

    `shard[i]` is the storage shard serving request i: the placement
    decision of a `ShardedStorageTier`, 0 for a single-queue storage tier,
    and -1 iff the serving tier is not storage-class (`shard_consistent`
    pins that invariant).  Shard ids drive shard-local 4 KB-line coalescing
    and the max-over-shards burst pricing.

    `remote[i]` (host planes only — core/hosts.py) marks requests whose
    serving host differs from the host that REQUESTED them; those rows'
    lines additionally transit the serving host's link in
    `StorageTimeline.price_host_burst`.  None on single-host planes —
    remote-ness is a pricing/telemetry annotation, never a routing one, so
    gathered bytes cannot depend on it."""

    node_ids: np.ndarray
    assignment: np.ndarray          # (B,) int8 index into `tiers`
    tiers: tuple
    shard: np.ndarray | None = None  # (B,) int16; -1 = not storage-bound
    remote: np.ndarray | None = None  # (B,) bool; True = crosses a host link

    def counts(self) -> np.ndarray:
        return np.bincount(self.assignment, minlength=len(self.tiers))

    def mask(self, tier_index: int) -> np.ndarray:
        return self.assignment == tier_index

    def is_partition(self) -> bool:
        a = self.assignment
        return bool(((a >= 0) & (a < len(self.tiers))).all()
                    and int(self.counts().sum()) == len(self.node_ids))

    def storage_mask(self) -> np.ndarray:
        """Requests whose serving tier is storage-class."""
        classes = np.array([t.latency_class == "storage" for t in self.tiers])
        return classes[self.assignment]

    @property
    def n_shards(self) -> int:
        """Shard count of the stack's storage namespace (1 when unsharded)."""
        return max((getattr(t, "n_shards", 1) for t in self.tiers), default=1)

    def shard_consistent(self) -> bool:
        """Shard ids are defined exactly where the serving tier is
        storage-class, and always index a real shard."""
        if self.shard is None:
            return not self.storage_mask().any()
        sm = self.storage_mask()
        s = self.shard
        return bool(((s[sm] >= 0) & (s[sm] < self.n_shards)).all()
                    and (s[~sm] == -1).all())

    def shard_counts(self) -> np.ndarray:
        """Storage-bound requests per shard, (n_shards,)."""
        if self.shard is None:
            return np.zeros(self.n_shards, np.int64)
        sm = self.shard >= 0
        return np.bincount(self.shard[sm], minlength=self.n_shards)

    def remote_counts(self) -> np.ndarray:
        """Cross-host storage requests per SERVING shard, (n_shards,) —
        the rows each host ships over its link (zeros off host planes)."""
        if self.shard is None or self.remote is None:
            return np.zeros(self.n_shards, np.int64)
        rm = self.remote & (self.shard >= 0)
        return np.bincount(self.shard[rm], minlength=self.n_shards)

    def kernel_slots(self, tier_index: int = 0) -> np.ndarray:
        """Slot array for `ops.tiered_gather`: requests served by the device
        tier carry their cache line, everything else -1 (staged row i).

        Slots are resolved against the tier's *post-probe* metadata — the
        same state `TieredFeatureStore.device_rows` materializes — so the
        (slots, rows) pair is always coherent.  A hit whose line was evicted
        later in the same batch (a colliding fill in its set) resolves to -1
        and is demoted to the staged path: the gathered bytes stay correct,
        at worst the pricing report counted one extra HBM hit."""
        tier = self.tiers[tier_index]
        slots = np.full(len(self.node_ids), -1, np.int32)
        m = self.mask(tier_index)
        if m.any():
            slots[m] = tier.lookup_slots(self.node_ids[m])
        return slots


def build_plan(tiers: Sequence[Tier], node_ids: np.ndarray,
               multiplicity: np.ndarray | None = None) -> GatherPlan:
    """Fold the ordered tier stack over one batch: each tier is offered the
    requests every faster tier declined; its hits are claimed.  The last tier
    must be a backstop (probe everything True), else the fold fails loudly.

    With `multiplicity` the fold is a merged-window one: `node_ids` is a
    window's UNIQUE request set, and tiers that understand merged windows
    (`probe_merged`) consume each node's full reuse multiplicity in the one
    pass; stateless tiers see a plain probe of the union either way."""
    node_ids = np.asarray(node_ids)
    n = len(node_ids)
    assignment = np.full(n, -1, np.int8)
    unclaimed = np.ones(n, dtype=bool)
    for ti, tier in enumerate(tiers):
        idx = np.nonzero(unclaimed)[0]
        if len(idx) == 0:
            break
        if multiplicity is not None and hasattr(tier, "probe_merged"):
            hits = np.asarray(tier.probe_merged(
                node_ids[idx], multiplicity[idx]), dtype=bool)
        else:
            hits = np.asarray(tier.probe(node_ids[idx]), dtype=bool)
        took = idx[hits]
        assignment[took] = ti
        unclaimed[took] = False
    if unclaimed.any():
        raise RuntimeError(
            f"tier stack {[t.name for t in tiers]} left "
            f"{int(unclaimed.sum())} of {n} requests unserved — the stack "
            "must end in a storage backstop")
    # storage-bound requests carry the serving tier's shard decision; a
    # single-queue storage tier is shard 0, redirected requests stay -1
    shard = np.full(n, -1, np.int16)
    remote = None
    for ti, tier in enumerate(tiers):
        if tier.latency_class != "storage":
            continue
        m = assignment == ti
        if not m.any():
            continue
        if hasattr(tier, "shard_of"):
            shard[m] = tier.shard_of(node_ids[m])
            if hasattr(tier, "remote_mask"):
                # host-level backstop: stamp which requests the serving
                # host ships over its link (requester != server)
                if remote is None:
                    remote = np.zeros(n, bool)
                remote[m] = tier.remote_mask(node_ids[m], shard[m])
        else:
            shard[m] = 0
    return GatherPlan(node_ids=node_ids, assignment=assignment,
                      tiers=tuple(tiers), shard=shard, remote=remote)


def build_plan_merged(tiers: Sequence[Tier], unique_nodes: np.ndarray,
                      multiplicity: np.ndarray) -> GatherPlan:
    """Dedup-aware fold for a merged window — `build_plan` over the unique
    set with the window multiplicity.  Same partition guarantee."""
    return build_plan(tiers, unique_nodes, multiplicity=multiplicity)


def record_tier_metrics(tiers: Sequence[Tier], registry) -> None:
    """Fold the tier stack's cumulative cache telemetry into a
    MetricsRegistry (repro.obs): one ``tier.<name>.hit_ratio`` gauge per
    cache-bearing tier, per-tenant gauges for a partitioned tier.  The
    registry replaces ad-hoc ``loader.store.cache.stats`` spelunking —
    observation only, nothing here feeds back into probe or admission."""
    for tier in tiers:
        name = getattr(tier, "name", type(tier).__name__)
        stats = getattr(getattr(tier, "cache", None), "stats", None)
        if stats is not None and stats.accesses:
            registry.gauge(f"tier.{name}.hit_ratio").set(stats.hit_ratio)
            registry.gauge(f"tier.{name}.accesses").set(stats.accesses)
            registry.gauge(f"tier.{name}.evictions").set(stats.evictions)
        ratios = getattr(tier, "hit_ratios", None)
        if callable(ratios):
            for tenant, ratio in enumerate(ratios()):
                registry.gauge(
                    f"tier.{name}.tenant{tenant}.hit_ratio").set(ratio)
