"""GIDSDataLoader — the end-to-end data-preparation pipeline (paper Fig. 1).

The loader is a genuine *two-stage pipeline*, split so prefetch can overlap
data preparation with model compute (§3.2):

  stage 1, `plan_next()`  — sampling + admit-side staging: refill the
    lookahead deque (sampling runs `merge_depth` iterations AHEAD under the
    accumulator), push future node lists into the windowed tiers
    (`admit()`), pop the next batch's blocks, and snapshot the sampler PRNG
    for checkpoint resume.  Produces a `BatchPlan`.
  stage 2, `execute(plan)` — data movement + pricing: fold the tier stack
    over the plan's nodes into one `GatherPlan`, gather the actual feature
    rows, feed accumulator telemetry, and price the batch from its tier
    split.  Produces a `Batch`.

`next_batch()` composes the stages.  On a synchronous plane the two run
back-to-back inside the call; on a prefetching plane (`DataPlaneSpec` with
`prefetch > 0`, e.g. the `gids-async` preset) a `PrefetchEngine`
(core/prefetch.py) has already staged the next `prefetch` batches ahead of
consumption, and `next_batch(compute_s=...)` re-prices the batch's
*exposed* prep time against the model-compute seconds it overlapped
(`Batch.exposed_prep_s = max(0, prep - compute)`); the raw `prep_time_s`
and every other `Batch` field stay bit-identical to the sync plane.

On a *merged* plane (`DataPlaneSpec.merge_execute`, e.g. the `gids-merged`
preset) stage 2 runs over a whole WINDOW of plans at once
(`plan_window()` / `execute_window()`): the accumulator's merge depth stops
being a pricing assumption and becomes the executed unit.  The window's
request lists are deduplicated into a `MergedWindow`
(`np.unique(..., return_inverse=True)`), the tier stack folds ONCE over the
unique set, each unique row is gathered exactly once, storage-bound rows
sharing a 4 KB IO line coalesce into single IOs, and the window is priced
as one storage burst (`StorageTimeline.price_merged_burst`) amortized
equally across its batches.  Per-batch features are bit-identical to the
per-batch path (the inverse index scatters unique rows back); each `Batch`
carries a `CoalescedReport` — the per-batch tier split plus the window-wide
merge telemetry (`window_batches`, `window_requests`, `n_unique`,
`n_duplicate`, `n_storage_unique`, `n_storage_lines`).  With
`prefetch > 0` as well (`gids-merged-async`) the prefetch engine stages
whole merged windows ahead of consumption.

On a *sharded* plane (`gids-sharded`, `gids-merged-sharded`) the storage
backstop is a `ShardedStorageTier`: the feature namespace is partitioned
across `LoaderConfig.n_shards` SSD queues by a registered placement policy
(`LoaderConfig.placement`; core/sharding.py), every storage-bound request
carries its shard id through the `GatherPlan`, 4 KB-line coalescing is
shard-local, and pricing completes each burst at the MAX over per-shard
queue drains (`storage_sim.price_sharded_burst` — the loader wires the
tier's per-shard `SSDSpec`s into `StorageTimeline.shard_specs`, and
`timeline.shard_burst` reports the straggler shard and queue
imbalance).  Features, blocks, and per-tier counts are bit-identical to the
unsharded plane — only the storage pricing and shard telemetry change.

On a *multi-host* plane (`gids-hosts`, `gids-hosts-merged`; core/hosts.py)
the backstop is a `HostShardTier`: the same shard vocabulary at host
granularity.  Each shard is a host (`HostLinkSpec` — interconnect + local
SSD), one co-partitioned placement decision drives the feature rows AND
the CSR edge pages of every node, and each storage-bound request carries a
remote bit (serving host != requesting host) through the `GatherPlan`.
Pricing routes through `StorageTimeline.price_host_burst`: each host's
local queue drain plus the link transit of the 4 KB lines other hosts
requested from it, completing at the max over hosts.  Features, blocks,
and per-tier counts are bit-identical to the single-host plane for ANY
host count and placement — hosts change pricing and telemetry, never
bytes — and `n_hosts=1` prices bit-identically too.

On a *topology* plane (`DataPlaneSpec.topology`, presets `gids-topo` /
`gids-topo-merged`) stage 1 itself is PRICED: sampling runs against a
`TieredTopologyStore` (core/topology.py) whose CSR edge pages are placed
across GPU/host/storage tiers by a registered admission policy, each hop
emits a `TopologyGatherReport` (edge pages by tier, coalesced page IOs,
modelled hop time), and the summed sampling time folds into
`Batch.prep_time_s` — so `exposed_prep_s` finally covers the whole Fig. 1
prep path, sampling and gather.  Blocks and features stay bit-identical to
the corresponding un-tiered plane (the tiered sampler shares the host
sampler's RNG stream and math).

Other orchestration, common to both stages:

  * the accumulator recomputes the merge depth from live telemetry
    (requests/iter, redirection rate);
  * feature gathers flow through a *pluggable tier stack*
    (`TieredFeatureStore`, see core/tiers.py) folded into one gather plan
    per batch;
  * the storage timeline prices each batch from the plan's tier split
    (benchmarks); the actual bytes are returned for real training.

Which tiers exist and how time is priced is declared by a `DataPlaneSpec`
(core/dataplane.py), not by mode strings.  The paper's three baselines are
presets of the same machinery:

  LoaderConfig(data_plane="gids")   # window cache + host cbuf + storage
  LoaderConfig(data_plane="bam")    # random-eviction cache + storage
  LoaderConfig(data_plane="mmap")   # storage only, page-fault pricing

or any registered/user-composed spec:

  LoaderConfig(data_plane=DataPlaneSpec.preset("gids-async"))

The old `mode="gids"` kwarg maps onto the preset of the same name through a
deprecation shim.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import deque
from typing import Iterator, Sequence

import numpy as np

from repro.graph.csr import CSRGraph
from repro.obs import NULL_TRACER, attach_burst_spans
from repro.sampling.neighbor import host_sample_blocks, SampledBlocks
from repro.sampling.ladies import ladies_sample_blocks
from .accumulator import DynamicAccessAccumulator, AccumulatorConfig
from .dataplane import DataPlane, DataPlaneSpec
from .feature_store import GatherReport
from .feedback import ShardHealthMonitor, ShardRebalancer, TopologyRefresher
from .prefetch import PrefetchEngine
from .storage_sim import SSDSpec, StorageTimeline, INTEL_OPTANE
from .topology import TieredTopologyStore

#: Sampler names the loader knows how to drive.  `LoaderConfig` validates
#: at construction — an unknown sampler fails when the config is built, not
#: on the first batch.
SAMPLERS = ("neighbor", "ladies")


@dataclasses.dataclass
class LoaderConfig:
    batch_size: int = 4096
    fanouts: Sequence[int] = (10, 5, 5)       # 3 sampling layers (paper §4.1)
    sampler: str = "neighbor"                  # or "ladies"
    ladies_layer_sizes: Sequence[int] = (512, 512, 512)
    data_plane: DataPlaneSpec | str | None = None  # preset name or spec;
                                               # None resolves to "gids"
    window_depth: int = 8                      # paper default
    cache_lines: int = 1 << 15                 # 8GB @4KB in paper; scaled here
    cache_ways: int = 8
    cbuf_fraction: float = 0.1                 # 10% of dataset (paper default)
    cbuf_selection: str = "pagerank"
    target_efficiency: float = 0.95
    n_ssd: int = 1
    # sharded-storage planes (gids-sharded / gids-merged-sharded): how many
    # SSD shards partition the feature namespace, and which registered
    # placement policy (core/sharding.py) decides node -> shard
    n_shards: int = 1
    placement: str = "hash"
    # multi-host planes (gids-hosts / gids-hosts-merged; core/hosts.py):
    # the storage backstop partitions across n_hosts HOSTS — each with its
    # own interconnect link and local SSD — under the same placement
    # registry ("metis-lite" adds min-cut partitioning over the CSR).
    # co_partition=True (default) drives a node's feature rows AND its CSR
    # edge pages off ONE placement decision; False stripes the adjacency
    # independently (the double-network-hop baseline).  host_link overrides
    # the 100GbE default (a HostLinkSpec, or one per host)
    n_hosts: int = 1
    co_partition: bool = True
    host_link: "object | None" = None
    # topology plane (gids-topo / gids-topo-merged): fraction of the CSR
    # edge pages resident in GPU memory / pinned host memory (remainder is
    # storage-backed), and which registered admission policy
    # (core/topology.py) ranks pages into the budgets
    topo_admission: str = "degree"
    topo_gpu_fraction: float = 0.25
    topo_host_fraction: float = 0.5
    # adaptive data plane (core/feedback.py; placement="adaptive" and/or
    # topo_admission="adaptive"): every `rebalance_interval` priced bursts
    # the controllers fold measured touches and consider a re-placement —
    # shard migration when the measured queue imbalance exceeds
    # `imbalance_threshold`, topology page re-admission when measured-hot
    # pages sit in slow tiers — committing only when the modelled saving
    # over `migration_horizon` future bursts beats the move's own priced IO
    # cost, which is then amortized into subsequent batches' prep
    rebalance_interval: int = 8
    imbalance_threshold: float = 1.25
    migration_horizon: int = 64
    # fault plane (core/faults.py): a seeded FaultSchedule injected into
    # every priced storage burst — per-shard brownouts, outages, transient
    # line failures, priced retries and hedged reads.  None (the default)
    # prices bit-identically to the fault-free plane.  replication_factor
    # wraps the placement in k-way ReplicatedPlacement so failover and
    # hedges have live replica queues to go to
    fault_schedule: "object | None" = None
    replication_factor: int = 1
    seed: int = 0
    # deprecated spelling of data_plane; kept so old call sites keep running
    mode: dataclasses.InitVar[str | None] = None

    def __post_init__(self, mode: str | None) -> None:
        if self.sampler not in SAMPLERS:
            raise ValueError(
                f"unknown sampler {self.sampler!r}; known samplers: "
                f"{SAMPLERS}")
        # an explicitly-set data_plane always wins over the deprecated mode
        # kwarg: dataclasses.replace() re-feeds the shimmed `mode` read back
        # through __init__, and must not revert a data_plane change or
        # degrade a spec object to its bare name
        if self.data_plane is None:
            if mode is not None:
                warnings.warn(
                    "LoaderConfig(mode=...) is deprecated; use "
                    "data_plane=<preset name> or "
                    "data_plane=DataPlaneSpec.preset(...)",
                    DeprecationWarning, stacklevel=3)
            self.data_plane = mode if mode is not None else "gids"

    def __getattr__(self, name: str):
        # read-side half of the shim: old call sites also *read* cfg.mode
        # (the InitVar is consumed by __init__ and never stored).  No
        # deprecation warning here — dataclasses.replace() reads it on
        # every call
        if name == "mode":
            dp = self.__dict__.get("data_plane", "gids")
            return dp if isinstance(dp, str) else dp.name
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")


# the InitVar's class-level default (mode = None) would shadow the
# __getattr__ read shim; the generated __init__ keeps its own reference
del LoaderConfig.mode


@dataclasses.dataclass
class BatchPlan:
    """Stage-1 output: what to gather, plus the resume point.  `snapshot` is
    the sampler state *before* this batch was sampled, so a checkpoint taken
    while the batch is staged-but-unconsumed replays it deterministically.
    `index` counts the loader's plans; it tags the batch's wall spans."""

    blocks: SampledBlocks
    merge_depth: int
    snapshot: dict
    index: int


@dataclasses.dataclass
class Batch:
    blocks: SampledBlocks
    features: np.ndarray          # rows for blocks.all_nodes
    report: GatherReport
    prep_time_s: float            # modelled data-preparation time; on a
                                  # topology plane this INCLUDES sampling
    merge_depth: int
    # modelled sampling time folded into prep_time_s (0 on planes without a
    # topology store; per-hop detail lives on blocks.hop_reports)
    sample_time_s: float = 0.0
    # critical-path prep after prefetch overlap; None at construction
    # resolves to prep_time_s (synchronous planes expose everything)
    exposed_prep_s: float | None = None

    def __post_init__(self) -> None:
        if self.exposed_prep_s is None:
            self.exposed_prep_s = self.prep_time_s


class GIDSDataLoader:
    def __init__(self, graph: CSRGraph, features: np.ndarray,
                 config: LoaderConfig | None = None,
                 ssd: SSDSpec = INTEL_OPTANE,
                 train_ids: np.ndarray | None = None,
                 tracer=None):
        self.graph = graph
        self.config = cfg = config or LoaderConfig()
        self.rng = np.random.default_rng(cfg.seed)
        self.train_ids = (train_ids if train_ids is not None
                          else np.arange(graph.num_nodes))
        self.spec = DataPlaneSpec.resolve(cfg.data_plane)
        self.plane: DataPlane = self.spec.build(graph, features, config=cfg)
        self.store = self.plane.store
        self.accumulator = DynamicAccessAccumulator(
            ssd, AccumulatorConfig(target_efficiency=cfg.target_efficiency,
                                   n_ssd=cfg.n_ssd,
                                   max_merge_iters=max(cfg.window_depth, 8)))
        self.timeline = StorageTimeline(ssd, cfg.n_ssd)
        # a sharded backstop prices per shard queue: hand the timeline the
        # per-shard device specs (heterogeneous arrays keep their own; a
        # spec-less tier inherits this loader's device on every shard)
        backstop = self.store.tiers[-1]
        if hasattr(backstop, "resolve_shard_specs"):
            if getattr(backstop, "n_shards", 1) > 1 and cfg.n_ssd > 1:
                raise ValueError(
                    f"n_ssd={cfg.n_ssd} with a {backstop.n_shards}-shard "
                    "storage tier: the legacy pooled-queue multiplier and "
                    "per-shard queues model the same devices twice — on a "
                    "sharded plane set n_shards (one queue per SSD) and "
                    "leave n_ssd=1")
            self.timeline.shard_specs = backstop.resolve_shard_specs(ssd)
        # multi-host backstop (core/hosts.py): the timeline additionally
        # needs each host's link spec — sharded bursts then price through
        # price_host_burst, composing local drains with link transit
        if hasattr(backstop, "resolve_hosts"):
            self.timeline.host_specs = backstop.resolve_hosts(ssd)
        # topology plane: sampling reads a tiered adjacency store and is
        # priced per hop (plan_next becomes a priced stage).  The store owns
        # its own StorageTimeline — the edge-page namespace drains separate
        # queues from the feature namespace
        self.topo: TieredTopologyStore | None = None
        if self.plane.topology:
            if cfg.sampler != "neighbor":
                raise ValueError(
                    f"topology plane {self.spec.name!r} requires the "
                    f"'neighbor' sampler (got {cfg.sampler!r}): LADIES "
                    "scores whole frontier columns, not page-local "
                    "adjacency reads, so its storage traffic is not "
                    "page-priceable")
            if hasattr(backstop, "topology_page_shard") \
                    and backstop.n_shards > 1:
                # co-partitioned cluster: the feature backstop's OWN host
                # table places the CSR edge pages — one placement decision
                # drives both namespaces, not two independent stripes
                topo_kwargs = dict(
                    n_shards=backstop.n_shards,
                    page_shard=backstop.topology_page_shard(),
                    shard_specs=backstop.resolve_shard_specs(ssd))
            else:
                topo_kwargs = dict(n_shards=cfg.n_shards,
                                   placement=cfg.placement)
            self.topo = TieredTopologyStore.from_graph(
                graph, admission=cfg.topo_admission,
                gpu_fraction=cfg.topo_gpu_fraction,
                host_fraction=cfg.topo_host_fraction,
                ssd=ssd, n_ssd=cfg.n_ssd, seed=cfg.seed, **topo_kwargs)
        # adaptive data plane: an adaptive placement/admission gets its
        # feedback controller (core/feedback.py).  Both tick once per priced
        # burst in _feedback_step; a static plane carries None and pays
        # nothing
        self.rebalancer: ShardRebalancer | None = None
        if hasattr(getattr(backstop, "placement", None), "plan_rebalance"):
            self.rebalancer = ShardRebalancer(
                backstop, self.timeline,
                bytes_per_row=features.shape[1] * features.dtype.itemsize,
                interval=cfg.rebalance_interval,
                threshold=cfg.imbalance_threshold,
                horizon=cfg.migration_horizon)
        self.topo_refresher: TopologyRefresher | None = None
        if self.topo is not None and self.topo.touches is not None:
            self.topo_refresher = TopologyRefresher(
                self.topo, interval=cfg.rebalance_interval,
                horizon=cfg.migration_horizon)
        # fault plane (core/faults.py): schedule-driven burst re-pricing,
        # per-shard health telemetry, and replica failover routing.  All
        # three stay None on a fault-free, unreplicated plane — which is
        # what keeps every default preset bit-identical.
        self.fault_injector = None
        self.health: ShardHealthMonitor | None = None
        n_queue_shards = getattr(backstop, "n_shards", 1)
        if cfg.replication_factor > 1 \
                and not hasattr(backstop, "placement"):
            raise ValueError(
                f"replication_factor={cfg.replication_factor} needs a "
                "sharded storage backstop (a *-sharded data plane with "
                "n_shards >= 2) — the unsharded plane has no replica "
                "queues to fail over to")
        if cfg.fault_schedule is not None:
            from .faults import FaultInjector
            self.fault_injector = FaultInjector(
                cfg.fault_schedule, n_queue_shards,
                replication=cfg.replication_factor)
            self.timeline.injector = self.fault_injector
            if self.topo is not None:
                # the topology namespace drains its own queues, so it gets
                # its OWN injector (independent burst counter) over the
                # same schedule: edge-page reads see brownouts/outages too
                self.topo.timeline.injector = FaultInjector(
                    cfg.fault_schedule, self.topo.n_shards)
        if n_queue_shards > 1 and (cfg.fault_schedule is not None
                                   or cfg.replication_factor > 1):
            self.health = ShardHealthMonitor(n_queue_shards)
            if self.rebalancer is not None:
                self.rebalancer.monitor = self.health
        if cfg.replication_factor > 1:
            from .faults import FailoverRouter
            backstop.router = FailoverRouter(
                backstop.placement, monitor=self.health,
                injector=self.fault_injector)
        self._lookahead: deque[tuple[dict, SampledBlocks]] = deque()
        self._win_idx = 0   # lookahead entries already pushed to cache window
        # merged-window planes stage whole executed windows here (snapshot
        # kept per batch so a checkpoint mid-window resumes that batch)
        self._merged_ready: deque[tuple[dict, Batch]] = deque()
        self._requests_per_iter = 0
        self.prefetch = (PrefetchEngine(self, self.plane.prefetch_depth)
                         if self.plane.prefetch_depth > 0 else None)
        # observability plane (repro.obs): off by default through the shared
        # no-op tracer.  An enabled tracer observes stage timings, builds
        # per-batch span trees, and receives burst/controller telemetry in
        # its MetricsRegistry — but never feeds back into sampling or
        # pricing, so features, blocks, and every priced float are
        # bit-identical either way.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._window_index = 0
        self._plans = 0
        if self.tracer.enabled:
            for tier in self.store.tiers:
                if hasattr(tier, "tracer"):
                    tier.tracer = self.tracer
            self.timeline.metrics = self.tracer.metrics
            if self.topo is not None:
                self.topo.timeline.metrics = self.tracer.metrics
            if self.rebalancer is not None:
                self.rebalancer.tracer = self.tracer
            if self.topo_refresher is not None:
                self.topo_refresher.tracer = self.tracer
            if hasattr(backstop, "record_metrics"):
                # static cluster telemetry (cut fraction, expected remote
                # share) — computed once, it never changes without a commit
                backstop.record_metrics(self.tracer.metrics)

    # -- sampling -------------------------------------------------------------
    def _sample_one(self) -> SampledBlocks:
        cfg = self.config
        seeds = self.rng.choice(self.train_ids, size=cfg.batch_size,
                                replace=len(self.train_ids) < cfg.batch_size)
        if cfg.sampler == "neighbor":
            if self.topo is not None:
                # same math, same RNG stream — blocks bit-identical to the
                # host sampler, plus per-hop priced TopologyGatherReports
                from repro.sampling.tiered import tiered_sample_blocks
                return tiered_sample_blocks(self.graph, self.topo, seeds,
                                            cfg.fanouts, self.rng,
                                            tracer=self.tracer)
            return host_sample_blocks(self.graph, seeds, cfg.fanouts, self.rng)
        elif cfg.sampler == "ladies":
            return ladies_sample_blocks(self.graph, seeds,
                                        cfg.ladies_layer_sizes, self.rng)
        raise ValueError(cfg.sampler)

    def _refill_lookahead(self) -> int:
        """Run sampling ahead until the accumulator's merge depth is covered.
        Planes without lookahead (mmap) sample synchronously, depth 1; a
        windowed tier floors the depth at its window size.  A merged plane
        samples one cache-window PAST the merge window, so the merged access
        can pin its fills by the NEXT window's reuse (the per-batch path
        gets the same look-ahead one batch at a time)."""
        if not self.plane.lookahead:
            depth = 1
        else:
            depth = self.accumulator.merge_depth(
                max(self._requests_per_iter, 1))
            depth = max(depth, self.plane.min_lookahead)
        sample_ahead = depth
        if self.plane.merge_execute:
            sample_ahead = depth + self.plane.min_lookahead
        while len(self._lookahead) < sample_ahead:
            # snapshot the sampler PRNG before sampling so a checkpoint
            # resumes at the logical consumption point, not the sampling
            # frontier (the lookahead queue is rebuilt deterministically)
            snap = {"rng": self.rng.bit_generator.state,
                    "requests_per_iter": self._requests_per_iter}
            self._lookahead.append((snap, self._sample_one()))
        self._sync_window()
        return depth

    def _sync_window(self) -> None:
        """Keep the windowed tier's look-ahead = first `window_depth`
        lookahead entries.  The lookahead may run deeper than the window
        (accumulator merge depth > window depth); extra batches are
        sampled-ahead only."""
        wt = self.store.windowed_tier
        if wt is None or wt.window_depth == 0:
            return
        while (len(wt.window) < wt.window_depth
               and self._win_idx < len(self._lookahead)):
            self.store.push_window(
                self._lookahead[self._win_idx][1].all_nodes)
            self._win_idx += 1

    # -- the two pipeline stages ----------------------------------------------
    def plan_next(self) -> BatchPlan:
        """Stage 1: sampling + admit-side staging.  Refills the lookahead
        (sampling ahead, window admits), pops the next batch's blocks."""
        index = self._plans
        self._plans += 1
        with self.tracer.stage("plan_next", batch=index):
            depth = self._refill_lookahead()
            snap, blocks = self._lookahead.popleft()
            self._win_idx = max(0, self._win_idx - 1)
            self._requests_per_iter = blocks.num_requests
        return BatchPlan(blocks=blocks, merge_depth=depth, snapshot=snap,
                         index=index)

    def execute(self, plan: BatchPlan) -> Batch:
        """Stage 2: data movement + pricing.  Folds the tier stack over the
        plan's nodes, gathers the rows, prices the tier split."""
        blocks = plan.blocks
        with self.tracer.stage("execute", batch=plan.index):
            rows, report = self.store.gather(blocks.all_nodes)
            with self.tracer.stage("price"):
                self.accumulator.update(report.n_requests, report.redirected)
                outstanding = self.accumulator.outstanding(
                    blocks.num_requests)
                prev_burst = self.timeline.shard_burst
                gather_s = self.plane.price(self.timeline, report,
                                            outstanding)
                charge = self._feedback_step(blocks.all_nodes, None)
                t = gather_s + charge
                # a topology plane priced the sampling stage when the blocks
                # were drawn (plan_next); prep now covers the full Fig. 1
                # path
                sample_s = float(getattr(blocks, "sample_time_s", 0.0))
                if self.tracer.enabled:
                    self._trace_batch(plan.index, blocks, report, gather_s,
                                      charge, t + sample_s, prev_burst)
        return Batch(blocks=blocks, features=rows, report=report,
                     prep_time_s=t + sample_s, merge_depth=plan.merge_depth,
                     sample_time_s=sample_s)

    def _feedback_step(self, node_ids: np.ndarray,
                       counts: np.ndarray | None) -> float:
        """One adaptive-plane tick per priced burst: record the burst's
        measured node touches, let each controller consider a (priced)
        re-placement, and return the burst's amortized share of any
        committed migration cost — folded into prep, so adaptive-vs-static
        comparisons are net of migration IOs.  A static plane returns 0.0
        without touching a thing."""
        charge = 0.0
        if self.health is not None \
                and self.timeline.shard_burst is not None:
            # the monitor sees every priced burst's per-shard drains —
            # detection is a function of priced telemetry, nothing else
            self.health.observe(self.timeline.shard_burst)
        if self.rebalancer is not None:
            self.rebalancer.observe(node_ids, counts)
            charge += self.rebalancer.step()
        if self.topo_refresher is not None:
            charge += self.topo_refresher.step()
        return charge

    # -- span-tree construction (enabled tracer only) --------------------------
    def _trace_hops(self, root, blocks) -> None:
        """One `sample` child holding the per-hop spans.  Its duration is
        `sample_time_s` itself, so the root's children add up in the same
        grouping as the prep time (`share + sample_time_s`)."""
        reports = getattr(blocks, "hop_reports", ())
        if not reports:
            return
        sample = root.child("sample", float(blocks.sample_time_s),
                            cat="sample")
        for r in reports:
            hbm, host, sto = r.pages_by_tier
            sample.child(f"sample/hop{r.hop}", float(r.time_s), cat="sample",
                         edge_reads=r.n_edge_reads, frontier=r.n_frontier,
                         pages_hbm=hbm, pages_host=host, pages_storage=sto)

    def _trace_batch(self, index: int, blocks, report, gather_s: float,
                     charge: float, prep_s: float, prev_burst) -> None:
        """One per-batch virtual span tree, tagged with the plan's `index`
        like the batch's wall stages: root duration is exactly
        `Batch.prep_time_s`, sequential children partition it into the
        per-hop sampling, the priced gather, and any feedback charge;
        per-shard/per-host drains (and fault recovery sub-events) overlay
        the gather span on their own tracks."""
        tr = self.tracer
        root = tr.batch("batch", track="pipeline", index=index,
                        requests=report.n_requests)
        self._trace_hops(root, blocks)
        g = root.child("gather", float(gather_s), cat="gather",
                       n_storage=report.n_storage,
                       n_host=report.n_host_hits, n_hbm=report.n_hbm_hits)
        burst = self.timeline.shard_burst
        if burst is not None and burst is not prev_burst:
            attach_burst_spans(g, burst)
        if charge:
            root.child("feedback", float(charge), cat="feedback")
        root.close(float(prep_s))
        self._record_batch_metrics(blocks, gather_s, charge, prep_s)

    def _trace_window(self, plans, window_report, gather_s: float,
                      charge: float, burst_s: float, prev_burst) -> None:
        """A merged window's spans: one window-level span (merged gather +
        feedback, on its own track) whose duration is the window's total
        priced burst, plus one batch tree per plan whose gather child is the
        batch's amortized share of that burst."""
        tr = self.tracer
        win = tr.batch("window", track="window", cat="window",
                       index=self._window_index, batches=len(plans),
                       requests=window_report.window_requests,
                       unique=window_report.n_unique)
        g = win.child("merged_gather", float(gather_s), cat="gather",
                      n_storage=window_report.n_storage,
                      n_lines=window_report.n_storage_lines,
                      n_host=window_report.n_host_hits,
                      n_hbm=window_report.n_hbm_hits)
        burst = self.timeline.shard_burst
        if burst is not None and burst is not prev_burst:
            attach_burst_spans(g, burst)
        if charge:
            win.child("feedback", float(charge), cat="feedback")
        win.close(float(burst_s))
        m = tr.metrics
        if window_report.n_unique:
            m.histogram("merged.dedup_factor").observe(
                window_report.window_requests / window_report.n_unique)
        if window_report.n_storage_lines:
            m.histogram("merged.coalesce_factor").observe(
                window_report.n_storage_unique
                / window_report.n_storage_lines)
        prep = burst_s / len(plans)
        for p in plans:
            sample_s = float(getattr(p.blocks, "sample_time_s", 0.0))
            root = tr.batch("batch", track="pipeline", index=p.index,
                            window=self._window_index)
            self._trace_hops(root, p.blocks)
            root.child("gather_share", float(prep), cat="gather",
                       window=self._window_index)
            root.close(float(prep + sample_s))
            self._record_batch_metrics(p.blocks, prep, 0.0, prep + sample_s)
        self._window_index += 1

    def _record_batch_metrics(self, blocks, gather_s: float, charge: float,
                              prep_s: float) -> None:
        """Fold one batch's per-stage priced seconds and the tier stack's
        cumulative hit telemetry into the registry (benchmarks/roofline.py
        decomposes the Fig. 1 prep path from exactly these counters)."""
        from .tiers import record_tier_metrics
        m = self.tracer.metrics
        m.counter("pipeline.batches").inc()
        m.counter("stage_s.sample").inc(
            float(getattr(blocks, "sample_time_s", 0.0)))
        m.counter("stage_s.gather").inc(float(gather_s))
        m.counter("stage_s.feedback").inc(float(charge))
        m.counter("stage_s.prep").inc(float(prep_s))
        record_tier_metrics(self.store.tiers, m)

    # -- merged-window execution ------------------------------------------------
    def plan_window(self) -> list[BatchPlan]:
        """Stage 1 for a whole merged window: plan `merge_depth` consecutive
        batches (the depth the first plan's accumulator decision reports —
        the lookahead already holds that many staged samples).  Each plan
        keeps its own resume snapshot, so a checkpoint mid-window restores
        the exact unconsumed batch."""
        plans = [self.plan_next()]
        if self.plane.merge_execute:
            while len(plans) < plans[0].merge_depth:
                plans.append(self.plan_next())
        return plans

    def execute_window(self, plans: Sequence[BatchPlan]) -> list[Batch]:
        """Stage 2 for a merged window: dedupe the plans' request lists into
        one `MergedWindow`, fold the tier stack once over the unique set,
        gather each unique row exactly once, scatter rows back per batch via
        the inverse index, and price the whole window as one line-coalesced
        storage burst amortized equally across its batches.

        Features are bit-identical to `execute()` run per plan; the reports
        (tier telemetry) and modelled times differ — that difference IS the
        modelled speedup of the §3.2 merge."""
        with self.tracer.stage("execute_window", batch=plans[0].index,
                               n_plans=len(plans)):
            merged = self.accumulator.merge(
                [p.blocks.all_nodes for p in plans])
            # retire the consumed window entries and stage the NEXT window's
            # into the freed slots: the one merged access then consumes this
            # window's reuse reservations (multiplicity decrements) while its
            # fills pin lines the upcoming window will reuse
            self.store.retire_window(len(plans))
            self._sync_window()
            rows_list, reports, window_report = \
                self.store.gather_merged(merged)
            with self.tracer.stage("price"):
                # one telemetry update per window: the merged burst's unique
                # split (what actually reached storage), not per-batch raw
                # counts
                self.accumulator.update(window_report.n_requests,
                                        window_report.redirected)
                prev_burst = self.timeline.shard_burst
                gather_s = self.timeline.price_merged_burst(window_report)
                # the window is one priced burst, so it is one feedback
                # tick: the unique request set (with window multiplicity) is
                # what the plane measured, and any migration charge
                # amortizes across the window's batches like the burst
                charge = self._feedback_step(merged.unique_nodes,
                                             merged.batch_multiplicity())
                burst_s = gather_s + charge
                if self.tracer.enabled:
                    self._trace_window(plans, window_report, gather_s,
                                       charge, burst_s, prev_burst)
            prep = burst_s / len(plans)
            # each batch's own priced sampling time rides on top of its
            # amortized share of the window's feature burst
            out = []
            for p, rows, rep in zip(plans, rows_list, reports):
                sample_s = float(getattr(p.blocks, "sample_time_s", 0.0))
                out.append(Batch(blocks=p.blocks, features=rows, report=rep,
                                 prep_time_s=prep + sample_s,
                                 merge_depth=len(plans),
                                 sample_time_s=sample_s))
        return out

    # -- iteration -------------------------------------------------------------
    def __iter__(self) -> Iterator[Batch]:
        while True:
            yield self.next_batch()

    def next_batch(self, compute_s: float = 0.0) -> Batch:
        """Deliver the next batch.  `compute_s` is the model-compute time of
        the iteration this batch's preparation overlapped with; a prefetching
        plane discounts the exposed prep time by it (a synchronous plane
        exposes the full prep and ignores it)."""
        if self.prefetch is not None:
            return self.prefetch.next(compute_s)
        if self.plane.merge_execute:
            if not self._merged_ready:
                plans = self.plan_window()
                for p, b in zip(plans, self.execute_window(plans)):
                    self._merged_ready.append((p.snapshot, b))
            return self._merged_ready.popleft()[1]
        return self.execute(self.plan_next())

    # -- state for checkpoint/restart (fault tolerance) -----------------------
    def state_dict(self) -> dict:
        if self.prefetch is not None:
            snap = self.prefetch.oldest_snapshot()
            if snap is not None:
                return self._with_tier_state(dict(snap))
        if self._merged_ready:
            # mid-window: the oldest executed-but-unconsumed batch's snapshot
            return self._with_tier_state(dict(self._merged_ready[0][0]))
        if self._lookahead:
            return self._with_tier_state(dict(self._lookahead[0][0]))
        return self._with_tier_state(
            {"rng": self.rng.bit_generator.state,
             "requests_per_iter": self._requests_per_iter})

    def _with_tier_state(self, state: dict) -> dict:
        """Attach durable tier state (shard placement assignment) to a
        sampler snapshot.  Cache contents rebuild deterministically on
        resume and are deliberately absent; placement is namespace layout
        and must round-trip.

        Capture happens at CHECKPOINT time, not when the snapshot's batch
        was staged (a per-snapshot copy would clone the whole placement
        table for every staged batch — prohibitive at real node counts).
        Contract for a future mutable placement: quiesce staged batches
        (drain the prefetch queue / finish the merged window) before
        mutating, else resume replays staged work under the post-mutation
        assignment."""
        tier_state = self.store.state_dict()
        if tier_state:
            state["tier_state"] = tier_state
        # fault plane: the injector's burst counter (what retry/hedge
        # decisions are a function of) and the health EMAs must resume —
        # a mid-brownout checkpoint replays the same recovery choices
        fault_state = {}
        if self.fault_injector is not None:
            fault_state["injector"] = self.fault_injector.state_dict()
        if self.topo is not None and self.topo.timeline.injector is not None:
            fault_state["topo_injector"] = \
                self.topo.timeline.injector.state_dict()
        if self.health is not None:
            fault_state["monitor"] = self.health.state_dict()
        if fault_state:
            state["fault_state"] = fault_state
        return state

    def load_state_dict(self, state: dict) -> None:
        self.rng.bit_generator.state = state["rng"]
        self._requests_per_iter = state["requests_per_iter"]
        self._lookahead.clear()
        self._win_idx = 0
        # resume must be bit-identical to a freshly-built loader fed the same
        # state: drop tier contents AND the accumulator's merge-depth EMA
        # (and any batches the prefetch engine staged past the resume point)
        if self.prefetch is not None:
            self.prefetch.reset()
        self._merged_ready.clear()
        self.plane.reset()
        if "tier_state" in state:
            self.store.load_state_dict(state["tier_state"])
        fault_state = state.get("fault_state", {})
        if "injector" in fault_state:
            if self.fault_injector is None:
                raise ValueError(
                    "checkpoint carries fault-injector state but this "
                    "plane has no fault_schedule — resume with the same "
                    "LoaderConfig.fault_schedule or recovery decisions "
                    "diverge from the checkpointed run")
            self.fault_injector.load_state_dict(fault_state["injector"])
        elif self.fault_injector is not None:
            self.fault_injector.reset()
        topo_injector = None if self.topo is None \
            else self.topo.timeline.injector
        if "topo_injector" in fault_state:
            if topo_injector is None:
                raise ValueError(
                    "checkpoint carries a topology-plane fault-injector "
                    "state but this plane has none — resume with the same "
                    "fault_schedule on the same topology preset")
            topo_injector.load_state_dict(fault_state["topo_injector"])
        elif topo_injector is not None:
            topo_injector.reset()
        if "monitor" in fault_state:
            if self.health is None:
                raise ValueError(
                    "checkpoint carries shard-health state but this plane "
                    "has no monitor (no fault_schedule / replication)")
            self.health.load_state_dict(fault_state["monitor"])
        elif self.health is not None:
            self.health.reset()
        self.accumulator.reset_telemetry()
        # telemetry is epoch-local: a resumed run must never report the
        # pre-restore run's last burst (or its spans / registry contents)
        # as its own — pricing state above already reset, so clearing the
        # observers cannot change any priced float
        self.timeline.reset_telemetry()
        if self.topo is not None:
            self.topo.timeline.reset_telemetry()
        self.tracer.reset()
