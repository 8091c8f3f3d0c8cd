"""Tiered GPU-initiated sampling — the priced twin of `host_sample_blocks`.

`tiered_sample_blocks` runs the exact host sampling math (the shared
`neighbor.sample_hop`, consuming the SAME `np.random.Generator` stream, so
blocks are bit-identical to `host_sample_blocks` given the same RNG
snapshot) while additionally resolving every adjacency read against a
`TieredTopologyStore` (core/topology.py): per hop it records which 4 KB
edge pages the sampled reads touched, splits them by placement tier
(GPU-resident hot adjacency / pinned host / storage-backed CSR pages),
and prices the hop through the store's `StorageTimeline` — producing one
`TopologyGatherReport` per hop and a total modelled `sample_time_s`.

That report is what turns `GIDSDataLoader.plan_next()` into a *priced*
pipeline stage: a topology plane (`gids-topo`, `gids-topo-merged`) folds
`sample_time_s` into `Batch.prep_time_s`, so `exposed_prep_s` finally
covers sampling AND feature gather (the paper's full Fig. 1 prep path),
not just the gather half.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.core.topology import TieredTopologyStore, TopologyGatherReport
from repro.graph.csr import CSRGraph
from .neighbor import SampledBlocks, run_sample_hops


@dataclasses.dataclass
class TieredSampledBlocks(SampledBlocks):
    """`SampledBlocks` plus the topology plane's sampling telemetry:
    one priced `TopologyGatherReport` per hop and their summed modelled
    time.  Block fields are bit-identical to the host sampler's."""

    hop_reports: list = dataclasses.field(default_factory=list)
    sample_time_s: float = 0.0


def tiered_sample_blocks(graph: CSRGraph, topo: TieredTopologyStore,
                         seeds: np.ndarray, fanouts: Sequence[int],
                         rng: np.random.Generator,
                         tracer=None) -> TieredSampledBlocks:
    """`tracer` (repro.obs) wall-clocks the whole sampling sweep —
    observation only, the sampled blocks and the per-hop reports are
    identical with or without it."""
    if tracer is None:
        from repro.obs import NULL_TRACER as tracer  # noqa: N811
    reports: list[TopologyGatherReport] = []

    def price_hop(hop: int, read_pos: np.ndarray, n_frontier: int) -> None:
        # only destinations with edges physically read adjacency words; a
        # degree-0 row's positions are self-loop padding (the driver
        # already filtered them out of read_pos)
        reports.append(topo.hop_report(read_pos, hop=hop,
                                       n_frontier=n_frontier))

    with tracer.stage("sample", cat="sample", seeds=len(seeds)):
        hop_nodes, all_nodes, n_req = run_sample_hops(graph, seeds, fanouts,
                                                      rng, hop_cb=price_hop)
        sample_time_s = float(sum(r.time_s for r in reports))
    return TieredSampledBlocks(
        seeds=seeds, hop_nodes=hop_nodes, all_nodes=all_nodes,
        num_requests=n_req, hop_reports=reports,
        sample_time_s=sample_time_s)
