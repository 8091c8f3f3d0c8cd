"""Observability-plane benchmark + CI gate (ISSUE 10).

Runs the merged training plane and the multi-tenant serve plane with an
ENABLED tracer and pins the three properties that make tracing safe to
leave on:

  * **bit-invisibility** — prep floats, sampled blocks, and gathered
    bytes are exactly equal to an untraced run of the same config;
  * **span-sum reconciliation** — every batch span tree sums to its
    `Batch.prep_time_s` (and serve request spans to end-to-end latency)
    within float eps;
  * **valid export** — the merged-window trace renders as well-formed
    Chrome trace-event JSON (nested spans, monotone per-track starts),
    loadable in Perfetto.

`export()` writes the Perfetto artifact (`trace.json`) and the metrics
snapshot (`metrics.json`) that `benchmarks/run.py --trace` publishes from
CI; `headline()` returns the gate booleans for BENCH_*.json.
"""
from __future__ import annotations

import json

import numpy as np

from benchmarks.common import row
from repro.core import GIDSDataLoader, LoaderConfig, SAMSUNG_980PRO
from repro.graph.synthetic import rmat_graph
from repro.obs import Tracer, validate_trace

RECONCILE_EPS = 1e-9


def _graph_and_feats(num_nodes: int = 20_000, seed: int = 1):
    g = rmat_graph(num_nodes, 12, 32, seed=seed)
    feats = np.random.default_rng(0).standard_normal(
        (g.num_nodes, 32)).astype(np.float32)
    return g, feats


def _loader(g, feats, tracer=None, preset: str = "gids-topo-merged"):
    return GIDSDataLoader(g, feats, LoaderConfig(
        batch_size=256, fanouts=(10, 5), data_plane=preset,
        cache_lines=4096, window_depth=4, seed=3),
        ssd=SAMSUNG_980PRO, tracer=tracer)


def traced_run(iters: int = 16, preset: str = "gids-topo-merged"):
    """One traced merged-window run plus its untraced twin's batches."""
    g, feats = _graph_and_feats()
    plain = _loader(g, feats, preset=preset)
    untraced = [plain.next_batch() for _ in range(iters)]
    tracer = Tracer()
    dl = _loader(g, feats, tracer=tracer, preset=preset)
    traced = [dl.next_batch() for _ in range(iters)]
    return tracer, traced, untraced


def _bit_invisible(traced, untraced) -> bool:
    for a, b in zip(traced, untraced):
        if a.prep_time_s != b.prep_time_s:
            return False
        if a.sample_time_s != b.sample_time_s:
            return False
        if not np.array_equal(a.blocks.all_nodes, b.blocks.all_nodes):
            return False
        if not np.array_equal(a.features, b.features):
            return False
    return True


def _spans_reconciled(tracer, traced) -> tuple[bool, float]:
    roots = [r for r in tracer.roots() if r.name == "batch"]
    if len(roots) != len(traced):
        return False, float("inf")
    err = max((abs(r.dur - b.prep_time_s)
               for r, b in zip(roots, traced)), default=0.0)
    err = max(err, tracer.max_reconcile_error())
    return err <= RECONCILE_EPS, err


def headline(iters: int = 16) -> dict:
    tracer, traced, untraced = traced_run(iters=iters)
    problems = validate_trace(tracer)
    events = tracer.chrome_events()
    reconciled, err = _spans_reconciled(tracer, traced)
    snap = tracer.metrics.snapshot()
    return {
        "tracer_bit_invisible": _bit_invisible(traced, untraced),
        "spans_reconciled": reconciled,
        "max_reconcile_error": err,
        "trace_valid": not problems,
        "n_trace_problems": len(problems),
        "n_trace_events": len(events),
        "n_batch_spans": sum(1 for r in tracer.roots()
                             if r.name == "batch"),
        "n_metric_keys": len(snap),
    }


def export(trace_path: str = "trace.json",
           metrics_path: str = "metrics.json", iters: int = 16) -> dict:
    """Write the Perfetto trace + metrics snapshot artifacts for CI and
    return the headline gate numbers computed from the same run."""
    tracer, traced, untraced = traced_run(iters=iters)
    problems = validate_trace(tracer)
    events = tracer.write(trace_path)
    snap = tracer.metrics.snapshot()
    with open(metrics_path, "w") as f:
        json.dump(snap, f, indent=2, default=float)
        f.write("\n")
    reconciled, err = _spans_reconciled(tracer, traced)
    print(f"# wrote {trace_path} ({len(events)} events) and "
          f"{metrics_path} ({len(snap)} metrics)", flush=True)
    return {
        "tracer_bit_invisible": _bit_invisible(traced, untraced),
        "spans_reconciled": reconciled,
        "max_reconcile_error": err,
        "trace_valid": not problems,
        "n_trace_problems": len(problems),
        "n_trace_events": len(events),
    }


def main():
    out = headline()
    row("trace/bit_invisible", 0.0, str(out["tracer_bit_invisible"]))
    row("trace/spans_reconciled", 0.0,
        f"max_err={out['max_reconcile_error']:.3e}")
    row("trace/valid_chrome_json", 0.0,
        f"{out['n_trace_events']} events, "
        f"{out['n_trace_problems']} problems")
    row("trace/metrics", 0.0, f"{out['n_metric_keys']} keys")


if __name__ == "__main__":
    main()
