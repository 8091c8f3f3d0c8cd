"""Span-sum reconciliation: the trace is an ACCOUNTING of priced time,
so every batch span tree must sum to its `Batch.prep_time_s` and every
serve record's latency breakdown must sum to its end-to-end latency —
exactly for the training pipeline (the spans are built from the very
floats the pricing produced) and within float eps for the serve plane
(whose breakdown re-associates sums).

The sweep runs as seeded parametrized cases everywhere; when `hypothesis`
is installed the same invariants are additionally fuzzed over random
loader shapes."""
import numpy as np
import pytest

from repro.core import GIDSDataLoader, LoaderConfig
from repro.graph.synthetic import rmat_graph
from repro.obs import Tracer, validate_trace

EPS = 1e-9


@pytest.fixture(scope="module")
def graph_and_feats():
    g = rmat_graph(4_000, 12, 16, seed=2)
    feats = np.random.default_rng(1).standard_normal(
        (g.num_nodes, 24)).astype(np.float32)
    return g, feats


def _run_traced(g, feats, preset, n_batches=8, **kw):
    tr = Tracer()
    dl = GIDSDataLoader(g, feats, LoaderConfig(
        batch_size=128, fanouts=(5, 5), data_plane=preset,
        cache_lines=2048, window_depth=4, **kw), tracer=tr)
    batches = [dl.next_batch() for _ in range(n_batches)]
    return tr, batches


PRESETS = [
    ("gids", {}),
    ("gids-merged", {}),
    ("gids-topo-merged", {}),
    ("gids-merged-sharded", {"n_shards": 4}),
    ("gids-hosts-merged", {"n_hosts": 4, "placement": "metis-lite"}),
]


@pytest.mark.parametrize("preset,kw", PRESETS,
                         ids=[p for p, _ in PRESETS])
def test_batch_span_tree_sums_to_prep_time(graph_and_feats, preset, kw):
    """Each batch root's duration IS its prep time, and its sequential
    children account for it with zero (exact float) error — the spans are
    built from the same floats the pricing path produced."""
    g, feats = graph_and_feats
    tr, batches = _run_traced(g, feats, preset, **kw)
    roots = [r for r in tr.roots() if r.name == "batch"]
    assert len(roots) == len(batches)
    for root, batch in zip(roots, batches):
        assert root.dur == batch.prep_time_s
        assert root.reconcile_error() == 0.0
    assert tr.max_reconcile_error() <= EPS
    assert validate_trace(tr) == []


@pytest.mark.parametrize("preset,kw", PRESETS,
                         ids=[p for p, _ in PRESETS])
def test_window_spans_account_merged_bursts(graph_and_feats, preset, kw):
    """On merged planes the window span's duration equals the sum of its
    member batches' gather shares plus the feedback charge — i.e. merged
    amortization is conserved, nothing is double- or under-counted."""
    g, feats = graph_and_feats
    tr, _ = _run_traced(g, feats, preset, **kw)
    batch_roots = [r for r in tr.roots() if r.name == "batch"]
    for win in (r for r in tr.roots() if r.name == "window"):
        gather = next(c for c in win.children if c.name == "merged_gather")
        members = [b for b in batch_roots
                   if b.args.get("window") == win.args["index"]]
        assert len(members) == win.args["batches"]
        shares = [sp.dur for b in members for sp in b.walk()
                  if sp.name == "gather_share"]
        assert len(shares) == len(members)
        assert abs(sum(shares) - win.dur) <= EPS * max(win.dur, 1.0)
        assert gather.dur <= win.dur + EPS


def test_serve_breakdown_sums_to_latency():
    """Every served record: queue wait + window burst + batched forward
    == end-to-end latency (the span children), and the request's OWN
    shares never exceed the window totals."""
    from repro.serve import (GNNServeConfig, GNNServeEngine, TenantSpec,
                             generate_stream)
    g = rmat_graph(2_000, 10, 16, seed=3)
    feats = np.random.default_rng(0).standard_normal(
        (g.num_nodes, 16)).astype(np.float32)
    reqs = generate_stream(
        g.num_nodes, [TenantSpec("a"), TenantSpec("b", arrival="mmpp")],
        offered_qps=2000, n_requests=48, seed=5)
    tr = Tracer()
    cfg = GNNServeConfig(fanouts=(5, 3), cache_lines=512, tenants=2)
    result = GNNServeEngine(g, feats, cfg, tracer=tr).run(reqs)

    req_spans = {sp.args["rid"]: sp for sp in tr.roots()
                 if sp.name == "request"}
    assert len(req_spans) == len(result.served)
    for rec in result.served:
        sp = req_spans[rec.rid]
        assert sp.reconcile_error() <= EPS
        assert abs(sp.dur - rec.latency_s) <= EPS
        parts = {c.name: c for c in sp.children}
        assert parts["queue_wait"].dur == rec.queue_wait_s
        # the record's shares are fractions of the window totals
        assert rec.gather_s <= parts["gather"].dur + EPS
        assert rec.forward_s <= parts["forward"].dur + EPS
    assert validate_trace(tr) == []


def test_serve_window_spans_match_window_traces():
    from repro.serve import (GNNServeConfig, GNNServeEngine, TenantSpec,
                             generate_stream)
    g = rmat_graph(2_000, 10, 16, seed=3)
    feats = np.random.default_rng(0).standard_normal(
        (g.num_nodes, 16)).astype(np.float32)
    reqs = generate_stream(g.num_nodes, [TenantSpec("a")],
                           offered_qps=1500, n_requests=32, seed=7)
    tr = Tracer()
    result = GNNServeEngine(
        g, feats, GNNServeConfig(fanouts=(4, 3), cache_lines=512),
        tracer=tr).run(reqs)
    spans = [r for r in tr.roots() if r.name == "serve_window"]
    assert len(spans) == len(result.windows)
    for sp, w in zip(spans, result.windows):
        assert sp.t0 == w.start_s
        assert sp.dur == w.service_s
        gather = next(c for c in sp.children if c.name == "gather")
        assert gather.dur == w.burst_s
        assert sp.reconcile_error() <= EPS


# -- fuzzed sweep (hypothesis when installed, seeded grid otherwise) -----------

try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # the container may not ship hypothesis
    HAVE_HYPOTHESIS = False


def _check_reconciles(batch_size, fanout, window_depth, n_batches):
    g = rmat_graph(1_000, 8, 8, seed=4)
    feats = np.zeros((g.num_nodes, 8), np.float32)
    tr = Tracer()
    dl = GIDSDataLoader(g, feats, LoaderConfig(
        batch_size=batch_size, fanouts=(fanout, fanout),
        data_plane="gids-merged", cache_lines=1024,
        window_depth=window_depth), tracer=tr)
    batches = [dl.next_batch() for _ in range(n_batches)]
    roots = [r for r in tr.roots() if r.name == "batch"]
    for root, batch in zip(roots, batches):
        assert root.dur == batch.prep_time_s
        assert root.reconcile_error() == 0.0
    assert validate_trace(tr) == []


if HAVE_HYPOTHESIS:
    @hypothesis.given(batch_size=st.integers(16, 256),
                      fanout=st.integers(2, 8),
                      window_depth=st.integers(1, 6),
                      n_batches=st.integers(1, 10))
    @hypothesis.settings(max_examples=20, deadline=None)
    def test_reconciliation_fuzzed(batch_size, fanout, window_depth,
                                   n_batches):
        _check_reconciles(batch_size, fanout, window_depth, n_batches)
else:
    @pytest.mark.parametrize("batch_size,fanout,window_depth,n_batches", [
        (16, 2, 1, 3), (64, 5, 3, 7), (256, 8, 6, 10), (37, 3, 2, 5),
        (128, 6, 4, 8),
    ])
    def test_reconciliation_fuzzed(batch_size, fanout, window_depth,
                                   n_batches):
        _check_reconciles(batch_size, fanout, window_depth, n_batches)
