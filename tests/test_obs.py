"""Observability plane (repro.obs): metrics registry semantics, span-tree
layout and Chrome export, trace validation, and — the load-bearing
invariant — bit-invisibility: an enabled tracer never changes a single
priced number, sampled block, or gathered byte anywhere in the data
plane, including across a mid-window checkpoint/resume."""
import json
import warnings

import numpy as np
import pytest

from repro.core import GIDSDataLoader, LoaderConfig
from repro.graph.synthetic import rmat_graph
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       NULL_METRICS, NULL_TRACER, Tracer, attach_burst_spans,
                       validate_events, validate_trace, validate_tracer)


@pytest.fixture(scope="module")
def graph_and_feats():
    g = rmat_graph(4_000, 12, 16, seed=7)
    feats = np.random.default_rng(3).standard_normal(
        (g.num_nodes, 24)).astype(np.float32)
    return g, feats


def _loader(g, feats, preset, tracer=None, **kw):
    cfg = LoaderConfig(batch_size=128, fanouts=(5, 5), data_plane=preset,
                       cache_lines=2048, window_depth=4, **kw)
    return GIDSDataLoader(g, feats, cfg, tracer=tracer)


# -- metrics registry ----------------------------------------------------------

def test_registry_instruments():
    m = MetricsRegistry()
    m.counter("a").inc()
    m.counter("a").inc(2.5)
    m.gauge("g").set(4.0)
    h = m.histogram("h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    assert m.counter("a").value == 3.5
    assert m.gauge("g").value == 4.0
    assert h.count == 3 and h.mean == 2.0 and h.min == 1.0 and h.max == 3.0
    snap = m.snapshot()
    assert snap["a"]["type"] == "counter" and snap["a"]["value"] == 3.5
    assert snap["h"]["count"] == 3
    json.dumps(snap)   # snapshot must be JSON-serializable as-is
    m.reset()
    assert m.snapshot() == {}


def test_registry_get_or_create_is_stable():
    m = MetricsRegistry()
    assert m.counter("x") is m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")   # name already bound to a different instrument type


def test_null_metrics_inert():
    NULL_METRICS.counter("x").inc(5)
    NULL_METRICS.histogram("y").observe(1.0)
    assert NULL_METRICS.snapshot() == {}


def test_instrument_classes_standalone():
    c, g, h = Counter("c"), Gauge("g"), Histogram("h")
    c.inc(2)
    g.set(-1.0)
    h.observe(0.5)
    assert c.value == 2 and g.value == -1.0 and h.count == 1


# -- span trees and export -----------------------------------------------------

def test_span_tree_layout_and_reconcile():
    tr = Tracer()
    root = tr.batch("batch", index=0)
    root.child("sample", 2.0)
    root.child("gather", 3.0)
    root.child("shard0", 2.5, track="shard0", parallel=True)
    root.close()
    assert root.dur == 5.0                       # sequential sum
    assert root.reconcile_error() == 0.0
    assert tr.max_reconcile_error() == 0.0
    assert validate_tracer(tr) == []
    # lazy layout: children packed from the root start, parallel overlay at t0
    seq = [c for c in root.children if not c.parallel]
    assert seq[0].t0 == root.t0 and seq[1].t0 == root.t0 + 2.0
    par = [c for c in root.children if c.parallel][0]
    assert par.t0 == root.t0


def test_chrome_export_schema():
    tr = Tracer()
    root = tr.batch("batch")
    root.child("gather", 1.0, rows=np.int64(7))
    root.close()
    tr.instant("migration", cost_s=0.25)
    with tr.stage("plan_next", batch=0):
        pass
    events = tr.chrome_events()
    assert validate_events(events) == []
    by_ph = {}
    for ev in events:
        by_ph.setdefault(ev["ph"], []).append(ev)
    assert len(by_ph["X"]) == 3 and len(by_ph["i"]) == 1
    # numpy args were jsonified
    gather = next(e for e in by_ph["X"] if e["name"] == "gather")
    assert gather["args"]["rows"] == 7 and isinstance(
        gather["args"]["rows"], int)
    json.dumps(events)


def test_trace_write_is_perfetto_loadable(tmp_path):
    tr = Tracer()
    tr.batch("b").child("g", 1.0)
    path = tmp_path / "trace.json"
    tr.write(str(path))
    doc = json.loads(path.read_text())
    assert "traceEvents" in doc
    assert validate_trace(doc) == []


def test_validate_catches_escaping_child():
    tr = Tracer()
    root = tr.batch("b")
    root.child("too-long", 2.0)
    root.close(1.0)           # child escapes parent interval
    assert any("escapes" in p for p in validate_tracer(tr))


def test_null_tracer_records_nothing():
    s = NULL_TRACER.batch("b")
    assert s.child("x", 1.0) is s
    with NULL_TRACER.stage("s", batch=0) as sp:
        assert sp is s
    assert NULL_TRACER.wall_spans() == []
    assert NULL_TRACER.chrome_events() == []
    assert NULL_TRACER.metrics.snapshot() == {}


def test_attach_burst_spans_duck_typed():
    class FakeBurst:
        per_shard_s = (0.5, 0.0)
        per_shard_rows = (10, 0)
        per_shard_lines = (4, 0)

        def recovery_events(self):
            return [("retry", 0, {"lines": 2, "recovery_s": 0.1})]

    tr = Tracer()
    root = tr.batch("b")
    g = root.child("gather", 0.5)
    attach_burst_spans(g, FakeBurst())
    names = [c.name for c in g.children]
    assert names == ["shard0", "fault/retry"]      # zero-work shard skipped
    assert all(c.parallel for c in g.children)
    root.close()
    assert validate_tracer(tr) == []


# -- bit-invisibility over the priced pipeline ---------------------------------

PRESETS = ["gids", "gids-merged", "gids-topo-merged", "gids-merged-sharded",
           "gids-hosts-merged"]


def _preset_kwargs(preset):
    if preset == "gids-merged-sharded":
        return {"n_shards": 4}
    if preset == "gids-hosts-merged":
        return {"n_hosts": 4, "placement": "metis-lite"}
    return {}


@pytest.mark.parametrize("preset", PRESETS)
def test_tracer_bit_invisible(graph_and_feats, preset):
    """Enabled tracer vs no tracer: every priced time and every gathered
    byte must be EXACTLY equal — observation never perturbs the plane."""
    g, feats = graph_and_feats
    kw = _preset_kwargs(preset)
    plain = _loader(g, feats, preset, **kw)
    traced = _loader(g, feats, preset, tracer=Tracer(), **kw)
    for _ in range(6):
        a, b = plain.next_batch(), traced.next_batch()
        assert a.prep_time_s == b.prep_time_s
        assert a.sample_time_s == b.sample_time_s
        np.testing.assert_array_equal(a.blocks.all_nodes, b.blocks.all_nodes)
        np.testing.assert_array_equal(a.features, b.features)
    probs = validate_trace(traced.tracer)
    assert probs == [], probs[:5]


def test_tracer_bit_invisible_across_checkpoint(graph_and_feats):
    """Checkpoint mid-window and resume, once untraced and once traced:
    the traced pair must replay the untraced pair bit-for-bit.  (A resumed
    stream may legitimately re-price its open window differently from a
    never-interrupted run; the tracer must not add to that.)"""
    g, feats = graph_and_feats

    def resume_run(tracer_factory):
        first = _loader(g, feats, "gids-merged", tracer=tracer_factory())
        got = [first.next_batch() for _ in range(3)]
        state = first.state_dict()
        resumed = _loader(g, feats, "gids-merged", tracer=tracer_factory())
        resumed.load_state_dict(state)
        got += [resumed.next_batch() for _ in range(3)]
        return got, resumed

    want, _ = resume_run(lambda: None)
    got, resumed = resume_run(Tracer)
    for a, b in zip(want, got):
        assert a.prep_time_s == b.prep_time_s
        np.testing.assert_array_equal(a.features, b.features)
    assert validate_trace(resumed.tracer) == []


def test_trace_covers_pipeline_stages(graph_and_feats):
    g, feats = graph_and_feats
    tr = Tracer()
    dl = _loader(g, feats, "gids-topo-merged", tracer=tr)
    for _ in range(6):
        dl.next_batch()
    roots = tr.roots()
    names = {sp.name for r in roots for sp in r.walk()}
    assert any(r.name.startswith("window") for r in roots)
    assert any(n.startswith("sample/hop") for n in names)
    assert "merged_gather" in names and "gather_share" in names
    wall = {w.name for w in dl.tracer.wall_spans()}
    assert {"plan_next", "execute_window", "sample"} <= wall
    snap = tr.metrics.snapshot()
    assert snap["pipeline.batches"]["value"] >= 6.0
    assert "topo.hops" in snap and "topo.edge_reads" in snap
    by_sid = {w.sid: w for w in dl.tracer.wall_spans()}
    assert all(by_sid[w.parent].name == "plan_next"
               for w in dl.tracer.wall_spans() if w.name == "sample")
    assert any(k.startswith("tier.") and k.endswith("hit_ratio")
               for k in snap)


def test_fault_recovery_spans(graph_and_feats):
    """Retry/hedge/failover telemetry surfaces as parallel fault spans and
    faults.* counters when a schedule injects into a traced sharded run."""
    from repro.core.faults import (BrownoutEvent, FaultSchedule,
                                   FlakyReadsEvent, OutageEvent)
    g, feats = graph_and_feats
    fs = FaultSchedule(events=(
        BrownoutEvent(shard=2, start=0, end=90, multiplier=10.0),
        OutageEvent(shard=0, start=1, end=7),
        FlakyReadsEvent(shard=1, start=0, end=90, fail_prob=0.4)), seed=3)
    tr = Tracer()
    dl = _loader(g, feats, "gids-merged-sharded", tracer=tr, n_shards=4,
                 placement="degree", fault_schedule=fs,
                 replication_factor=2)
    for _ in range(16):
        dl.next_batch()
    fault_spans = [sp for r in tr.roots() for sp in r.walk()
                   if sp.name.startswith("fault/")]
    assert fault_spans, "fault schedule produced no fault spans"
    snap = tr.metrics.snapshot()
    assert any(k.startswith("faults.") for k in snap)
    assert snap["storage.bursts"]["value"] > 0   # sharded bursts were noted
    assert validate_trace(tr) == []


# -- telemetry reset on restore (the stale-burst regression) -------------------

def test_restore_clears_stale_burst_telemetry(graph_and_feats):
    """load_state_dict must drop the pre-restore epoch's last burst and
    telemetry: a restored loader reports None until it prices a burst of
    its own, instead of resurfacing another run's straggler profile."""
    g, feats = graph_and_feats
    tr = Tracer()
    dl = _loader(g, feats, "gids-merged-sharded", tracer=tr, n_shards=4)
    for _ in range(4):
        dl.next_batch()
    assert dl.timeline.shard_burst is not None
    state = dl.state_dict()
    assert dl.tracer.metrics.snapshot()   # non-empty before restore

    dl.load_state_dict(state)
    assert dl.timeline.shard_burst is None
    assert dl.tracer.roots() == []
    assert dl.tracer.metrics.snapshot() == {}
    # and the loader still runs after the reset
    assert dl.next_batch().prep_time_s > 0.0
    assert dl.timeline.shard_burst is not None


def test_deprecated_accessors_warn(graph_and_feats):
    g, feats = graph_and_feats
    dl = _loader(g, feats, "gids-merged")
    dl.next_batch()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        burst = dl.timeline.last_shard_burst
        _ = dl.timeline.last_host_burst
    assert burst is dl.timeline.shard_burst
    assert len(caught) == 2
    assert all(issubclass(w.category, DeprecationWarning) for w in caught)


# -- serve engine --------------------------------------------------------------

def _serve_setup():
    from repro.serve import TenantSpec, generate_stream
    g = rmat_graph(2_000, 10, 16, seed=3)
    feats = np.random.default_rng(0).standard_normal(
        (g.num_nodes, 16)).astype(np.float32)
    tenants = [TenantSpec("a"), TenantSpec("b", arrival="mmpp")]
    reqs = generate_stream(g.num_nodes, tenants, offered_qps=2000,
                           n_requests=40, seed=5)
    return g, feats, reqs


def test_serve_tracer_bit_invisible():
    from repro.serve import GNNServeConfig, GNNServeEngine
    g, feats, reqs = _serve_setup()
    cfg = GNNServeConfig(fanouts=(5, 3), cache_lines=512, tenants=2)
    r0 = GNNServeEngine(g, feats, cfg).run(reqs)
    tr = Tracer()
    r1 = GNNServeEngine(g, feats, cfg, tracer=tr).run(reqs)
    for a, b in zip(r0.records, r1.records):
        assert (a.rid, a.latency_s, a.queue_wait_s, a.sample_s, a.gather_s,
                a.forward_s, a.rejected) == \
               (b.rid, b.latency_s, b.queue_wait_s, b.sample_s, b.gather_s,
                b.forward_s, b.rejected)
    probs = validate_trace(tr)
    assert probs == [], probs[:5]
    snap = tr.metrics.snapshot()
    assert snap["serve.requests"]["value"] == len(reqs)
    assert snap["serve.windows"]["value"] == len(r1.windows)
    # one request span per served request, on its tenant's track
    req_spans = [r for r in tr.roots() if r.name == "request"]
    assert len(req_spans) == len(r1.served)
    assert {sp.track for sp in req_spans} <= {"tenant0", "tenant1"}
