"""Wall stages inside the loader: nesting with parent ids and one batch
index per batch, the device tier's probe split into `stage_rows`, `h2d`,
`device_wait` and `d2h` under `execute`, the `price` stage, the transfer
byte counters on `GatherReport`, the stages' mirror on the JAX profiler's
host plane, and the benchmark's readers of all of them."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import GIDSDataLoader, LoaderConfig
from repro.graph.synthetic import rmat_graph
from repro.obs import NULL_TRACER, Tracer, validate_trace, validate_tracer

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
PROBE_STAGES = ("stage_rows", "h2d", "device_wait", "d2h")


@pytest.fixture(scope="module")
def graph_and_feats():
    g = rmat_graph(2_000, 10, 16, seed=3)
    feats = np.random.default_rng(5).standard_normal(
        (g.num_nodes, 16)).astype(np.float32)
    return g, feats


def _loader(g, feats, preset="gids-device", tracer=None):
    return GIDSDataLoader(g, feats, LoaderConfig(
        batch_size=64, fanouts=(3, 3), data_plane=preset, cache_lines=512,
        window_depth=2), tracer=tracer)


def _bucket(n):
    return max(8, 1 << (n - 1).bit_length())


def test_nested_stages_record_parent_and_batch():
    tr = Tracer()
    with tr.stage("plan_next", batch=7):
        with tr.stage("sample"):
            pass
    with tr.stage("execute", batch=7):
        with tr.stage("stage_rows"):
            with tr.stage("inner"):
                pass
        with tr.stage("price"):
            pass
    with tr.stage("plan_next", batch=8):
        pass
    w = {s.name + str(s.batch): s for s in tr.wall_spans()}
    assert len({s.sid for s in tr.wall_spans()}) == 7
    assert w["plan_next7"].parent is None and w["execute7"].parent is None
    assert w["sample7"].parent == w["plan_next7"].sid
    assert w["stage_rows7"].parent == w["execute7"].sid
    assert w["inner7"].parent == w["stage_rows7"].sid
    assert w["price7"].parent == w["execute7"].sid
    assert w["plan_next8"].batch == 8
    for s in tr.wall_spans():
        if s.parent is not None:
            p = next(q for q in tr.wall_spans() if q.sid == s.parent)
            assert p.wall_t0 <= s.wall_t0
            assert s.wall_t0 + s.wall_dur <= p.wall_t0 + p.wall_dur
    assert validate_trace(tr) == []
    ev = {(e["name"], e["args"]["batch"]): e["args"]
          for e in tr.chrome_events() if e.get("pid") == 2 and e["ph"] == "X"}
    assert ev[("inner", 7)]["parent"] == ev[("stage_rows", 7)]["id"]
    assert ev[("execute", 7)]["parent"] is None


def test_validate_catches_wall_stage_outside_its_parent():
    tr = Tracer()
    with tr.stage("execute", batch=0):
        with tr.stage("h2d"):
            pass
    child = next(s for s in tr.wall_spans() if s.name == "h2d")
    child.wall_dur += 10.0
    assert any("escapes its parent" in p for p in validate_tracer(tr))
    child.wall_dur -= 10.0
    child.batch = 1
    assert any("differs from its parent" in p for p in validate_tracer(tr))


def test_execute_holds_the_probe_and_price_stages(graph_and_feats):
    g, feats = graph_and_feats
    tr = Tracer()
    dl = _loader(g, feats, tracer=tr)
    for _ in range(4):
        dl.next_batch()
    spans = tr.wall_spans()
    execs = [s for s in spans if s.name == "execute"]
    assert len(execs) == 4
    for ex in execs:
        kids = [s for s in spans if s.parent == ex.sid]
        assert sorted(k.name for k in kids) == sorted(PROBE_STAGES
                                                      + ("price",))
        assert sum(k.wall_dur for k in kids) <= ex.wall_dur
        assert {k.batch for k in kids} == {ex.batch}
    top = [(s.batch, s.name) for s in spans if s.parent is None]
    assert sorted(top) == [(i, n) for i in range(4)
                           for n in ("execute", "plan_next")]
    # the priced span tree of a batch carries the same index
    assert [r.args["index"] for r in tr.roots()] == list(range(4))
    assert validate_trace(tr) == []


@pytest.mark.parametrize("preset", ["gids-device", "gids"])
def test_transfer_bytes_are_the_bucket_arithmetic(graph_and_feats, preset):
    g, feats = graph_and_feats
    dl = _loader(g, feats, preset)
    row = feats.shape[1] * feats.itemsize
    for _ in range(3):
        b = dl.next_batch()
        pad = _bucket(len(b.blocks.all_nodes))
        if preset == "gids-device":
            # int32 ids and reuse counts up, bool hit mask back
            assert b.report.h2d_bytes == pad * (row + 8)
            assert b.report.d2h_bytes == pad * (row + 1)
        else:
            assert b.report.h2d_bytes == b.report.d2h_bytes == 0


def test_null_tracer_records_nothing_and_features_match(graph_and_feats):
    g, feats = graph_and_feats
    plain = _loader(g, feats)
    traced = _loader(g, feats, tracer=Tracer())
    assert plain.store.tiers[0].tracer is NULL_TRACER
    assert traced.store.tiers[0].tracer is traced.tracer
    for _ in range(4):
        a, b = plain.next_batch(), traced.next_batch()
        np.testing.assert_array_equal(a.blocks.all_nodes, b.blocks.all_nodes)
        np.testing.assert_array_equal(a.features, b.features)
        assert a.report == b.report and a.prep_time_s == b.prep_time_s
        assert (a.report.h2d_bytes, a.report.d2h_bytes) == \
            (b.report.h2d_bytes, b.report.d2h_bytes)
    assert NULL_TRACER.wall_spans() == [] and NULL_TRACER.chrome_events() == []


def test_stages_land_on_the_profiler_host_plane(graph_and_feats, tmp_path):
    import jax

    g, feats = graph_and_feats
    dl = _loader(g, feats, tracer=Tracer())
    dl.next_batch()                      # compile outside the profile
    jax.profiler.start_trace(str(tmp_path))
    try:
        dl.next_batch()
    finally:
        jax.profiler.stop_trace()
    xp = next(tmp_path.rglob("*.xplane.pb"))
    host = [p for p in jax.profiler.ProfileData.from_file(str(xp)).planes
            if p.name == "/host:CPU"]
    names = {ev.name for p in host for line in p.lines for ev in line.events}
    assert {"plan_next", "execute", "price", *PROBE_STAGES} <= names


# -- the benchmark's readers of these stages and counters ---------------------

READERS = [
    ("loader.stage_ms", 1e3 * 0.3 / 2),
    ("loader.h2d_ms", 1e3 * 0.04 / 2),
    ("loader.device_wait_ms", 1e3 * 1.5 / 2),
    ("loader.d2h_ms", 1e3 * 0.06 / 2),
    ("loader.price_ms", 1e3 * 0.002 / 2),
    ("loader.h2d_mb", (3_000_000 + 1_000_000) / 1e6 / 2),
    ("loader.d2h_mb", (2_000_000 + 500_000) / 1e6 / 2),
    ("device_tier.access_rounds", (11 + 9) / 2),
]


@pytest.mark.parametrize("name,want", READERS, ids=[n for n, _ in READERS])
def test_loader_stage_readers(name, want):
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from gnnbench import cells
    from gnnbench.loop import Record, Step

    def step(h2d, d2h, rounds):
        report = SimpleNamespace(h2d_bytes=h2d, d2h_bytes=d2h,
                                 access_rounds=rounds)
        return Step(t0=0.0, t1=1.0, unique=1, report=report, loss=None)

    spans = [("plan_next", 0.01), ("execute", 2.0), ("stage_rows", 0.1),
             ("h2d", 0.04), ("device_wait", 1.5), ("d2h", 0.06),
             ("price", 0.002), ("stage_rows", 0.2)]
    rec = Record(cell=None, steps=[step(3_000_000, 2_000_000, 11),
                                   step(1_000_000, 500_000, 9)],
                 window_s=2.0, spans=spans, profiled=[], trace=None,
                 peak=None)
    read = cells.reader(name, BENCH)
    assert read(rec) == pytest.approx(want)
    # what a program without these stages and counters leaves: nothing
    bare = Record(cell=None, steps=[Step(t0=0.0, t1=1.0, unique=1,
                                         report=SimpleNamespace(), loss=None)],
                  window_s=1.0, spans=[("execute", 2.0)], profiled=[],
                  trace=None, peak=None)
    assert read(bare) is None
