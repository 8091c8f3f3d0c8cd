"""The benchmark's yardstick on the CPU: trace reduction, FLOP and byte
arithmetic, resolving cells by name, and the refusal to run off a TPU."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from gnnbench import arith, cells, correct, devtrace  # noqa: E402

RECORDED = HERE / "testdata" / "toy_step.xplane.pb"


# -- trace reduction ---------------------------------------------------------

def test_merge_and_gaps():
    s, e = devtrace.merge(np.array([5, 0, 2, 20, 21]),
                          np.array([7, 3, 4, 22, 30]), 1, 25)
    assert s.tolist() == [1, 5, 20] and e.tolist() == [4, 7, 25]
    assert devtrace.gaps(s, e, 0, 30) == [(0, 1), (4, 5), (7, 20), (25, 30)]
    s, e = devtrace.merge(np.array([]), np.array([]), 0, 1)
    assert devtrace.gaps(s, e, 0, 1) == [(0, 1)]


def _toy_trace():
    ev = devtrace.Events.of
    ops = ev([("segment_mean.3", 10, 30), ("fusion.1", 25, 40),
              ("segment_mean", 60, 70), ("tiered_gather.7", 80, 90),
              ("copy.2", 200, 210)])
    mods = ev([("jit_sgd_step(4)", 10, 70), ("jit_device_gather", 80, 95)])
    notes = ev([("next_batch", 0, 55), ("sgd_step", 55, 75),
                ("wait", 76, 100)])
    return devtrace.DeviceTrace(ops={0: ops}, modules={0: mods},
                                annotations=notes, lo=0, hi=100)


def test_device_trace_reduction():
    tr = _toy_trace()
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s() == pytest.approx(50e-9)      # 10-40, 60-70, 80-90
    # idle 0-10, 40-60, 70-80, 90-100, longest first
    assert tr.idle_gaps() == [["next_batch", pytest.approx(20e-9)],
                              ["next_batch", pytest.approx(10e-9)],
                              ["sgd_step", pytest.approx(10e-9)],
                              ["wait", pytest.approx(10e-9)]]
    assert tr.idle_gaps(top=1) == [["next_batch", pytest.approx(20e-9)]]
    seg = tr.kernel("segment_mean")
    assert len(seg) == 2 and seg.total_s() == pytest.approx(30e-9)
    assert len(tr.kernel("segment")) == 0
    assert tr.program("sgd_step").total_s() == pytest.approx(60e-9)
    assert tr.program("device_gather").total_s() == pytest.approx(15e-9)
    top = dict(tr.top_ops())
    assert top["segment_mean"] == pytest.approx(30e-9)
    assert "copy" not in top                        # outside the window


def test_host_label_is_innermost_open_annotation():
    tr = _toy_trace()
    tr.annotations = devtrace.Events.of(
        [("next_batch", 0, 100), ("upload", 40, 60)])
    assert tr.host_label(50) == "upload"
    assert tr.host_label(70) == "next_batch"
    assert tr.host_label(150) == "none"


def test_recorded_chip_trace():
    """A trace recorded on a v5e by `record_test_trace.py`: two annotated
    steps with the `tiered_gather` and `segment_mean` kernels, and a 10 ms
    host pause in each `next_batch`."""
    from gnnbench import loop
    tr = devtrace.load(RECORDED, loop.ANNOTATIONS)
    assert tr is not None and list(tr.ops) == [0]
    assert tr.annotations.name == list(loop.ANNOTATIONS) * 2
    assert 0 < tr.busy_s() < tr.window_s
    assert len(tr.kernel("tiered_gather")) == 2
    seg = tr.kernel("segment_mean")
    assert len(seg) == 2 and 0 < seg.total_s() < tr.busy_s()
    assert len(tr.program("sgd_step")) == 2
    assert dict(tr.top_ops())["segment_mean"] == pytest.approx(seg.total_s())
    gaps = tr.idle_gaps(top=10**6)
    assert {g[0] for g in gaps} <= set(loop.ANNOTATIONS)
    assert sum(g[1] for g in gaps) == pytest.approx(
        tr.window_s - tr.busy_s())
    assert sum(g[1] > 0.009 for g in gaps if g[0] == "next_batch") == 2
    assert tr.idle_gaps() == gaps[:10]


# -- arithmetic --------------------------------------------------------------

def test_peaks_keyed_by_kind():
    assert arith.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        arith.peaks("cpu")


def test_sage_step_flops():
    # one layer, fanout 2, batch 1: levels 1 and 2 nodes; D=4, H=3, C=5
    # layer 0 at level 0: two 1x4 @ 4x3 products, forward + dW only
    got = arith.sage_step_flops(1, [2], 4, 3, 5)
    assert got == 2 * (2 * 1 * 4 * 3) * 2 + 3 * 2 * 1 * 3 * 5
    igb = arith.sage_step_flops(1024, [10, 5, 5], 1024, 128, 19)
    assert igb == pytest.approx(68.5e9, rel=0.01)


def test_kernel_costs_and_least_time():
    peak = arith.peaks("TPU v5 lite")
    b = arith.tiered_gather_bytes(65536, 1024, 4)
    assert b == 2 * 65536 * 4096
    assert arith.least_time_s(0, b, peak) == pytest.approx(b / 819e9)
    calls = arith.segment_mean_calls(1024, [10, 5, 5])
    assert calls == [(1024, 10), (10240, 5), (51200, 5)]
    fl, by = zip(*(arith.segment_mean_cost(n, f, 1024, 4) for n, f in calls))
    assert sum(by) == (317440 + 62464) * 4096
    assert sum(fl) == 317440 * 1024
    assert arith.least_time_s(sum(fl), sum(by), peak) == sum(by) / 819e9


def test_step_mfu_reader():
    from gnnbench.loop import Record, Step
    cell = cells.resolve(ROOT / "BENCHMARK.json", "igb-sage.train")
    steps = [Step(t0=2.0 * i, t1=2.0 * i + 2.0, unique=1, report=None,
                  loss=None) for i in range(4)]
    rec = Record(cell=cell, steps=steps, window_s=8.0, spans=[],
                 profiled=steps[:1], trace=None,
                 peak=arith.peaks("TPU v5 lite"))
    flops = arith.sage_step_flops(1024, [10, 5, 5], 1024, 128, 19)
    assert cells.reader("step.mfu")(rec) == pytest.approx(
        100 * flops / 2.0 / 197e12)
    assert cells.reader("tiered_gather_roofline")(rec) is None


# -- data --------------------------------------------------------------------

@pytest.mark.parametrize("workers", [1, 3])
def test_rmat_keys_depend_on_the_seed_alone(monkeypatch, workers):
    from gnnbench import data
    want = data.rmat_keys(500, 4000, 7)
    monkeypatch.setattr(data, "threads", lambda: workers)
    got = data.rmat_keys(500, 4000, 7)
    np.testing.assert_array_equal(got, want)
    assert len(got) == 4000 and len(np.unique(got)) == 4000
    assert np.all(np.diff(got) > 0) and not np.any(got // 500 == got % 500)
    assert not np.array_equal(data.rmat_keys(500, 4000, 8), want)
    np.testing.assert_array_equal(data.feature_table(300, 4, 7),
                                  data.feature_table(300, 4, 7))


# -- correctness numbers -----------------------------------------------------

def test_sampled_edges_invalid_counts_each_fault():
    # 0->1, 0->2, 1->2, 2->0; node 3 has no edges and samples itself
    edges = correct.EdgeSet.of_pairs(np.array([0, 0, 1, 2, 0]),
                            np.array([1, 2, 2, 0, 1]), 4)
    split = np.array([0, 1, 3])
    seeds, hops = [0, 3], [np.array([1, 2, 3, 3])]
    ok = dict(edges=edges, train_ids=split, batch=2, fanouts=[2])
    assert correct.sampled_edges_invalid(**ok, seeds=seeds, hops=hops,
                                         all_nodes=[0, 1, 2, 3]) == 0
    assert correct.sampled_edges_invalid(                  # 0->3 is no edge
        **ok, seeds=seeds, hops=[np.array([1, 3, 3, 3])],
        all_nodes=[0, 1, 3]) == 1
    assert correct.sampled_edges_invalid(                  # 3 samples 0
        **ok, seeds=seeds, hops=[np.array([1, 2, 0, 3])],
        all_nodes=[0, 1, 2, 3]) == 1
    assert correct.sampled_edges_invalid(                  # 2 not in split
        **ok, seeds=[0, 2], hops=[np.array([1, 2, 0, 0])],
        all_nodes=[0, 1, 2]) == 1
    assert correct.sampled_edges_invalid(                  # node 2 dropped
        **ok, seeds=seeds, hops=hops, all_nodes=[0, 1, 3]) == 1


def test_trajectory_gaps():
    p0 = {"a": np.ones(4), "b": np.full(2, 2.0)}
    step = lambda p, g: {k: p[k] - 0.1 * g[k] for k in p}
    g = {"a": np.array([1.0, -1, 0, 2]), "b": np.array([0.5, 0.5])}
    ref = [step(p0, g)]
    ref += [step(ref[-1], g), step(step(ref[-1], g), g)]
    same = correct.trajectory_gaps(p0, [1.0, 0.9, 0.8], ref,
                                   [1.0, 0.9, 0.8], ref, 0.1)
    assert same == pytest.approx({"loss_gap": 0, "grad_norm_gap": 0,
                                  "update_norm_gap": 0}, abs=1e-12)
    frozen = correct.trajectory_gaps(p0, [1.01, 1.01, 1.01], [p0] * 3,
                                     [1.0, 0.9, 0.8], ref, 0.1)
    assert frozen["grad_norm_gap"] == pytest.approx(1.0)
    assert frozen["update_norm_gap"] == pytest.approx(1.0)
    assert frozen["loss_gap"] == pytest.approx(0.21 / 0.8)


def test_judge():
    limits = {k: 0 for k in correct.EXACT}
    limits.update({k: 0.1 for k in correct.CALIBRATED})
    good = {k: 0 for k in correct.EXACT + correct.CALIBRATED}
    assert correct.judge(good, limits)[0] is True
    assert correct.judge({**good, "feature_rows_wrong": 1}, limits)[0] is False
    assert correct.judge({**good, "loss_gap": float("nan")}, limits)[0] is False
    ok, out = correct.judge({**good, "loss_gap": 0.2}, limits)
    assert not ok and out["loss_gap"] == {"value": 0.2, "limit": 0.1}


# -- cells -------------------------------------------------------------------

def test_cells_resolve_by_name():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = cells.resolve(ROOT / "BENCHMARK.json", w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert cell.limits_path.is_file()
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert callable(cells.reader(m["name"]))
    with pytest.raises(KeyError):
        cells.resolve(ROOT / "BENCHMARK.json", "no-such.cell")


def test_config_files_match_benchmark_json():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert cfg[key] != cfg["published"][key]


def test_refuses_without_tpu(capsys):
    import run
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", "igb-sage.train", "--seed", "1",
                  "--seconds", "1"])
    assert exc.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out
