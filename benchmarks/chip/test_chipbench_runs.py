"""Whole runs of the harness on the CPU at toy size, past its look for a
chip: the Pallas kernels run in interpret mode through the same loop as on
the chip.

- A cell added as data: a configuration, a traffic mix, its limits and a
  per-layer metric reader in a directory of their own, found by name.
- The timed path broken underneath, once for each fault a training cell
  on one chip can have, and the bfloat16 control in the program's place:
  `correct` comes out false for each, against the limits of the
  benchmark's own cells.
"""
import functools
import json
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import run  # noqa: E402

NEW_METRIC = '''"""Batches in the window (a counter)."""


def read(rec):
    return float(len(rec.steps))
'''


def toy_cell(tmp: Path, limits_from: str) -> tuple[Path, Path]:
    """A benchmark directory with one new toy cell, `toy.train-toy`, whose
    limits are those of the benchmark's cell `limits_from`."""
    cfg = json.loads((HERE / "configs" / "igb-sage.json").read_text())
    cfg.update(name="toy", num_nodes=3000, num_edges=24000, feature_dim=128,
               num_classes=5, hidden_dim=16, cache_lines=768)
    tr = json.loads((HERE / "traffic" / "train.json").read_text())
    tr.update(name="train-toy", batch_size=16, fanouts=[3, 2, 2],
              warm_batches=4)
    for sub in ("configs", "traffic", "limits", "metrics"):
        (tmp / sub).mkdir()
    (tmp / "configs" / "toy.json").write_text(json.dumps(cfg))
    (tmp / "traffic" / "train-toy.json").write_text(json.dumps(tr))
    shutil.copy(HERE / "limits" / f"{limits_from}.json",
                tmp / "limits" / "toy.train-toy.json")
    (tmp / "metrics" / "toy.window_batches.py").write_text(NEW_METRIC)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": "toy.train-toy", "config": "toy",
                          "traffic": "train-toy", "chips": 1, "why": "toy"}]
    spec["per_layer"] = [{"name": "toy.window_batches", "unit": "batches",
                          "better": "higher", "source": "program_counter",
                          "layer": "host sampling", "moves": "seeds_per_s",
                          "workloads": ["toy.train-toy"]}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp / "BENCHMARK.json", tmp


def toy_run(tmp, capsys, monkeypatch, trace=0, limits="igb-sage.train"):
    monkeypatch.setattr(run, "OUT", tmp / "out")
    monkeypatch.setattr(run, "enable_cache", lambda jax: "off")
    bench_json, bench_dir = toy_cell(tmp, limits)
    assert run.main(["--workload", "toy.train-toy", "--seed",
                     str(2**31 + 12345), "--seconds", "0.3", "--trace",
                     str(trace)], on_chip=False, bench_json=bench_json,
                    bench_dir=bench_dir) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("limits", ["igb-sage.train", "papers-sage.train"])
def test_sound_run_is_correct(tmp_path, capsys, monkeypatch, limits):
    line = toy_run(tmp_path, capsys, monkeypatch, limits=limits)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"seeds_per_s", "step_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


def test_cell_added_as_data(tmp_path, capsys, monkeypatch):
    """A traced run of the toy cell: the harness finds its configuration,
    mix, limits and new metric reader by name."""
    line = toy_run(tmp_path, capsys, monkeypatch, trace=1)
    assert line["correct"] is True
    assert line["metrics"]["toy.window_batches"]["value"] == \
        line["attempted"] > 0


def _reference_step(variant):
    """A variant of the plain reference in the program's place: it takes
    the program's bucketed table and hop indices, which index the table
    by level as the reference's own inputs do."""
    from gnnbench import reference

    def step(gnn, params, feats, hop_idx, labels, lr):
        fn = reference._step_fn(tuple(gnn.cfg.fanouts), variant)
        return fn(params, feats, hop_idx, labels, lr)
    return step


def _unchanged_step():
    import jax

    @functools.partial(jax.jit, static_argnums=0)
    def step(gnn, params, feats, hop_idx, labels, lr):
        return params, gnn.loss(params, feats, hop_idx, labels)
    return step


def _altered_gather(orig):
    def gather(store, ids, staged, future_counts, use_pallas=True):
        store, rows, hits = orig(store, ids, staged, future_counts,
                                 use_pallas=use_pallas)
        return store, rows.at[0, 0].add(1.0), hits
    return gather


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_row", "bf16_control"])
def test_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch,
                                          fault):
    from repro.core import device_store
    from repro.models import gnn

    if fault == "altered_row":
        monkeypatch.setattr(device_store, "device_gather",
                            _altered_gather(device_store.device_gather))
    else:
        monkeypatch.setattr(gnn, "sgd_step", {
            "unchanged_state": _unchanged_step,
            "half_batch": lambda: _reference_step("half_batch"),
            "bf16_control": lambda: _reference_step("bf16")}[fault]())
    line = toy_run(tmp_path, capsys, monkeypatch)
    assert line["correct"] is False
    failed = {k for k, c in line["checks"].items()
              if not c["value"] <= c["limit"]}
    want = {"unchanged_state": {"grad_norm_gap", "update_norm_gap"},
            "half_batch": {"grad_norm_gap"},
            "altered_row": {"feature_rows_wrong"},
            "bf16_control": {"update_norm_gap"}}[fault]
    assert want <= failed, line["checks"]
