"""A cell resolved by name from `BENCHMARK.json` into the files that
belong to it:

    configs/<config>.json     the configuration's sizes
    traffic/<traffic>.json    the traffic mix's parameters
    limits/<cell>.json        the limits of the numbers that decide `correct`
    metrics/<metric>.py       one reader per per-layer metric

A later cell, mix or metric is added as files under these names and an
entry in `BENCHMARK.json`; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits_path: Path
    end_to_end: list      # metric entries this cell reports
    per_layer: list
    bench_dir: Path = BENCH_DIR


def _read(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    return json.loads(path.read_text())


def _reported(metric: dict, cell: str, reported_e2e: set | None) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported_e2e is None or metric.get("moves") in reported_e2e


def resolve(bench_json: Path, cell: str, bench_dir: Path = BENCH_DIR) -> Cell:
    spec = _read(bench_json)
    entry = next((w for w in spec["workloads"] if w["name"] == cell), None)
    if entry is None:
        known = [w["name"] for w in spec["workloads"]]
        raise KeyError(f"no workload {cell!r} in {bench_json}; known: {known}")
    config = _read(bench_dir / "configs" / f"{entry['config']}.json")
    traffic = _read(bench_dir / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _reported(m, cell, None)]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _reported(m, cell, names)]
    return Cell(name=cell, chips=int(entry["chips"]), config=config,
                traffic=traffic, limits_path=bench_dir / "limits"
                / f"{cell}.json", end_to_end=e2e, per_layer=layer,
                bench_dir=bench_dir)


def reader(name: str, bench_dir: Path = BENCH_DIR):
    """The `read(record)` function of `metrics/<name>.py`."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "gnnbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
