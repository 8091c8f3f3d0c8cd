"""On-chip benchmark of GNN training through `GIDSDataLoader`.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the TPU it is started on and prints,
as the last line of standard output, one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics with `--trace 1`), `device`, with
`--trace 1` a `breakdown`, and last `checks`: each number that decided
`correct` with its limit.  The same numbers are the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.  Profiles and other run
outputs go under `benchmarks/chip/out/`.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
OUT = HERE / "out"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(jax, chips: int):
    """The devices, if they are at least `chips` TPUs; otherwise exit
    non-zero.  There is no fallback to another platform."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: JAX found no TPU (device 0 is "
                         f"{devs[0].platform!r}); refusing to run")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs


def enable_cache(jax) -> str:
    """JAX's persistent compilation cache, where the program keeps it (a
    fixed directory inside the checkout, or `JAX_COMPILATION_CACHE_DIR`),
    for every program however short its compile."""
    from repro.runtime import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


def main(argv=None, *, on_chip: bool = True,
         bench_json: Path = ROOT / "BENCHMARK.json",
         bench_dir: Path = HERE) -> int:
    """`on_chip=False` skips the look for a chip (tests on the CPU)."""
    args = parse(argv)
    for p in (ROOT / "src", HERE):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from gnnbench import cells, loop

    cell = cells.resolve(bench_json, args.workload, bench_dir)
    import jax

    devs = require_chips(jax, cell.chips) if on_chip else jax.devices()
    loop.log(f"compile cache: {enable_cache(jax)}")
    OUT.mkdir(exist_ok=True)
    dev = devs[0]
    res = loop.run(cell, args.seed, args.seconds, bool(args.trace), OUT,
                   T_PROCESS, dev.device_kind if on_chip else None,
                   dev.memory_stats)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": res["peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    if res["busy"] is not None:
        device["busy_s"] = res["busy"]["busy_s"]
        device["window_s"] = res["busy"]["window_s"]
        line["breakdown"] = res["busy"]["breakdown"]
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        loop.log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
