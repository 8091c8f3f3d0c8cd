"""Readings that the limits of `correct` are set from; not part of a run.

    python benchmarks/chip/calibrate.py --workload <cell> --seeds <n>...

For each seed, in one process: the cell's data and loader, the program's
checked steps through the timed loop, and the plain reference after them.
Beside the program, the same steps are followed by the reference in
bfloat16 (the control) and with half of the batch left out of the loss
(a fault), each compared with the float32 reference as the program is.
One JSON line per seed: {seed, program, control, half_batch}, each the
three calibrated gaps.  A state left unchanged reads 1 on both norm gaps
by their definition and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (ROOT / "src", HERE):
        sys.path.insert(0, str(p))
    import jax

    import run
    from gnnbench import cells, correct, loop, reference

    cell = cells.resolve(ROOT / "BENCHMARK.json", args.workload)
    run.require_chips(jax, cell.chips)
    loop.log(f"compile cache: {run.enable_cache(jax)}")
    tr, cfg = cell.traffic, cell.config
    lr = float(cfg["lr"])
    _, graph, split = loop.make_structure(cfg)
    for seed in args.seeds:
        t = time.perf_counter()
        feats, labels = loop.make_inputs(cfg, seed)
        trainer = loop.build(cell, seed, graph, feats, labels, split, None)
        p0, checked = trainer.params, []
        for _ in range(loop.CHECK_STEPS):
            b, st = trainer.step()
            checked.append((b.blocks, trainer.params, float(st.loss)))
        del trainer, b, st
        gc.collect()
        batches = []
        for blk, _, _ in checked:
            table, levels = reference.batch_inputs(feats, blk.seeds,
                                                   blk.hop_nodes)
            batches.append((table, levels, labels[blk.seeds]))
        ref_l, ref_s = reference.follow(p0, batches, tr["fanouts"], lr)
        out = {"seed": seed, "program": correct.trajectory_gaps(
            p0, [c[2] for c in checked], [c[1] for c in checked], ref_l,
            ref_s, lr)}
        for variant in ("bf16", "half_batch"):
            v_l, v_s = reference.follow(p0, batches, tr["fanouts"], lr,
                                        variant)
            out["control" if variant == "bf16" else variant] = \
                correct.trajectory_gaps(p0, v_l, v_s, ref_l, ref_s, lr)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
        del feats, labels, checked, batches
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
