"""Aggregate experiments/dryrun/*.json into the §Roofline table
(markdown + CSV under experiments/), plus the data-plane stage roofline:
the per-stage split of priced prep time (sample / gather / feedback) read
from the observability plane's MetricsRegistry — the `stage_s.*` counters
the traced pipeline accumulates — instead of re-deriving it by walking
batches and reports."""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import row

DRYRUN = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
OUT = DRYRUN.parent

SHAPE_ORDER = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def load_records():
    recs = []
    for fn in sorted(DRYRUN.glob("*.json")):
        r = json.loads(fn.read_text())
        if "hillclimb" in fn.name or r.get("tag"):
            continue
        recs.append(r)
    return recs


def fmt_table(recs, mesh: str) -> str:
    lines = ["| arch | shape | status | strat | peak GiB/dev | compute s | "
             "memory s | collective s | bottleneck | useful |",
             "|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(recs, key=lambda r: (r["arch"],
                                         SHAPE_ORDER.index(r["shape"]))):
        if r["mesh"] != mesh:
            continue
        if not r.get("roofline"):
            lines.append(f"| {r['arch']} | {r['shape']} | {r['status']} |"
                         " — | — | — | — | — | — | — |")
            continue
        ro = r["roofline"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['status']} "
            f"| {r.get('strategy','')} "
            f"| {r['memory']['peak_per_device_gib']:.1f} "
            f"| {ro['compute_term']:.4f} | {ro['memory_term']:.4f} "
            f"| {ro['collective_term']:.4f} | {ro['bottleneck']} "
            f"| {ro['useful_ratio']:.3f} |")
    return "\n".join(lines)


def data_plane_stage_split(iters: int = 16) -> dict:
    """Per-stage priced-seconds split of the merged topo plane, consumed
    from the metrics registry of a traced run.  The split is exactly what
    the pricing charged (the counters are incremented with the same floats
    the batches carry), so ``prep == sample + gather + feedback`` holds to
    float eps."""
    import numpy as np

    from repro.core import GIDSDataLoader, LoaderConfig
    from repro.graph.synthetic import rmat_graph
    from repro.obs import Tracer

    g = rmat_graph(20_000, 12, 32, seed=1)
    feats = np.zeros((g.num_nodes, 32), np.float32)
    tracer = Tracer()
    dl = GIDSDataLoader(g, feats, LoaderConfig(
        batch_size=256, fanouts=(10, 5), data_plane="gids-topo-merged",
        cache_lines=4096, window_depth=4, seed=3), tracer=tracer)
    for _ in range(iters):
        dl.next_batch()

    m = tracer.metrics
    stages = {name: m.counter(f"stage_s.{name}").value
              for name in ("sample", "gather", "feedback", "prep")}
    n = m.counter("pipeline.batches").value or 1.0
    out = {f"{k}_s": v for k, v in stages.items()}
    out["n_batches"] = n
    out["split_residual_s"] = stages["prep"] - (
        stages["sample"] + stages["gather"] + stages["feedback"])
    return out


def main():
    sp = data_plane_stage_split()
    n = sp["n_batches"]
    for stage in ("sample", "gather", "feedback"):
        share = (sp[f"{stage}_s"] / sp["prep_s"]) if sp["prep_s"] else 0.0
        row(f"roofline_dataplane_{stage}", sp[f"{stage}_s"] / n * 1e6,
            f"share={share:.3f}")
    row("roofline_dataplane_prep", sp["prep_s"] / n * 1e6,
        f"residual={sp['split_residual_s']:.3e}s")

    recs = load_records()
    OUT.mkdir(parents=True, exist_ok=True)
    ok = [r for r in recs if r.get("status") == "OK"]
    skip = [r for r in recs if str(r.get("status", "")).startswith("SKIP")]
    fail = [r for r in recs if str(r.get("status", "")).startswith("FAIL")]
    row("roofline_cells", 0.0,
        f"ok={len(ok)}_skip={len(skip)}_fail={len(fail)}")
    for mesh in ("16x16", "2x16x16"):
        md = fmt_table(recs, mesh)
        (OUT / f"roofline_{mesh}.md").write_text(md + "\n")
    # csv
    csv = ["arch,shape,mesh,status,strategy,peak_gib,compute_s,memory_s,"
           "collective_s,bottleneck,useful_ratio"]
    for r in recs:
        ro = r.get("roofline") or {}
        mem = r.get("memory") or {}
        csv.append(",".join(str(x) for x in [
            r["arch"], r["shape"], r["mesh"], r.get("status"),
            r.get("strategy", ""), mem.get("peak_per_device_gib", ""),
            ro.get("compute_term", ""), ro.get("memory_term", ""),
            ro.get("collective_term", ""), ro.get("bottleneck", ""),
            ro.get("useful_ratio", "")]))
    (OUT / "roofline.csv").write_text("\n".join(csv) + "\n")
    # headline stats for the bench log
    if ok:
        worst = min((r for r in ok if r["shape"] == "train_4k"),
                    key=lambda r: r["roofline"]["useful_ratio"],
                    default=None)
        if worst:
            row("roofline_worst_train_useful", 0.0,
                f"{worst['arch']}_{worst['mesh']}="
                f"{worst['roofline']['useful_ratio']:.3f}")
        collbound = [r for r in ok
                     if r["roofline"]["bottleneck"] == "collective"]
        row("roofline_collective_bound_cells", 0.0, str(len(collbound)))


if __name__ == "__main__":
    main()
