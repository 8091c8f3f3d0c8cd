"""One run of a training cell: the loop a user runs, timed and checked.

Set-up makes the data from the seed, builds the loader through the user's
entry point, and drives ONE trainer object through its first steps (the
checked steps, which the reference follows) and the warm batches that
bring the HBM cache to its steady state.  The same object then runs the
measured window:

    b = loader.next_batch()
    upload bucket_rows(b.features), hop_indices(b.blocks), labels
    params, loss = sgd_step(...)
    block on the loss

A traced run profiles one more step after the window, so the profiler
never runs before or inside it.
"""
from __future__ import annotations

import contextlib
import ctypes
import dataclasses
import gc
import math
import resource
import statistics
import sys
import time
from pathlib import Path

from . import arith, correct, data, devtrace, reference
from .cells import Cell, reader

ANNOTATIONS = ("next_batch", "upload", "sgd_step", "wait")
CHECK_STEPS = 3          # set-up's first steps, which the reference follows
CHECK_BATCHES = 4        # window batches drawn from the seed and checked
STRUCTURE_SEED = 1       # graph, train split and the loader's sampling
PROFILE_LOOKAHEAD = 8    # planned batches searched for the one to profile


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def bucket(n: int) -> int:
    """The power-of-two row count `device_store.bucket_rows` pads `n` to."""
    return max(8, 1 << (n - 1).bit_length())


class _MallInfo2(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]


def _mallinfo():
    try:
        fn = ctypes.CDLL(None).mallinfo2
    except (OSError, AttributeError):
        return None
    fn.restype = _MallInfo2
    return fn


MALLINFO = _mallinfo()


def host_counters() -> tuple:
    """Process counters read around each step, to place host stalls: the
    peak resident set in KiB, and glibc's heap bytes (`arena`), mmapped
    bytes (`hblkhd`) and mmapped chunks (`hblks`)."""
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    mi = MALLINFO() if MALLINFO else None
    return (rss,) + ((mi.arena, mi.hblkhd, mi.hblks) if mi else (-1,) * 3)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while
    `active`: JAX reports both as one backend-compile event."""

    def __init__(self):
        import jax
        self.active = False
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, fun_name="?", **kw):
        if self.active and name == "/jax/core/compile/backend_compile_duration":
            self.names.append(fun_name)


@dataclasses.dataclass
class Step:
    t0: float             # next_batch called
    t1: float             # loss ready
    unique: int
    report: object        # the loader's GatherReport
    loss: object          # device scalar
    marks: tuple = ()     # ends of next_batch, bucket copy, upload,
                          # sgd_step dispatch
    counters: tuple = ()  # host_counters() after the step minus before


class Trainer:
    """The user's loop over a `GIDSDataLoader` and the jitted step."""

    def __init__(self, loader, gnn, params, labels, lr, annotate: bool):
        import jax
        import jax.numpy as jnp

        from repro.core.device_store import bucket_rows
        from repro.models.gnn import hop_indices, sgd_step

        self.loader, self.gnn, self.params = loader, gnn, params
        self.labels = labels
        self.lr = jnp.float32(lr)
        self._jnp = jnp
        self._bucket_rows, self._hop_indices = bucket_rows, hop_indices
        self._sgd_step = sgd_step
        self._note = (jax.profiler.TraceAnnotation if annotate
                      else lambda name: contextlib.nullcontext())

    def step(self, plan=None):
        """One turn of the loop.  With `plan` (from `loader.plan_next()`)
        the loader executes that plan: `next_batch` on this data plane is
        `execute(plan_next())`."""
        jnp, note = self._jnp, self._note
        c0 = host_counters()
        t0 = time.perf_counter()
        with note("next_batch"):
            b = (self.loader.next_batch() if plan is None
                 else self.loader.execute(plan))
        t_batch = time.perf_counter()
        with note("upload"):
            rows = self._bucket_rows(b.features)
            t_copy = time.perf_counter()
            table = jnp.asarray(rows)
            hops = [jnp.asarray(h) for h in self._hop_indices(b.blocks)]
            y = jnp.asarray(self.labels[b.blocks.seeds])
        t_upload = time.perf_counter()
        with note("sgd_step"):
            self.params, loss = self._sgd_step(self.gnn, self.params, table,
                                               hops, y, self.lr)
        t_step = time.perf_counter()
        with note("wait"):
            loss.block_until_ready()
        t1 = time.perf_counter()
        c1 = host_counters()
        return b, Step(t0, t1, len(b.blocks.all_nodes), b.report, loss,
                       (t_batch, t_copy, t_upload, t_step),
                       tuple(y - x for x, y in zip(c0, c1)))


@dataclasses.dataclass
class Record:
    """What a per-layer metric reads.  `steps` are the window's, and
    `spans` the program's stage spans inside it.  In a traced run
    `profiled` are the steps run under the profiler after the window,
    which `trace` (a `devtrace.DeviceTrace`, or None) covers."""
    cell: Cell
    steps: list
    window_s: float
    spans: list           # (stage name, wall seconds)
    profiled: list
    trace: object
    peak: dict | None

    @property
    def batch_size(self) -> int:
        return int(self.cell.traffic["batch_size"])

    @property
    def fanouts(self) -> list:
        return list(self.cell.traffic["fanouts"])


def nearest_rank(values, q: float) -> float:
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def make_structure(cfg: dict):
    """The generated graph's distinct edges, the program's CSR of them
    (built by `from_edge_list`) and the train split, all from the fixed
    STRUCTURE_SEED: every run trains the same batches, with the same
    unique-row counts in the same order, whatever its `--seed`."""
    from repro.graph.csr import from_edge_list

    n = int(cfg["num_nodes"])
    t = time.perf_counter()
    edges = correct.EdgeSet(data.rmat_keys(n, int(cfg["num_edges"]),
                                           STRUCTURE_SEED), n)
    t_edges = time.perf_counter()
    graph = from_edge_list(*edges.endpoints(), n, dedup=False,
                           feature_dim=int(cfg["feature_dim"]),
                           name=cfg["name"])
    split = data.train_split(n, float(cfg["train_fraction"]),
                             STRUCTURE_SEED)
    log(f"setup structure: edges_s={t_edges - t!r} "
        f"csr_s={time.perf_counter() - t_edges!r} "
        f"edges={len(graph.indices)}")
    return edges, graph, split


def make_inputs(cfg: dict, seed: int):
    """The feature table and the labels."""
    t = time.perf_counter()
    feats = data.feature_table(int(cfg["num_nodes"]),
                               int(cfg["feature_dim"]), seed)
    labels = data.labels(int(cfg["num_nodes"]), int(cfg["num_classes"]),
                         seed)
    log(f"setup inputs: features_s={time.perf_counter() - t!r} "
        f"table_bytes={feats.nbytes}")
    return feats, labels


def build(cell: Cell, seed: int, graph, feats, labels, split, tracer):
    """The loader, through the user's entry point, and the trainer: the
    loader samples from STRUCTURE_SEED, the weights come from `seed`."""
    from repro.core import GIDSDataLoader, LoaderConfig
    from repro.models.gnn import GNN, GNNConfig

    cfg, tr = cell.config, cell.traffic
    loader = GIDSDataLoader(graph, feats, LoaderConfig(
        batch_size=int(tr["batch_size"]), fanouts=tuple(tr["fanouts"]),
        data_plane=cfg["data_plane"], cache_lines=int(cfg["cache_lines"]),
        cache_ways=int(cfg["cache_ways"]),
        window_depth=int(cfg["window_depth"]),
        cbuf_fraction=float(cfg["cbuf_fraction"]),
        seed=data.word(STRUCTURE_SEED, "loader")),
        train_ids=split, tracer=tracer)
    gnn = GNN(GNNConfig(model=cfg["model"], in_dim=int(cfg["feature_dim"]),
                        hidden_dim=int(cfg["hidden_dim"]),
                        num_classes=int(cfg["num_classes"]),
                        fanouts=tuple(tr["fanouts"])))
    params = data.init_params(seed, int(cfg["feature_dim"]),
                              int(cfg["hidden_dim"]),
                              int(cfg["num_classes"]), len(tr["fanouts"]))
    return Trainer(loader, gnn, params, labels, float(cfg["lr"]),
                   annotate=tracer is not None)


def warm(trainer: Trainer, cell: Cell):
    """The checked steps and the warm batches, up to the mix's count; they
    compile every table bucket the window's batches reach, which the
    window's compile count confirms.  Returns the initial state and the
    checked steps as (blocks, features, state after the step, loss)."""
    p0 = trainer.params
    checked, uniq = [], []
    for i in range(int(cell.traffic["warm_batches"])):
        b, st = trainer.step()
        uniq.append(st.unique)
        if i < CHECK_STEPS:
            checked.append((b.blocks, b.features, trainer.params,
                            float(st.loss)))
        r = st.report
        log(f"warm batch {i}: hbm_hit_share={r.n_hbm_hits / r.n_requests!r} "
            f"unique={st.unique} cycle_s={st.t1 - st.t0!r}")
    log(f"setup buckets: {sorted({bucket(u) for u in uniq})} run by warm "
        f"batches (unique rows {min(uniq)}..{max(uniq)})")
    return p0, checked


def profile(trainer: Trainer, rows_bucket: int, profile_dir: Path) -> list:
    """One step of the loop under the JAX profiler, which records every
    device operation, host annotations only, and no Python calls.  The
    step is the first of the next PROFILE_LOOKAHEAD planned batches whose
    table bucket is `rows_bucket`; the batches before it run unprofiled."""
    import jax

    for i in range(PROFILE_LOOKAHEAD):
        plan = trainer.loader.plan_next()
        if (bucket(len(plan.blocks.all_nodes)) == rows_bucket
                or i == PROFILE_LOOKAHEAD - 1):
            break
        trainer.step(plan)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
    try:
        return [trainer.step(plan)[1]]
    finally:
        jax.profiler.stop_trace()


def window(trainer: Trainer, seconds: float, sample_k: int, seed: int,
           compiles):
    """Run the loop for `seconds`.  Returns the steps, the window's host
    span and a sample of (blocks, features) drawn from the seed."""
    rng = data.streams(seed, "window-sample", 1)[0]
    steps, sample = [], []
    compiles.active = True
    t_start = time.perf_counter()
    while True:
        b, st = trainer.step()
        steps.append(st)
        i = len(steps) - 1
        if len(sample) < sample_k:
            sample.append((b.blocks, b.features))
        else:
            j = int(rng.integers(0, i + 1))
            if j < sample_k:
                sample[j] = (b.blocks, b.features)
        del b
        if st.t1 - t_start >= seconds:
            break
    compiles.active = False
    return steps, steps[-1].t1 - t_start, sample


def checks(cell: Cell, feats, edges, split, checked, sample, losses,
           p0, labels) -> dict:
    """Every number `correct` compares, the reference run included."""
    tr, cfg = cell.traffic, cell.config
    t = time.perf_counter()
    batches = [(blk, f) for blk, f, _, _ in checked] + list(sample)
    rows = sum(correct.feature_rows_wrong(feats, blk.all_nodes, f)
               for blk, f in batches)
    bad = sum(correct.sampled_edges_invalid(
        edges, split, int(tr["batch_size"]), tr["fanouts"], blk.seeds,
        blk.hop_nodes, blk.all_nodes) for blk, _ in batches)
    t_exact = time.perf_counter()
    ref_in = []
    for blk, _, _, _ in checked:
        table, levels = reference.batch_inputs(feats, blk.seeds,
                                               blk.hop_nodes)
        ref_in.append((table, levels, labels[blk.seeds]))
    ref_losses, ref_states = reference.follow(p0, ref_in, tr["fanouts"],
                                              float(cfg["lr"]))
    gaps = correct.trajectory_gaps(
        p0, [c[3] for c in checked], [c[2] for c in checked], ref_losses,
        ref_states, float(cfg["lr"]))
    log(f"check timing: exact_s={t_exact - t!r} "
        f"reference_s={time.perf_counter() - t_exact!r} "
        f"batches_checked={len(batches)}")
    log(f"check losses: program={[c[3] for c in checked]!r} "
        f"reference={ref_losses!r}")
    return {"feature_rows_wrong": rows, "sampled_edges_invalid": bad,
            "window_losses_nonfinite": int(sum(not math.isfinite(x)
                                               for x in losses)),
            **gaps}


def per_layer(cell: Cell, steps, window_s, spans, profiled, trace,
              kind) -> dict:
    rec = Record(cell=cell, steps=steps, window_s=window_s, spans=spans,
                 profiled=profiled, trace=trace,
                 peak=arith.peaks(kind) if kind else None)
    out = {}
    for m in cell.per_layer:
        v = reader(m["name"], cell.bench_dir)(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def log_window(steps, window_s: float, compiles) -> None:
    """The window's steps on standard error: cycles, unique rows, each
    step's phases and host counters, to place a slow step."""
    cycles = [s.t1 - s.t0 for s in steps]
    uniq = [s.unique for s in steps]
    log(f"window: steps={len(steps)} window_s={window_s!r} "
        f"compiles_in_window={len(compiles.names)} {compiles.names} "
        f"unique_rows={min(uniq)}..{max(uniq)} "
        f"cycle_median_s={statistics.median(cycles)!r}")
    log(f"window cycles_ms: {[round(c * 1e3) for c in cycles]} "
        f"unique: {uniq}")
    phases = [[round((b - a) * 1e3, 1) for a, b in
               zip((s.t0,) + s.marks, s.marks + (s.t1,))] for s in steps]
    log(f"window phases_ms (next_batch, bucket copy, transfers, dispatch, "
        f"wait): {phases}")
    log("window host counters (maxrss_kib, arena_bytes, mmapped_bytes, "
        f"mmapped_chunks; change over the step): "
        f"{[list(s.counters) for s in steps]}")
    log(f"process maxrss_kib="
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}")


def run(cell: Cell, seed: int, seconds: float, trace: bool, out_dir: Path,
        t_process: float, device_kind: str | None, memory_stats) -> dict:
    """One run; returns the result line's fields.  `device_kind` names the
    chip whose peaks the metrics use (None: no peaks, as on a CPU)."""
    from repro.obs import Tracer

    compiles = CompileCounter()
    edges, graph, split = make_structure(cell.config)
    feats, labels = make_inputs(cell.config, seed)
    tracer = Tracer() if trace else None
    t = time.perf_counter()
    trainer = build(cell, seed, graph, feats, labels, split, tracer)
    log(f"setup loader_s={time.perf_counter() - t!r}")
    t = time.perf_counter()
    p0, checked = warm(trainer, cell)
    log(f"setup warm_s={time.perf_counter() - t!r}")
    setup_s = time.perf_counter() - t_process
    steps, window_s, sample = window(trainer, seconds, CHECK_BATCHES, seed,
                                     compiles)
    log_window(steps, window_s, compiles)
    losses = [float(s.loss) for s in steps]

    busy = None
    if trace:
        lo, hi = steps[0].t0, steps[-1].t1
        spans = [(s.name, s.wall_dur) for s in tracer.wall_spans()
                 if s.wall_t0 is not None and lo <= s.wall_t0 <= hi]
        rows_bucket = statistics.mode(bucket(s.unique) for s in steps)
        prof_dir = out_dir / f"profile-{cell.name}-{seed}"
        profiled = profile(trainer, rows_bucket, prof_dir)
        log(f"profiled step: unique={profiled[0].unique} "
            f"bucket={bucket(profiled[0].unique)} (window's most common "
            f"{rows_bucket}) cycle_s={profiled[0].t1 - profiled[0].t0!r}")
        dtrace, xp = None, devtrace.find_xplane(prof_dir)
        if xp is not None:
            t = time.perf_counter()
            dtrace = devtrace.load(xp, ANNOTATIONS)
            log(f"trace: file={xp} bytes={xp.stat().st_size} "
                f"read_s={time.perf_counter() - t!r}")
        metrics = per_layer(cell, steps, window_s, spans, profiled, dtrace,
                            device_kind)
        if dtrace is not None:
            busy = {"busy_s": dtrace.busy_s(), "window_s": dtrace.window_s,
                    "breakdown": {"device_ops": dtrace.top_ops(),
                                  "idle_gaps": dtrace.idle_gaps()}}
        del dtrace
    else:
        values = {
            "seeds_per_s": len(steps) * int(cell.traffic["batch_size"])
            / window_s,
            "step_p95_ms": nearest_rank([s.t1 - s.t0 for s in steps],
                                        0.95) * 1e3,
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    peak_bytes = (memory_stats() or {}).get("peak_bytes_in_use")
    # the program's state goes before the reference runs
    del trainer, steps
    gc.collect()
    readings = checks(cell, feats, edges, split, checked, sample, losses,
                      p0, labels)
    ok, compared = correct.judge(readings,
                                 correct.load_limits(cell.limits_path))
    return {"correct": ok, "attempted": len(losses),
            "failed": readings["window_losses_nonfinite"],
            "metrics": metrics, "peak_bytes": peak_bytes, "busy": busy,
            "checks": compared}
