"""Record the small profiler trace that the harness's tests read.

    python benchmarks/chip/record_test_trace.py <out_dir>

On a TPU: two steps of the benchmark's annotated loop shape at toy size,
with the program's `segment_mean` and `tiered_gather` kernels and a host
pause inside `next_batch`, so the trace has device ops, jitted programs,
host annotations and idle gaps.  The `.xplane.pb` lands under `<out_dir>`;
the test file is a copy of it.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out_dir: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_test_trace.py needs a TPU")
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (1024, 256), jnp.float32)
    idx = jax.random.randint(key, (64, 4), 0, 1024, jnp.int32)
    slots = jnp.where(jnp.arange(256) % 2 == 0, jnp.arange(256), -1)
    staged = jnp.ones((256, 256), jnp.float32)
    w = jnp.ones((256, 256), jnp.float32)

    @jax.jit
    def sgd_step(t, w):
        return jnp.tanh(ops.segment_mean(idx, t) @ w).sum()

    jax.block_until_ready((sgd_step(table, w),
                           ops.tiered_gather(slots, table, staged)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("next_batch"):
            time.sleep(0.01)
            rows = ops.tiered_gather(slots, table, staged)
            rows.block_until_ready()
        with jax.profiler.TraceAnnotation("upload"):
            x = jnp.asarray(jax.device_get(rows))
        with jax.profiler.TraceAnnotation("sgd_step"):
            loss = sgd_step(table + x.sum(), w)
        with jax.profiler.TraceAnnotation("wait"):
            loss.block_until_ready()
    jax.profiler.stop_trace()
    print(sorted(Path(out_dir).rglob("*.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
