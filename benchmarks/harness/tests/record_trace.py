"""Record the small TPU trace that `test_trace_reduce.py` reads.

    python3 benchmarks/harness/tests/record_trace.py <out_dir>

On one TPU chip, inside the window annotation: a jitted XLA program, a
host sleep of 50 ms under its own annotation (the idle gap the
reduction must attribute to it), then a Pallas kernel, each run to
completion. Writes `<out_dir>/recorded_tpu.xplane.pb`.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    out_dir = sys.argv[1]

    @jax.jit
    def xla_step(x):
        return jnp.tanh(x @ x) + 1.0

    def add_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    @jax.jit
    def pallas_step(x):
        return pl.pallas_call(
            add_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)
        )(x)

    x = jnp.ones((512, 512), jnp.float32)
    jax.block_until_ready((xla_step(x), pallas_step(x)))
    tmp = tempfile.mkdtemp(prefix="record-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation("harness.window"):
            with jax.profiler.TraceAnnotation("step.xla"):
                jax.block_until_ready(xla_step(x))
            with jax.profiler.TraceAnnotation("host.sleep"):
                time.sleep(0.05)
            with jax.profiler.TraceAnnotation("step.pallas"):
                jax.block_until_ready(pallas_step(x))
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(path, os.path.join(out_dir, "recorded_tpu.xplane.pb"))
        print(os.path.getsize(path), "bytes")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
