"""How tests/data/v5e_small.xplane.pb was made (on one v5e chip):

    python3 benchmark/tests/record_trace.py <out_dir>

A few milliseconds of a program whose trace has everything the reduction
reads: plain fused ops, a `while` loop that encloses its body's ops, a
Mosaic (pallas) kernel, a host sleep that leaves the device idle inside a
`phase:rollout` annotation, and the `bench:traced` annotation around it all.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SLEEP_S = 0.02


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def main(out_dir: str) -> None:
    x = jnp.ones((512, 512), jnp.float32)

    @jax.jit
    def work(x):
        y = jax.lax.fori_loop(0, 4, lambda i, a: jnp.tanh(a @ a) / 512.0, x)
        return pl.pallas_call(
            _double_kernel, out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype)
        )(y).sum()

    work(x).block_until_ready()  # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    tmp = os.path.join(out_dir, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:traced"):
        with jax.profiler.TraceAnnotation("phase:fused_block"):
            work(x).block_until_ready()
        with jax.profiler.TraceAnnotation("phase:rollout"):
            time.sleep(SLEEP_S)
            work(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, os.path.join(out_dir, "v5e_small.xplane.pb"))
    shutil.rmtree(tmp)
    print(f"{os.path.getsize(os.path.join(out_dir, 'v5e_small.xplane.pb'))} bytes on "
          f"{jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main(sys.argv[1])
