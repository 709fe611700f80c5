"""How the traces under tests/data/ were made (on one v5e chip):

    python3 benchmark/tests/record_trace.py <out_dir>

`v5e_small.xplane.pb`: a few milliseconds of a program whose trace has
everything the reduction reads: plain fused ops, a `while` loop that encloses
its body's ops, a Mosaic (pallas) kernel, a host sleep that leaves the device
idle inside a `phase:rollout` annotation, and the `bench:traced` annotation
around it all.

`v5e_scopes.xplane.pb`: two programs, `jit_scoped_matmuls` and `jit_plain_sum`,
the first with a loop of matmuls under `jax.named_scope("known_scope")` (and a
`tanh` nested under `known_scope/inner`) beside a matmul under no scope, run
SCOPED_RUNS and PLAIN_RUNS times: what the reduction by scope and by program
reads.
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
SCOPED_RUNS, PLAIN_RUNS = 2, 3


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _record(out_dir: str, name: str, body) -> None:
    """Trace `body()` (already compiled) and keep the one .xplane.pb."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    tmp = os.path.join(out_dir, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:traced"):
        body()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, os.path.join(out_dir, name))
    shutil.rmtree(tmp)
    print(f"{name}: {os.path.getsize(os.path.join(out_dir, name))} bytes on "
          f"{jax.devices()[0].device_kind}")


def record_small(out_dir: str) -> None:
    x = jnp.ones((512, 512), jnp.float32)

    @jax.jit
    def work(x):
        y = jax.lax.fori_loop(0, 4, lambda i, a: jnp.tanh(a @ a) / 512.0, x)
        return pl.pallas_call(
            _double_kernel, out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype)
        )(y).sum()

    work(x).block_until_ready()  # compile outside the trace

    def body():
        with jax.profiler.TraceAnnotation("phase:fused_block"):
            work(x).block_until_ready()
        with jax.profiler.TraceAnnotation("phase:rollout"):
            time.sleep(SLEEP_S)
            work(x).block_until_ready()

    _record(out_dir, "v5e_small.xplane.pb", body)


def record_scopes(out_dir: str) -> None:
    x = jnp.ones((1024, 1024), jnp.float32)

    @jax.jit
    def scoped_matmuls(x):
        def step(i, a):
            with jax.named_scope("known_scope"):
                b = a @ a
                with jax.named_scope("inner"):
                    return jnp.tanh(b) / 1024.0

        return jax.lax.fori_loop(0, 8, step, x) + x @ x  # the last matmul under no scope

    @jax.jit
    def plain_sum(x):
        return (x * 2.0).sum()

    scoped_matmuls(x).block_until_ready()
    plain_sum(x).block_until_ready()

    def body():
        time.sleep(SLEEP_S)  # the host's and the chip's clocks agree to a millisecond or so
        for _ in range(SCOPED_RUNS):
            scoped_matmuls(x).block_until_ready()
        for _ in range(PLAIN_RUNS):
            plain_sum(x).block_until_ready()
        time.sleep(SLEEP_S)

    _record(out_dir, "v5e_scopes.xplane.pb", body)


if __name__ == "__main__":
    if "--scopes-only" not in sys.argv:
        record_small(sys.argv[1])
    record_scopes(sys.argv[1])
