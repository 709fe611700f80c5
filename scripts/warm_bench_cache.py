"""Warm the persistent XLA compile cache for every bench section.

Run this AFTER the last code change that touches bench.py or any model
code it drives: the compile-cache key covers the lowered module
(including source locations of traced functions), so an edit to bench.py
invalidates the entries its sections wrote. With a warm cache every
bench section fits its reserved time slice; cold, the 1.3B sections
alone can blow the whole budget (see bench.SECTIONS).

One process per chip: this parent imports `bench` only (no JAX backend)
and runs the sections serially, each in its own child, same as
bench.main. The cache goes to $JAX_COMPILATION_CACHE_DIR, else
<checkout>/.jax_cache (trlx_tpu/utils/compile_cache.py) — it only helps
a later run that sees the same directory on the same machine.
"""

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402

if __name__ == "__main__":
    for name, fn_name, _reserve, gate in bench.SECTIONS:
        if not bench._section_enabled(gate):
            continue
        t0 = time.time()
        out = bench._run_section(name, fn_name, timeout_s=1200)
        print(f"warm[{name}] {time.time() - t0:.1f}s -> {out}", flush=True)
