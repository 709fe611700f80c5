"""Where the persistent XLA compilation cache lives.

One placement rule for every entry point (`trlx_tpu.train`, `bench.py`,
`chip_smoke.py`): the directory is part of the cache key, so it must be
the same path from one process to the next — never `/tmp`, a pid, a
time or `tempfile`.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache, resolved from the package's own location
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set in code (`jax.config.update` would override the
    environment); otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    Call before the first compile; touches no backend.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # sub-2s compiles are cheaper to redo than to serialize and look up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    return cache_dir
