"""Trace, lowering and backend-compile seconds of the programs built (not
read from the cache) before the window: what `setup_programs_built` cost."""

from benchmark.layer_metrics import _setup


def read(r):
    return _setup.compiled(r, "built_s")
