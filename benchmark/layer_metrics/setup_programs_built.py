"""Backend compiles before the window that were not read from the
compilation cache: on a warm start, the small eager programs under the
cache's floor, built anew in every process."""

from benchmark.layer_metrics import _setup


def read(r):
    return _setup.compiled(r, "built")
