"""Percent of `setup_s` the program spent building programs at all: trace,
lowering, backend-compile and cache-read seconds before the window, as the
program's own listener on JAX's monitoring events timed them. Falls with
every program taken out of start-up."""

from benchmark.layer_metrics import _setup


def read(r):
    seconds = _setup.compiled(r, *_setup.TOTALS)
    return None if seconds is None else 100.0 * seconds / r.setup_s
