"""Percent of `setup_s` inside the spans `model_init` (`ref_init` lies
inside it), `opt_init` and `router_balance`: the weights made and sharded,
the optimizer state's init, a routed model's balancing steps. Overlaps
`setup_compile_share` by what the row's `by_span` says those spans compiled."""

from benchmark.layer_metrics import _setup


def read(r):
    seconds = _setup.span_seconds(r, "model_init", "opt_init", "router_balance")
    return None if seconds is None else 100.0 * seconds / r.setup_s
