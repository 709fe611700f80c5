"""Percent of the device's busy time spent under the `jax.named_scope` `hc_mix`:
the mixing matrices of a multi-stream residual path (the projection of the
normed state, the Sinkhorn steps) and both stream products around every
sub-layer. None for a program or a model without the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics._device_seconds import busy_share


def read(r):
    if not r.trace:
        return None
    return busy_share(r.trace, trace_reduce.scope_seconds(r.trace, "hc_mix"))
