"""Percent of the device's busy time spent under the `jax.named_scope`
`optimizer_update` of the train step (`trainer/base.py` `_step_update`): the
optimizer's walk over parameters and moments with what it takes to cut them to
the trained rows and write them back. None for a program without the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics._device_seconds import busy_share


def read(r):
    if not r.trace:
        return None
    return busy_share(r.trace, trace_reduce.scope_seconds(r.trace, "optimizer_update"))
