"""Percent of the device's busy time spent under the `jax.named_scope`
`decode_attn`: attention over the quantised cache inside the decode loop, on
the XLA path (`models/transformer.py`). Self time of every instruction whose
scope path holds `decode_attn` (`trace_reduce.scope_seconds`: a fusion counts
by the one `op_name` it carries), over busy time, both means over the chips. A
program that decodes through a kernel of its own, outside that scope, leaves
this metric out of the line."""

from benchmark import trace_reduce
from benchmark.layer_metrics._device_seconds import busy_share


def read(r):
    if not r.trace:
        return None
    return busy_share(r.trace, trace_reduce.scope_seconds(r.trace, "decode_attn"))
