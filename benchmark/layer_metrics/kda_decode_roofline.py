"""Percent of its roofline a decode step of the delta-rule layers reaches: the
recurrent state and the convolutions' inputs of every row, read once and written
once at the HBM peak, over the steps and KDA layers of the traced cycles
(`_kda.decode_least_seconds`), over the device seconds under the scope `kda_step`.
A step that passes over the state more than twice stays under 100%. None without
the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _kda


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "kda_step")
    least = _kda.decode_least_seconds(r)
    if not took or least is None:
        return None
    return 100.0 * least / took
