"""Percent of its roofline a decode step of the state-space layers reaches: the float32
state and the convolution's tail of every row, read once and written once at the HBM
peak, over the steps and `M` layers of the traced cycles (`_ssm.decode_least_seconds`),
over the device seconds under the scope `ssm_step`. A step that passes over the state
more than twice stays under 100%. None without the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _ssm


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "ssm_step")
    least = _ssm.decode_least_seconds(r)
    if not took or least is None:
        return None
    return 100.0 * least / took
