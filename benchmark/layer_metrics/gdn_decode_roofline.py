"""Percent of its roofline a decode step of the Gated DeltaNet layers reaches: the
recurrent state and the convolution's inputs of every row, read once and written
once at the HBM peak, over the steps and GDN layers of the traced cycles
(`_gdn.decode_least_seconds`), over the device seconds under the scope `gdn_step`.
A step that passes over the state more than twice stays under 100%. None without
the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _gdn


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "gdn_step")
    least = _gdn.decode_least_seconds(r)
    if not took or least is None:
        return None
    return 100.0 * least / took
