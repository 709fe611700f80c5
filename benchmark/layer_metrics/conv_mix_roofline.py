"""Percent of its roofline the gated short-convolution mixer's teacher-forced form
reaches, which is in effect THE ROOFLINE OF ITS TWO PROJECTIONS: the least time the
traced cycle REQUIRES for the form (`_conv.mix_least_seconds`: W_in and W_out, 8 d^2
FLOPs a token and conv layer at the bf16 peak, against x read and the output written
at the HBM peak; prefill, scorer with the reference branch, the frozen trunk once a
fused block, the trainable layers forward and backward every optimizer step; nothing
for remat) over the device seconds under the scope `conv_mix`, which holds the whole
form. The pass between the projections (B u, the convolution, C c) is fused by the
compiler into them and is under 0.1% of the FLOPs counted, so a change to the pass
alone barely moves this number; `conv_share` is the mechanism's witness. The count is
of the algorithm: a later kernel, Pallas or XLA, is judged by the same yardstick. None
without the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _conv


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "conv_mix")
    least = _conv.mix_least_seconds(r)
    if not took or least is None:
        return None
    return 100.0 * least / took
