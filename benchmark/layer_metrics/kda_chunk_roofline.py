"""Percent of its roofline the chunked form of the delta-rule layers reaches: the
least time for the recurrence the traced cycle requires (`_kda.chunked_least_seconds`:
6 d_k d_v FLOPs a token and head at the bf16 peak against q, k, v, g, beta read and o
written at the HBM peak, prefill, scorer with the reference branch, training forward
and the backward of the trainable layers; nothing for what a chunked form adds or for
remat) over the device seconds under the scope `kda_chunk`. The count is of the
algorithm: a later kernel is judged by the same yardstick. None without the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _kda


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "kda_chunk")
    least = _kda.chunked_least_seconds(r)
    if not took or least is None:
        return None
    return 100.0 * least / took
