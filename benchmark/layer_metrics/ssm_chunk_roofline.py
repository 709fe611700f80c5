"""Percent of its roofline the teacher-forced form of the state-space layers reaches:
the least time for the recurrence the traced cycle requires (`_ssm.chunked_least_seconds`:
4 P N FLOPs a token and head at the bf16 peak against x', z, B, C, dt read and y written
at the HBM peak; prefill, scorer with the reference branch, training forward and the
backward of the trainable layers; nothing for what a chunked form adds or for remat) over
the device seconds under the scope `ssm_chunk`. The count is of the algorithm: a later
kernel is judged by the same yardstick. None without the scope."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _ssm


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "ssm_chunk")
    least = _ssm.chunked_least_seconds(r)
    if not took or least is None:
        return None
    return 100.0 * least / took
