"""Percent of the device's busy time spent under the state-space layers' four
`jax.named_scope`s: `ssm_conv` (the convolution, SiLU and the split into x', B and C),
`ssm_gate` (softplus of dt, the gated grouped norm), `ssm_chunk` (the chunked form) and
`ssm_step` (a decode step on the carried state): whether the mechanism is the larger
part of the cycle, and what its parts are (one `[benchmark]` line with the four terms).
The projections in and out are counted with the rest of the layer, as attention's are.
None for a program without the scopes."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _ssm
from benchmark.layer_metrics._device_seconds import busy_share


def read(r):
    if not r.trace:
        return None
    parts = {scope: trace_reduce.scope_seconds(r.trace, scope) for scope in _ssm.SCOPES}
    if not any(parts.values()):
        return None
    print("[benchmark] ssm_share: " + ", ".join(
        f"{scope} {seconds or 0.0:.4f} s" for scope, seconds in parts.items()), flush=True)
    return busy_share(r.trace, sum(seconds or 0.0 for seconds in parts.values()))
