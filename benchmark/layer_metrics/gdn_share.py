"""Percent of the device's busy time spent under the Gated DeltaNet layers' four
`jax.named_scope`s: `gdn_conv` (the one convolution over q, k and v, SiLU, the norms
of q and k and their repetition to the value heads), `gdn_gate` (the decay and beta
from W_ba, the output norm and SiLU(z)), `gdn_chunk` (the chunked form) and `gdn_step`
(a decode step on the carried state): whether the mechanism is the larger part of the
cycle, and what its parts are (one `[benchmark]` line with the four terms). The
projections in and out are counted with the rest of the layer, as attention's are.
None for a program without the scopes."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _gdn
from benchmark.layer_metrics._device_seconds import busy_share


def read(r):
    if not r.trace:
        return None
    parts = {scope: trace_reduce.scope_seconds(r.trace, scope) for scope in _gdn.SCOPES}
    if not any(parts.values()):
        return None
    print("[benchmark] gdn_share: " + ", ".join(
        f"{scope} {seconds or 0.0:.4f} s" for scope, seconds in parts.items()), flush=True)
    return busy_share(r.trace, sum(seconds or 0.0 for seconds in parts.values()))
