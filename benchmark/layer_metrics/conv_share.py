"""Percent of the device's busy time spent in the short-convolution layers' mixers:
the instructions under any of the three `jax.named_scope`s `conv_mix` (the
teacher-forced form, projections included), `conv_step` (a decode step's form) and
`conv_proj` (both projections, nested in either), each counted once: whether the
mechanism is the larger part of the cycle, and what its parts are (one `[benchmark]`
line with the three terms; `conv_proj` overlaps the other two). None for a program
without the scopes."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _conv
from benchmark.layer_metrics._device_seconds import busy_share


def read(r):
    if not r.trace:
        return None
    # the scope rule of `trace_reduce.scope_seconds`, path by path, so that an
    # instruction under two of the scopes counts once
    held = lambda row: any(trace_reduce.scope_seconds({"scopes_by_self_time": [row]}, scope) is not None
                           for scope in _conv.SCOPES)
    total = sum(row[1] for row in r.trace["scopes_by_self_time"] if held(row))
    if not total:
        return None
    parts = {scope: trace_reduce.scope_seconds(r.trace, scope) for scope in _conv.SCOPES}
    print("[benchmark] conv_share: " + ", ".join(
        f"{scope} {seconds or 0.0:.4f} s" for scope, seconds in parts.items()) + f"; together {total:.4f} s",
          flush=True)
    return busy_share(r.trace, total)
