"""Percent of its roofline the causal flash-attention forward reaches: the
least time the chips could take for the forwards the traced cycles REQUIRE
over the self time of the `flash_fwd` kernel (instructions named
`%flash_fwd*`). Required: the prefill of every layer over the prompt; the
scoring forward of every layer plus the frozen reference branch; one training
forward of every layer per optimizer step. Nothing for the recomputation
under remat, which the program runs and the algorithm does not need: the
share cannot pass 100%."""

from benchmark import flops
from benchmark.layer_metrics import _flash


def read(r):
    if not r.trace:
        return None
    took = _flash.kernel_seconds(r, "%flash_fwd")
    if not took:
        return None
    d = r.cell.reference.dims(r.hf)
    t = r.traffic
    seq = t["prompt_tokens"] + t["new_tokens"]
    scoring = d["n_layer"] + flops.trainable_layers(d, r.unfrozen)
    # every optimizer step of a cycle sees `batch` rows; steps x batch =
    # epochs x rollouts, so count layer-forwards over all rollouts
    training = t["method_kwargs"]["ppo_epochs"] * d["n_layer"]
    least = (_flash.least_seconds(r, flops.flash_fwd, t["prompt_tokens"], d["n_layer"])
             + _flash.least_seconds(r, flops.flash_fwd, seq, scoring + training))
    return 100.0 * least / took
