"""Percent of its roofline the flash-attention backward reaches: the least
time the chips could take for the backward of the TRAINABLE layers only, once
per optimizer step (`flops.flash_bwd`, `flops.trainable_layers`), over the
self time of the `flash_bwd_dq` and `flash_bwd_dkv` kernels. A program that
runs the backward through frozen layers too reads low here, and one that
stops doing so rises by that factor; the share cannot pass 100%."""

from benchmark import flops
from benchmark.layer_metrics import _flash


def read(r):
    if not r.trace:
        return None
    took = _flash.kernel_seconds(r, "%flash_bwd_dq", "%flash_bwd_dkv")
    if not took:
        return None
    d = r.cell.reference.dims(r.hf)
    t = r.traffic
    seq = t["prompt_tokens"] + t["new_tokens"]
    calls = t["method_kwargs"]["ppo_epochs"] * flops.trainable_layers(d, r.unfrozen)
    return 100.0 * _flash.least_seconds(r, flops.flash_bwd, seq, calls) / took
