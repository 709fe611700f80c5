"""Percent of its roofline the flash-attention backward reaches under latent
attention: the backward of the TRAINABLE layers only, once per optimizer step,
2.5 forwards each with a pair's FLOPs from the family's `pair_flops`, over the
self time of the instructions named `%flash_bwd_dq*` and `%flash_bwd_dkv*`. The
share cannot pass 100%."""

from benchmark import flops
from benchmark.layer_metrics import _flash, _latent_flash


def read(r):
    if not r.trace or not hasattr(r.cell.reference, "work"):
        return None
    took = _flash.kernel_seconds(r, "%flash_bwd_dq", "%flash_bwd_dkv")
    if not took:
        return None
    t = r.traffic
    calls = t["method_kwargs"]["ppo_epochs"] * flops.trainable_layers(
        flops.work(r.cell.reference, r.hf), r.unfrozen)
    least = _latent_flash.least_seconds(r, t["prompt_tokens"] + t["new_tokens"], calls, backward=True)
    return None if least is None else 100.0 * least / took
