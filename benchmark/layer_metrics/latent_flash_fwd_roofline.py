"""Percent of its roofline the flash-attention forward reaches under latent
attention (keys 192 wide, values 128 at the published sizes): as
`flash_fwd_roofline` counts (the prefill of every layer over the prompt; the
scoring forward of every layer plus the frozen reference branch; one training
forward of every layer per optimizer step; nothing for recomputation), with a
pair's FLOPs from the family's `pair_flops`, over the self time of the
instructions named `%flash_fwd*`. The share cannot pass 100%."""

from benchmark import flops
from benchmark.layer_metrics import _flash, _latent_flash, _routed


def read(r):
    if not r.trace:
        return None
    took = _flash.kernel_seconds(r, "%flash_fwd")
    all_layers, _ = _routed.layers(r)
    if not took or not all_layers:
        return None
    t, n = r.traffic, len(all_layers)
    seq = t["prompt_tokens"] + t["new_tokens"]
    scoring = n + flops.trainable_layers(flops.work(r.cell.reference, r.hf), r.unfrozen)
    training = t["method_kwargs"]["ppo_epochs"] * n
    prefill = _latent_flash.least_seconds(r, t["prompt_tokens"], n)
    rest = _latent_flash.least_seconds(r, seq, scoring + training)
    if prefill is None or rest is None:
        return None
    return 100.0 * (prefill + rest) / took
