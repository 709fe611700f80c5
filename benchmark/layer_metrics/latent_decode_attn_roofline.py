"""Percent of its roofline the absorbed decode form of latent attention reaches:
what a decode step must read at the HBM peak (of every row, the cached positions
written so far, `cache_elems` numbers each in the cache's 2 bytes, and the latent
up-projection, which the step applies to query and output, in the compute dtype),
summed over the steps and layers of the traced cycles, over the device seconds
under the scope `latent_decode_attn`. A step that reads the whole allocated cache
and not the written part reads more than it must and stays under 100%."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _routed


def read(r):
    if not r.trace:
        return None
    took = trace_reduce.scope_seconds(r.trace, "latent_decode_attn")
    all_layers, _ = _routed.layers(r)
    if not took or not all_layers:
        return None
    hf, t = r.hf, r.traffic
    up_projection = hf["kv_lora_rank"] * hf["num_attention_heads"] * (hf["qk_nope_head_dim"] + hf["v_head_dim"])
    # step i of n - 1 sees the prompt and the i tokens before it
    positions = sum(t["prompt_tokens"] + i for i in range(1, t["new_tokens"]))
    per_layer = 2.0 * (all_layers[0]["cache_elems"] * t["rollouts"] * positions
                       + up_projection * (t["new_tokens"] - 1) * max(t["rollouts"] // t["chunk"], 1))
    least = len(all_layers) * per_layer / r.peaks["hbm_bytes_per_s"]
    return 100.0 * t["trace_cycles"] * least / r.chips / took
