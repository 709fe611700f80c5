"""The flash-attention kernels under latent attention: keys `qk_nope + qk_rope`
wide, values `v_head_dim`, so a (query, key) pair costs the family's `pair_flops`
and a call moves q, k (key width) and v, o (value width)."""

from benchmark import flops
from benchmark.layer_metrics import _routed


def call_work(r, seq, backward=False):
    """FLOPs and HBM bytes of one layer's call over every rollout of a cycle at
    `seq` tokens: the forward, or the backward (2.5 forwards: four matmuls of the
    forward's size and the recomputed scores; reads q, k, v, o, do, writes dq, dk, dv)."""
    all_layers, _ = _routed.layers(r)
    if not all_layers or "kv_lora_rank" not in r.hf:
        return None
    hf, rows = r.hf, r.traffic["rollouts"]
    key_w, val_w = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"], hf["v_head_dim"]
    pairs = rows * seq * (seq + 1) / 2.0
    elems = rows * seq * hf["num_attention_heads"] * (2 * key_w + 2 * val_w)
    fwd = {"flops": all_layers[0]["pair_flops"] * pairs, "bytes": 2.0 * elems}
    if not backward:
        return fwd
    return {"flops": 2.5 * fwd["flops"], "bytes": 2.0 * 2 * elems}


def least_seconds(r, seq, layer_calls, backward=False):
    work = call_work(r, seq, backward)
    if work is None:
        return None
    return r.traffic["trace_cycles"] * layer_calls * flops.roofline_seconds(work, r.peaks)["seconds"] / r.chips
