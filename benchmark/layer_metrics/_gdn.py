"""Operations and bytes of the gated delta rule with a scalar decay a head (Gated
DeltaNet), counted from the algorithm and the same whatever implements it, for the
readers `gdn_chunk_roofline`, `gdn_decode_roofline` and `gdn_share`. A family
without `linear_num_value_heads` gives None throughout.

Per token and VALUE head the recurrence is three products of a [d_k, d_v] state: the
decayed state times k, the rank-one update, the state times q: 6 d_k d_v FLOPs, the
least any form needs (a chunked form adds its in-chunk products, remat its
recomputation: neither is credited). The teacher-forced form must read q and k (a key
head each, compute dtype, 2 bytes), v (a value head), the scalar log-decay g and beta
(float32, one a value head) and write o (a value head, 2 bytes); a decode step must
read the float32 state and the convolution's last inputs once and write them once."""

from benchmark import flops
from benchmark.layer_metrics import _routed

SCOPES = ("gdn_conv", "gdn_gate", "gdn_chunk", "gdn_step")


def layers(r):
    """(GDN layers run, those of them a hydra branch trains) or None for another family."""
    if "linear_num_value_heads" not in r.hf:
        return None
    every, depth = r.hf["full_attention_interval"], r.hf["num_hidden_layers"]
    run = [layer for layer in range(1, depth + 1) if layer % every]  # 1-based
    trainable = flops.trainable_layers(flops.work(r.cell.reference, r.hf), r.unfrozen)
    return len(run), sum(layer > depth - trainable for layer in run)


def token_work(hf):
    """FLOPs and HBM bytes one token requires of one GDN layer's recurrence."""
    kh, vh, dk, dv = (hf[k] for k in ("linear_num_key_heads", "linear_num_value_heads",
                                      "linear_key_head_dim", "linear_value_head_dim"))
    return {"flops": 6.0 * vh * dk * dv,
            "bytes": 2.0 * (2 * kh * dk + 2 * vh * dv) + 4.0 * 2 * vh}  # q, k, v, o; g, beta


def chunked_least_seconds(r):
    """Least time for the chunked-form work one traced cycle REQUIRES: prefill over the
    prompt, the scorer over the sequence with the reference branch's GDN layers, one
    training forward a step over every GDN layer and the backward (twice the forward)
    over the trainable ones; each call at the longer of its FLOPs at the bf16 peak and
    its bytes at the HBM peak (as `_kda.chunked_least_seconds` counts)."""
    found = layers(r)
    if found is None:
        return None
    n, trainable = found
    t = r.traffic
    seq = t["prompt_tokens"] + t["new_tokens"]
    calls = _routed.cycle_calls(r)
    tokens = (
        calls["sampler"] * t["chunk"] * t["prompt_tokens"] * n
        + calls["scorer"] * t["chunk"] * seq * (n + trainable)
        + calls["train"] * t["batch"] * seq * (n + 2 * trainable)
    )
    work = {k: v * tokens for k, v in token_work(r.hf).items()}
    return t["trace_cycles"] * flops.roofline_seconds(work, r.peaks)["seconds"] / r.chips


def decode_least_seconds(r):
    """Least time for the recurrent state one traced cycle's decode steps must move:
    a step and a GDN layer, the float32 state [value heads, d_k, d_v] and the
    convolution's `taps - 1` inputs (q, k and v wide, 2 bytes) of every row, read once
    and written once, at the HBM peak."""
    found = layers(r)
    if found is None:
        return None
    n, hf, t = found[0], r.hf, r.traffic
    kh, vh, dk, dv = (hf[k] for k in ("linear_num_key_heads", "linear_num_value_heads",
                                      "linear_key_head_dim", "linear_value_head_dim"))
    row = 4.0 * vh * dk * dv + 2.0 * (hf["linear_conv_kernel_dim"] - 1) * (2 * kh * dk + vh * dv)
    steps = (t["new_tokens"] - 1) * max(t["rollouts"] // t["chunk"], 1)
    moved = 2.0 * row * t["chunk"] * steps * n
    return t["trace_cycles"] * moved / r.peaks["hbm_bytes_per_s"] / r.chips
