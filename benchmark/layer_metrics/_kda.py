"""Operations and bytes of the gated delta rule (KDA), counted from the algorithm
and the same whatever implements it, for the readers `kda_chunk_roofline`,
`kda_decode_roofline` and `kda_share`. A family without
`linear_attn_config.kda_layers` gives None throughout.

Per token and head the recurrence is three products of a [d_k, d_v] state: the
decayed state times k, the rank-one update, the state times q: 6 d_k d_v FLOPs, the
least any form needs (a chunked form adds its in-chunk products, remat its
recomputation: neither is credited). The teacher-forced form must read q, k, v
(compute dtype, 2 bytes), the per-channel log-gate g and beta (float32) and write o;
a decode step must read the float32 state and the convolutions' last inputs once
and write them once."""

from benchmark import flops
from benchmark.layer_metrics import _routed

SCOPES = ("kda_conv", "kda_gate", "kda_chunk", "kda_step")


def layers(r):
    """(KDA layers run, those of them a hydra branch trains, the group of the config
    that sizes them) or None for another family."""
    lin = r.hf.get("linear_attn_config")
    if not lin or not lin.get("kda_layers"):
        return None
    depth = r.hf["num_hidden_layers"]
    run = [layer for layer in lin["kda_layers"] if layer <= depth]  # 1-based
    trainable = flops.trainable_layers(flops.work(r.cell.reference, r.hf), r.unfrozen)
    return len(run), sum(layer > depth - trainable for layer in run), lin


def token_work(lin):
    """FLOPs and HBM bytes one token requires of one KDA layer's recurrence."""
    heads, d = lin["num_heads"], lin["head_dim"]
    return {"flops": 6.0 * heads * d * d,
            "bytes": heads * (2.0 * 4 * d + 4.0 * d + 4.0)}  # q, k, v, o; g; beta


def chunked_least_seconds(r):
    """Least time for the chunked-form work one traced cycle REQUIRES: prefill over the
    prompt, the scorer over the sequence with the reference branch's KDA layers, one
    training forward a step over every KDA layer and the backward (twice the forward)
    over the trainable ones; each call at the longer of its FLOPs at the bf16 peak and
    its bytes at the HBM peak."""
    found = layers(r)
    if found is None:
        return None
    n, trainable, lin = found
    t = r.traffic
    seq = t["prompt_tokens"] + t["new_tokens"]
    calls = _routed.cycle_calls(r)
    per_token = token_work(lin)
    tokens = (
        calls["sampler"] * t["chunk"] * t["prompt_tokens"] * n
        + calls["scorer"] * t["chunk"] * seq * (n + trainable)
        + calls["train"] * t["batch"] * seq * (n + 2 * trainable)
    )
    work = {k: v * tokens for k, v in per_token.items()}
    return t["trace_cycles"] * flops.roofline_seconds(work, r.peaks)["seconds"] / r.chips


def decode_least_seconds(r):
    """Least time for the recurrent state one traced cycle's decode steps must move:
    a step and a KDA layer, the float32 state [heads, d, d] and the convolutions'
    `taps - 1` inputs (three of them, heads x d wide, 2 bytes) of every row, read once
    and written once, at the HBM peak."""
    found = layers(r)
    if found is None:
        return None
    n, _, lin = found
    t = r.traffic
    heads, d = lin["num_heads"], lin["head_dim"]
    row = 4.0 * heads * d * d + 2.0 * (lin["short_conv_kernel_size"] - 1) * 3 * heads * d
    steps = (t["new_tokens"] - 1) * max(t["rollouts"] // t["chunk"], 1)
    moved = 2.0 * row * t["chunk"] * steps * n
    return t["trace_cycles"] * moved / r.peaks["hbm_bytes_per_s"] / r.chips
