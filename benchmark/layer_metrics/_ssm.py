"""Operations and bytes of the Mamba-2 selective state space, counted from the
algorithm and the same whatever implements it, for the readers `ssm_chunk_roofline`,
`ssm_decode_roofline` and `ssm_share`. A family without an `M` in
`hybrid_override_pattern` gives None throughout.

Per token and head the recurrence is two products of a [P, N] state: the rank-one
update dt x (x) B and the read-out h C: 4 P N FLOPs, the least any form needs (a
chunked form adds its in-chunk products, remat its recomputation: neither is
credited). The teacher-forced form must read x' and z and write y (compute dtype, 2
bytes; z because the gated norm that follows cannot do without it), read B and C (2
bytes, one a group) and dt (float32, one a head); a decode step must read the float32
state and the convolution's tail once and write them once."""

from benchmark import flops
from benchmark.layer_metrics import _routed

SCOPES = ("ssm_conv", "ssm_gate", "ssm_chunk", "ssm_step")


def layers(r):
    """(`M` layers run, those of them a hydra branch trains) or None for another family."""
    letters = r.hf.get("hybrid_override_pattern")
    if not letters or "M" not in letters or "mamba_num_heads" not in r.hf:
        return None
    depth = r.hf["num_hidden_layers"]
    run = [index for index, letter in enumerate(letters[:depth]) if letter == "M"]
    trainable = flops.trainable_layers(flops.work(r.cell.reference, r.hf), r.unfrozen)
    return len(run), sum(index >= depth - trainable for index in run)


def token_work(hf):
    """FLOPs and HBM bytes one token requires of one `M` layer's recurrence."""
    heads, p, n, groups = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"], hf["n_groups"]
    return {"flops": 4.0 * heads * p * n,
            "bytes": 2.0 * 3 * heads * p + 2.0 * 2 * groups * n + 4.0 * heads}  # x', y, z; B, C; dt


def state_row_bytes(hf):
    """What one row keeps in one `M` layer: the float32 state [heads, P, N] and the
    convolution's `taps - 1` last inputs (x', B and C wide, 2 bytes)."""
    heads, p, n = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"]
    conv = heads * p + 2 * hf["n_groups"] * n
    return 4.0 * heads * p * n + 2.0 * (hf["conv_kernel"] - 1) * conv


def chunked_least_seconds(r):
    """Least time for the teacher-forced work one traced cycle REQUIRES: prefill over the
    prompt, the scorer over the sequence with the reference branch's `M` layers, one
    training forward a step over every `M` layer and the backward (twice the forward)
    over the trainable ones; each call at the longer of its FLOPs at the bf16 peak and
    its bytes at the HBM peak."""
    found = layers(r)
    if found is None:
        return None
    n, trainable = found
    t = r.traffic
    seq = t["prompt_tokens"] + t["new_tokens"]
    calls = _routed.cycle_calls(r)
    per_token = token_work(r.hf)
    tokens = (
        calls["sampler"] * t["chunk"] * t["prompt_tokens"] * n
        + calls["scorer"] * t["chunk"] * seq * (n + trainable)
        + calls["train"] * t["batch"] * seq * (n + 2 * trainable)
    )
    work = {k: v * tokens for k, v in per_token.items()}
    return t["trace_cycles"] * flops.roofline_seconds(work, r.peaks)["seconds"] / r.chips


def decode_least_seconds(r):
    """Least time for the recurrent state one traced cycle's decode steps must move: a
    step and an `M` layer, every row's `state_row_bytes` read once and written once, at
    the HBM peak."""
    found = layers(r)
    if found is None:
        return None
    n, _ = found
    t = r.traffic
    steps = (t["new_tokens"] - 1) * max(t["rollouts"] // t["chunk"], 1)
    moved = 2.0 * state_row_bytes(r.hf) * t["chunk"] * steps * n
    return t["trace_cycles"] * moved / r.peaks["hbm_bytes_per_s"] / r.chips
