"""Operations and bytes of the gated short-convolution mixer's teacher-forced form,
counted from the algorithm and the same whatever implements it, for the readers
`conv_mix_roofline` and `conv_share`. A family without a "conv" in `layer_types`
gives None throughout.

Per token and conv layer the form is two projections, W_in (d -> 3d) and W_out
(d -> d), with the gated causal convolution between them (v = B u, `conv_L_cache`
taps over v, y = C c): 2 x 4 d^2 FLOPs of products (the 2 + 2 taps a channel of the
pass are under 0.1% of them), against x read and the output written, 2 d numbers of 2
bytes (the pass's B, C and u need never reach the HBM: the compiler fuses it into the
two projections, so nothing of it is required there). The scope `conv_mix` holds the
whole form for that reason; a decode step's form is under `conv_step`."""

from benchmark import flops
from benchmark.layer_metrics import _routed

SCOPES = ("conv_proj", "conv_mix", "conv_step")


def layers(r):
    """(conv layers run, those of them a hydra branch trains) or None for another family."""
    kinds = r.hf.get("layer_types")
    if not kinds or "conv" not in kinds or "conv_L_cache" not in r.hf:
        return None
    depth = r.hf["num_hidden_layers"]
    run = [index for index, kind in enumerate(kinds[:depth]) if kind == "conv"]
    trainable = flops.trainable_layers(flops.work(r.cell.reference, r.hf), r.unfrozen)
    return len(run), sum(index >= depth - trainable for index in run)


def token_work(hf):
    """FLOPs and HBM bytes one token requires of one conv layer's teacher-forced form."""
    d = hf["hidden_size"]
    return {"flops": 2.0 * 4 * d * d, "bytes": 2.0 * 2 * d}


def mix_least_seconds(r):
    """Least time for the teacher-forced forms one traced cycle REQUIRES: prefill over
    the prompt through every conv layer; the scorer over prompt and response through
    every conv layer and the reference branch's; the frozen trunk's conv layers once a
    fused block over every rollout (as the program runs them); the trainable ones
    forward and backward (twice the forward) every optimizer step; nothing for remat.
    At the longer of the FLOPs at the bf16 peak and the bytes at the HBM peak."""
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
        + t["rollouts"] * seq * (n - trainable)
        + calls["train"] * t["batch"] * seq * 3 * trainable
    )
    work = {k: v * tokens for k, v in token_work(r.hf).items()}
    return t["trace_cycles"] * flops.roofline_seconds(work, r.peaks)["seconds"] / r.chips

