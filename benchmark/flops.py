"""Operations and bytes the work requires, from shapes alone.

The benchmark's own arithmetic (the program has `obs/telemetry.mfu_estimate`
at 6N per token and `bench.py:cycle_flops` for GPT-2 blocks; neither is
read). A FLOP is one multiply or one add, so a matmul of [m, k] by [k, n]
is 2mkn. Only matmuls are counted: norms, activations, softmax, rotary,
the sampler and the 512-wide value head are under 1% at these widths.
What is counted is what the algorithm requires, not what the program
runs: nothing for recomputation under remat, nothing for gradients of
frozen layers, logits only where a log-probability is used, causal
attention as the lower triangle.

`dims` is the dict a reference module's `dims()` returns.
"""

from __future__ import annotations

from typing import Dict


def layer_linear_flops(d: Dict) -> float:
    """Forward matmul FLOPs of one block for one token, attention scores
    aside: q, k, v, o and the MLP matrices."""
    e, hd = d["hidden"], d["head_dim"]
    qkv = 2 * e * hd * (d["n_head"] + 2 * d["n_kv_head"])
    out = 2 * d["n_head"] * hd * e
    mlp = 2 * d["mlp_matrices"] * e * d["intermediate"]
    return float(qkv + out + mlp)


def attention_flops(d: Dict, queries: float, keys_each: float) -> float:
    """Scores and weighted values of one layer: each query does two
    D-wide dot products per head with each key it sees."""
    return 4.0 * d["n_head"] * d["head_dim"] * queries * keys_each


def causal_forward_flops(d: Dict, tokens: int, n_layers: int) -> float:
    """One sequence of `tokens` through `n_layers` blocks, teacher-forced:
    query t sees t keys, (tokens + 1) / 2 on average."""
    per_layer = tokens * layer_linear_flops(d) + attention_flops(
        d, tokens, (tokens + 1) / 2.0
    )
    return n_layers * per_layer


def logits_flops(d: Dict, positions: float) -> float:
    return 2.0 * d["hidden"] * d["vocab"] * positions


def generation_flops(d: Dict, prompt: int, new: int) -> float:
    """One row: prefill of `prompt` tokens (which yields the first new
    token) and new - 1 single-token steps against a growing cache."""
    total = causal_forward_flops(d, prompt, d["n_layer"]) + logits_flops(d, 1)
    steps = new - 1
    keys = prompt + (steps + 1) / 2.0  # step i sees prompt + i keys
    total += steps * d["n_layer"] * layer_linear_flops(d)
    total += d["n_layer"] * attention_flops(d, steps, keys)
    total += logits_flops(d, steps)
    return total


def trainable_layers(d: Dict, unfrozen: int) -> int:
    return d["n_layer"] if unfrozen is None or unfrozen < 0 else min(unfrozen, d["n_layer"])


def ppo_scoring_flops(d: Dict, prompt: int, new: int, unfrozen: int) -> float:
    """One row of experience scoring: the policy forward, the frozen
    reference (the top `unfrozen` layers from the shared trunk, or a
    whole second model when every layer trains) and the log-probabilities
    of the `new` response tokens under both."""
    seq = prompt + new
    ref_layers = trainable_layers(d, unfrozen)
    return (
        causal_forward_flops(d, seq, d["n_layer"])
        + causal_forward_flops(d, seq, ref_layers)
        + 2 * logits_flops(d, new)
    )


def ppo_train_flops(d: Dict, prompt: int, new: int, unfrozen: int) -> float:
    """One row in one optimizer step: forward through every layer,
    backward (input and weight gradients, twice the forward) through the
    trainable top layers and the output projection at the `new` response
    positions. Frozen layers need no gradient."""
    seq = prompt + new
    k = trainable_layers(d, unfrozen)
    return (
        causal_forward_flops(d, seq, d["n_layer"])
        + 2 * causal_forward_flops(d, seq, k)
        + 3 * logits_flops(d, new)
    )


def ppo_cycle_flops(d: Dict, traffic: Dict, unfrozen: int) -> Dict[str, float]:
    """What one PPO cycle of the traffic mix requires, by phase."""
    p, n, rows = traffic["prompt_tokens"], traffic["new_tokens"], traffic["rollouts"]
    out = {
        "generation": rows * generation_flops(d, p, n),
        "scoring": rows * ppo_scoring_flops(d, p, n, unfrozen),
        "training": traffic["method_kwargs"]["ppo_epochs"] * rows * ppo_train_flops(d, p, n, unfrozen),
    }
    out["total"] = sum(out.values())
    return out


# -- kernels: FLOPs and HBM bytes of one call, for roofline shares ------


def flash_fwd(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> Dict[str, float]:
    """Causal fused attention forward: reads q, k, v, writes o."""
    flops = 4.0 * batch * heads * head_dim * seq * (seq + 1) / 2.0
    elems = batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return {"flops": flops, "bytes": float(elems * itemsize)}


def flash_bwd(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
              itemsize: int = 2) -> Dict[str, float]:
    """Backward: dq, dk, dv are four matmuls of the forward's size plus
    the recomputed scores (required by the algorithm: the forward keeps
    no score matrix), 2.5x the forward; reads q, k, v, o, do and writes
    dq, dk, dv."""
    fwd = flash_fwd(batch, heads, kv_heads, seq, head_dim, itemsize)
    elems = batch * seq * head_dim * (4 * heads + 4 * kv_heads)
    return {"flops": 2.5 * fwd["flops"], "bytes": float(elems * itemsize)}


def adam8bit_bytes(n_params: float, grad_itemsize: int = 2) -> float:
    """Fused int8-moment AdamW over float32 masters: reads the master,
    the gradient and two int8 moments, writes the master and both
    moments (block scales, one float per 256, are under 2%)."""
    return n_params * (4 + grad_itemsize + 2 + 4 + 2)


def decode_step_bytes(d: Dict, batch: int, keys: float, weight_itemsize: int = 1,
                      kv_itemsize: int = 1, head_itemsize: int = 2) -> float:
    """One decode step must read every block weight once, the output
    projection once and the keys and values of every row."""
    e, hd = d["hidden"], d["head_dim"]
    block = e * hd * (d["n_head"] + 2 * d["n_kv_head"]) + d["n_head"] * hd * e
    block += d["mlp_matrices"] * e * d["intermediate"]
    weights = d["n_layer"] * block * weight_itemsize + e * d["vocab"] * head_itemsize
    kv = 2.0 * d["n_layer"] * batch * d["n_kv_head"] * hd * keys * kv_itemsize
    return float(weights + kv)


def roofline_seconds(work: Dict[str, float], peak: Dict[str, float]) -> Dict:
    """The least time the chip could take for `work`, and which bound
    sets it."""
    t_flops = work.get("flops", 0.0) / peak["bf16_flops_per_s"]
    t_bytes = work.get("bytes", 0.0) / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes"}
