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

**The family states the work, this file adds it up.** Every function below
takes the description of a model as run on this chip, `work(reference, hf)`:

    {"layers": [layer, ...],   bottom to top, one entry for each layer that is run
     "head": {"flops": ..., "weight_elems": ...},   the output projection, one position
     "leading": 0}             bottom layers a hydra branch never trains (leading dense ones)

    layer = {"linear_flops": forward matmul FLOPs one token requires, attention scores aside
             "pair_flops":   FLOPs of one (query, key) pair of its attention, all heads:
                             score and weighted value, 2 x heads x (qk width + v width)
             "weight_elems": weight elements one decode step must read, at the item size
                             the recipe decodes with
             "cache_elems":  elements one cached position costs a row, at the cache's item size
             "routed":       optional, experts chosen per token, not counted above:
                 {"expert_flops", "expert_elems": one expert, one token
                  "published": experts the router scores, "held": experts on this chip,
                  "per_token": experts a token is sent to}}

A reference module gives it as `work(hf)`; one that gives only `dims(hf)` (one
kind of layer: `n_head`/`n_kv_head` heads of `head_dim`, `mlp_matrices` x `hidden`
x `intermediate`) has it derived here by `describe`, so the functions also take
such a `dims` dict. For routed experts the count from shapes is an EXPECTATION
under uniform routing: a token meets `per_token x held / published` of the experts
held here, and a decode step over `batch` rows reads `held x (1 - (1 - per_token /
published)^batch)` of them. The program's counter of tokens routed here is what a
later reader holds against it.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def work(reference, hf: Dict) -> Dict:
    """The description of `hf` by its reference module: its own `work`,
    or the one derived from its `dims`."""
    return reference.work(hf) if hasattr(reference, "work") else describe(reference.dims(hf))


def describe(d: Dict) -> Dict:
    """`d` if it is a description already; else the description of
    `d["n_layer"]` equal blocks with q, k, v, o and the MLP matrices."""
    if "layers" in d:
        return d
    e, hd = d["hidden"], d["head_dim"]
    qkv = e * hd * (d["n_head"] + 2 * d["n_kv_head"])
    out = d["n_head"] * hd * e
    mlp = d["mlp_matrices"] * e * d["intermediate"]
    layer = {
        "linear_flops": float(2 * (qkv + out + mlp)),
        "pair_flops": 4.0 * d["n_head"] * hd,  # two D-wide dot products a head
        "weight_elems": qkv + out + mlp,
        "cache_elems": 2 * d["n_kv_head"] * hd,
    }
    return {"layers": [layer] * d["n_layer"], "leading": 0,
            "head": {"flops": 2.0 * e * d["vocab"], "weight_elems": e * d["vocab"]}}


def top_layers(d: Dict, top: Optional[int] = None) -> List[Dict]:
    layers = describe(d)["layers"]
    return layers if top is None else layers[len(layers) - top:]


def layer_linear_flops(layer: Dict) -> float:
    """Forward matmul FLOPs of one layer for one token, attention scores
    aside; with routed experts, the expectation (module doc-string)."""
    routed = layer.get("routed")
    if not routed:
        return layer["linear_flops"]
    met = routed["per_token"] * routed["held"] / routed["published"]
    return layer["linear_flops"] + met * routed["expert_flops"]


def attention_flops(layer: Dict, queries: float, keys_each: float) -> float:
    """Scores and weighted values of one layer: each query does its
    `pair_flops` with each key it sees."""
    return layer["pair_flops"] * queries * keys_each


def causal_forward_flops(d: Dict, tokens: int, top: Optional[int] = None) -> float:
    """One sequence of `tokens` through every layer, or the `top` ones,
    teacher-forced: query t sees t keys, (tokens + 1) / 2 on average."""
    return sum(
        tokens * layer_linear_flops(layer) + attention_flops(layer, tokens, (tokens + 1) / 2.0)
        for layer in top_layers(d, top)
    )


def logits_flops(d: Dict, positions: float) -> float:
    return describe(d)["head"]["flops"] * positions


def generation_flops(d: Dict, prompt: int, new: int) -> float:
    """One row: prefill of `prompt` tokens (which yields the first new
    token) and new - 1 single-token steps against a growing cache."""
    total = causal_forward_flops(d, prompt) + logits_flops(d, 1)
    steps = new - 1
    keys = prompt + (steps + 1) / 2.0  # step i sees prompt + i keys
    for layer in top_layers(d):
        total += steps * layer_linear_flops(layer) + attention_flops(layer, steps, keys)
    total += logits_flops(d, steps)
    return total


def trainable_layers(d: Dict, unfrozen: int) -> int:
    """How many layers, from the top, train: all of them, or the hydra
    branch's `unfrozen`, which never reaches into the leading layers."""
    w = describe(d)
    n = len(w["layers"])
    return n if unfrozen is None or unfrozen < 0 else min(unfrozen, n - w["leading"])


def ppo_scoring_flops(d: Dict, prompt: int, new: int, unfrozen: int) -> float:
    """One row of experience scoring: the policy forward, the frozen
    reference (the top `unfrozen` layers from the shared trunk, or a
    whole second model when every layer trains) and the log-probabilities
    of the `new` response tokens under both."""
    seq = prompt + new
    return (
        causal_forward_flops(d, seq)
        + causal_forward_flops(d, seq, trainable_layers(d, unfrozen))
        + 2 * logits_flops(d, new)
    )


def ppo_train_flops(d: Dict, prompt: int, new: int, unfrozen: int) -> float:
    """One row in one optimizer step: forward through every layer,
    backward (input and weight gradients, twice the forward) through the
    trainable top layers and the output projection at the `new` response
    positions. Frozen layers need no gradient."""
    seq = prompt + new
    return (
        causal_forward_flops(d, seq)
        + 2 * causal_forward_flops(d, seq, trainable_layers(d, unfrozen))
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
    """One decode step must read every layer's weights once (of routed
    experts, those the batch's rows are expected to reach), the output
    projection once and what the cache holds of `keys` positions of every row."""
    w = describe(d)
    elems, cached = 0.0, 0.0
    for layer in w["layers"]:
        elems += layer["weight_elems"]
        routed = layer.get("routed")
        if routed:
            reached = 1.0 - (1.0 - routed["per_token"] / routed["published"]) ** batch
            elems += routed["held"] * reached * routed["expert_elems"]
        cached += layer["cache_elems"]
    weights = elems * weight_itemsize + w["head"]["weight_elems"] * head_itemsize
    return float(weights + cached * batch * keys * kv_itemsize)


def roofline_seconds(work: Dict[str, float], peak: Dict[str, float]) -> Dict:
    """The least time the chip could take for `work`, and which bound
    sets it."""
    t_flops = work.get("flops", 0.0) / peak["bf16_flops_per_s"]
    t_bytes = work.get("bytes", 0.0) / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops >= t_bytes else "bytes"}
