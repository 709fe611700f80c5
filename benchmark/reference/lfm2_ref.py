"""Plain LFM2-MoE reference: float32 `jax.numpy`, no kernels, no cache, no chunks.

Written from the published `config.json` keys (model_type `lfm2_moe`) and the
equations they name, and independent of `trlx_tpu/models/transformer.py`: nothing
here imports the program. d = hidden_size.

Every layer is a mixer sub-layer, then a feed-forward sub-layer, each behind its own
RMSNorm (eps `norm_eps`, weight * x * rsqrt(mean x^2 + eps)), no bias anywhere:

    x <- x + Mixer(RMSNorm(x));   x <- x + FFN(RMSNorm(x))

After the last layer the final RMSNorm, then the head TIED to the embedding (the
published config omits `tie_word_embeddings`; the family's config class defaults to
a tied head, and the published parameter count bears it out: `assumed`).

Mixer by `layer_types`:

"conv", a gated short convolution (`conv_L_cache` taps, `conv_bias` false):

    [B | C | u] = x W_in                        W_in d x 3d, split in this order
    v = B u
    c_t = sum_{i=0..taps-1} w_i v_{t-(taps-1)+i}   causal depthwise, zeros before the start
    y = (C c) W_out                             W_out d x d

A padded position's input x is zeroed before W_in, as the published code zeroes
it, so B, C and u are zero there.

"full_attention": `num_attention_heads` query heads over `num_key_value_heads`
key-value heads of head_dim = d / heads (not given: `assumed`); q and k each through
an RMSNorm over the head's channels with a learned weight (`q_layernorm`,
`k_layernorm`), then rotary over the whole head, rotate-half pairs (c, c + D/2),
theta `rope_parameters.rope_theta`; causal, scale head_dim^-0.5.

FFN: the layers below `num_dense_layers` W2(SiLU(x W1) * x W3) at `intermediate_size`;
the others routed, no shared expert:

    s = sigmoid(x W_g)                          `num_experts_published` wide
    chosen = top-k of (s + b),  k = num_experts_per_tok      (b: `use_expert_bias`)
    w = routed_scaling_factor * s[chosen] / (sum(s[chosen]) + 1e-6)   (norm_topk_prob)
    y = sum_{e in chosen, e HELD HERE} w_e W2_e(SiLU(x W1_e) * x W3_e)    width moe_intermediate_size

THE SHARE: the configuration's file gives under `num_experts` the experts held on
this chip (experts first_expert_held ...), under `num_experts_published` the router's
width, and a vocabulary slice. The reference routes over all published experts and
adds the held experts' part alone; what the absent experts would add is left out,
here and in the program. The shares' parts of a layer add up to the uncut layer's.

`hidden_states` also returns, per position, whether its routing was DECISIVE: in every
routed layer, either the k-th and (k+1)-th of (s + b) lie more than
`correct.routing_margin` apart, or no expert held here lies within that margin of the
boundary between them; and no position one to taps - 1 back was within
`correct.neighbour_margin` of such a tie in a routed layer below a conv layer. A
convolution adds its taps' inputs at full weight, so an expert swapped at a
neighbour (bf16 swaps a near-exact tie about half the time) reaches the position
through the next conv layer, where attention averages it over hundreds of positions.

Every matmul runs under `jax.default_matmul_precision("highest")`. On the chip it fits
as it is: the experts run one held expert at a time over every position.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

MIXERS = {"conv": "conv", "full_attention": "softmax"}


def layer_types(hf: Dict):
    """The mixer of each layer run, as published ("conv" or "full_attention")."""
    kinds = list(hf["layer_types"])
    if len(kinds) != hf["num_hidden_layers"] or set(kinds) - set(MIXERS):
        raise ValueError(f"layer_types names 'conv' or 'full_attention' for each of the "
                         f"{hf['num_hidden_layers']} layers")
    return kinds


def head_dim(hf: Dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def system_config(hf: Dict) -> Dict:
    """The published keys as the keyword arguments of the system's
    `TransformerConfig` (the only place that knows both names)."""
    if hf["conv_bias"] or not hf["norm_topk_prob"] or not hf["use_expert_bias"]:
        raise NotImplementedError("a convolution bias or a router weighting other than the published ones")
    rope = hf["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise NotImplementedError(f"rope_type {rope['rope_type']!r}")
    return dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"],
        head_dim=head_dim(hf),
        intermediate_size=hf["intermediate_size"],
        pos_embed="rotary",
        rotary_style="neox",
        rope_theta=float(rope["rope_theta"]),
        norm="rmsnorm",
        layer_norm_epsilon=hf["norm_eps"],
        activation="silu",
        mlp_gated=True,
        use_attn_bias=False,
        use_mlp_bias=False,
        use_norm_bias=False,
        tie_word_embeddings=hf.get("tie_word_embeddings", True),
        mixer_layers=tuple(MIXERS[kind] for kind in layer_types(hf)),
        conv_taps=hf["conv_L_cache"],
        qk_norm=True,
        n_routed_experts=hf["num_experts_published"],
        n_experts_held=hf["num_experts"],
        first_expert_held=hf.get("first_expert_held", 0),
        n_experts_per_token=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        first_k_dense=hf["num_dense_layers"],
        router_balance_steps=hf.get("router_bias_balance_steps", 0),
    )


def toy_sizes(hf: Dict) -> Dict:
    """The overrides `--rehearse` runs this family at: every mechanism, no cost. A
    leading dense conv layer, then attention, conv, conv, attention, conv, conv: the
    top-2 branch is two conv + routed layers, as at full size; grouped heads 4 over 2
    of 16 channels; a token is sent to 3 of 16 experts and 4 are held."""
    return {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
            "intermediate_size": 96, "moe_intermediate_size": 32,
            "num_hidden_layers": 7, "num_dense_layers": 1,
            "layer_types": ["conv", "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
            "num_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 3,
            "vocab_size": 512}


# -- the work, for benchmark/flops.py ----------------------------------------


def _elems(hf: Dict) -> Dict[str, int]:
    """Weight elements of one layer's parts, from the published keys."""
    d, heads, kv, hd = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], head_dim(hf)
    return {
        "conv_written": 3 * d * d + d * d,  # W_in and W_out, int8 in the rollout
        "conv_taps": hf["conv_L_cache"] * d,  # float32, elementwise
        "attention": d * hd * (2 * heads + 2 * kv),  # q and o, k and v
        "qk_norms": 2 * hd,
        "dense_mlp": 3 * d * hf["intermediate_size"],
        "router": d * hf["num_experts_published"],
        "expert": 3 * d * hf["moe_intermediate_size"],
    }


def work(hf: Dict) -> Dict:
    """The layers one by one, as the doc-string of `benchmark/flops.py` sets out.
    `linear_flops` of a conv layer: its two projections (the 6 d FLOPs a token of
    the gates and taps are under 1%), `pair_flops` 0 and `cache_elems` 0 (its window
    does not grow); of an attention layer q, k, v, o with 4 x heads x head_dim a pair
    and 2 x kv heads x head_dim cached; then the dense MLP, or the router with one
    routed expert's three products under `routed`. `weight_elems` in units of the
    item size the recipe decodes with: under int8 rollout weights the float32 taps,
    norms and router count four times."""
    e = _elems(hf)
    int8 = (hf.get("recipe", {}).get("model", {}).get("model_extra_configs", {})
            .get("transformer", {}).get("decode_weights_quant") == "int8")
    f32 = 4 if int8 else 2
    hd = head_dim(hf)
    mixers = {
        "conv": dict(linear_flops=2.0 * e["conv_written"],
                     weight_elems=e["conv_written"] + f32 * e["conv_taps"], pair_flops=0.0, cache_elems=0),
        "full_attention": dict(linear_flops=2.0 * e["attention"],
                               weight_elems=e["attention"] + f32 * e["qk_norms"],
                               pair_flops=4.0 * hf["num_attention_heads"] * hd,
                               cache_elems=2 * hf["num_key_value_heads"] * hd),
    }
    lead = hf["num_dense_layers"]
    routed = {"expert_flops": 2.0 * e["expert"], "expert_elems": e["expert"],
              "published": hf["num_experts_published"], "held": hf["num_experts"],
              "per_token": hf["num_experts_per_tok"]}
    layers = []
    for index, kind in enumerate(layer_types(hf)):
        layer = dict(mixers[kind])
        if index < lead:
            layer["linear_flops"] += 2.0 * e["dense_mlp"]
            layer["weight_elems"] += e["dense_mlp"]
        else:
            layer["linear_flops"] += 2.0 * e["router"]
            layer["weight_elems"] += f32 * e["router"]
            layer["routed"] = routed
        layers.append(layer)
    head = hf["hidden_size"] * hf["vocab_size"]
    return {"layers": layers, "leading": lead, "head": {"flops": 2.0 * head, "weight_elems": head}}


def params_held(hf: Dict) -> Dict[str, int]:
    """Parameters held here by kind of layer, each with its two norms."""
    e, d = _elems(hf), hf["hidden_size"]
    conv = e["conv_written"] + e["conv_taps"]
    attention = e["attention"] + e["qk_norms"]
    routed = e["router"] + hf["num_experts_published"] + hf["num_experts"] * e["expert"]
    lead = hf["num_dense_layers"]
    sizes = {"dense_layer": conv + e["dense_mlp"] + 2 * d, "conv_routed_layer": conv + routed + 2 * d,
             "attention_routed_layer": attention + routed + 2 * d}
    embed = d * hf["vocab_size"]  # tied: the head is the embedding
    total = embed + d
    for index, kind in enumerate(layer_types(hf)):
        mixer = conv if kind == "conv" else attention
        total += mixer + 2 * d + (e["dense_mlp"] if index < lead else routed)
    return dict(sizes, conv_mixer=conv, attention_mixer=attention, routed_expert=e["expert"],
                router=e["router"] + hf["num_experts_published"], dense_mlp=e["dense_mlp"],
                embed_and_head=embed, total=total)


# -- the program's tree under this file's names -------------------------------


def _layer_names(blk: Dict) -> Dict:
    out = {"norm_1": blk["ln_1"]["scale"], "norm_2": blk["ln_2"]["scale"]}
    if "conv" in blk:
        conv = blk["conv"]
        out["conv"] = {"w_in": conv["in_proj"]["kernel"], "w_out": conv["out_proj"]["kernel"],
                       "taps": conv["conv_w"]}  # taps [L, taps, d]
    if "attn" in blk:
        attn = blk["attn"]
        out["attn"] = {"w_q": attn["q"]["kernel"], "w_k": attn["k"]["kernel"], "w_v": attn["v"]["kernel"],
                       "w_o": attn["o"]["kernel"],  # [L, d, heads, hd]; w_o [L, heads, hd, d]
                       "q_norm": attn["q_norm"], "k_norm": attn["k_norm"]}
    if "mlp" in blk:
        mlp = blk["mlp"]
        out["ffn"] = {"w_1": mlp["fc_in"]["kernel"], "w_3": mlp["fc_gate"]["kernel"],
                      "w_2": mlp["fc_out"]["kernel"]}
    if "moe" in blk:
        moe = blk["moe"]
        out["moe"] = {"w_r": moe["router_gate"], "b": moe["router_bias"],
                      "w_1": moe["experts_fc_in"]["kernel"], "w_3": moe["experts_fc_gate"]["kernel"],
                      "w_2": moe["experts_fc_out"]["kernel"]}  # [L, held, ...]
    return out


def params_from_system(base: Dict) -> Dict:
    """The system's language-model tree (`params["base"]`: the leading dense layers
    stacked under `dense_blocks`, the conv + routed layers under `conv_blocks`, the
    attention + routed ones under `blocks`, each in layer order) renamed to this
    file's layout. No arithmetic. `hidden_states` walks `layer_types` and takes each
    layer from its stack's next row."""
    out = {"embed": base["embed"]["wte"], "lnf": base["ln_f"]["scale"]}
    for mine, theirs in (("dense", "dense_blocks"), ("conv", "conv_blocks"), ("attn", "blocks")):
        if theirs in base:
            out[mine] = _layer_names(base[theirs])
    return out


# -- the forward ---------------------------------------------------------------


def _rms(x, g, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if g is None else y * g


def short_conv(x, w, mask):
    """x [B, T, d] -> [B, T, d]: the gated short convolution, padded positions zeroed first."""
    d, taps, T = x.shape[-1], w["taps"].shape[0], x.shape[1]
    bcu = (x * mask[..., None]) @ w["w_in"]
    b, c, u = bcu[..., :d], bcu[..., d : 2 * d], bcu[..., 2 * d :]
    v = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w["taps"][i] * v[:, i : i + T] for i in range(taps))
    return (c * conv) @ w["w_out"]


def _rotate(x, cos, sin):
    """x [B, T, H, D]; pairs (c, c + D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w, hf, positions, visible):
    heads, kv, hd, eps = hf["num_attention_heads"], hf["num_key_value_heads"], head_dim(hf), hf["norm_eps"]
    inv = hf["rope_parameters"]["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[..., None].astype(jnp.float32) * inv  # [B, T, D / 2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    q = _rotate(_rms(jnp.einsum("btd,dhk->bthk", x, w["w_q"]), w["q_norm"], eps), cos, sin)
    k = _rotate(_rms(jnp.einsum("btd,dhk->bthk", x, w["w_k"]), w["k_norm"], eps), cos, sin)
    v = jnp.einsum("btd,dhk->bthk", x, w["w_v"])
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    s = jnp.einsum("bthk,bshk->bhts", q, k) / math.sqrt(hd)
    s = jnp.where(visible, s, jnp.finfo(jnp.float32).min)
    o = jnp.einsum("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bthk,hkd->btd", o, w["w_o"])


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["w_1"]) * (x @ w["w_3"])) @ w["w_2"]


def route(x, w, hf):
    """(weights [B, T, published] with zeros off the chosen, values s + b)."""
    k = hf["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["w_r"])
    values = s + w["b"]
    kth = jnp.sort(values, axis=-1)[..., -k][..., None]
    picked = jnp.where(values >= kth, s, 0.0)
    weights = hf["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-6)
    return weights, values


def sure(values, hf, margin):
    """Per position: the k-th and (k+1)-th of `values` (s + b) lie more than `margin`
    apart, or no expert held here lies within `margin` of the boundary between them."""
    first, held, k = hf.get("first_expert_held", 0), hf["num_experts"], hf["num_experts_per_tok"]
    ordered = jnp.sort(values, axis=-1)
    kth, nxt = ordered[..., -k], ordered[..., -k - 1]
    boundary = 0.5 * (kth + nxt)[..., None]
    near_held = (jnp.abs(values - boundary) <= margin)[..., first : first + held].any(-1)
    return ((kth - nxt) > margin) | ~near_held


def experts(x, w, hf):
    """(The HELD experts' part of a routed layer, the values s + b it chose by). One held
    expert at a time, every position through it, weighted by its routing weight (zero
    where it was not chosen)."""
    first, held = hf.get("first_expert_held", 0), hf["num_experts"]
    weights, values = route(x, w, hf)
    y = jnp.zeros_like(x)
    for e in range(held):
        y = y + weights[..., first + e, None] * _swiglu(x, {name: w[name][e] for name in ("w_1", "w_2", "w_3")})
    return y, values


def hidden_states(p: Dict, hf: Dict, tokens, mask):
    """(final-norm hidden states [B, T, d], decisive [B, T]) for `tokens` [B, T]
    under the padding `mask` [B, T] (1 = real token)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    eps, lead, taps = hf["norm_eps"], hf["num_dense_layers"], hf["conv_L_cache"]
    margin = hf.get("correct", {}).get("routing_margin", 0.0)
    neighbour = hf.get("correct", {}).get("neighbour_margin", 0.0)
    T = tokens.shape[1]
    live = mask.astype(jnp.float32)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)
    taken = {"dense": 0, "conv": 0, "attn": 0}
    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        decisive = jnp.ones(tokens.shape, bool)
        tied = jnp.zeros(tokens.shape, bool)  # a near-exact tie so far at the position
        for index, kind in enumerate(layer_types(hf)):
            stack = "dense" if index < lead else ("conv" if kind == "conv" else "attn")
            w = jax.tree_util.tree_map(lambda a: a[taken[stack]], p[stack])
            taken[stack] += 1
            h = _rms(x, w["norm_1"], eps)
            if kind == "conv":
                for back in range(1, taps):
                    decisive = decisive & ~jnp.pad(tied, ((0, 0), (back, 0)))[:, :T]
                x = x + short_conv(h, w["conv"], live)
            else:
                x = x + attention(h, w["attn"], hf, positions, visible)
            h = _rms(x, w["norm_2"], eps)
            if "ffn" in w:
                x = x + _swiglu(h, w["ffn"])
            else:
                y, values = experts(h, w["moe"], hf)
                x, decisive = x + y, decisive & sure(values, hf, margin)
                tied = tied | ~sure(values, hf, neighbour)
        return _rms(x, p["lnf"], eps), decisive


def logits(p: Dict, hidden):
    """The tied head over the vocabulary slice, float32 [..., V]."""
    with jax.default_matmul_precision("highest"):
        return hidden.astype(jnp.float32) @ p["embed"].astype(jnp.float32).T
