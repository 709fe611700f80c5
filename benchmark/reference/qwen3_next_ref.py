"""Plain Qwen3-Next reference: float32 `jax.numpy`, no kernels, no cache, no chunks.

Written from the published `config.json` keys (model_type `qwen3_next`) and the
equations they name, and independent of `trlx_tpu/models/transformer.py`: nothing
here imports the program. d = hidden_size.

Every layer is a mixer sub-layer, then a routed feed-forward sub-layer, each behind
its own ZERO-CENTRED RMSNorm (eps `rms_norm_eps`, float32):

    RMSNorm(x) = x * rsqrt(mean x^2 + eps) * (1 + w)          w initialised to 0
    x <- x + Mixer(RMSNorm(x));   x <- x + MoE(RMSNorm(x))

After the last layer the final RMSNorm, then an untied head. Layer l (1-based) is
gated attention where l is a multiple of `full_attention_interval`, Gated DeltaNet
elsewhere; every layer is routed (`decoder_sparse_step` 1, `mlp_only_layers` []).

Gated DeltaNet, Hk = `linear_num_key_heads` key heads and Hv = `linear_num_value_heads`
value heads of d_k = d_v = 128, per position t, as a plain RECURRENCE (a `lax.scan`
over positions):

    [q | k | v | z] = x W_qkvz       q, k: Hk heads; v, z: Hv heads
    [b | a]         = x W_ba         Hv each
    q, k, v = split(SiLU(Conv([q | k | v])))      ONE causal depthwise convolution of
              `linear_conv_kernel_dim` taps over 2 Hk d_k + Hv d_v channels, no bias,
              zeros before the start
    q = q / sqrt(|q|^2 + 1e-6) * d_k^-0.5,   k = k / sqrt(|k|^2 + 1e-6)       per head
    value head h reads key head floor(h / (Hv / Hk))                        (repeat_interleave)
    g_h = -exp(A_log_h) * softplus(a_h + dt_bias_h);   beta_h = sigmoid(b_h)   a scalar a head
    S_t = exp(g_t) S_{t-1};   S_t = S_t + beta_t k_t (v_t - S_t^T k_t)^T        S_0 = 0
    o_t = S_t^T q_t
    y = (w_o * RMSNorm_head(o) * SiLU(z)) W_out          w_o initialised to 1 (not zero-centred)

A position whose mask is 0 changes nothing: its convolution input is zero, its beta 0
and its g 0.

Gated attention: `num_attention_heads` query heads over `num_key_value_heads` of
`head_dim` (not d / heads):

    [q | gate] = x W_q          a head's 2 x head_dim columns: its query, then its gate
    q = RMSNorm(q), k = RMSNorm(x W_k) a head (zero-centred), then rotary on the first
        head_dim x `partial_rotary_factor` channels, rotate-half pairs, theta `rope_theta`
    y = (softmax(q k^T / sqrt(head_dim), causal) v  *  sigmoid(gate)) W_o

Experts, `num_experts_published` of SwiGLU at `moe_intermediate_size`:

    p = softmax(x W_g) over all published experts (float32)
    chosen = top-k of p,  k = num_experts_per_tok;   w = p[chosen] / sum(p[chosen])  (norm_topk_prob)
    y = sigmoid(x w_sg) * Shared(x) + sum_{e in chosen, e HELD HERE} w_e Expert_e(x)
        Shared: SwiGLU at `shared_expert_intermediate_size`

THE SHARE: the configuration's file gives under `num_experts` the experts held on
this chip (experts first_expert_held ...), under `num_experts_published` the router's
width, and a vocabulary slice. The reference routes over all published experts and
adds the held experts' part alone; what the absent experts would add is left out,
here and in the program. The shares' parts of a layer, the shared expert counted
once, add up to the uncut layer's.

Departures, each listed in the configuration's `assumed` or `reduced`: the published
checkpoint lays W_qkvz and W_ba out interleaved by key-head group, which a loader
would permute into the split above (with random weights the function is the same);
initial values the config does not give are the program's; the multi-token-prediction
module `described_as` mentions is not in the config and is not run.

`hidden_states` also returns, per position, whether its routing was DECISIVE: in
every routed layer, either the k-th and (k+1)-th router LOGITS lie more than
`correct.routing_margin` apart (softmax keeps the order), or no expert held here lies
within that margin of the boundary between them; and no position one to taps - 1
back was within `correct.neighbour_margin` of such a tie in a routed layer below a
delta-rule layer, whose convolution adds a neighbour's input at full weight.

Every matmul runs under `jax.default_matmul_precision("highest")`. On the chip it
fits as it is: the recurrence holds one [rows, Hv, d_k, d_v] state, the experts run
one held expert at a time over every position.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def mixers(hf: Dict):
    """The mixer of each of the layers run: "softmax" at every
    `full_attention_interval`-th (1-based), "delta" elsewhere."""
    every = hf["full_attention_interval"]
    return tuple("softmax" if layer % every == 0 else "delta" for layer in range(1, hf["num_hidden_layers"] + 1))


def rotary_dim(hf: Dict) -> int:
    return int(hf["head_dim"] * hf["partial_rotary_factor"])


def system_config(hf: Dict) -> Dict:
    """The published keys as the keyword arguments of the system's
    `TransformerConfig` (the only place that knows both names)."""
    if (hf["decoder_sparse_step"] != 1 or hf["mlp_only_layers"] or not hf["norm_topk_prob"]
            or hf["linear_key_head_dim"] != hf["linear_value_head_dim"]
            or hf.get("rope_scaling") or hf.get("use_sliding_window")):
        raise NotImplementedError("dense layers, an unnormalised top-k, key and value heads of two "
                                  "widths, rope scaling or sliding windows")
    return dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"],
        head_dim=hf["head_dim"],
        intermediate_size=hf["intermediate_size"],
        pos_embed="rotary",
        rotary_style="neox",
        rotary_dim=rotary_dim(hf),
        rope_theta=float(hf["rope_theta"]),
        norm="rmsnorm",
        norm_zero_centred=True,
        layer_norm_epsilon=hf["rms_norm_eps"],
        activation=hf["hidden_act"],
        mlp_gated=True,
        use_attn_bias=False,
        use_mlp_bias=False,
        use_norm_bias=False,
        tie_word_embeddings=hf["tie_word_embeddings"],
        qk_norm=True,
        attn_output_gate=True,
        mixer_layers=mixers(hf),
        delta_form="gated",
        delta_heads=hf["linear_num_key_heads"],
        delta_value_heads=hf["linear_num_value_heads"],
        delta_head_dim=hf["linear_key_head_dim"],
        delta_conv=hf["linear_conv_kernel_dim"],
        n_routed_experts=hf["num_experts_published"],
        n_experts_held=hf["num_experts"],
        first_expert_held=hf.get("first_expert_held", 0),
        n_experts_per_token=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_shared_experts=1,
        moe_shared_intermediate_size=hf["shared_expert_intermediate_size"],
        shared_expert_gate=True,
        router_score="softmax",
        router_balance_steps=hf.get("router_balance_steps", 0),
    )


def toy_sizes(hf: Dict) -> Dict:
    """The overrides `--rehearse` runs this family at: every mechanism, no cost.
    GDN, attention, GDN, attention: the top-2 branch is one layer of each mixer, as
    at full size; 4 value heads over 2 key heads of 16, 4 query heads over 2 of 16
    with 4 rotary channels; a token is sent to 3 of 16 experts and 4 are held."""
    return {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
            "linear_num_key_heads": 2, "linear_num_value_heads": 4,
            "linear_key_head_dim": 16, "linear_value_head_dim": 16,
            "intermediate_size": 96, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
            "num_hidden_layers": 4, "full_attention_interval": 2,
            "num_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 3,
            "vocab_size": 512}


# -- the work, for benchmark/flops.py ----------------------------------------


def _elems(hf: Dict) -> Dict[str, int]:
    """Weight elements of one layer's parts, from the published keys."""
    d, heads, kv, hd = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    kh, vh, dk = hf["linear_num_key_heads"], hf["linear_num_value_heads"], hf["linear_key_head_dim"]
    conv = (2 * kh + vh) * dk
    return {
        # GDN: W_qkvz and W_out, int8 in the rollout
        "gdn_written": d * (conv + vh * dk) + vh * dk * d,
        # W_ba: compute dtype in the rollout (it feeds the float32 decay and beta)
        "gdn_ba": 2 * d * vh,
        # the taps, A_log, dt_bias, the output norm: float32, elementwise
        "gdn_vectors": hf["linear_conv_kernel_dim"] * conv + 2 * vh + dk,
        # attention: q with its gate, k, v, o, int8 in the rollout; the two norms float32
        "attention": d * hd * (2 * heads + 2 * kv) + heads * hd * d,
        "qk_norms": 2 * hd,
        "router": d * hf["num_experts_published"],
        "expert": 3 * d * hf["moe_intermediate_size"],
        "shared": 3 * d * hf["shared_expert_intermediate_size"],
        "shared_gate": d,
    }


def work(hf: Dict) -> Dict:
    """The layers one by one, as the doc-string of `benchmark/flops.py` sets out.
    `linear_flops` of a GDN layer: its three projections and, a value head, the three
    products of the recurrence (decayed state times k, the rank-one update, state
    times q: 6 d_k d_v a token), the least any form needs; `pair_flops` 0 and
    `cache_elems` 0 there (its state does not grow). Of an attention layer q with its
    gate, k, v, o, a pair 2 x heads x (head_dim + head_dim), 2 x kv heads x head_dim
    cached. Then the router, the shared expert and its gate, and one routed expert's
    three products under `routed`. `weight_elems` in units of the item size the
    recipe decodes with: under int8 rollout weights what stays in the compute dtype
    counts twice, the float32 vectors and router four times."""
    e = _elems(hf)
    int8 = (hf.get("recipe", {}).get("model", {}).get("model_extra_configs", {})
            .get("transformer", {}).get("decode_weights_quant") == "int8")
    wide, f32 = (2, 4) if int8 else (1, 2)
    vh, dk = hf["linear_num_value_heads"], hf["linear_key_head_dim"]
    hd = hf["head_dim"]
    mixer = {
        "delta": dict(linear_flops=2.0 * (e["gdn_written"] + e["gdn_ba"]) + 6.0 * vh * dk * dk,
                      weight_elems=e["gdn_written"] + wide * e["gdn_ba"] + f32 * e["gdn_vectors"],
                      pair_flops=0.0, cache_elems=0),
        "softmax": dict(linear_flops=2.0 * e["attention"],
                        weight_elems=e["attention"] + f32 * e["qk_norms"],
                        pair_flops=2.0 * hf["num_attention_heads"] * (hd + hd),
                        cache_elems=2 * hf["num_key_value_heads"] * hd),
    }
    routed = {"expert_flops": 2.0 * e["expert"], "expert_elems": e["expert"],
              "published": hf["num_experts_published"], "held": hf["num_experts"],
              "per_token": hf["num_experts_per_tok"]}
    layers = []
    for kind in mixers(hf):
        layer = dict(mixer[kind])
        layer["linear_flops"] += 2.0 * (e["router"] + e["shared"] + e["shared_gate"])
        layer["weight_elems"] += f32 * e["router"] + e["shared"] + wide * e["shared_gate"]
        layer["routed"] = routed
        layers.append(layer)
    head = hf["hidden_size"] * hf["vocab_size"]
    return {"layers": layers, "leading": 0, "head": {"flops": 2.0 * head, "weight_elems": head}}


def params_held(hf: Dict) -> Dict[str, int]:
    """Parameters held here by part, each layer with its two norms."""
    e, d = _elems(hf), hf["hidden_size"]
    gdn = e["gdn_written"] + e["gdn_ba"] + e["gdn_vectors"]
    attention = e["attention"] + e["qk_norms"]
    outside = e["router"] + e["shared"] + e["shared_gate"]
    routed = outside + hf["num_experts"] * e["expert"]
    embed = 2 * d * hf["vocab_size"]  # the embedding and the untied head
    total = embed + d
    for kind in mixers(hf):
        total += (gdn if kind == "delta" else attention) + routed + 2 * d
    return {"gdn_mixer": gdn, "attention_mixer": attention, "router_and_shared": outside,
            "routed_expert": e["expert"], "embed_and_head": embed, "total": total}


# -- the program's tree under this file's names -------------------------------


def _layer_names(blk: Dict) -> Dict:
    attn, moe = blk["attn"], blk["moe"]
    out = {"norm_1": blk["ln_1"]["scale"], "norm_2": blk["ln_2"]["scale"]}
    if "qkvz" in attn:
        out["gdn"] = {"w_qkvz": attn["qkvz"]["kernel"], "w_ba": attn["ba"]["kernel"],
                      "taps": attn["conv"],  # [L, taps, channels]
                      "a_log": attn["A_log"], "dt_bias": attn["dt_bias"],
                      "o_norm": attn["o_norm"], "w_out": attn["o"]["kernel"]}  # w_out [L, Hv, d_v, d]
    else:
        out["attn"] = {"w_q": attn["q"]["kernel"],  # [L, d, heads, 2 hd]: q, then its gate
                       "w_k": attn["k"]["kernel"], "w_v": attn["v"]["kernel"],
                       "w_o": attn["o"]["kernel"],  # [L, heads, hd, d]
                       "q_norm": attn["q_norm"], "k_norm": attn["k_norm"]}
    out["moe"] = {"w_r": moe["router_gate"],
                  "w_1": moe["experts_fc_in"]["kernel"], "w_3": moe["experts_fc_gate"]["kernel"],
                  "w_2": moe["experts_fc_out"]["kernel"],  # [L, held, ...]
                  "shared": {"w_1": moe["shared"]["fc_in"]["kernel"], "w_3": moe["shared"]["fc_gate"]["kernel"],
                             "w_2": moe["shared"]["fc_out"]["kernel"]},
                  "w_sg": moe["shared_gate"]["kernel"]}  # [L, d, 1]
    return out


def params_from_system(base: Dict) -> Dict:
    """The system's language-model tree (`params["base"]`: the GDN + routed layers
    stacked under `delta_blocks`, the attention + routed ones under `blocks`, each in
    layer order) renamed to this file's layout. No arithmetic. `hidden_states` walks
    the layers in order and takes each from its stack's next row."""
    out = {"embed": base["embed"]["wte"], "lnf": base["ln_f"]["scale"], "unembed": base["lm_head"]["kernel"]}
    for mine, theirs in (("delta", "delta_blocks"), ("softmax", "blocks")):
        if theirs in base:
            out[mine] = _layer_names(base[theirs])
    return out


# -- the forward ---------------------------------------------------------------


def _rms(x, w, eps):
    """The zero-centred norm: x * rsqrt(mean x^2 + eps) * (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _conv(u, w):
    """u [B, T, C], w [taps, C]: y_t = sum_i w[i] u_{t - (taps - 1) + i}, zeros before the start."""
    taps, T = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[i] * padded[:, i : i + T] for i in range(taps))


def recurrence(q, k, v, g, beta):
    """q, k [B, T, H, dk], v [B, T, H, dv], g, beta [B, T, H] -> o [B, T, H, dv]: the
    delta rule with a scalar decay a head, one position at a time, S_0 = 0."""
    B, T, H, dk = k.shape

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None, None]
        S = S + (beta[..., None] * k)[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))[1]
    return jnp.moveaxis(o, 0, 1)


def gated_delta_net(x, w, hf, mask):
    B, T, d = x.shape
    kh, vh, dk = hf["linear_num_key_heads"], hf["linear_num_value_heads"], hf["linear_key_head_dim"]
    channels = (2 * kh + vh) * dk
    live = mask.astype(jnp.float32)[..., None]
    qkvz = x @ w["w_qkvz"]
    z = qkvz[..., channels:].reshape(B, T, vh, dk)
    y = jax.nn.silu(_conv(qkvz[..., :channels] * live, w["taps"]))
    q = y[..., : kh * dk].reshape(B, T, kh, dk)
    k = y[..., kh * dk : 2 * kh * dk].reshape(B, T, kh, dk)
    v = y[..., 2 * kh * dk :].reshape(B, T, vh, dk)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(a, vh // kh, axis=2) for a in (q, k))
    ba = x @ w["w_ba"]
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(ba[..., vh:] + w["dt_bias"]) * live
    beta = jax.nn.sigmoid(ba[..., :vh]) * live
    o = recurrence(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + hf["rms_norm_eps"]) * w["o_norm"]
    return jnp.einsum("bthv,hvd->btd", o * jax.nn.silu(z), w["w_out"])


def _rotate(x, cos, sin):
    """The first 2 cos.shape[-1] channels of x [B, T, H, D] rotated, pairs (c, c + r/2)."""
    r = 2 * cos.shape[-1]
    x1, x2, rest = x[..., : r // 2], x[..., r // 2 : r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attention(x, w, hf, positions, visible):
    heads, kv, hd, eps = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"], hf["rms_norm_eps"]
    r = rotary_dim(hf)
    inv = hf["rope_theta"] ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions[..., None].astype(jnp.float32) * inv  # [B, T, r / 2]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    qg = jnp.einsum("btd,dhk->bthk", x, w["w_q"])
    q, gate = qg[..., :hd], qg[..., hd:]
    q = _rotate(_rms(q, w["q_norm"], eps), cos, sin)
    k = _rotate(_rms(jnp.einsum("btd,dhk->bthk", x, w["w_k"]), w["k_norm"], eps), cos, sin)
    v = jnp.einsum("btd,dhk->bthk", x, w["w_v"])
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    s = jnp.einsum("bthk,bshk->bhts", q, k) / math.sqrt(hd)
    s = jnp.where(visible, s, jnp.finfo(jnp.float32).min)
    o = jnp.einsum("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v) * jax.nn.sigmoid(gate)
    return jnp.einsum("bthk,hkd->btd", o, w["w_o"])


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["w_1"]) * (x @ w["w_3"])) @ w["w_2"]


def route(x, w, hf):
    """(weights [B, T, published] with zeros off the chosen, the router's logits)."""
    k = hf["num_experts_per_tok"]
    logits = x @ w["w_r"]
    p = jax.nn.softmax(logits, axis=-1)
    kth = jnp.sort(p, axis=-1)[..., -k][..., None]
    picked = jnp.where(p >= kth, p, 0.0)
    return picked / picked.sum(-1, keepdims=True), logits


def sure(values, hf, margin):
    """Per position: the k-th and (k+1)-th of `values` (the logits) lie more than
    `margin` apart, or no expert held here lies within `margin` of the boundary."""
    first, held, k = hf.get("first_expert_held", 0), hf["num_experts"], hf["num_experts_per_tok"]
    ordered = jnp.sort(values, axis=-1)
    kth, nxt = ordered[..., -k], ordered[..., -k - 1]
    boundary = 0.5 * (kth + nxt)[..., None]
    near_held = (jnp.abs(values - boundary) <= margin)[..., first : first + held].any(-1)
    return ((kth - nxt) > margin) | ~near_held


def experts(x, w, hf):
    """(The gated shared expert plus the HELD experts' part of a routed layer, the
    logits it chose by). One held expert at a time, every position through it,
    weighted by its routing weight (zero where it was not chosen)."""
    first, held = hf.get("first_expert_held", 0), hf["num_experts"]
    weights, logits = route(x, w, hf)
    y = jax.nn.sigmoid(x @ w["w_sg"]) * _swiglu(x, w["shared"])
    for e in range(held):
        y = y + weights[..., first + e, None] * _swiglu(x, {name: w[name][e] for name in ("w_1", "w_2", "w_3")})
    return y, logits


def hidden_states(p: Dict, hf: Dict, tokens, mask):
    """(final-norm hidden states [B, T, d], decisive [B, T]) for `tokens` [B, T]
    under the padding `mask` [B, T] (1 = real token)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    eps, taps = hf["rms_norm_eps"], hf["linear_conv_kernel_dim"]
    margin = hf.get("correct", {}).get("routing_margin", 0.0)
    neighbour = hf.get("correct", {}).get("neighbour_margin", 0.0)
    T = tokens.shape[1]
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)
    taken = {"delta": 0, "softmax": 0}
    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        decisive = jnp.ones(tokens.shape, bool)
        tied = jnp.zeros(tokens.shape, bool)  # a near tie so far at the position
        for kind in mixers(hf):
            w = jax.tree_util.tree_map(lambda a: a[taken[kind]], p[kind])
            taken[kind] += 1
            h = _rms(x, w["norm_1"], eps)
            if kind == "delta":
                for back in range(1, taps):
                    decisive = decisive & ~jnp.pad(tied, ((0, 0), (back, 0)))[:, :T]
                x = x + gated_delta_net(h, w["gdn"], hf, mask)
            else:
                x = x + attention(h, w["attn"], hf, positions, visible)
            y, logits = experts(_rms(x, w["norm_2"], eps), w["moe"], hf)
            x = x + y
            decisive = decisive & sure(logits, hf, margin)
            tied = tied | ~sure(logits, hf, neighbour)
        return _rms(x, p["lnf"], eps), decisive


def logits(p: Dict, hidden):
    """Untied output projection over the vocabulary slice, float32 [..., V]."""
    with jax.default_matmul_precision("highest"):
        return hidden.astype(jnp.float32) @ p["unembed"].astype(jnp.float32)
