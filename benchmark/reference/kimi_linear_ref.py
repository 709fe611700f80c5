"""Plain Kimi-Linear reference: float32 `jax.numpy`, no kernels, no cache, no chunks.

Written from the published `config.json` keys (model_type `kimi_linear`) and the
equations they name, and independent of `trlx_tpu/models/transformer.py`: nothing
here imports the program. d = hidden_size.

Block, every layer: x = x + Mixer(RMSNorm(x)); x = x + FF(RMSNorm(x)); after the
last layer the final RMSNorm, then an untied head. Layer l (1-based) runs the KDA
mixer if l is in `linear_attn_config.kda_layers`, the MLA mixer if it is in
`linear_attn_config.full_attn_layers`.

KDA mixer (gated delta rule with a per-channel decay), H heads of d_k = d_v =
`linear_attn_config.head_dim`, per position t and head h, as a plain RECURRENCE
(a `lax.scan` over positions):

    q~ = SiLU(Conv(x W_q)),  k~ = SiLU(Conv(x W_k)),  v = SiLU(Conv(x W_v))
         Conv: causal depthwise, y_t[c] = sum_{i=0..taps-1} w[i, c] u_{t-(taps-1)+i}[c],
         no bias, zeros before the start
    q = q~ / sqrt(|q~|^2 + 1e-6) * d_k^-0.5,   k = k~ / sqrt(|k~|^2 + 1e-6)         per head
    g = -exp(A_log[h]) * softplus((x W_fa) W_fb + dt_bias)      log-decay per channel
    beta = sigmoid(x W_b)                                        one number per head
    S_t = Diag(exp(g_t)) S_{t-1};   S_t = S_t + beta_t k_t (v_t - S_t^T k_t)^T     S_0 = 0
    o_t = S_t^T q_t
    y = (RMSNorm_head(o_t) * sigmoid((x W_ga) W_gb)) W_o        norm over d_v, learned weight

A position whose mask is 0 changes nothing: its convolution input is zero, its
beta 0 and its g 0.

MLA mixer, `q_lora_rank` null and no rotation (`mla_use_nope`):

    [q_n | q_r] = x W_q  per head (128 | 64)
    [c_kv | k_r] = x W_dkv  (512 | 64);  c_kv = RMSNorm(c_kv);  k_r one for all heads, unrotated
    [k_n | v] = c_kv W_ukv  per head (128 | 128)
    score = (q_n . k_n + q_r . k_r) / sqrt(192), causal;  y = (softmax(score) v) W_o

Feed-forward. Layers below first_k_dense_replace: W_d(silu(W_g x) * W_u x), width
intermediate_size. Above:

    s = sigmoid(x W_r) (float32);  chosen = top-k of (s + b)          (one group)
    w = routed_scaling_factor * s[chosen] / (sum(s[chosen]) + 1e-20)  (moe_renormalize)
    y = Shared(x) + sum_{e in chosen, e HELD HERE} w_e Expert_e(x),   width moe_intermediate_size

THE SHARE: the configuration's file gives under `num_experts` the experts held on
this chip (experts first_expert_held ...), under `num_experts_published` the
router's width, and a vocabulary slice. The reference routes over all published
experts and adds the held experts' part alone; what the absent experts would add
is left out, here and in the program.

Departures, each listed in the configuration's `assumed` or `reduced`: initial
values the config does not give are the program's; `num_expert_group` /
`topk_group` of 1 are read as no grouping.

`hidden_states` also returns, per position, whether its routing was DECISIVE:
in every routed layer, either the k-th and (k+1)-th of (s + b) lie more than
`correct.routing_margin` apart, or no expert held here lies within that margin of
the boundary between them. An earlier position's swapped choice reaches a later
one through attention and through the recurrent state, averaged with its other
keys; that is left to the tolerance of the decisive group.

Every matmul runs under `jax.default_matmul_precision("highest")`. On the chip it
fits as it is: the recurrence holds one [rows, heads, d_k, d_v] state, the experts
run one held expert at a time over every position.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def _lin(hf: Dict) -> Dict:
    return hf["linear_attn_config"]


def mixers(hf: Dict):
    """The mixer of each of the layers run, from the two 1-based lists."""
    kda = set(_lin(hf)["kda_layers"])
    full = set(_lin(hf)["full_attn_layers"])
    out = []
    for layer in range(1, hf["num_hidden_layers"] + 1):
        if (layer in kda) == (layer in full):
            raise ValueError(f"layer {layer} is in both or in neither of kda_layers and full_attn_layers")
        out.append("delta" if layer in kda else "latent")
    return tuple(out)


def system_config(hf: Dict) -> Dict:
    """The published keys as the keyword arguments of the system's
    `TransformerConfig` (the only place that knows both names)."""
    if hf.get("num_expert_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise NotImplementedError("grouped top-k with more than one group")
    return dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"],
        intermediate_size=hf["intermediate_size"],
        pos_embed="none" if hf["mla_use_nope"] else "rotary",
        rope_theta=float(hf["rope_theta"]),
        norm="rmsnorm",
        layer_norm_epsilon=hf["rms_norm_eps"],
        activation=hf["hidden_act"],
        mlp_gated=True,
        use_attn_bias=False,
        use_mlp_bias=False,
        use_norm_bias=False,
        tie_word_embeddings=hf["tie_word_embeddings"],
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        mixer_layers=mixers(hf),
        delta_heads=_lin(hf)["num_heads"],
        delta_head_dim=_lin(hf)["head_dim"],
        delta_conv=_lin(hf)["short_conv_kernel_size"],
        n_routed_experts=hf["num_experts_published"],
        n_experts_held=hf["num_experts"],
        first_expert_held=hf.get("first_expert_held", 0),
        n_experts_per_token=hf["num_experts_per_token"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_shared_experts=hf["num_shared_experts"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        first_k_dense=hf["first_k_dense_replace"],
        router_balance_steps=hf.get("router_bias_balance_steps", 0),
    )


def toy_sizes(hf: Dict) -> Dict:
    """The overrides `--rehearse` runs this family at: every mechanism, no cost.
    One leading dense KDA layer, then KDA, KDA, MLA, KDA: a top-2 branch is one
    layer of each mixer."""
    lin = dict(_lin(hf), head_dim=16, num_heads=2, kda_layers=[1, 2, 3, 5], full_attn_layers=[4])
    return {"hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
            "intermediate_size": 96, "moe_intermediate_size": 32,
            "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "num_hidden_layers": 5, "first_k_dense_replace": 1, "num_experts": 4,
            "num_experts_published": 16, "num_experts_per_token": 4, "vocab_size": 512,
            "linear_attn_config": lin}


# -- the work, for benchmark/flops.py ----------------------------------------


def _elems(hf: Dict) -> Dict[str, int]:
    """Weight elements of one layer's parts, from the published keys."""
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    dn, dr, dv, rank = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
    kh, kd = _lin(hf)["num_heads"], _lin(hf)["head_dim"]
    w = kh * kd
    return {
        # KDA: W_q, W_k, W_v, W_o: int8 in the rollout
        "kda_written": 4 * d * w,
        # the two low-rank pairs and beta's projection: compute dtype in the rollout
        "kda_small": 2 * (d * kd + kd * w) + d * kh,
        # taps, A_log, dt_bias, the output norm: float32, elementwise
        "kda_vectors": 3 * w * _lin(hf)["short_conv_kernel_size"] + kh + w + kd,
        # MLA: W_q, W_dkv, W_o used as written (int8 in the rollout); W_ukv compute dtype
        "mla_written": d * heads * (dn + dr) + d * (rank + dr) + heads * dv * d,
        "mla_ukv": rank * heads * (dn + dv),
        "dense_mlp": 3 * d * hf["intermediate_size"],
        "expert": 3 * d * hf["moe_intermediate_size"],
        "router": d * hf["num_experts_published"],
    }


def work(hf: Dict) -> Dict:
    """The layers one by one, as the doc-string of `benchmark/flops.py` sets out.
    `linear_flops` of a KDA layer: its eight projections and, a head, the three
    products of the recurrence (decayed state times k, the rank-one update, state
    times q: 6 d_k d_v a token), the least any form needs; `pair_flops` 0 and
    `cache_elems` 0 there (its state does not grow). `weight_elems` in units of
    the item size the recipe decodes with: under int8 rollout weights what stays
    in the compute dtype counts twice, the float32 router four times."""
    e = _elems(hf)
    int8 = (hf.get("recipe", {}).get("model", {}).get("model_extra_configs", {})
            .get("transformer", {}).get("decode_weights_quant") == "int8")
    wide, f32 = (2, 4) if int8 else (1, 2)
    kh, kd = _lin(hf)["num_heads"], _lin(hf)["head_dim"]
    mixer = {
        "delta": dict(
            linear=2.0 * (e["kda_written"] + e["kda_small"]) + 6.0 * kh * kd * kd,
            read=e["kda_written"] + wide * e["kda_small"] + f32 * e["kda_vectors"],
            pair_flops=0.0, cache_elems=0),
        "latent": dict(
            linear=2.0 * (e["mla_written"] + e["mla_ukv"]),
            read=e["mla_written"] + wide * e["mla_ukv"],
            pair_flops=2.0 * hf["num_attention_heads"] * (
                hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"]),
            cache_elems=hf["kv_lora_rank"] + hf["qk_rope_head_dim"]),
    }
    shared = hf["num_shared_experts"] * e["expert"]
    lead = hf["first_k_dense_replace"]
    layers = []
    for index, kind in enumerate(mixers(hf)):
        m = mixer[kind]
        common = {"pair_flops": m["pair_flops"], "cache_elems": m["cache_elems"]}
        if index < lead:
            layers.append(dict(common, linear_flops=m["linear"] + 2.0 * e["dense_mlp"],
                               weight_elems=m["read"] + e["dense_mlp"]))
        else:
            layers.append(dict(
                common, linear_flops=m["linear"] + 2.0 * (shared + e["router"]),
                weight_elems=m["read"] + shared + f32 * e["router"],
                routed={"expert_flops": 2.0 * e["expert"], "expert_elems": e["expert"],
                        "published": hf["num_experts_published"], "held": hf["num_experts"],
                        "per_token": hf["num_experts_per_token"]}))
    head = hf["hidden_size"] * hf["vocab_size"]
    return {"layers": layers, "leading": lead, "head": {"flops": 2.0 * head, "weight_elems": head}}


def params_held(hf: Dict) -> Dict[str, int]:
    """Parameters held here by kind of layer, norms included."""
    e, d = _elems(hf), hf["hidden_size"]
    kda = e["kda_written"] + e["kda_small"] + e["kda_vectors"] + 2 * d
    mla = e["mla_written"] + e["mla_ukv"] + hf["kv_lora_rank"] + 2 * d
    experts = (e["router"] + hf["num_experts_published"]
               + (hf["num_shared_experts"] + hf["num_experts"]) * e["expert"])
    lead = hf["first_k_dense_replace"]
    kinds = mixers(hf)
    embed = 2 * d * hf["vocab_size"]
    total = embed + d
    for index, kind in enumerate(kinds):
        total += (kda if kind == "delta" else mla) + (e["dense_mlp"] if index < lead else experts)
    return {"kda_mixer": kda - 2 * d, "mla_mixer": mla - 2 * d,
            "leading_layer": (kda if kinds[0] == "delta" else mla) + e["dense_mlp"],
            "routed_kda_layer": kda + experts, "routed_mla_layer": mla + experts,
            "embed_and_head": embed, "total": total}


# -- the program's tree under this file's names -------------------------------


def _mixer_names(attn: Dict, kind: str) -> Dict:
    if kind == "delta":
        return {"w_q": attn["q"]["kernel"], "w_k": attn["k"]["kernel"], "w_v": attn["v"]["kernel"],  # [L, d, H, dk]
                "conv_q": attn["conv_q"], "conv_k": attn["conv_k"], "conv_v": attn["conv_v"],  # [L, taps, H dk]
                "w_fa": attn["f_a"]["kernel"], "w_fb": attn["f_b"]["kernel"],
                "w_ga": attn["g_a"]["kernel"], "w_gb": attn["g_b"]["kernel"],
                "w_b": attn["b"]["kernel"], "a_log": attn["A_log"], "dt_bias": attn["dt_bias"],
                "o_norm": attn["o_norm"], "w_o": attn["o"]["kernel"]}  # w_o [L, H, dv, d]
    return {"w_q": attn["q_b"]["kernel"],  # [L, d, H, dn + dr]: q straight from x
            "w_dkv": attn["kv_a"]["kernel"], "kv_norm": attn["kv_a_norm"],
            "w_ukv": attn["kv_b"]["kernel"],  # [L, kv_rank, H, dn + dv]
            "w_o": attn["o"]["kernel"]}


def _layer_names(blk: Dict, kind: str) -> Dict:
    out = dict(_mixer_names(blk["attn"], kind), ln1=blk["ln_1"]["scale"], ln2=blk["ln_2"]["scale"])
    if "mlp" in blk:
        mlp = blk["mlp"]
        out["ffn"] = {"w_g": mlp["fc_in"]["kernel"], "w_u": mlp["fc_gate"]["kernel"],
                      "w_d": mlp["fc_out"]["kernel"]}
    else:
        moe = blk["moe"]
        out["moe"] = {
            "w_r": moe["router_gate"], "b": moe["router_bias"],
            "w_g": moe["experts_fc_in"]["kernel"], "w_u": moe["experts_fc_gate"]["kernel"],
            "w_d": moe["experts_fc_out"]["kernel"],  # [L, held, ...]
            "shared": {"w_g": moe["shared"]["fc_in"]["kernel"], "w_u": moe["shared"]["fc_gate"]["kernel"],
                       "w_d": moe["shared"]["fc_out"]["kernel"]},
        }
    return out


def params_from_system(base: Dict) -> Dict:
    """The system's language-model tree (`params["base"]`: the leading dense
    layers stacked under `dense_blocks`, above them the KDA layers under
    `delta_blocks` and the MLA layers under `blocks`, each in layer order)
    renamed to this file's layout. No arithmetic. `hidden_states` walks the
    layers in order and takes each from its stack's next row."""
    lead_kind = "delta" if "conv_q" in base["dense_blocks"]["attn"] else "latent"
    out = {"embed": base["embed"]["wte"], "lnf": base["ln_f"]["scale"], "unembed": base["lm_head"]["kernel"],
           "lead": _layer_names(base["dense_blocks"], lead_kind)}
    if "delta_blocks" in base:
        out["delta"] = _layer_names(base["delta_blocks"], "delta")
    if "blocks" in base:
        out["latent"] = _layer_names(base["blocks"], "latent")
    return out


# -- the forward ---------------------------------------------------------------

def _rms(x, g, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if g is None else y * g


def _conv(u, w):
    """u [B, T, C], w [taps, C]: y_t = sum_i w[i] u_{t - (taps - 1) + i}, zeros before the start."""
    taps, T = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[i] * padded[:, i : i + T] for i in range(taps))


def _recurrence(q, k, v, g, beta):
    """q, k, g [B, T, H, dk], v [B, T, H, dv], beta [B, T, H] -> o [B, T, H, dv]: the
    delta rule one position at a time, S_0 = 0."""
    B, T, H, dk = k.shape

    def step(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None]
        S = S + (beta[..., None] * k)[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    o = jax.lax.scan(step, S0, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))[1]
    return jnp.moveaxis(o, 0, 1)


def _kda(x, w, hf, mask):
    B, T, d = x.shape
    H, dk = _lin(hf)["num_heads"], _lin(hf)["head_dim"]
    live = mask.astype(jnp.float32)[..., None]

    def branch(name):
        u = jnp.einsum("btd,dhk->bthk", x, w["w_" + name]).reshape(B, T, H * dk) * live
        return jax.nn.silu(_conv(u, w["conv_" + name])).reshape(B, T, H, dk)

    q, k, v = branch("q"), branch("k"), branch("v")
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    f = jnp.einsum("btr,rhk->bthk", x @ w["w_fa"], w["w_fb"])
    g = -jnp.exp(w["a_log"])[:, None] * jax.nn.softplus(f + w["dt_bias"]) * live[..., None]
    beta = jax.nn.sigmoid(x @ w["w_b"]) * live
    o = _recurrence(q, k, v, g, beta)
    gate = jax.nn.sigmoid(jnp.einsum("btr,rhk->bthk", x @ w["w_ga"], w["w_gb"]))
    return jnp.einsum("bthv,hvd->btd", _rms(o, w["o_norm"], hf["rms_norm_eps"]) * gate, w["w_o"])


def _mla(x, w, hf, visible):
    dn, rank = hf["qk_nope_head_dim"], hf["kv_lora_rank"]
    q = jnp.einsum("btd,dhk->bthk", x, w["w_q"])
    kv = x @ w["w_dkv"]
    c_kv = _rms(kv[..., :rank], w["kv_norm"], hf["rms_norm_eps"])
    k_r = kv[..., rank:]  # [B, T, dr], one for all heads, unrotated
    up = jnp.einsum("btc,chd->bthd", c_kv, w["w_ukv"])
    k_n, v = up[..., :dn], up[..., dn:]
    s = jnp.einsum("bthd,bshd->bhts", q[..., :dn], k_n) + jnp.einsum("bthr,bsr->bhts", q[..., dn:], k_r)
    s = s / math.sqrt(dn + hf["qk_rope_head_dim"])
    s = jnp.where(visible, s, jnp.finfo(jnp.float32).min)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bthd,hde->bte", o, w["w_o"])


def _gated(x, w):
    return (jax.nn.silu(x @ w["w_g"]) * (x @ w["w_u"])) @ w["w_d"]


def route(x, w, hf):
    """(weights [B, T, published] with zeros off the chosen, values s + b)."""
    k = hf["num_experts_per_token"]
    s = jax.nn.sigmoid(x @ w["w_r"])
    values = s + w["b"]
    kth = jnp.sort(values, axis=-1)[..., -k][..., None]
    picked = jnp.where(values >= kth, s, 0.0)
    weights = hf["routed_scaling_factor"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return weights, values


def _experts(x, w, hf, margin):
    """The shared expert plus the HELD experts' part, and per position whether
    the routing was decisive (module doc-string). One held expert at a time,
    every position through it, weighted by its routing weight (zero where it
    was not chosen): nothing of [positions, experts, width] is ever built."""
    first, held, k = hf.get("first_expert_held", 0), hf["num_experts"], hf["num_experts_per_token"]
    weights, values = route(x, w, hf)
    y = _gated(x, w["shared"]) if hf["num_shared_experts"] else jnp.zeros_like(x)
    for e in range(held):
        expert = {name: w[name][e] for name in ("w_g", "w_u", "w_d")}
        y = y + weights[..., first + e, None] * _gated(x, expert)
    ordered = jnp.sort(values, axis=-1)
    kth, nxt = ordered[..., -k], ordered[..., -k - 1]
    boundary = 0.5 * (kth + nxt)[..., None]
    near_held = (jnp.abs(values - boundary) <= margin)[..., first : first + held].any(-1)
    return y, ((kth - nxt) > margin) | ~near_held


def hidden_states(p: Dict, hf: Dict, tokens, mask):
    """(final-norm hidden states [B, T, d], decisive [B, T]) for `tokens` [B, T]
    under the padding `mask` [B, T] (1 = real token)."""
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    eps = hf["rms_norm_eps"]
    margin = hf.get("correct", {}).get("routing_margin", 0.0)
    T = tokens.shape[1]
    visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)
    lead = hf["first_k_dense_replace"]
    taken = {"lead": 0, "delta": 0, "latent": 0}
    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        decisive = jnp.ones(tokens.shape, bool)
        for index, kind in enumerate(mixers(hf)):
            stack = "lead" if index < lead else kind
            w = jax.tree_util.tree_map(lambda a: a[taken[stack]], p[stack])
            taken[stack] += 1
            h = _rms(x, w["ln1"], eps)
            x = x + (_kda(h, w, hf, mask) if kind == "delta" else _mla(h, w, hf, visible))
            h = _rms(x, w["ln2"], eps)
            if "ffn" in w:
                x = x + _gated(h, w["ffn"])
            else:
                y, sure = _experts(h, w["moe"], hf, margin)
                x, decisive = x + y, decisive & sure
        return _rms(x, p["lnf"], eps), decisive


def logits(p: Dict, hidden):
    """Untied output projection over the vocabulary slice, float32 [..., V]."""
    with jax.default_matmul_precision("highest"):
        return hidden.astype(jnp.float32) @ p["unembed"].astype(jnp.float32)
