"""Plain Xing4.0 reference: float32 `jax.numpy`, no kernels, no cache.

Written from the published `config.json` keys (model_type `xing4_0`) and the
equations they name, and independent of `trlx_tpu/models/transformer.py`:
nothing here imports the program. d = hidden_size, n = hc_mult streams.

Residual path (manifold-constrained hyper-connections). The state is X in
R^{n x d}: the embedding copied to the n streams; after the last layer the
streams are summed, then the final RMSNorm. Each sub-layer F (attention, then
the dense MLP or the experts; each with its own pre-RMSNorm over d and its own
mixing parameters Phi in R^{nd x (n^2 + 2n)}, scalars a_pre, a_post, a_res,
biases b):

    x~     = RMSNorm(vec(X))                                     (nd wide)
    H_pre  = sigmoid(a_pre * (x~ Phi_pre) + b_pre)               (1 x n)
    H_post = 2 sigmoid(a_post * (x~ Phi_post) + b_post)          (1 x n)
    H_res  = SK(exp(clamp(a_res * mat(x~ Phi_res) + b_res, -30, 30)))   (n x n)
    SK: hc_sinkhorn_iters times { rows / (row sums + hc_eps); columns / (column sums + hc_eps) }
    X'     = H_res X + H_post^T F(H_pre X)

Latent attention:

    c_q = RMSNorm(x W_dq);  [q_n | q_r] = c_q W_uq  per head (128 | 64);  q_r rotated
    [c_kv | k_r] = x W_dkv  (512 | 64);  c_kv = RMSNorm(c_kv);  k_r rotated, one for all heads
    [k_n | v] = c_kv W_ukv  per head (128 | 128)
    score = (q_n . k_n + q_r . k_r) * m^2 / sqrt(192), causal;  y = (softmax(score) v) W_o

Rotary: YaRN over the rotary channels: per-channel blend of 1/theta_i and
1/(factor theta_i) by the linear ramp between the two correction dimensions
(beta_fast, beta_slow over original_max_position_embeddings);
m = 0.1 * mscale_all_dim * ln(factor) + 1; cos and sin are scaled by
mscale-ratio, which is 1 when mscale = mscale_all_dim.

Feed-forward. Layers below first_k_dense_replace: W_d(silu(W_g x) * W_u x),
width intermediate_size. Above:

    s = sigmoid(x W_r) (float32);  chosen = top-k of (s + b)       (noaux_tc, one group)
    w = routed_scaling_factor * s[chosen] / sum(s[chosen])         (norm_topk_prob)
    y = Shared(x) + sum_{e in chosen, e HELD HERE} w_e Expert_e(x),  width moe_intermediate_size

THE SHARE: the configuration's file gives under `n_routed_experts` the experts
held on this chip (experts first_expert_held ...), under
`n_routed_experts_published` the router's width, and a vocabulary slice. The
reference routes over all published experts and adds the held experts' part
alone; what the absent experts would add is left out, here and in the program.

Departures, each listed in the configuration's `assumed` or `reduced`: the
multi-token-prediction module is not run; RMSNorm(vec(X)) has no learned
weight; rotary pairing is rotate-half (c, c + r/2) on the channels as stored.

`hidden_states` also returns, per position, whether its routing was DECISIVE:
in every routed layer, either the k-th and (k+1)-th of (s + b) lie more than
`correct.routing_margin` apart, or no expert held here lies within that
margin of the boundary between them (a swap among absent experts changes
nothing here: the chosen sum moves by the margin itself). An earlier
position's swapped choice reaches a later one only through attention, averaged
with its other keys; that is left to the tolerance of the decisive group.

Every matmul runs under `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def system_config(hf: Dict) -> Dict:
    """The published keys as the keyword arguments of the system's
    `TransformerConfig` (the only place that knows both names)."""
    rope = hf["rope_scaling"]
    return dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_key_value_heads"],
        intermediate_size=hf["intermediate_size"],
        pos_embed="rotary",
        rotary_style="neox",
        rope_theta=float(hf["rope_theta"]),
        norm="rmsnorm",
        layer_norm_epsilon=hf["rms_norm_eps"],
        activation=hf["hidden_act"],
        mlp_gated=True,
        use_attn_bias=hf["attention_bias"],
        use_mlp_bias=False,
        use_norm_bias=False,
        tie_word_embeddings=hf["tie_word_embeddings"],
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        yarn_factor=float(rope["factor"]),
        yarn_original_positions=rope["original_max_position_embeddings"],
        yarn_beta_fast=float(rope["beta_fast"]),
        yarn_beta_slow=float(rope["beta_slow"]),
        yarn_mscale=float(rope["mscale"]),
        yarn_mscale_all_dim=float(rope["mscale_all_dim"]),
        n_routed_experts=hf["n_routed_experts_published"],
        n_experts_held=hf["n_routed_experts"],
        first_expert_held=hf.get("first_expert_held", 0),
        n_experts_per_token=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_shared_experts=hf["n_shared_experts"],
        routed_scaling_factor=float(hf["routed_scaling_factor"]),
        first_k_dense=hf["first_k_dense_replace"],
        router_balance_steps=hf.get("router_bias_balance_steps", 0),
        residual_streams=hf["hc_mult"],
        sinkhorn_iters=hf["hc_sinkhorn_iters"],
        hc_eps=hf["hc_eps"],
        hc_clamp=float(hf["mhc_h_res_clamp_max"]),
    )


def toy_sizes(hf: Dict) -> Dict:
    """The overrides `--rehearse` runs this family at: every mechanism, no cost."""
    return {"hidden_size": 64, "num_attention_heads": 2, "num_key_value_heads": 2,
            "intermediate_size": 96, "moe_intermediate_size": 32, "q_lora_rank": 16,
            "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "num_hidden_layers": 3, "first_k_dense_replace": 1, "n_routed_experts": 4,
            "n_routed_experts_published": 16, "vocab_size": 512}


# -- the work, for benchmark/flops.py ----------------------------------------


def _elems(hf: Dict) -> Dict[str, int]:
    """Weight elements of one layer's parts, from the published keys."""
    d, heads, n = hf["hidden_size"], hf["num_attention_heads"], hf["hc_mult"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    q_rank, kv_rank = hf["q_lora_rank"], hf["kv_lora_rank"]
    return {
        # W_dq, W_uq, W_dkv, W_o: used as written, int8 in the rollout
        "attn_written": d * q_rank + q_rank * heads * (dn + dr) + d * (kv_rank + dr) + heads * dv * d,
        # W_ukv: used transposed in the decode form, stays in the compute dtype
        "attn_ukv": kv_rank * heads * (dn + dv),
        "dense_mlp": 3 * d * hf["intermediate_size"],
        "expert": 3 * d * hf["moe_intermediate_size"],
        "router": d * hf["n_routed_experts_published"],
        "mixing": 2 * n * d * (n * n + 2 * n),  # two sub-layers
    }


def work(hf: Dict) -> Dict:
    """The layers one by one, as the doc-string of `benchmark/flops.py` sets out.
    `linear_flops`: the five latent projections, the stream mixing, and the dense
    MLP or the shared expert and the router. `weight_elems` in units of the item
    size the recipe decodes with: under int8 rollout weights W_ukv (compute
    dtype) counts twice, the float32 router and mixing matrices four times."""
    e = _elems(hf)
    int8 = (hf.get("recipe", {}).get("model", {}).get("model_extra_configs", {})
            .get("transformer", {}).get("decode_weights_quant") == "int8")
    wide, f32 = (2, 4) if int8 else (1, 2)  # against a compute-dtype (2-byte) decode: 1 and 2
    attn = e["attn_written"] + e["attn_ukv"]
    shared = hf["n_shared_experts"] * e["expert"]
    common = {
        "pair_flops": 2.0 * hf["num_attention_heads"] * (
            hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"]),
        "cache_elems": hf["kv_lora_rank"] + hf["qk_rope_head_dim"],
    }
    read_attn = e["attn_written"] + wide * e["attn_ukv"] + f32 * e["mixing"]
    dense = dict(common, linear_flops=2.0 * (attn + e["mixing"] + e["dense_mlp"]),
                 weight_elems=read_attn + e["dense_mlp"])
    routed = dict(
        common, linear_flops=2.0 * (attn + e["mixing"] + shared + e["router"]),
        weight_elems=read_attn + shared + f32 * e["router"],
        routed={"expert_flops": 2.0 * e["expert"], "expert_elems": e["expert"],
                "published": hf["n_routed_experts_published"], "held": hf["n_routed_experts"],
                "per_token": hf["num_experts_per_tok"]},
    )
    lead = hf["first_k_dense_replace"]
    head = hf["hidden_size"] * hf["vocab_size"]
    return {"layers": [dense] * lead + [routed] * (hf["num_hidden_layers"] - lead),
            "leading": lead, "head": {"flops": 2.0 * head, "weight_elems": head}}


def params_held(hf: Dict) -> Dict[str, int]:
    """Parameters held here by kind of layer, norms and biases included."""
    e, d, n = _elems(hf), hf["hidden_size"], hf["hc_mult"]
    mix_small = 2 * (3 + 2 * n + n * n)
    base = (e["attn_written"] + e["attn_ukv"] + hf["q_lora_rank"] + hf["kv_lora_rank"]
            + 2 * d + e["mixing"] + mix_small)
    dense = base + e["dense_mlp"]
    routed = (base + e["router"] + hf["n_routed_experts_published"]
              + (hf["n_shared_experts"] + hf["n_routed_experts"]) * e["expert"])
    lead = hf["first_k_dense_replace"]
    embed = 2 * d * hf["vocab_size"]
    return {"dense_layer": dense, "routed_layer": routed, "embed_and_head": embed,
            "total": lead * dense + (hf["num_hidden_layers"] - lead) * routed + embed + d}


# -- the program's tree under this file's names -------------------------------


def _layer_names(blk: Dict) -> Dict:
    attn = blk["attn"]
    out = {
        "ln1": blk["ln_1"]["scale"], "ln2": blk["ln_2"]["scale"],
        "w_dq": attn["q_a"]["kernel"], "q_norm": attn["q_a_norm"],
        "w_uq": attn["q_b"]["kernel"],  # [L, q_rank, H, dn + dr]
        "w_dkv": attn["kv_a"]["kernel"], "kv_norm": attn["kv_a_norm"],
        "w_ukv": attn["kv_b"]["kernel"],  # [L, kv_rank, H, dn + dv]
        "w_o": attn["o"]["kernel"],  # [L, H, dv, d]
    }
    for name, mix in (("mix_attn", blk["hc_attn"]), ("mix_ffn", blk["hc_mlp"])):
        out[name] = {"phi": mix["phi"], "a": mix["alpha"], "b_pre": mix["b_pre"],
                     "b_post": mix["b_post"], "b_res": mix["b_res"]}
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
    layers stacked under `dense_blocks`, the routed ones under `blocks`)
    renamed to this file's layout. No arithmetic."""
    out = {"embed": base["embed"]["wte"], "routed": _layer_names(base["blocks"]),
           "lnf": base["ln_f"]["scale"], "unembed": base["lm_head"]["kernel"]}
    if "dense_blocks" in base:
        out["dense"] = _layer_names(base["dense_blocks"])
    return out


# -- the forward ---------------------------------------------------------------


def _rms(x, g, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if g is None else y * g


def yarn_inv_freq(hf: Dict):
    """[qk_rope_head_dim / 2] rotary frequencies under the config's rope_scaling."""
    dim, base, rope = hf["qk_rope_head_dim"], hf["rope_theta"], hf["rope_scaling"]
    freq = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    factor = rope["factor"]

    def correction_dim(rotations):
        return dim * math.log(rope["original_max_position_embeddings"] / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(hf: Dict) -> float:
    m = _mscale(hf["rope_scaling"]["factor"], hf["rope_scaling"]["mscale_all_dim"])
    return m * m / math.sqrt(hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"])


def _rotate(x, cos, sin):
    """x [..., r]; pairs (c, c + r/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def _mixing(X, w, hf):
    """X [B, T, n, d] -> H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n]."""
    B, T, n, d = X.shape
    z = _rms(X.reshape(B, T, n * d), None, hf["rms_norm_eps"]) @ w["phi"]
    h_pre = jax.nn.sigmoid(w["a"][0] * z[..., :n] + w["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(w["a"][1] * z[..., n : 2 * n] + w["b_post"])
    logits = w["a"][2] * z[..., 2 * n :].reshape(B, T, n, n) + w["b_res"]
    logits = jnp.clip(logits, hf["mhc_h_res_clamp_min"], hf["mhc_h_res_clamp_max"])
    return h_pre, h_post, sinkhorn(jnp.exp(logits), hf["hc_sinkhorn_iters"], hf["hc_eps"])


def _sub_layer(X, w_mix, hf, fn):
    h_pre, h_post, h_res = _mixing(X, w_mix, hf)
    y = fn(jnp.einsum("btn,btnd->btd", h_pre, X))
    return jnp.einsum("btij,btjd->btid", h_res, X) + h_post[..., None] * y[:, :, None, :]


def _attention(x, w, hf, cos, sin, visible):
    dn, rank = hf["qk_nope_head_dim"], hf["kv_lora_rank"]
    eps = hf["rms_norm_eps"]
    q = jnp.einsum("btr,rhd->bthd", _rms(x @ w["w_dq"], w["q_norm"], eps), w["w_uq"])
    kv = x @ w["w_dkv"]
    c_kv = _rms(kv[..., :rank], w["kv_norm"], eps)
    k_r = _rotate(kv[..., rank:], cos, sin)  # [B, T, dr], one for all heads
    q_n, q_r = q[..., :dn], _rotate(q[..., dn:], cos[:, :, None], sin[:, :, None])
    up = jnp.einsum("btc,chd->bthd", c_kv, w["w_ukv"])
    k_n, v = up[..., :dn], up[..., dn:]
    s = (jnp.einsum("bthd,bshd->bhts", q_n, k_n) + jnp.einsum("bthr,bsr->bhts", q_r, k_r))
    s = jnp.where(visible, s * softmax_scale(hf), jnp.finfo(jnp.float32).min)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bthd,hde->bte", o, w["w_o"])


def _gated(x, w):
    return (jax.nn.silu(x @ w["w_g"]) * (x @ w["w_u"])) @ w["w_d"]


def route(x, w, hf):
    """(weights [B, T, published] with zeros off the chosen, values s + b)."""
    k = hf["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["w_r"])
    values = s + w["b"]
    kth = jnp.sort(values, axis=-1)[..., -k][..., None]
    chosen = values >= kth
    picked = jnp.where(chosen, s, 0.0)
    weights = hf["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True)
    return weights, values


def _experts(x, w, hf, margin):
    """The shared expert plus the HELD experts' part, and per position whether
    the routing was decisive (module doc-string). One held expert at a time,
    every position through it, weighted by its routing weight (zero where it
    was not chosen): nothing of [positions, experts, width] is ever built."""
    first, held, k = hf.get("first_expert_held", 0), hf["n_routed_experts"], hf["num_experts_per_tok"]
    weights, values = route(x, w, hf)
    y = _gated(x, w["shared"]) if hf["n_shared_experts"] else jnp.zeros_like(x)
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
    n, eps = hf["hc_mult"], hf["rms_norm_eps"]
    margin = hf.get("correct", {}).get("routing_margin", 0.0)
    T = tokens.shape[1]
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(hf)  # [B, T, dr / 2]
    ratio = (_mscale(hf["rope_scaling"]["factor"], hf["rope_scaling"]["mscale"])
             / _mscale(hf["rope_scaling"]["factor"], hf["rope_scaling"]["mscale_all_dim"]))
    cos, sin = jnp.cos(ang) * ratio, jnp.sin(ang) * ratio
    visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)

    def layer(carry, w):
        X, decisive = carry
        X = _sub_layer(X, w["mix_attn"], hf,
                       lambda u: _attention(_rms(u, w["ln1"], eps), w, hf, cos, sin, visible))
        if "ffn" in w:
            X = _sub_layer(X, w["mix_ffn"], hf, lambda u: _gated(_rms(u, w["ln2"], eps), w["ffn"]))
        else:
            sure = []

            def experts(u):
                y, ok = _experts(_rms(u, w["ln2"], eps), w["moe"], hf, margin)
                sure.append(ok)
                return y

            X = _sub_layer(X, w["mix_ffn"], hf, experts)
            decisive = decisive & sure[0]
        return (X, decisive), None

    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        carry = (jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n, x.shape[-1])),
                 jnp.ones(tokens.shape, bool))
        if "dense" in p:
            carry, _ = jax.lax.scan(layer, carry, p["dense"])
        (X, decisive), _ = jax.lax.scan(layer, carry, p["routed"])
        return _rms(X.sum(axis=2), p["lnf"], eps), decisive


def logits(p: Dict, hidden):
    """Untied output projection over the vocabulary slice, float32 [..., V]."""
    with jax.default_matmul_precision("highest"):
        return hidden.astype(jnp.float32) @ p["unembed"].astype(jnp.float32)
