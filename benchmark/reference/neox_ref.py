"""Plain GPT-NeoX reference: float32 `jax.numpy`, no kernels, no cache.

Written from the GPT-NeoX equations (Black et al. 2022, arXiv:2204.06745,
section 2, and the `GPTNeoXLayer` of the published implementation) and
independent of `trlx_tpu/models/transformer.py`: nothing here imports the
program. Per layer, on the residual stream x [T, E]:

    a = LayerNorm_1(x)                    m = LayerNorm_2(x)
    q, k, v = a W_q + b_q, a W_k + b_k, a W_v + b_v        (per head, D wide)
    q, k <- rotary on the first int(D * rotary_pct) channels, rotate-half
            pairing (c, c + r/2), angle = position * base^(-2c/r)
    s = q k^T / sqrt(D), causal and padding masked;  o = softmax(s) v
    x <- x + (concat_heads(o) W_o + b_o) + (gelu(m W_in + b_in) W_out + b_out)

(`use_parallel_residual: false` would chain the two instead.) Then
LayerNorm_f and the untied output projection. gelu is the exact erf form
(`hidden_act: gelu`).

Departure from the published model, forced by the system under test: the
system's decoder has ONE LayerNorm per block under the parallel residual
(its `ln_1` feeds attention and MLP alike, the GPT-J layout), while
GPT-NeoX has two with separate weights. `params_from_system` therefore
gives LayerNorm_2 the weights of LayerNorm_1: under that tie, and only
under it, the system computes a GPT-NeoX function. PERF.md lists the
missing second norm under Open questions.

Every matmul runs under `jax.default_matmul_precision("highest")`: on a TPU
a float32 matmul is otherwise done in bf16 passes.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

def system_config(hf: Dict) -> Dict:
    """The published `config.json` keys as the keyword arguments of the
    system's `TransformerConfig` (the only place that knows both names)."""
    head = hf["hidden_size"] // hf["num_attention_heads"]
    return dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        n_layer=hf["num_hidden_layers"],
        n_head=hf["num_attention_heads"],
        intermediate_size=hf["intermediate_size"],
        pos_embed="rotary",
        rotary_style="neox",
        rotary_dim=int(head * hf["rotary_pct"]),
        rope_theta=float(hf["rotary_emb_base"]),
        activation=hf["hidden_act"],
        layer_norm_epsilon=hf["layer_norm_eps"],
        parallel_residual=hf["use_parallel_residual"],
        use_attn_bias=True,
        use_mlp_bias=True,
        tie_word_embeddings=hf["tie_word_embeddings"],
    )


def dims(hf: Dict) -> Dict:
    """The sizes `benchmark/flops.py` counts with."""
    return dict(
        n_layer=hf["num_hidden_layers"],
        hidden=hf["hidden_size"],
        n_head=hf["num_attention_heads"],
        n_kv_head=hf["num_attention_heads"],
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        intermediate=hf["intermediate_size"],
        mlp_matrices=2,
        vocab=hf["vocab_size"],
        tied=hf["tie_word_embeddings"],
    )


def params_from_system(base: Dict) -> Dict:
    """The system's language-model tree (`params["base"]`, layers stacked
    on a leading axis) renamed to this file's layout. No arithmetic."""
    blk = base["blocks"]
    attn, mlp = blk["attn"], blk["mlp"]
    ln2 = blk.get("ln_2", blk["ln_1"])  # see the module docstring
    return {
        "embed": base["embed"]["wte"],  # [V, E]
        "ln1_g": blk["ln_1"]["scale"], "ln1_b": blk["ln_1"]["bias"],  # [L, E]
        "ln2_g": ln2["scale"], "ln2_b": ln2["bias"],
        "w_q": attn["q"]["kernel"], "b_q": attn["q"]["bias"],  # [L,E,H,D] [L,H,D]
        "w_k": attn["k"]["kernel"], "b_k": attn["k"]["bias"],
        "w_v": attn["v"]["kernel"], "b_v": attn["v"]["bias"],
        "w_o": attn["o"]["kernel"], "b_o": attn["o"]["bias"],  # [L,H,D,E] [L,E]
        "w_in": mlp["fc_in"]["kernel"], "b_in": mlp["fc_in"]["bias"],  # [L,E,I]
        "w_out": mlp["fc_out"]["kernel"], "b_out": mlp["fc_out"]["bias"],
        "lnf_g": base["ln_f"]["scale"], "lnf_b": base["ln_f"]["bias"],
        "unembed": base["lm_head"]["kernel"],  # [E, V]
    }


def _layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _rotary(x, positions, rot, base):
    """x [B, T, H, D]; rotate the first `rot` channels."""
    half = rot // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = positions[..., None].astype(jnp.float32) * freq  # [B, T, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


def hidden_states(p: Dict, hf: Dict, tokens, mask):
    """Final-norm hidden states [B, T, E] for `tokens` [B, T] under the
    padding `mask` [B, T] (1 = real token)."""
    f32 = lambda t: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), t)
    p = f32(p)
    heads = hf["num_attention_heads"]
    d = hf["hidden_size"] // heads
    rot, base, eps = int(d * hf["rotary_pct"]), hf["rotary_emb_base"], hf["layer_norm_eps"]
    T = tokens.shape[1]
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    causal = jnp.tril(jnp.ones((T, T), bool))
    visible = causal[None, None] & (mask[:, None, None, :] > 0)  # [B,1,T,T]

    def layer(x, w):
        a = _layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
        m = _layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
        q = jnp.einsum("bte,ehd->bthd", a, w["w_q"]) + w["b_q"]
        k = jnp.einsum("bte,ehd->bthd", a, w["w_k"]) + w["b_k"]
        v = jnp.einsum("bte,ehd->bthd", a, w["w_v"]) + w["b_v"]
        q, k = _rotary(q, positions, rot, base), _rotary(k, positions, rot, base)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
        s = jnp.where(visible, s, jnp.finfo(jnp.float32).min)  # finite: a padded query row stays NaN-free
        o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
        attn = jnp.einsum("bthd,hde->bte", o, w["w_o"]) + w["b_o"]
        if not hf["use_parallel_residual"]:
            x = x + attn
            m = _layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
            attn = 0.0
        h = jax.nn.gelu(m @ w["w_in"] + w["b_in"], approximate=False)
        return x + attn + (h @ w["w_out"] + w["b_out"]), None

    stacked = {k: v for k, v in p.items() if v.ndim >= 2 and k not in ("embed", "unembed")}
    with jax.default_matmul_precision("highest"):
        x = p["embed"][tokens]
        x, _ = jax.lax.scan(layer, x, stacked)
        return _layer_norm(x, p["lnf_g"], p["lnf_b"], eps)


def logits(p: Dict, hidden):
    """Untied output projection, float32 [..., V]."""
    with jax.default_matmul_precision("highest"):
        return hidden.astype(jnp.float32) @ p["unembed"].astype(jnp.float32)
