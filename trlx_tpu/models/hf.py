"""HuggingFace checkpoint interop: config mapping + weight conversion.

Parity: /root/reference/trlx/models/modeling_base.py:124-326
(from_pretrained with sharded-index merging) — here torch state dicts are
converted into the stacked-layer functional param tree of
trlx_tpu.models.transformer, and back (HF export for deploy parity,
reference accelerate_ppo_trainer.py:526-553).

Supported model families: gpt2, gptj, gpt_neo, gpt_neox, gpt_bigcode,
llama, opt, bloom — the reference's full decoder dispatch table
(modeling_ppo.py:1598-1637). Each family is a declarative layout
description, not a separate model class.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


# ---------------------------------------------------------------------------
# config mapping
# ---------------------------------------------------------------------------


def _activation_name(hf_name: str) -> str:
    """HF activation_function -> TransformerConfig.activation."""
    table = {
        "gelu_new": "gelu_new",
        "gelu_pytorch_tanh": "gelu_new",
        "gelu_fast": "gelu_new",
        "gelu": "gelu",
        "relu": "relu",
        "silu": "silu",
        "swish": "silu",
    }
    if hf_name not in table:
        raise ValueError(f"unsupported activation_function {hf_name!r}")
    return table[hf_name]


def config_from_hf(hf_config: Any, dtype=None, param_dtype=None) -> TransformerConfig:
    """Translate a transformers PretrainedConfig into a TransformerConfig."""
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    param_dtype = param_dtype or jnp.float32
    mt = hf_config.model_type

    if mt == "gpt2":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            n_layer=hf_config.n_layer,
            n_head=hf_config.n_head,
            n_positions=hf_config.n_positions,
            intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
            pos_embed="learned",
            activation="gelu_new",
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            tie_word_embeddings=True,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if mt == "gptj":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            n_layer=hf_config.n_layer,
            n_head=hf_config.n_head,
            n_positions=hf_config.n_positions,
            intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
            pos_embed="rotary",
            rotary_style="gptj",
            rotary_dim=hf_config.rotary_dim,
            activation="gelu_new",
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            parallel_residual=True,
            use_attn_bias=False,
            use_mlp_bias=True,
            tie_word_embeddings=False,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if mt == "gpt_neox":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            n_layer=hf_config.num_hidden_layers,
            n_head=hf_config.num_attention_heads,
            n_positions=hf_config.max_position_embeddings,
            intermediate_size=hf_config.intermediate_size,
            pos_embed="rotary",
            rotary_style="neox",
            rotary_dim=int(
                (hf_config.hidden_size // hf_config.num_attention_heads)
                * hf_config.rotary_pct
            ),
            rope_theta=getattr(hf_config, "rotary_emb_base", 10000.0),
            activation="gelu",
            layer_norm_epsilon=hf_config.layer_norm_eps,
            parallel_residual=getattr(hf_config, "use_parallel_residual", True),
            use_attn_bias=True,
            use_mlp_bias=True,
            tie_word_embeddings=False,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if mt == "llama":
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            n_layer=hf_config.num_hidden_layers,
            n_head=hf_config.num_attention_heads,
            n_kv_head=getattr(hf_config, "num_key_value_heads", None)
            or hf_config.num_attention_heads,
            n_positions=hf_config.max_position_embeddings,
            intermediate_size=hf_config.intermediate_size,
            pos_embed="rotary",
            rotary_style="neox",
            rope_theta=getattr(hf_config, "rope_theta", 10000.0),
            norm="rmsnorm",
            layer_norm_epsilon=hf_config.rms_norm_eps,
            activation="silu",
            mlp_gated=True,
            use_attn_bias=False,
            use_mlp_bias=False,
            use_norm_bias=False,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if mt == "opt":
        # ref: OPTModelBranch (modeling_ppo.py:689-813). HF OPT computes
        # positions from the attention-mask cumsum (as we always do) and
        # offsets the learned table by 2 pad rows.
        if not getattr(hf_config, "do_layer_norm_before", True):
            raise ValueError("OPT variants with do_layer_norm_before=False (350m) unsupported")
        if getattr(hf_config, "word_embed_proj_dim", hf_config.hidden_size) != hf_config.hidden_size:
            raise ValueError("OPT word_embed_proj_dim != hidden_size unsupported")
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            n_layer=hf_config.num_hidden_layers,
            n_head=hf_config.num_attention_heads,
            n_positions=hf_config.max_position_embeddings,
            intermediate_size=hf_config.ffn_dim,
            pos_embed="learned",
            pos_offset=2,
            activation=_activation_name(hf_config.activation_function),
            layer_norm_epsilon=1e-5,
            tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", True),
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if mt == "bloom":
        # ref: BloomModelBranch (modeling_ppo.py:816-929). ALiBi position
        # bias, LayerNorm directly after word embeddings, per-head fused QKV.
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            n_layer=hf_config.n_layer,
            n_head=hf_config.n_head,
            n_positions=getattr(hf_config, "seq_length", 2048),
            pos_embed="alibi",
            embed_layernorm=True,
            activation="gelu_new",
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            tie_word_embeddings=True,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if mt == "gpt_bigcode":
        # ref: GPTBigCodeModelBranch (modeling_ppo.py:1079-1222).
        # Multi-query attention: a single shared KV head.
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.n_embd,
            n_layer=hf_config.n_layer,
            n_head=hf_config.n_head,
            n_kv_head=1 if getattr(hf_config, "multi_query", True) else hf_config.n_head,
            n_positions=hf_config.n_positions,
            intermediate_size=hf_config.n_inner or 4 * hf_config.n_embd,
            pos_embed="learned",
            activation=_activation_name(hf_config.activation_function),
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            tie_word_embeddings=True,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if mt == "gpt_neo":
        # ref: GPTModelBranch covers gpt_neo (modeling_ppo.py:1598-1637).
        # Quirks: queries are NOT scaled by 1/sqrt(D); alternate layers use
        # a sliding local-attention window; q/k/v projections have no bias.
        return TransformerConfig(
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            n_layer=hf_config.num_layers,
            n_head=hf_config.num_heads,
            n_positions=hf_config.max_position_embeddings,
            intermediate_size=hf_config.intermediate_size
            or 4 * hf_config.hidden_size,
            pos_embed="learned",
            activation=_activation_name(hf_config.activation_function),
            layer_norm_epsilon=hf_config.layer_norm_epsilon,
            attn_scale=1.0,
            local_window=hf_config.window_size,
            attn_layers=tuple(hf_config.attention_layers),
            use_attn_bias=False,
            use_attn_out_bias=True,
            tie_word_embeddings=True,
            dtype=dtype,
            param_dtype=param_dtype,
        )
    if (getattr(hf_config, "kv_lora_rank", None) or getattr(hf_config, "n_routed_experts", None)
            or getattr(hf_config, "linear_attn_config", None)
            or getattr(hf_config, "hybrid_override_pattern", None)
            or getattr(hf_config, "conv_L_cache", None)):
        raise NotImplementedError(
            f"no loader for model_type {mt!r}: the program runs latent attention, delta-rule "
            "(KDA), state-space (Mamba-2) and short-convolution layers, layers of one sub-layer, routed experts "
            "and several residual streams (TransformerConfig's kv_lora_rank, mixer_layers, "
            "ffn_layers, n_routed_experts, residual_streams) from random weights only; a "
            "checkpoint's tensor names would have to be mapped onto the stacks of "
            "`transformer.STACKS` (params['dense_blocks'] / params['blocks'] / "
            "params['delta_blocks'] / params['ssm_blocks'] ...), and no such map is written here"
        )
    raise ValueError(
        f"unsupported model_type {mt!r} (supported: gpt2, gptj, gpt_neo, "
        "gpt_neox, gpt_bigcode, llama, opt, bloom)"
    )


def seq2seq_config_from_hf(hf_config: Any, dtype=None, param_dtype=None):
    """Translate an HF T5Config into a Seq2SeqConfig."""
    import jax.numpy as jnp

    from trlx_tpu.models.seq2seq import Seq2SeqConfig

    if hf_config.model_type not in ("t5", "mt5"):
        raise ValueError(f"unsupported seq2seq model_type {hf_config.model_type!r}")
    ff = getattr(hf_config, "feed_forward_proj", "relu")
    return Seq2SeqConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.d_model,
        n_layer=hf_config.num_layers,
        n_decoder_layer=getattr(hf_config, "num_decoder_layers", hf_config.num_layers),
        n_head=hf_config.num_heads,
        d_kv=hf_config.d_kv,
        d_ff=hf_config.d_ff,
        relative_attention_num_buckets=hf_config.relative_attention_num_buckets,
        relative_attention_max_distance=getattr(
            hf_config, "relative_attention_max_distance", 128
        ),
        layer_norm_epsilon=hf_config.layer_norm_epsilon,
        activation="gated-gelu" if "gated" in ff else "relu",
        tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", True),
        decoder_start_token_id=hf_config.decoder_start_token_id or 0,
        dtype=dtype or jnp.bfloat16,
        param_dtype=param_dtype or jnp.float32,
    )


def t5_params_from_state_dict(sd: Dict[str, Any], cfg) -> Dict:
    """Convert an HF T5 torch state_dict into the T5LM param tree."""
    H, Dk, D = cfg.n_head, cfg.d_kv, cfg.d_model

    def attn(prefix: str) -> Dict[str, Any]:
        return {
            "q": {"kernel": _np(sd[prefix + ".q.weight"]).T.reshape(D, H, Dk)},
            "k": {"kernel": _np(sd[prefix + ".k.weight"]).T.reshape(D, H, Dk)},
            "v": {"kernel": _np(sd[prefix + ".v.weight"]).T.reshape(D, H, Dk)},
            "o": {"kernel": _np(sd[prefix + ".o.weight"]).T.reshape(H, Dk, D)},
        }

    def mlp(prefix: str) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "fc_out": {"kernel": _np(sd[prefix + ".wo.weight"]).T}
        }
        if prefix + ".wi.weight" in sd:
            out["fc_in"] = {"kernel": _np(sd[prefix + ".wi.weight"]).T}
        else:  # gated (v1.1): wi_0 activated, wi_1 linear
            out["fc_in"] = {"kernel": _np(sd[prefix + ".wi_0.weight"]).T}
            out["fc_gate"] = {"kernel": _np(sd[prefix + ".wi_1.weight"]).T}
        return out

    def stack(side: str, n: int, is_decoder: bool) -> Dict[str, Any]:
        layers = []
        for i in range(n):
            b = f"{side}.block.{i}.layer"
            layer = {
                "ln_1": {"scale": _np(sd[f"{b}.0.layer_norm.weight"])},
                "self_attn": attn(f"{b}.0.SelfAttention"),
            }
            if is_decoder:
                layer["ln_cross"] = {"scale": _np(sd[f"{b}.1.layer_norm.weight"])}
                layer["cross_attn"] = attn(f"{b}.1.EncDecAttention")
                ff = 2
            else:
                ff = 1
            layer["ln_2"] = {"scale": _np(sd[f"{b}.{ff}.layer_norm.weight"])}
            layer["mlp"] = mlp(f"{b}.{ff}.DenseReluDense")
            layers.append(layer)
        return _stack(layers)

    params = {
        "shared": {"wte": _np(sd["shared.weight"])},
        "encoder": {
            "blocks": stack("encoder", cfg.n_layer, False),
            "ln_f": {"scale": _np(sd["encoder.final_layer_norm.weight"])},
            "rel_bias": _np(
                sd["encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"]
            ),
        },
        "decoder": {
            "blocks": stack("decoder", cfg.n_decoder_layer, True),
            "ln_f": {"scale": _np(sd["decoder.final_layer_norm.weight"])},
            "rel_bias": _np(
                sd["decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight"]
            ),
        },
    }
    if not cfg.tie_word_embeddings and "lm_head.weight" in sd:
        params["lm_head"] = {"kernel": _np(sd["lm_head.weight"]).T}
    return params


def t5_state_dict_from_params(params: Dict, cfg) -> Dict[str, np.ndarray]:
    """Inverse of t5_params_from_state_dict: T5LM param tree -> HF torch
    state_dict names (deploy artifact for seq2seq policies)."""
    H, Dk, D = cfg.n_head, cfg.d_kv, cfg.d_model
    out: Dict[str, np.ndarray] = {}

    def A(x):
        return np.asarray(x, dtype=np.float32)

    def attn_out(prefix: str, blk: Dict) -> None:
        out[prefix + ".q.weight"] = A(blk["q"]["kernel"]).reshape(D, H * Dk).T
        out[prefix + ".k.weight"] = A(blk["k"]["kernel"]).reshape(D, H * Dk).T
        out[prefix + ".v.weight"] = A(blk["v"]["kernel"]).reshape(D, H * Dk).T
        out[prefix + ".o.weight"] = A(blk["o"]["kernel"]).reshape(H * Dk, D).T

    def mlp_out(prefix: str, blk: Dict) -> None:
        out[prefix + ".wo.weight"] = A(blk["fc_out"]["kernel"]).T
        if "fc_gate" in blk:  # gated (v1.1)
            out[prefix + ".wi_0.weight"] = A(blk["fc_in"]["kernel"]).T
            out[prefix + ".wi_1.weight"] = A(blk["fc_gate"]["kernel"]).T
        else:
            out[prefix + ".wi.weight"] = A(blk["fc_in"]["kernel"]).T

    def stack_out(side: str, tree: Dict, n: int, is_decoder: bool) -> None:
        for i in range(n):
            b = f"{side}.block.{i}.layer"
            blk = {k: A_tree(v, i) for k, v in tree["blocks"].items()}
            out[f"{b}.0.layer_norm.weight"] = blk["ln_1"]["scale"]
            attn_out(f"{b}.0.SelfAttention", blk["self_attn"])
            if is_decoder:
                out[f"{b}.1.layer_norm.weight"] = blk["ln_cross"]["scale"]
                attn_out(f"{b}.1.EncDecAttention", blk["cross_attn"])
                ff = 2
            else:
                ff = 1
            out[f"{b}.{ff}.layer_norm.weight"] = blk["ln_2"]["scale"]
            mlp_out(f"{b}.{ff}.DenseReluDense", blk["mlp"])
        out[f"{side}.final_layer_norm.weight"] = A(tree["ln_f"]["scale"])
        # HF keeps the relative bias on block 0 only
        out[f"{side}.block.0.layer.0.SelfAttention.relative_attention_bias.weight"] = A(
            tree["rel_bias"]
        )

    shared = A(params["shared"]["wte"])
    out["shared.weight"] = shared
    out["encoder.embed_tokens.weight"] = shared
    out["decoder.embed_tokens.weight"] = shared
    stack_out("encoder", params["encoder"], cfg.n_layer, False)
    stack_out("decoder", params["decoder"], cfg.n_decoder_layer, True)
    if "lm_head" in params:
        out["lm_head.weight"] = A(params["lm_head"]["kernel"]).T
    else:  # tied: HF still carries the (shared) lm_head tensor
        out["lm_head.weight"] = shared
    return out


def load_pretrained_seq2seq(path: str, dtype=None, param_dtype=None):
    """Load an HF-layout T5 checkpoint directory -> (T5LM, params)."""
    import transformers

    from trlx_tpu.models.seq2seq import T5LM

    hf_config = transformers.AutoConfig.from_pretrained(path)
    cfg = seq2seq_config_from_hf(hf_config, dtype=dtype, param_dtype=param_dtype)
    sd = _read_state_dict(path)
    params = t5_params_from_state_dict(sd, cfg)
    return T5LM(cfg), params, hf_config.model_type


# ---------------------------------------------------------------------------
# weight conversion: torch state_dict -> stacked functional param tree
# ---------------------------------------------------------------------------


def _np(t) -> np.ndarray:
    # torch tensor or numpy array -> float32 numpy (bf16-safe via float())
    if hasattr(t, "detach"):
        t = t.detach().to("cpu").float().numpy()
    return np.asarray(t, dtype=np.float32)


def _stack(layers: List[Dict[str, Any]]) -> Dict[str, Any]:
    """[{'a': arr}, ...] per layer -> {'a': arr[L, ...]} stacked."""
    import jax

    return jax.tree_util.tree_map(lambda *xs: np.stack(xs, axis=0), *layers)


def params_from_state_dict(sd: Dict[str, Any], cfg: TransformerConfig, model_type: str) -> Dict:
    """Convert an HF torch state_dict to the functional param tree."""
    H, D, E = cfg.n_head, cfg.head_dim, cfg.hidden_size
    Hkv = cfg.n_kv_head

    def qkv_from_fused(w, b, order: str = "qkv"):
        """Fused c_attn [E, 3E] (+bias) -> q/k/v dicts with [E,H,D] kernels."""
        ws = np.split(w, 3, axis=-1)
        out = {}
        for name, wi in zip(order, ws):
            out[name] = {"kernel": wi.reshape(E, H, D)}
        if b is not None:
            bs = np.split(b, 3, axis=-1)
            for name, bi in zip(order, bs):
                out[name]["bias"] = bi.reshape(H, D)
        return out

    if model_type == "gpt2":
        # HF Conv1D stores [in, out] — same as our kernels, no transpose.
        pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}h.{i}."
            attn = qkv_from_fused(_np(sd[b + "attn.c_attn.weight"]), _np(sd[b + "attn.c_attn.bias"]))
            attn["o"] = {
                "kernel": _np(sd[b + "attn.c_proj.weight"]).reshape(H, D, E),
                "bias": _np(sd[b + "attn.c_proj.bias"]),
            }
            layers.append(
                {
                    "ln_1": {"scale": _np(sd[b + "ln_1.weight"]), "bias": _np(sd[b + "ln_1.bias"])},
                    "attn": attn,
                    "ln_2": {"scale": _np(sd[b + "ln_2.weight"]), "bias": _np(sd[b + "ln_2.bias"])},
                    "mlp": {
                        "fc_in": {"kernel": _np(sd[b + "mlp.c_fc.weight"]), "bias": _np(sd[b + "mlp.c_fc.bias"])},
                        "fc_out": {"kernel": _np(sd[b + "mlp.c_proj.weight"]), "bias": _np(sd[b + "mlp.c_proj.bias"])},
                    },
                }
            )
        return {
            "embed": {"wte": _np(sd[pfx + "wte.weight"]), "wpe": _np(sd[pfx + "wpe.weight"])},
            "blocks": _stack(layers),
            "ln_f": {"scale": _np(sd[pfx + "ln_f.weight"]), "bias": _np(sd[pfx + "ln_f.bias"])},
        }

    if model_type == "gptj":
        pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}h.{i}."
            attn = {}
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj")):
                attn[ours] = {"kernel": _np(sd[f"{b}attn.{theirs}.weight"]).T.reshape(E, H, D)}
            attn["o"] = {"kernel": _np(sd[b + "attn.out_proj.weight"]).T.reshape(H, D, E)}
            layers.append(
                {
                    "ln_1": {"scale": _np(sd[b + "ln_1.weight"]), "bias": _np(sd[b + "ln_1.bias"])},
                    "attn": attn,
                    "mlp": {
                        "fc_in": {"kernel": _np(sd[b + "mlp.fc_in.weight"]).T, "bias": _np(sd[b + "mlp.fc_in.bias"])},
                        "fc_out": {"kernel": _np(sd[b + "mlp.fc_out.weight"]).T, "bias": _np(sd[b + "mlp.fc_out.bias"])},
                    },
                }
            )
        params = {
            "embed": {"wte": _np(sd[pfx + "wte.weight"])},
            "blocks": _stack(layers),
            "ln_f": {"scale": _np(sd[pfx + "ln_f.weight"]), "bias": _np(sd[pfx + "ln_f.bias"])},
            "lm_head": {"kernel": _np(sd["lm_head.weight"]).T},
        }
        return params

    if model_type == "gpt_neox":
        pfx = "gpt_neox." if any(k.startswith("gpt_neox.") for k in sd) else ""
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}layers.{i}."
            # fused qkv [3E, E], interleaved per head: [H, 3, D, E]
            w = _np(sd[b + "attention.query_key_value.weight"]).reshape(H, 3, D, E)
            bias = _np(sd[b + "attention.query_key_value.bias"]).reshape(H, 3, D)
            attn = {
                name: {
                    "kernel": np.moveaxis(w[:, j], -1, 0).reshape(E, H, D),
                    "bias": bias[:, j],
                }
                for j, name in enumerate("qkv")
            }
            attn["o"] = {
                "kernel": _np(sd[b + "attention.dense.weight"]).T.reshape(H, D, E),
                "bias": _np(sd[b + "attention.dense.bias"]),
            }
            layers.append(
                {
                    "ln_1": {
                        "scale": _np(sd[b + "input_layernorm.weight"]),
                        "bias": _np(sd[b + "input_layernorm.bias"]),
                    },
                    "attn": attn,
                    "ln_2": {
                        "scale": _np(sd[b + "post_attention_layernorm.weight"]),
                        "bias": _np(sd[b + "post_attention_layernorm.bias"]),
                    },
                    "mlp": {
                        "fc_in": {
                            "kernel": _np(sd[b + "mlp.dense_h_to_4h.weight"]).T,
                            "bias": _np(sd[b + "mlp.dense_h_to_4h.bias"]),
                        },
                        "fc_out": {
                            "kernel": _np(sd[b + "mlp.dense_4h_to_h.weight"]).T,
                            "bias": _np(sd[b + "mlp.dense_4h_to_h.bias"]),
                        },
                    },
                }
            )
        stacked = _stack(layers)
        if not getattr(cfg, "parallel_residual", True):
            pass  # ln_2 still present in sequential layout
        return {
            "embed": {"wte": _np(sd[pfx + "embed_in.weight"])},
            "blocks": stacked,
            "ln_f": {
                "scale": _np(sd[pfx + "final_layer_norm.weight"]),
                "bias": _np(sd[pfx + "final_layer_norm.bias"]),
            },
            "lm_head": {"kernel": _np(sd["embed_out.weight"]).T},
        }

    if model_type == "llama":
        pfx = "model." if any(k.startswith("model.") for k in sd) else ""
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}layers.{i}."
            attn = {
                "q": {"kernel": _np(sd[b + "self_attn.q_proj.weight"]).T.reshape(E, H, D)},
                "k": {"kernel": _np(sd[b + "self_attn.k_proj.weight"]).T.reshape(E, Hkv, D)},
                "v": {"kernel": _np(sd[b + "self_attn.v_proj.weight"]).T.reshape(E, Hkv, D)},
                "o": {"kernel": _np(sd[b + "self_attn.o_proj.weight"]).T.reshape(H, D, E)},
            }
            layers.append(
                {
                    "ln_1": {"scale": _np(sd[b + "input_layernorm.weight"])},
                    "attn": attn,
                    "ln_2": {"scale": _np(sd[b + "post_attention_layernorm.weight"])},
                    "mlp": {
                        # HF: gate_proj activated, up_proj linear; ours:
                        # fc_in activated, fc_gate linear multiplier
                        "fc_in": {"kernel": _np(sd[b + "mlp.gate_proj.weight"]).T},
                        "fc_gate": {"kernel": _np(sd[b + "mlp.up_proj.weight"]).T},
                        "fc_out": {"kernel": _np(sd[b + "mlp.down_proj.weight"]).T},
                    },
                }
            )
        params = {
            "embed": {"wte": _np(sd[pfx + "embed_tokens.weight"])},
            "blocks": _stack(layers),
            "ln_f": {"scale": _np(sd[pfx + "norm.weight"])},
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": _np(sd["lm_head.weight"]).T}
        return params

    if model_type == "opt":
        pfx = (
            "model.decoder."
            if any(k.startswith("model.decoder.") for k in sd)
            else "decoder."
            if any(k.startswith("decoder.") for k in sd)
            else ""
        )
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}layers.{i}."
            attn = {}
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj")):
                attn[ours] = {
                    "kernel": _np(sd[f"{b}self_attn.{theirs}.weight"]).T.reshape(E, H, D),
                    "bias": _np(sd[f"{b}self_attn.{theirs}.bias"]).reshape(H, D),
                }
            attn["o"] = {
                "kernel": _np(sd[b + "self_attn.out_proj.weight"]).T.reshape(H, D, E),
                "bias": _np(sd[b + "self_attn.out_proj.bias"]),
            }
            layers.append(
                {
                    "ln_1": {
                        "scale": _np(sd[b + "self_attn_layer_norm.weight"]),
                        "bias": _np(sd[b + "self_attn_layer_norm.bias"]),
                    },
                    "attn": attn,
                    "ln_2": {
                        "scale": _np(sd[b + "final_layer_norm.weight"]),
                        "bias": _np(sd[b + "final_layer_norm.bias"]),
                    },
                    "mlp": {
                        "fc_in": {"kernel": _np(sd[b + "fc1.weight"]).T, "bias": _np(sd[b + "fc1.bias"])},
                        "fc_out": {"kernel": _np(sd[b + "fc2.weight"]).T, "bias": _np(sd[b + "fc2.bias"])},
                    },
                }
            )
        params = {
            # wpe keeps OPT's full table (2 leading pad rows; cfg.pos_offset=2)
            "embed": {
                "wte": _np(sd[pfx + "embed_tokens.weight"]),
                "wpe": _np(sd[pfx + "embed_positions.weight"]),
            },
            "blocks": _stack(layers),
            "ln_f": {
                "scale": _np(sd[pfx + "final_layer_norm.weight"]),
                "bias": _np(sd[pfx + "final_layer_norm.bias"]),
            },
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {"kernel": _np(sd["lm_head.weight"]).T}
        return params

    if model_type == "bloom":
        pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}h.{i}."
            # fused qkv [3E, E], per-head interleave: rows view as [H, 3, D]
            w = _np(sd[b + "self_attention.query_key_value.weight"]).reshape(H, 3, D, E)
            bias = _np(sd[b + "self_attention.query_key_value.bias"]).reshape(H, 3, D)
            attn = {
                name: {
                    "kernel": np.moveaxis(w[:, j], -1, 0).reshape(E, H, D),
                    "bias": bias[:, j],
                }
                for j, name in enumerate("qkv")
            }
            attn["o"] = {
                "kernel": _np(sd[b + "self_attention.dense.weight"]).T.reshape(H, D, E),
                "bias": _np(sd[b + "self_attention.dense.bias"]),
            }
            layers.append(
                {
                    "ln_1": {
                        "scale": _np(sd[b + "input_layernorm.weight"]),
                        "bias": _np(sd[b + "input_layernorm.bias"]),
                    },
                    "attn": attn,
                    "ln_2": {
                        "scale": _np(sd[b + "post_attention_layernorm.weight"]),
                        "bias": _np(sd[b + "post_attention_layernorm.bias"]),
                    },
                    "mlp": {
                        "fc_in": {
                            "kernel": _np(sd[b + "mlp.dense_h_to_4h.weight"]).T,
                            "bias": _np(sd[b + "mlp.dense_h_to_4h.bias"]),
                        },
                        "fc_out": {
                            "kernel": _np(sd[b + "mlp.dense_4h_to_h.weight"]).T,
                            "bias": _np(sd[b + "mlp.dense_4h_to_h.bias"]),
                        },
                    },
                }
            )
        return {
            "embed": {"wte": _np(sd[pfx + "word_embeddings.weight"])},
            "ln_embed": {
                "scale": _np(sd[pfx + "word_embeddings_layernorm.weight"]),
                "bias": _np(sd[pfx + "word_embeddings_layernorm.bias"]),
            },
            "blocks": _stack(layers),
            "ln_f": {
                "scale": _np(sd[pfx + "ln_f.weight"]),
                "bias": _np(sd[pfx + "ln_f.bias"]),
            },
        }

    if model_type == "gpt_bigcode":
        pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
        kv_dim = Hkv * D
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}h.{i}."
            # c_attn is a Linear [E + 2*kv_dim, E]: q rows then shared k, v
            w = _np(sd[b + "attn.c_attn.weight"]).T  # [E, E + 2*kv_dim]
            bias = _np(sd[b + "attn.c_attn.bias"])
            attn = {
                "q": {
                    "kernel": w[:, :E].reshape(E, H, D),
                    "bias": bias[:E].reshape(H, D),
                },
                "k": {
                    "kernel": w[:, E : E + kv_dim].reshape(E, Hkv, D),
                    "bias": bias[E : E + kv_dim].reshape(Hkv, D),
                },
                "v": {
                    "kernel": w[:, E + kv_dim :].reshape(E, Hkv, D),
                    "bias": bias[E + kv_dim :].reshape(Hkv, D),
                },
                "o": {
                    "kernel": _np(sd[b + "attn.c_proj.weight"]).T.reshape(H, D, E),
                    "bias": _np(sd[b + "attn.c_proj.bias"]),
                },
            }
            layers.append(
                {
                    "ln_1": {"scale": _np(sd[b + "ln_1.weight"]), "bias": _np(sd[b + "ln_1.bias"])},
                    "attn": attn,
                    "ln_2": {"scale": _np(sd[b + "ln_2.weight"]), "bias": _np(sd[b + "ln_2.bias"])},
                    "mlp": {
                        "fc_in": {"kernel": _np(sd[b + "mlp.c_fc.weight"]).T, "bias": _np(sd[b + "mlp.c_fc.bias"])},
                        "fc_out": {"kernel": _np(sd[b + "mlp.c_proj.weight"]).T, "bias": _np(sd[b + "mlp.c_proj.bias"])},
                    },
                }
            )
        return {
            "embed": {"wte": _np(sd[pfx + "wte.weight"]), "wpe": _np(sd[pfx + "wpe.weight"])},
            "blocks": _stack(layers),
            "ln_f": {"scale": _np(sd[pfx + "ln_f.weight"]), "bias": _np(sd[pfx + "ln_f.bias"])},
        }

    if model_type == "gpt_neo":
        pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
        layers = []
        for i in range(cfg.n_layer):
            b = f"{pfx}h.{i}."
            attn = {
                ours: {"kernel": _np(sd[f"{b}attn.attention.{theirs}.weight"]).T.reshape(E, H, D)}
                for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj"))
            }
            attn["o"] = {
                "kernel": _np(sd[b + "attn.attention.out_proj.weight"]).T.reshape(H, D, E),
                "bias": _np(sd[b + "attn.attention.out_proj.bias"]),
            }
            layers.append(
                {
                    "ln_1": {"scale": _np(sd[b + "ln_1.weight"]), "bias": _np(sd[b + "ln_1.bias"])},
                    "attn": attn,
                    "ln_2": {"scale": _np(sd[b + "ln_2.weight"]), "bias": _np(sd[b + "ln_2.bias"])},
                    "mlp": {
                        "fc_in": {"kernel": _np(sd[b + "mlp.c_fc.weight"]).T, "bias": _np(sd[b + "mlp.c_fc.bias"])},
                        "fc_out": {"kernel": _np(sd[b + "mlp.c_proj.weight"]).T, "bias": _np(sd[b + "mlp.c_proj.bias"])},
                    },
                }
            )
        return {
            "embed": {"wte": _np(sd[pfx + "wte.weight"]), "wpe": _np(sd[pfx + "wpe.weight"])},
            "blocks": _stack(layers),
            "ln_f": {"scale": _np(sd[pfx + "ln_f.weight"]), "bias": _np(sd[pfx + "ln_f.bias"])},
        }

    raise ValueError(f"unsupported model_type {model_type!r}")


# ---------------------------------------------------------------------------
# checkpoint IO
# ---------------------------------------------------------------------------


def _read_state_dict(path: str) -> Dict[str, Any]:
    """Read torch-format weights from an HF-layout directory, merging
    sharded checkpoints via the index file when present (parity:
    reference modeling_base.py:277-315)."""
    single_bins = ["pytorch_model.bin", "model.safetensors"]
    index_files = ["pytorch_model.bin.index.json", "model.safetensors.index.json"]

    def _load_file(fp: str) -> Dict[str, Any]:
        if fp.endswith(".safetensors"):
            from safetensors import safe_open

            out = {}
            with safe_open(fp, framework="np") as f:
                for key in f.keys():
                    out[key] = f.get_tensor(key)
            return out
        import torch

        return torch.load(fp, map_location="cpu", weights_only=True)

    for idx_name in index_files:
        idx_fp = os.path.join(path, idx_name)
        if os.path.exists(idx_fp):
            with open(idx_fp) as f:
                index = json.load(f)
            sd: Dict[str, Any] = {}
            for shard in sorted(set(index["weight_map"].values())):
                sd.update(_load_file(os.path.join(path, shard)))
            return sd
    for bin_name in single_bins:
        fp = os.path.join(path, bin_name)
        if os.path.exists(fp):
            return _load_file(fp)
    raise FileNotFoundError(f"no model weights found under {path}")


def load_pretrained(
    path: str, dtype=None, param_dtype=None
) -> Tuple[TransformerLM, Dict, str]:
    """Load an HF-layout local checkpoint directory.

    Returns (model, params, model_type). `params` leaves are numpy arrays
    (host memory) — the trainer device_puts them with shardings.
    """
    import transformers

    hf_config = transformers.AutoConfig.from_pretrained(path)
    cfg = config_from_hf(hf_config, dtype=dtype, param_dtype=param_dtype)
    sd = _read_state_dict(path)
    params = params_from_state_dict(sd, cfg, hf_config.model_type)
    return TransformerLM(cfg), params, hf_config.model_type


def save_pretrained_hf(
    params: Dict, cfg: TransformerConfig, model_type: str, hf_config: Any, path: str
) -> None:
    """Export the param tree as a plain HF torch checkpoint (deploy
    artifact parity: reference accelerate_base_trainer save_pretrained)."""
    import torch

    os.makedirs(path, exist_ok=True)
    if model_type in ("t5", "mt5"):
        sd = t5_state_dict_from_params(params, cfg)
    else:
        sd = state_dict_from_params(params, cfg, model_type)
    torch.save({k: torch.from_numpy(np.asarray(v)) for k, v in sd.items()},
               os.path.join(path, "pytorch_model.bin"))
    hf_config.save_pretrained(path)


def state_dict_from_params(params: Dict, cfg: TransformerConfig, model_type: str) -> Dict[str, np.ndarray]:
    """Inverse of params_from_state_dict (all supported causal families)."""
    H, D, E = cfg.n_head, cfg.head_dim, cfg.hidden_size
    Hkv = cfg.n_kv_head
    out: Dict[str, np.ndarray] = {}

    def A(x):
        return np.asarray(x, dtype=np.float32)

    blocks = params["blocks"]
    if model_type == "gpt2":
        out["transformer.wte.weight"] = A(params["embed"]["wte"])
        out["transformer.wpe.weight"] = A(params["embed"]["wpe"])
        for i in range(cfg.n_layer):
            b = f"transformer.h.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "ln_1.weight"] = blk["ln_1"]["scale"]
            out[b + "ln_1.bias"] = blk["ln_1"]["bias"]
            qkv_w = np.concatenate(
                [blk["attn"][n]["kernel"].reshape(E, E) for n in "qkv"], axis=-1
            )
            qkv_b = np.concatenate(
                [blk["attn"][n]["bias"].reshape(E) for n in "qkv"], axis=-1
            )
            out[b + "attn.c_attn.weight"] = qkv_w
            out[b + "attn.c_attn.bias"] = qkv_b
            out[b + "attn.c_proj.weight"] = blk["attn"]["o"]["kernel"].reshape(E, E)
            out[b + "attn.c_proj.bias"] = blk["attn"]["o"]["bias"]
            out[b + "ln_2.weight"] = blk["ln_2"]["scale"]
            out[b + "ln_2.bias"] = blk["ln_2"]["bias"]
            out[b + "mlp.c_fc.weight"] = blk["mlp"]["fc_in"]["kernel"]
            out[b + "mlp.c_fc.bias"] = blk["mlp"]["fc_in"]["bias"]
            out[b + "mlp.c_proj.weight"] = blk["mlp"]["fc_out"]["kernel"]
            out[b + "mlp.c_proj.bias"] = blk["mlp"]["fc_out"]["bias"]
        out["transformer.ln_f.weight"] = A(params["ln_f"]["scale"])
        out["transformer.ln_f.bias"] = A(params["ln_f"]["bias"])
        out["lm_head.weight"] = out["transformer.wte.weight"]
        return out

    if model_type == "llama":
        out["model.embed_tokens.weight"] = A(params["embed"]["wte"])
        for i in range(cfg.n_layer):
            b = f"model.layers.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "input_layernorm.weight"] = blk["ln_1"]["scale"]
            out[b + "self_attn.q_proj.weight"] = blk["attn"]["q"]["kernel"].reshape(E, H * D).T
            out[b + "self_attn.k_proj.weight"] = blk["attn"]["k"]["kernel"].reshape(E, Hkv * D).T
            out[b + "self_attn.v_proj.weight"] = blk["attn"]["v"]["kernel"].reshape(E, Hkv * D).T
            out[b + "self_attn.o_proj.weight"] = blk["attn"]["o"]["kernel"].reshape(H * D, E).T
            out[b + "post_attention_layernorm.weight"] = blk["ln_2"]["scale"]
            out[b + "mlp.gate_proj.weight"] = blk["mlp"]["fc_in"]["kernel"].T
            out[b + "mlp.up_proj.weight"] = blk["mlp"]["fc_gate"]["kernel"].T
            out[b + "mlp.down_proj.weight"] = blk["mlp"]["fc_out"]["kernel"].T
        out["model.norm.weight"] = A(params["ln_f"]["scale"])
        if "lm_head" in params:
            out["lm_head.weight"] = A(params["lm_head"]["kernel"]).T
        else:
            out["lm_head.weight"] = out["model.embed_tokens.weight"]
        return out

    if model_type == "gptj":
        out["transformer.wte.weight"] = A(params["embed"]["wte"])
        for i in range(cfg.n_layer):
            b = f"transformer.h.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "ln_1.weight"] = blk["ln_1"]["scale"]
            out[b + "ln_1.bias"] = blk["ln_1"]["bias"]
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj")):
                out[b + f"attn.{theirs}.weight"] = (
                    blk["attn"][ours]["kernel"].reshape(E, H * D).T
                )
            out[b + "attn.out_proj.weight"] = blk["attn"]["o"]["kernel"].reshape(H * D, E).T
            out[b + "mlp.fc_in.weight"] = blk["mlp"]["fc_in"]["kernel"].T
            out[b + "mlp.fc_in.bias"] = blk["mlp"]["fc_in"]["bias"]
            out[b + "mlp.fc_out.weight"] = blk["mlp"]["fc_out"]["kernel"].T
            out[b + "mlp.fc_out.bias"] = blk["mlp"]["fc_out"]["bias"]
        out["transformer.ln_f.weight"] = A(params["ln_f"]["scale"])
        out["transformer.ln_f.bias"] = A(params["ln_f"]["bias"])
        out["lm_head.weight"] = A(params["lm_head"]["kernel"]).T
        out["lm_head.bias"] = np.zeros(cfg.vocab_size, np.float32)
        return out

    if model_type == "gpt_neox":
        out["gpt_neox.embed_in.weight"] = A(params["embed"]["wte"])
        for i in range(cfg.n_layer):
            b = f"gpt_neox.layers.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "input_layernorm.weight"] = blk["ln_1"]["scale"]
            out[b + "input_layernorm.bias"] = blk["ln_1"]["bias"]
            # fused qkv [3E, E], interleaved per head: [H, 3, D, E]
            w = np.stack(
                [np.moveaxis(blk["attn"][n]["kernel"], 0, -1) for n in "qkv"], axis=1
            )  # [H, 3, D, E]
            out[b + "attention.query_key_value.weight"] = w.reshape(3 * E, E)
            bias = np.stack([blk["attn"][n]["bias"] for n in "qkv"], axis=1)
            out[b + "attention.query_key_value.bias"] = bias.reshape(3 * E)
            out[b + "attention.dense.weight"] = blk["attn"]["o"]["kernel"].reshape(H * D, E).T
            out[b + "attention.dense.bias"] = blk["attn"]["o"]["bias"]
            out[b + "post_attention_layernorm.weight"] = blk["ln_2"]["scale"]
            out[b + "post_attention_layernorm.bias"] = blk["ln_2"]["bias"]
            out[b + "mlp.dense_h_to_4h.weight"] = blk["mlp"]["fc_in"]["kernel"].T
            out[b + "mlp.dense_h_to_4h.bias"] = blk["mlp"]["fc_in"]["bias"]
            out[b + "mlp.dense_4h_to_h.weight"] = blk["mlp"]["fc_out"]["kernel"].T
            out[b + "mlp.dense_4h_to_h.bias"] = blk["mlp"]["fc_out"]["bias"]
        out["gpt_neox.final_layer_norm.weight"] = A(params["ln_f"]["scale"])
        out["gpt_neox.final_layer_norm.bias"] = A(params["ln_f"]["bias"])
        out["embed_out.weight"] = A(params["lm_head"]["kernel"]).T
        return out

    if model_type == "opt":
        out["model.decoder.embed_tokens.weight"] = A(params["embed"]["wte"])
        out["model.decoder.embed_positions.weight"] = A(params["embed"]["wpe"])
        for i in range(cfg.n_layer):
            b = f"model.decoder.layers.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "self_attn_layer_norm.weight"] = blk["ln_1"]["scale"]
            out[b + "self_attn_layer_norm.bias"] = blk["ln_1"]["bias"]
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj")):
                out[b + f"self_attn.{theirs}.weight"] = blk["attn"][ours]["kernel"].reshape(E, H * D).T
                out[b + f"self_attn.{theirs}.bias"] = blk["attn"][ours]["bias"].reshape(H * D)
            out[b + "self_attn.out_proj.weight"] = blk["attn"]["o"]["kernel"].reshape(H * D, E).T
            out[b + "self_attn.out_proj.bias"] = blk["attn"]["o"]["bias"]
            out[b + "final_layer_norm.weight"] = blk["ln_2"]["scale"]
            out[b + "final_layer_norm.bias"] = blk["ln_2"]["bias"]
            out[b + "fc1.weight"] = blk["mlp"]["fc_in"]["kernel"].T
            out[b + "fc1.bias"] = blk["mlp"]["fc_in"]["bias"]
            out[b + "fc2.weight"] = blk["mlp"]["fc_out"]["kernel"].T
            out[b + "fc2.bias"] = blk["mlp"]["fc_out"]["bias"]
        out["model.decoder.final_layer_norm.weight"] = A(params["ln_f"]["scale"])
        out["model.decoder.final_layer_norm.bias"] = A(params["ln_f"]["bias"])
        if "lm_head" in params:
            out["lm_head.weight"] = A(params["lm_head"]["kernel"]).T
        else:
            out["lm_head.weight"] = out["model.decoder.embed_tokens.weight"]
        return out

    if model_type == "bloom":
        out["transformer.word_embeddings.weight"] = A(params["embed"]["wte"])
        out["transformer.word_embeddings_layernorm.weight"] = A(params["ln_embed"]["scale"])
        out["transformer.word_embeddings_layernorm.bias"] = A(params["ln_embed"]["bias"])
        for i in range(cfg.n_layer):
            b = f"transformer.h.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "input_layernorm.weight"] = blk["ln_1"]["scale"]
            out[b + "input_layernorm.bias"] = blk["ln_1"]["bias"]
            # [H, 3, D, E] per-head interleave -> fused [3E, E]
            w = np.stack(
                [np.moveaxis(blk["attn"][n]["kernel"], 0, -1) for n in "qkv"], axis=1
            )
            out[b + "self_attention.query_key_value.weight"] = w.reshape(3 * E, E)
            bias = np.stack([blk["attn"][n]["bias"] for n in "qkv"], axis=1)
            out[b + "self_attention.query_key_value.bias"] = bias.reshape(3 * E)
            out[b + "self_attention.dense.weight"] = blk["attn"]["o"]["kernel"].reshape(H * D, E).T
            out[b + "self_attention.dense.bias"] = blk["attn"]["o"]["bias"]
            out[b + "post_attention_layernorm.weight"] = blk["ln_2"]["scale"]
            out[b + "post_attention_layernorm.bias"] = blk["ln_2"]["bias"]
            out[b + "mlp.dense_h_to_4h.weight"] = blk["mlp"]["fc_in"]["kernel"].T
            out[b + "mlp.dense_h_to_4h.bias"] = blk["mlp"]["fc_in"]["bias"]
            out[b + "mlp.dense_4h_to_h.weight"] = blk["mlp"]["fc_out"]["kernel"].T
            out[b + "mlp.dense_4h_to_h.bias"] = blk["mlp"]["fc_out"]["bias"]
        out["transformer.ln_f.weight"] = A(params["ln_f"]["scale"])
        out["transformer.ln_f.bias"] = A(params["ln_f"]["bias"])
        out["lm_head.weight"] = out["transformer.word_embeddings.weight"]
        return out

    if model_type == "gpt_bigcode":
        out["transformer.wte.weight"] = A(params["embed"]["wte"])
        out["transformer.wpe.weight"] = A(params["embed"]["wpe"])
        kv_dim = Hkv * D
        for i in range(cfg.n_layer):
            b = f"transformer.h.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "ln_1.weight"] = blk["ln_1"]["scale"]
            out[b + "ln_1.bias"] = blk["ln_1"]["bias"]
            w = np.concatenate(
                [
                    blk["attn"]["q"]["kernel"].reshape(E, H * D),
                    blk["attn"]["k"]["kernel"].reshape(E, kv_dim),
                    blk["attn"]["v"]["kernel"].reshape(E, kv_dim),
                ],
                axis=-1,
            )
            out[b + "attn.c_attn.weight"] = w.T
            out[b + "attn.c_attn.bias"] = np.concatenate(
                [
                    blk["attn"]["q"]["bias"].reshape(H * D),
                    blk["attn"]["k"]["bias"].reshape(kv_dim),
                    blk["attn"]["v"]["bias"].reshape(kv_dim),
                ]
            )
            out[b + "attn.c_proj.weight"] = blk["attn"]["o"]["kernel"].reshape(H * D, E).T
            out[b + "attn.c_proj.bias"] = blk["attn"]["o"]["bias"]
            out[b + "ln_2.weight"] = blk["ln_2"]["scale"]
            out[b + "ln_2.bias"] = blk["ln_2"]["bias"]
            out[b + "mlp.c_fc.weight"] = blk["mlp"]["fc_in"]["kernel"].T
            out[b + "mlp.c_fc.bias"] = blk["mlp"]["fc_in"]["bias"]
            out[b + "mlp.c_proj.weight"] = blk["mlp"]["fc_out"]["kernel"].T
            out[b + "mlp.c_proj.bias"] = blk["mlp"]["fc_out"]["bias"]
        out["transformer.ln_f.weight"] = A(params["ln_f"]["scale"])
        out["transformer.ln_f.bias"] = A(params["ln_f"]["bias"])
        out["lm_head.weight"] = out["transformer.wte.weight"]
        return out

    if model_type == "gpt_neo":
        out["transformer.wte.weight"] = A(params["embed"]["wte"])
        out["transformer.wpe.weight"] = A(params["embed"]["wpe"])
        for i in range(cfg.n_layer):
            b = f"transformer.h.{i}."
            blk = {k: A_tree(v, i) for k, v in blocks.items()}
            out[b + "ln_1.weight"] = blk["ln_1"]["scale"]
            out[b + "ln_1.bias"] = blk["ln_1"]["bias"]
            for ours, theirs in (("q", "q_proj"), ("k", "k_proj"), ("v", "v_proj")):
                out[b + f"attn.attention.{theirs}.weight"] = blk["attn"][ours]["kernel"].reshape(E, H * D).T
            out[b + "attn.attention.out_proj.weight"] = blk["attn"]["o"]["kernel"].reshape(H * D, E).T
            out[b + "attn.attention.out_proj.bias"] = blk["attn"]["o"]["bias"]
            out[b + "ln_2.weight"] = blk["ln_2"]["scale"]
            out[b + "ln_2.bias"] = blk["ln_2"]["bias"]
            out[b + "mlp.c_fc.weight"] = blk["mlp"]["fc_in"]["kernel"].T
            out[b + "mlp.c_fc.bias"] = blk["mlp"]["fc_in"]["bias"]
            out[b + "mlp.c_proj.weight"] = blk["mlp"]["fc_out"]["kernel"].T
            out[b + "mlp.c_proj.bias"] = blk["mlp"]["fc_out"]["bias"]
        out["transformer.ln_f.weight"] = A(params["ln_f"]["scale"])
        out["transformer.ln_f.bias"] = A(params["ln_f"]["bias"])
        out["lm_head.weight"] = out["transformer.wte.weight"]
        return out

    raise ValueError(f"export not implemented for {model_type!r}")


def A_tree(tree, i: int):
    """Select layer i from a stacked subtree, as float32 numpy."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: np.asarray(x[i], dtype=np.float32), tree
    )
