"""The plain reference against the system's decoder at a toy NeoX config, and
the tolerance's power to tell a lower precision from the stated one."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import neox_ref
from trlx_tpu.models.transformer import TransformerConfig, TransformerLM

HF = dict(hidden_size=64, num_hidden_layers=3, num_attention_heads=4, intermediate_size=256,
          hidden_act="gelu", rotary_pct=0.25, rotary_emb_base=10000, use_parallel_residual=True,
          layer_norm_eps=1e-5, vocab_size=300, tie_word_embeddings=False)


def system(dtype):
    cfg = TransformerConfig(dtype=dtype, param_dtype=jnp.float32, n_positions=64,
                            **neox_ref.system_config(HF))
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    # seeded norms away from (1, 0), so that a norm applied wrongly shows
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    ln = params["blocks"]["ln_1"]
    ln["scale"] = 1.0 + 0.1 * jax.random.normal(k1, ln["scale"].shape)
    ln["bias"] = 0.1 * jax.random.normal(k2, ln["bias"].shape)
    return lm, params


def inputs(left_pad=0):
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, HF["vocab_size"])
    mask = jnp.ones((2, 32), jnp.int32).at[0, :left_pad].set(0)
    return tokens, mask


@pytest.mark.parametrize("left_pad", [0, 5])
def test_reference_matches_the_system_in_float32(left_pad):
    lm, params = system(jnp.float32)
    tokens, mask = inputs(left_pad)
    with jax.default_matmul_precision("highest"):
        want = lm(params, tokens, mask)["logits"]
    p = neox_ref.params_from_system(params)
    got = neox_ref.logits(p, neox_ref.hidden_states(p, HF, tokens, mask))
    real = np.asarray(mask, bool)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real], atol=2e-4)


def test_sequential_residual_is_the_other_published_layout():
    hf = dict(HF, use_parallel_residual=False)
    cfg = TransformerConfig(dtype=jnp.float32, param_dtype=jnp.float32, n_positions=64,
                            **neox_ref.system_config(hf))
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    tokens, mask = inputs()
    with jax.default_matmul_precision("highest"):
        want = lm(params, tokens, mask)["logits"]
    p = neox_ref.params_from_system(params)
    got = neox_ref.logits(p, neox_ref.hidden_states(p, hf, tokens, mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_int8_weights_are_told_from_bf16():
    """The recipe states bf16 compute on float32 masters. The same forward
    on weights rounded to int8, one scale per output channel (the rollout
    policy's precision), is several times further from the reference: the
    margin the chip tolerance rests on."""
    lm, params = system(jnp.bfloat16)
    tokens, mask = inputs()
    p = neox_ref.params_from_system(params)
    want = jax.nn.log_softmax(neox_ref.logits(p, neox_ref.hidden_states(p, HF, tokens, mask)))

    def to_int8_and_back(w):
        if w.ndim < 2:
            return w
        scale = jnp.maximum(jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True), 1e-12) / 127.0
        return jnp.round(w / scale) * scale

    def rms(param_tree):
        got = jax.nn.log_softmax(lm(param_tree, tokens, mask)["logits"].astype(jnp.float32))
        return float(jnp.sqrt(jnp.mean((got - want) ** 2)))

    bf16, int8 = rms(params), rms(jax.tree_util.tree_map(to_int8_and_back, params))
    # the chip tolerance is 1.6 times the bf16 error measured there
    assert int8 > 2.0 * bf16, (bf16, int8)


@pytest.mark.parametrize("name", ["pythia-1.4b", "pythia-6.9b"])
def test_config_files_give_the_published_parameter_counts(name):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "configs", name + ".json")) as f:
        hf = json.load(f)
    published = {"pythia-1.4b": 1_414_647_808, "pythia-6.9b": 6_857_302_016}[name]
    e, i, v = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    layers = hf["reduced"]["num_hidden_layers"]["published"]
    block = 4 * e * e + 4 * e + 2 * e * i + i + e + 4 * e  # qkvo, MLP, two norms
    assert 2 * v * e + layers * block + 2 * e == published
    assert e // hf["num_attention_heads"] == 128
