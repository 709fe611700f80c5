"""Gated DeltaNet layers (the delta rule with a scalar decay a head and grouped value
heads) beside output-gated grouped-query attention, zero-centred norms and softmax-routed
SwiGLU experts with a gated shared expert: the program against the plain reference
(`benchmark/reference/qwen3_next_ref.py`, which imports nothing of the program) at toy
sizes from its `toy_sizes`, on seeded random weights.

Tolerances: compute is float32 on the CPU here, the reference float32 at the highest
matmul precision; what differs is the order of reductions (and, in the chunked form,
the inverse of a unit lower-triangular matrix against a recurrence): 2e-4 on logits
of order 3, 1e-3 relative on gradients, 1e-5 on recurrences of outputs of order 1.

One toy stack serves the parity tests: GDN, attention, GDN, attention, so that the
top-2 branch point leaves one layer of each mixer trainable, as at full size (layers 7
and 8 of 8). It is built, run forward and differentiated ONCE, jitted (`world`, module
scope), beside the reference's forward and `jax.grad`. Sequences are 40 long with row
0 left-padded; the chunked form over several chunks is tested on its own at 150."""

import json
import os
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next_ref as ref
from trlx_tpu.models.generation import state_bytes_per_step
from trlx_tpu.models.transformer import (
    GDN_CHUNK,
    Attention,
    RoutedMLP,
    TransformerConfig,
    TransformerLM,
    balance_router_gates,
    kda_chunked,
    kda_step,
    layer_stacks,
    quantize_decode_weights,
)
from trlx_tpu.models.wrappers import CausalLMWithValueHead

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as _f:
    PUBLISHED = json.load(_f)
LOGIT_TOL, GRAD_RTOL, STATE_TOL = 2e-4, 1e-3, 1e-5
LAYERS, SEQ = 4, 40


def toy(**over):
    hf = dict(PUBLISHED, **ref.toy_sizes(PUBLISHED))
    hf.update(correct={"routing_margin": 1e-4})
    hf.update(over)
    return hf


def liven(params):
    """Seeded values for what initialises to constants (the zero-centred norms' w, the
    output norm's weight) and larger weights, so that every term of every equation
    carries signal. The taps, A_log and dt_bias keep their own seeded start."""
    def one(path, x):
        name = path[-1].key
        key = jax.random.fold_in(jax.random.PRNGKey(7), zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31))
        if name in ("scale", "q_norm", "k_norm"):  # w of (1 + w)
            return 0.2 * jax.random.normal(key, x.shape)
        if name == "o_norm":
            return 1.0 + 0.2 * jax.random.normal(key, x.shape)
        if name in ("conv", "A_log", "dt_bias"):
            return x
        return 5.0 * x

    return jax.tree_util.tree_map_with_path(one, params)


def batch(rows=2, seq=SEQ, vocab=512, pad=5):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    return ids, jnp.ones((rows, seq), jnp.int32).at[0, :pad].set(0)  # row 0 left-padded


def top2(tree):
    """What `num_layers_unfrozen` 2 trains: the last row of `delta_blocks` (layer 2 of the
    toy's 4) and of `blocks` (layer 3), the final norm and the untied head."""
    last = lambda name: jax.tree_util.tree_map(lambda x: x[-1:], tree[name])
    return {"delta_blocks": last("delta_blocks"), "blocks": last("blocks"), "ln_f": tree["ln_f"],
            "lm_head": tree["lm_head"]}


def assert_gradients_close(got, want):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.abs(g - w).max() <= GRAD_RTOL * np.abs(w).max() + 1e-6, jax.tree_util.keystr(path)


@pytest.fixture(scope="module")
def world():
    """The toy model under the hydra wrapper (policy branch at the top 2, value branch
    at the top 1), its teacher-forced pass and the gradient of a random linear
    functional of its logits, and the same from the reference: two jitted programs."""
    hf = toy()
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=128, dtype=jnp.float32))
    model = CausalLMWithValueHead(cfg, branch_at=LAYERS - 2, value_branch_at=LAYERS - 1)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    params["base"] = liven(params["base"])
    ref_params = model.make_ref_params(params)
    ids, mask = batch()
    cotangent = jax.random.normal(jax.random.PRNGKey(2), ids.shape + (hf["vocab_size"],)) * mask[..., None]

    def system(base):
        out = model.forward_train(dict(params, base=base), ref_params, ids, mask)
        return jnp.sum(out["logits"] * cotangent), out

    def plain(trained, base):
        # the reference differentiated with respect to the trainable part alone
        whole = dict(base, ln_f=trained["ln_f"], lm_head=trained["lm_head"])
        for name in ("delta_blocks", "blocks"):
            whole[name] = jax.tree_util.tree_map(
                lambda low, top: jnp.concatenate([low[:-1], top]), base[name], trained[name])
        p = ref.params_from_system(whole)
        hidden, decisive = ref.hidden_states(p, hf, ids, mask)
        logits = ref.logits(p, hidden)
        return jnp.sum(logits * cotangent), (logits, decisive)

    (_, out), got = jax.jit(jax.value_and_grad(system, has_aux=True))(params["base"])
    (_, (want_logits, decisive)), want = jax.jit(jax.value_and_grad(plain, has_aux=True))(
        top2(params["base"]), params["base"])
    return SimpleNamespace(hf=hf, cfg=cfg, model=model, lm=model.lm, params=params, base=params["base"],
                           ids=ids, mask=mask, out=out, grads=got, want_logits=want_logits,
                           want_grads=want, decisive=decisive)


def test_the_stack_keeps_gdn_and_attention_layers_in_stacks_of_their_own(world):
    assert world.cfg.mixers == ("delta", "softmax", "delta", "softmax")
    assert world.cfg.ffns == ("routed",) * 4
    assert layer_stacks(world.cfg) == (("delta_blocks", 0), ("blocks", 0), ("delta_blocks", 1), ("blocks", 1))
    base = world.base
    assert set(base["delta_blocks"]["attn"]) == {"qkvz", "ba", "conv", "A_log", "dt_bias", "o_norm", "o"}
    assert base["delta_blocks"]["attn"]["qkvz"]["kernel"].shape == (2, 64, (2 * 2 + 2 * 4) * 16)
    assert base["delta_blocks"]["attn"]["conv"].shape == (2, 4, (2 * 2 + 4) * 16)
    assert base["delta_blocks"]["attn"]["A_log"].shape == base["delta_blocks"]["attn"]["dt_bias"].shape == (2, 4)
    assert base["blocks"]["attn"]["q"]["kernel"].shape == (2, 64, 4, 2 * 16)  # a head's query, then its gate
    moe = base["blocks"]["moe"]
    assert "router_bias" not in moe and moe["shared_gate"]["kernel"].shape == (2, 64, 1)
    assert "lm_head" in base and "dense_blocks" not in base
    # the published cut: two whole periods of GDN, GDN, GDN, attention
    published = TransformerConfig(**dict(ref.system_config(PUBLISHED), n_positions=1024))
    assert [name for name, _ in layer_stacks(published)] == (["delta_blocks"] * 3 + ["blocks"]) * 2
    assert (published.delta_heads, published.delta_value_heads, published.delta_head_dim) == (16, 32, 128)
    assert published.delta_conv_width == 8192 and published.cache_layers == 2
    assert published.head_dim == 256 and published.rotary_dim == 64 and (published.n_head, published.n_kv_head) == (16, 2)
    # a row's state: 6 layers x (32 x 128 x 128 float32 state + 3 x 8,192 convolution inputs)
    assert published.state_elems_per_row == 6 * (32 * 128 * 128 + 3 * 8192)
    # a decode step at 32 rows reads and writes 0.40 GB of state and 9.4 MB of tails
    assert state_bytes_per_step(published, 32) == 2 * 6 * 32 * (4 * 32 * 128 * 128 + 2 * 3 * 8192)
    assert (published.n_routed_experts, published.n_experts_held, published.n_experts_per_token) == (512, 32, 10)


def test_scorer_logits_match_the_reference(world):
    """The teacher-forced forward (the chunked form in two layers, gated attention in
    two, softmax-routed experts with the gated shared expert in four) through the hydra
    capture against the reference, on real positions of a left-padded and an unpadded
    row; the reference branch's logits too."""
    real = np.asarray(world.mask) > 0
    assert np.abs(np.asarray(world.want_logits))[real].max() > 1.0
    assert np.abs(np.asarray(world.out["logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert np.abs(np.asarray(world.out["ref_logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert float(world.decisive.mean()) > 0.9  # the margin 1e-4 leaves ties to chance alone


def test_trainable_gradients_match_the_reference_and_the_backward_stops_at_the_branch_point(world):
    """`frozen_below` at the top-2 branch point: one GDN and one gated-attention layer
    with their experts, the final norm and the head against the reference's
    `jax.grad`; below the branch point the backward never ran."""
    grads = jax.tree_util.tree_map(np.asarray, world.grads)
    assert_gradients_close(top2(grads), world.want_grads)
    below = [jax.tree_util.tree_map(lambda x: x[:-1], grads[name]) for name in ("delta_blocks", "blocks")]
    assert all(np.abs(x).max() == 0.0 for x in jax.tree_util.tree_leaves(below))
    mixer = top2(grads)["delta_blocks"]["attn"]
    # every part of the trainable GDN layer moves: W_qkvz, W_ba, the taps, A_log, dt_bias, the norm, W_out
    assert min(np.abs(x).max() for x in jax.tree_util.tree_leaves(mixer)) > 0.0
    assert np.abs(top2(grads)["blocks"]["moe"]["shared_gate"]["kernel"]).max() > 0.0


def test_the_chunked_form_of_a_scalar_decay_is_the_recurrence_over_grouped_value_heads():
    """Three chunks and a part (150 positions), 4 value heads over 2 key heads
    (repeated), a scalar log-decay a head, row 0 masked for 7 positions (beta 0, g 0):
    the chunked form against the reference's recurrence, and against the per-channel
    form with the scalar broadcast (one rule); the state it leaves is the step's."""
    B, T, H, D = 2, 150, 4, 16
    assert T > 2 * GDN_CHUNK
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = jnp.repeat(unit(jax.random.normal(ks[0], (B, T, 2, D))), 2, axis=2)
    k = jnp.repeat(unit(jax.random.normal(ks[1], (B, T, 2, D))), 2, axis=2)
    v = jax.random.normal(ks[2], (B, T, H, D))
    live = jnp.ones((B, T)).at[0, :7].set(0)[..., None]
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H))) * live
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))) * live
    o, state = jax.jit(kda_chunked)(q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.recurrence)(q, k, v, g, beta)
        per_channel, same = jax.jit(kda_chunked)(q, k, v, jnp.broadcast_to(g[..., None], (B, T, H, D)), beta)
    assert float(jnp.abs(want).max()) > 0.5 and float(jnp.abs(o[0, :7]).max()) == 0.0
    assert float(jnp.abs(o - want).max()) < STATE_TOL
    assert float(jnp.abs(per_channel - want).max()) < STATE_TOL
    assert float(jnp.abs(state - same).max()) < STATE_TOL
    # one more position by the step form, from the chunked state
    step_o, _ = kda_step(q[:, -1], k[:, -1], v[:, -1], jnp.broadcast_to(g[:, -1, :, None], (B, H, D)),
                         beta[:, -1], jnp.zeros_like(state))
    assert step_o.shape == (B, H, D)


def _decoder(lm, base):
    """Prefill of all but 8 tokens (row 0 left-padded), then 8 single-token steps: the
    prefill's state and last three convolution inputs of each GDN layer handed to the
    step form (the state-step kernel, interpreted), the attention layers' k and v rows
    beside them. Returns `run(ids, mask)`: the logits of each step and the cache."""

    @jax.jit
    def prefill(base, ids, mask, cache_mask):
        positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
        cache = lm.init_cache(ids.shape[0], cache_mask.shape[1], cache_mask)
        return lm(base, ids, mask, positions=positions, cache=cache)

    @jax.jit
    def step(base, token, position, cache):
        return lm(base, token, positions=position, cache=cache)

    def run(ids, mask):
        total = ids.shape[1]
        P = total - 8
        positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
        out = prefill(base, ids[:, :P], mask[:, :P], mask)
        got, cache = [out["logits"][:, -1]], out["cache"]
        for t in range(P, total - 1):
            out = step(base, ids[:, t : t + 1], positions[:, t : t + 1], cache)
            got.append(out["logits"][:, 0])
            cache = out["cache"]
        return jnp.stack(got, axis=1), cache

    return run


def test_prefill_then_decode_steps_through_the_state_and_kv_rows_match_the_full_forward(world):
    """The sampler's path: logits (not tokens) of the prefill's last position and of 7
    decode steps against the reference's full forward, for a left-padded and an
    unpadded row; the padded row reads as the same row run alone."""
    hf, lm = world.hf, world.lm
    cache = lm.init_cache(2, SEQ, world.mask)
    assert cache["kda_s"].shape == (2, 2, 4, 16, 16)  # [GDN layers, rows, value heads, d_k, d_v]
    assert cache["kda_u"].shape == (2, 2, 3, (2 * 2 + 4) * 16)  # q, k and v: the one convolution's tail
    assert cache["k"].shape == cache["v"].shape == (2, 2, SEQ, hf["num_key_value_heads"], hf["head_dim"])
    run = _decoder(lm, world.base)
    got, cache = run(world.ids, world.mask)
    P0 = SEQ - 8
    assert float(jnp.abs(got - world.want_logits[:, P0 - 1 : SEQ - 1]).max()) < LOGIT_TOL
    assert int(cache["index"]) == SEQ - 1 and float(jnp.abs(cache["kda_s"]).max()) > 0
    pad = 5
    alone = world.ids[:1, pad:]
    want, unpadded = run(alone, jnp.ones_like(alone))
    assert float(jnp.abs(got[:1] - want).max()) < LOGIT_TOL
    np.testing.assert_allclose(np.asarray(cache["kda_s"][:, :1]), np.asarray(unpadded["kda_s"]), atol=STATE_TOL)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_output_gated_attention_at_head_256_and_its_gradients_match_the_reference(impl):
    """One attention layer as published in shape: 4 query heads over 2 key-value heads
    of 256, zero-centred QK norms, rotary on the first 64 channels, the sigmoid output
    gate: the XLA branch and the flash kernels (interpreted here) against the
    reference's attention, forward and gradients."""
    hf = toy(hidden_size=256, num_attention_heads=4, num_key_value_heads=2, head_dim=256)
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32,
                                   attention_impl=impl))
    assert cfg.rotary_dim == 64
    T = 32
    x = jax.random.normal(jax.random.PRNGKey(4), (2, T, 256))
    mask = jnp.ones((2, T), jnp.int32).at[0, :3].set(0)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    bias = TransformerLM(cfg)._build_bias(mask, jnp.arange(T), jnp.arange(T))[0]
    attn = Attention(cfg)
    p = attn.init(jax.random.PRNGKey(5), x, bias, positions)["params"]
    p = dict(jax.tree_util.tree_map(lambda a: 3.0 * a, p),
             q_norm=0.2 * jax.random.normal(jax.random.PRNGKey(6), (256,)),
             k_norm=0.2 * jax.random.normal(jax.random.PRNGKey(7), (256,)))
    real = mask[..., None].astype(jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def system(p, x):
        return jnp.sum(attn.apply({"params": p}, x, bias, positions, key_mask=mask)[0] * cot * real)

    def plain(p, x):
        w = {"w_q": p["q"]["kernel"], "w_k": p["k"]["kernel"], "w_v": p["v"]["kernel"], "w_o": p["o"]["kernel"],
             "q_norm": p["q_norm"], "k_norm": p["k_norm"]}
        visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.attention(x, w, hf, positions, visible) * cot * real)

    got, got_grads = jax.jit(jax.value_and_grad(system, (0, 1)))(p, x)
    want, want_grads = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, x)
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want)) + 1e-3
    assert_gradients_close(got_grads, want_grads)
    assert float(jnp.abs(got_grads[0]["q"]["kernel"][..., 256:]).max()) > 0  # the gate's columns


def test_sixteen_shares_in_four_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed layer with 4 of 16 experts held, top-3,
    on each of the 4 chips that share it, against the reference's uncut layer (all 16
    held): the routed parts added over the chips, the gated shared expert (what every
    chip computes alike) counted once."""
    hf = toy()
    kw = dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 16, hf["hidden_size"]))
    whole = RoutedMLP(TransformerConfig(**dict(kw, n_experts_held=16)))
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, jax.jit(lambda k: whole.init(k, x)["params"])(key))
    experts = ("experts_fc_in", "experts_fc_gate", "experts_fc_out")
    names = {"w_r": p["router_gate"], "w_1": p["experts_fc_in"]["kernel"], "w_3": p["experts_fc_gate"]["kernel"],
             "w_2": p["experts_fc_out"]["kernel"], "w_sg": p["shared_gate"]["kernel"],
             "shared": {"w_1": p["shared"]["fc_in"]["kernel"], "w_3": p["shared"]["fc_gate"]["kernel"],
                        "w_2": p["shared"]["fc_out"]["kernel"]}}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda n: ref.experts(x, n, dict(hf, num_experts=16))[0])(names)
        shared = jax.jit(lambda n: ref.experts(x, n, dict(hf, num_experts=0))[0])(names)

    def chip(first):
        cfg = TransformerConfig(**dict(kw, n_experts_held=4, first_expert_held=first))
        mine = dict(p, **{k: {"kernel": p[k]["kernel"][first : first + 4]} for k in experts})
        return jax.jit(lambda mine: RoutedMLP(cfg).apply({"params": mine}, x))(mine)

    total, pairs = shared, 0.0
    for index in range(4):
        y, stats = chip(4 * index)
        total = total + (y - shared)
        pairs += float(stats["load"].sum())
    assert float(jnp.abs(want - shared).max()) > 0.05  # the experts' part is there
    assert float(jnp.abs(total - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert pairs == 2 * 16 * 3  # every assignment computed on exactly one chip


def test_the_setup_balance_lowers_the_fullest_expert_over_the_mean(world):
    """`balance_router_gates` on a batch of 8 x 32 tokens: the softmax routers' gates
    alone move, and the fullest expert's choices over the mean fall in every layer."""
    ids = jax.random.randint(jax.random.PRNGKey(5), (8, 32), 0, 512)
    new, (before, after) = balance_router_gates(world.lm, world.base, ids, jnp.ones_like(ids), steps=24)
    before, after = np.asarray(before), np.asarray(after)
    assert before.shape == (4,) and (after < before).all() and after.max() < 1.2 < before.max()
    changed = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new), jax.tree_util.tree_leaves(world.base)) if (a != b).any()]
    assert sorted(changed) == ["['blocks']['moe']['router_gate']", "['delta_blocks']['moe']['router_gate']"]


def test_int8_rollout_weights_cover_the_joint_projection_attention_and_the_experts(world):
    q = jax.jit(quantize_decode_weights)(world.base)
    gdn = q["delta_blocks"]["attn"]
    assert gdn["qkvz"]["kernel"].dtype == gdn["o"]["kernel"].dtype == jnp.int8
    assert gdn["ba"]["kernel"].dtype == gdn["conv"].dtype == jnp.float32 and "kernel_scale" not in gdn["ba"]
    assert q["blocks"]["attn"]["q"]["kernel_scale"].shape == (2, 4, 32)
    assert q["blocks"]["moe"]["router_gate"].dtype == jnp.float32
    assert q["blocks"]["moe"]["experts_fc_in"]["kernel"].dtype == jnp.int8
    full = world.out["logits"]
    quant = jax.jit(lambda q: world.lm(q, world.ids, world.mask)["logits"])(q)
    err = float(jnp.sqrt(jnp.mean((full - quant) ** 2)) / jnp.sqrt(jnp.mean(full**2)))
    assert 0 < err < 0.1


@pytest.mark.parametrize("bad, match", [
    (dict(kv_cache_quant="int8"), "KDA"),
    (dict(attention_impl="ring"), "ring"),
    (dict(delta_value_heads=3), "whole multiple"),
    (dict(delta_form="kda"), "equal to it"),
    (dict(delta_form="mamba"), "delta_form"),
    (dict(router_score="relu"), "router_score"),
    (dict(norm="layernorm"), "norm_zero_centred"),
])
def test_what_the_family_does_not_reach_raises_at_configuration_time(bad, match):
    kw = dict(ref.system_config(toy()), n_positions=64)
    with pytest.raises((NotImplementedError, ValueError), match=match):
        TransformerConfig(**dict(kw, **bad))


def test_the_memory_plan_counts_what_the_published_cut_holds():
    from trlx_tpu.utils.memdoctor import analytic_param_count

    kw = dict(ref.system_config(PUBLISHED), n_positions=1024)
    assert analytic_param_count(kw) == ref.params_held(PUBLISHED)["total"] == 1_173_540_992
    hf = toy()
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=64))
    held = jax.eval_shape(TransformerLM(cfg).init, jax.random.PRNGKey(0))
    assert analytic_param_count(dict(ref.system_config(hf), n_positions=64)) == sum(
        x.size for x in jax.tree_util.tree_leaves(held)) == ref.params_held(hf)["total"]
