"""Gated short-convolution layers beside grouped-query softmax attention with per-head
QK norms, a leading dense layer and sigmoid-routed SwiGLU experts: the program against
the plain reference (`benchmark/reference/lfm2_ref.py`, which imports nothing of the
program) at toy sizes from its `toy_sizes`, on seeded random weights.

Tolerances: compute is float32 on the CPU here, the reference float32 at the highest
matmul precision; what differs is the order of reductions: 2e-4 on logits of order 3
and 1e-3 relative on gradients.

One toy stack serves every parity test: a leading dense conv layer, then attention,
conv, conv, attention, conv, conv, so that the top-2 branch point leaves two conv +
routed layers trainable above both attention layers, as at full size. It is built,
run forward and differentiated ONCE, jitted (`world`, module scope), beside the
reference's forward and `jax.grad`. Sequences are 40 long with row 0 left-padded."""

import json
import os
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_ref as ref
from trlx_tpu.models.generation import SamplerSettings, generate, state_bytes_per_step
from trlx_tpu.models.transformer import (
    Attention,
    RoutedMLP,
    TransformerConfig,
    TransformerLM,
    layer_stacks,
    quantize_decode_weights,
)
from trlx_tpu.models.wrappers import CausalLMWithValueHead

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "lfm2-24b-a2b.json")) as _f:
    PUBLISHED = json.load(_f)
LOGIT_TOL, GRAD_RTOL = 2e-4, 1e-3
LAYERS, SEQ = 7, 40


def toy(**over):
    hf = dict(PUBLISHED, **ref.toy_sizes(PUBLISHED))
    hf.update(correct={"routing_margin": 1e-4})
    hf.update(over)
    return hf


def liven(params):
    """Seeded values for what initialises to constants (norm scales, the QK norms, the
    selection bias) and larger weights, so that every term of every equation carries
    signal. The taps keep their own seeded start."""
    def one(path, x):
        name = path[-1].key
        key = jax.random.fold_in(jax.random.PRNGKey(7), zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31))
        if name == "router_bias":
            return 0.3 * jax.random.normal(key, x.shape)
        if name in ("scale", "q_norm", "k_norm"):
            return 1.0 + 0.2 * jax.random.normal(key, x.shape)
        if name == "conv_w":
            return x
        return 5.0 * x

    return jax.tree_util.tree_map_with_path(one, params)


def batch(rows=2, seq=SEQ, vocab=512, pad=5):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    return ids, jnp.ones((rows, seq), jnp.int32).at[0, :pad].set(0)  # row 0 left-padded


def top2(tree):
    """What `num_layers_unfrozen` 2 trains: the last two rows of `conv_blocks` (layers 5
    and 6 of the toy's 7), the final norm; the head is the tied embedding's."""
    last = lambda name: jax.tree_util.tree_map(lambda x: x[-2:], tree[name])
    return {"conv_blocks": last("conv_blocks"), "ln_f": tree["ln_f"]}


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
        convs = jax.tree_util.tree_map(
            lambda low, top: jnp.concatenate([low[:-2], top]), base["conv_blocks"], trained["conv_blocks"])
        p = ref.params_from_system(dict(base, ln_f=trained["ln_f"], conv_blocks=convs))
        hidden, decisive = ref.hidden_states(p, hf, ids, mask)
        logits = ref.logits(p, hidden)
        return jnp.sum(logits * cotangent), (logits, decisive)

    (_, out), got = jax.jit(jax.value_and_grad(system, has_aux=True))(params["base"])
    (_, (want_logits, decisive)), want = jax.jit(jax.value_and_grad(plain, has_aux=True))(
        top2(params["base"]), params["base"])
    return SimpleNamespace(hf=hf, cfg=cfg, model=model, lm=model.lm, params=params, base=params["base"],
                           ids=ids, mask=mask, out=out, grads=got, want_logits=want_logits,
                           want_grads=want, decisive=decisive)


def test_the_stack_keeps_conv_and_attention_layers_in_stacks_of_their_own(world):
    assert world.cfg.mixers == ("conv", "softmax", "conv", "conv", "softmax", "conv", "conv")
    assert world.cfg.ffns == ("dense",) + ("routed",) * 6
    assert layer_stacks(world.cfg) == (
        ("dense_blocks", 0), ("blocks", 0), ("conv_blocks", 0), ("conv_blocks", 1), ("blocks", 1),
        ("conv_blocks", 2), ("conv_blocks", 3))
    base = world.base
    assert set(base["dense_blocks"]) == {"ln_1", "ln_2", "conv", "mlp"}
    assert set(base["conv_blocks"]) == {"ln_1", "ln_2", "conv", "moe"}
    assert set(base["blocks"]) == {"ln_1", "ln_2", "attn", "moe"}
    assert set(base["conv_blocks"]["conv"]) == {"in_proj", "out_proj", "conv_w"}
    assert base["conv_blocks"]["conv"]["in_proj"]["kernel"].shape == (4, 64, 3 * 64)
    assert base["conv_blocks"]["conv"]["conv_w"].shape == (4, 3, 64)
    assert base["blocks"]["attn"]["q_norm"].shape == base["blocks"]["attn"]["k_norm"].shape == (2, 16)
    assert "shared" not in base["conv_blocks"]["moe"] and "lm_head" not in base  # no shared expert; tied head
    # the published cut: a leading conv layer, then three periods of attention, conv, conv, conv
    published = TransformerConfig(**dict(ref.system_config(PUBLISHED), n_positions=1024))
    assert [name for name, _ in layer_stacks(published)] == ["dense_blocks"] + ["blocks"] + (
        ["conv_blocks"] * 3 + ["blocks"]) * 2 + ["conv_blocks"] * 3
    assert published.mixers.count("conv") == 10 and published.cache_layers == 3
    assert published.head_dim == 64 and (published.n_head, published.n_kv_head) == (32, 8)
    assert published.cache_elems_per_position == 2 * 8 * 64
    assert published.state_elems_per_row == 10 * 2 * 2048  # the tails: 10 layers x 2 inputs x d
    assert (published.n_routed_experts, published.n_experts_held, published.n_experts_per_token) == (64, 8, 4)
    assert published.qk_norm and published.conv_taps == 3


def test_scorer_logits_match_the_reference(world):
    """The teacher-forced forward (convolutions in five layers, QK-normed grouped
    attention in two, experts in six) through the hydra capture against the reference,
    on real positions of a left-padded and an unpadded row."""
    real = np.asarray(world.mask) > 0
    assert np.abs(np.asarray(world.want_logits))[real].max() > 1.0
    assert np.abs(np.asarray(world.out["logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert float(world.decisive.mean()) > 0.9  # the margin 1e-4 leaves ties to chance alone


def test_a_tie_reaches_the_next_two_positions_through_a_conv_layer(world):
    """The reference's decisive rule: with every routing a near tie at the neighbour
    margin, the conv layer after the first routed layer leaves only the first position
    of each row decisive (nothing lies one or two back of it); the hidden states do not
    depend on the rule."""
    hf = toy(correct={"routing_margin": 0.0, "neighbour_margin": 10.0})
    p = ref.params_from_system(world.base)
    hidden, decisive = jax.jit(lambda p: ref.hidden_states(p, hf, world.ids, world.mask))(p)
    decisive = np.asarray(decisive)
    assert decisive[:, 0].all() and not decisive[:, 1:].any()
    logits = jax.jit(ref.logits)(p, hidden)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(world.want_logits), atol=1e-4)


def test_trainable_gradients_match_the_reference_and_the_backward_stops_at_the_branch_point(world):
    """`frozen_below` at the top-2 branch point: two conv + routed layers, the final
    norm and the head against the reference's `jax.grad`; below the branch point the
    backward never ran."""
    grads = jax.tree_util.tree_map(np.asarray, world.grads)
    assert_gradients_close(top2(grads), world.want_grads)
    below = jax.tree_util.tree_map(lambda x: x[:-2], grads["conv_blocks"])
    frozen = [below, grads["blocks"], grads["dense_blocks"]]
    assert all(np.abs(x).max() == 0.0 for x in jax.tree_util.tree_leaves(frozen))
    moved = [np.abs(x).max() for x in jax.tree_util.tree_leaves(top2(grads)["conv_blocks"]["conv"])]
    assert min(moved) > 0.0  # W_in, W_out and the taps of both trainable layers


def _decoder(lm, base):
    """Prefill of all but 8 tokens (row 0 left-padded), then 8 single-token steps: the
    prefill's last two products of each conv layer handed to the one-step form, the
    attention layers' k and v rows beside them. Returns `run(ids, mask)`: the logits
    of each step and the cache at the end."""

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


@pytest.fixture(scope="module")
def decoded(world):
    run = _decoder(world.lm, world.base)
    return SimpleNamespace(P=SEQ - 8, run=run, result=run(world.ids, world.mask))


def test_prefill_then_decode_steps_through_conv_tails_and_kv_rows_match_the_full_forward(world, decoded):
    hf, lm = world.hf, world.lm
    cache = lm.init_cache(2, SEQ, world.mask)
    d, kv, hd = hf["hidden_size"], hf["num_key_value_heads"], ref.head_dim(hf)
    # each stack its own arrays: the leading layer's tail, the four conv + routed layers'
    # tails (two inputs of d a row) and the two attention layers' per-head k and v rows
    assert cache["conv_u_lead"].shape == (1, 2, 2, d) and cache["conv_u"].shape == (4, 2, 2, d)
    assert cache["k"].shape == cache["v"].shape == (2, 2, SEQ, kv, hd)
    assert set(cache) == {"conv_u", "conv_u_lead", "k", "v", "index", "static_index", "key_mask"}
    got, cache = decoded.result
    P0 = decoded.P
    assert float(jnp.abs(got - world.want_logits[:, P0 - 1 : SEQ - 1]).max()) < LOGIT_TOL
    assert int(cache["index"]) == SEQ - 1
    assert float(jnp.abs(cache["conv_u"]).min(axis=(1, 2, 3)).max()) >= 0 and float(jnp.abs(cache["conv_u"]).max()) > 0
    assert float(jnp.abs(cache["conv_u_lead"]).max()) > 0 and float(jnp.abs(cache["k"][1, :, SEQ - 2]).max()) > 0


def test_a_left_padded_row_decodes_as_the_same_row_unpadded(world, decoded):
    """Row 0 has 5 pad slots in front: its inputs are zeroed there, so its convolution
    window is empty until its first token, and its logits are those of the 35 real
    tokens run alone, through prefill and through the decode steps."""
    pad = 5
    alone = world.ids[:1, pad:]
    want, cache = decoded.run(alone, jnp.ones_like(alone))
    got, padded = decoded.result
    assert float(jnp.abs(got[:1] - want).max()) < LOGIT_TOL
    np.testing.assert_allclose(np.asarray(padded["conv_u"][:, :1]), np.asarray(cache["conv_u"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(padded["conv_u_lead"][:, :1]), np.asarray(cache["conv_u_lead"]),
                               atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_qk_normed_grouped_attention_and_its_gradients_match_the_reference(impl):
    """One attention layer at head 64 with 8 query heads over 2 key-value heads (the
    published 4 : 1), QK norms and full rotary: the XLA branch and the flash kernels
    (interpreted here) against the reference's attention, forward and gradients."""
    hf = toy(hidden_size=512, num_attention_heads=8, num_key_value_heads=2)
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32,
                                   attention_impl=impl))
    assert cfg.head_dim == 64
    T = 32
    x = jax.random.normal(jax.random.PRNGKey(4), (2, T, 512))
    mask = jnp.ones((2, T), jnp.int32).at[0, :3].set(0)
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    lm = TransformerLM(cfg)
    bias = lm._build_bias(mask, jnp.arange(T), jnp.arange(T))[0]
    attn = Attention(cfg)
    p = attn.init(jax.random.PRNGKey(5), x, bias, positions)["params"]
    p = dict(jax.tree_util.tree_map(lambda a: 3.0 * a, p),
             q_norm=1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(6), (64,)),
             k_norm=1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(7), (64,)))
    real = mask[..., None].astype(jnp.float32)
    cot = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def system(p, x):
        return jnp.sum(attn.apply({"params": p}, x, bias, positions, key_mask=mask)[0] * cot * real)

    def plain(p, x):
        w = {"w_q": p["q"]["kernel"], "w_k": p["k"]["kernel"], "w_v": p["v"]["kernel"], "w_o": p["o"]["kernel"],
             "q_norm": p["q_norm"], "k_norm": p["k_norm"]}
        T = x.shape[1]
        visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref.attention(x, w, hf, positions, visible) * cot * real)

    got, got_grads = jax.jit(jax.value_and_grad(system, (0, 1)))(p, x)
    want, want_grads = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, x)
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want)) + 1e-3
    assert_gradients_close(got_grads, want_grads)
    assert float(jnp.abs(got_grads[0]["q_norm"]).max()) > 0 and float(jnp.abs(got_grads[0]["k_norm"]).max()) > 0


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed layer with 8 of 64 experts held, top-4, on
    each of the 8 chips of the deployment, against the reference's uncut layer (all 64
    held). The reference adds the published 1e-6 to the renormalising sum, the program
    nothing: under 1e-6 of a routing weight."""
    hf = toy(num_experts_published=64, num_experts=8, num_experts_per_tok=4)
    kw = dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32)
    whole_hf = dict(hf, num_experts=64, first_expert_held=0)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 16, hf["hidden_size"]))
    whole = RoutedMLP(TransformerConfig(**dict(kw, n_experts_held=64)))
    p = jax.jit(lambda k: whole.init(k, x)["params"])(key)
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, p)
    p["router_bias"] = 0.3 * jax.random.normal(key, p["router_bias"].shape)
    experts = ("experts_fc_in", "experts_fc_gate", "experts_fc_out")
    names = {"w_r": p["router_gate"], "b": p["router_bias"], "w_1": p["experts_fc_in"]["kernel"],
             "w_3": p["experts_fc_gate"]["kernel"], "w_2": p["experts_fc_out"]["kernel"]}

    @jax.jit
    def uncut(names):
        with jax.default_matmul_precision("highest"):
            return ref.experts(x, names, whole_hf)[0]

    def chip(first):
        cfg = TransformerConfig(**dict(kw, n_experts_held=8, first_expert_held=first))

        def apply(p):
            mine = dict(p, **{k: {"kernel": p[k]["kernel"][first : first + 8]} for k in experts})
            return RoutedMLP(cfg).apply({"params": mine}, x)
        return jax.jit(apply)

    want = uncut(names)
    total, pairs = jnp.zeros_like(want), 0.0
    for index in range(8):
        y, stats = chip(8 * index)(p)
        total = total + y
        pairs += float(stats["load"].sum())
    assert float(jnp.abs(want).max()) > 0.05  # the experts' part is there
    assert float(jnp.abs(total - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert pairs == 2 * 16 * 4  # every assignment computed on exactly one chip
    # a chip's part is the reference's held part
    held_hf = dict(hf, first_expert_held=16)
    mine = dict(names, **{k: names[k][16:24] for k in ("w_1", "w_2", "w_3")})
    with jax.default_matmul_precision("highest"):
        ref_part = jax.jit(lambda x, mine: ref.experts(x, mine, held_hf)[0])(x, mine)
    assert float(jnp.abs(chip(16)(p)[0] - ref_part).max()) < 1e-4 * float(jnp.abs(want).max())


def test_trunk_constants_resume_at_the_branch_point_over_conv_and_attention_segments(world):
    """What the fused block holds of the trunk: the capture entering layer 5 (after the dense
    conv layer, attention, conv, conv, attention), and the forward resumed there gives
    the uncut forward's logits; the reference branch holds the top two conv layers."""
    lm, ids, mask = world.lm, world.ids, world.mask
    assert lm.resume_point((5,), 5) == 5

    @jax.jit
    def both(base):
        trunk = lm.trunk_constants(base, ids, mask, (5,), 5)
        resumed = lm.forward_with_multi_capture(base, ids, mask, (5,), frozen_below=5, trunk=trunk)
        whole = lm.forward_with_multi_capture(base, ids, mask, (5,), frozen_below=5)
        return trunk, resumed, whole

    (captures, counters), resumed, whole = both(world.base)
    assert len(captures) == 1 and counters["load"].shape == (4, world.hf["num_experts"])
    real = np.asarray(mask) > 0
    assert np.abs(np.asarray(resumed["logits"] - whole["logits"]))[real].max() < 1e-5
    assert np.abs(np.asarray(world.out["ref_logits"] - world.want_logits))[real].max() < LOGIT_TOL
    ref_params = world.model.make_ref_params(world.params)
    rows = lambda tree, name: jax.tree_util.tree_leaves(tree[name])[0].shape[0]
    assert [rows(ref_params, n) for n in ("conv_blocks", "blocks")] == [2, 0] and "dense_blocks" not in ref_params


def test_int8_rollout_weights_cover_the_conv_projections_attention_and_the_experts(world):
    hf, lm, params, ids, mask = world.hf, world.lm, world.base, world.ids, world.mask
    q = jax.jit(quantize_decode_weights)(params)
    for stack in ("dense_blocks", "conv_blocks"):
        conv = q[stack]["conv"]
        assert conv["in_proj"]["kernel"].dtype == conv["out_proj"]["kernel"].dtype == jnp.int8, stack
        assert conv["conv_w"].dtype == jnp.float32
    attn = q["blocks"]["attn"]
    for name in ("q", "k", "v", "o"):
        assert attn[name]["kernel"].dtype == jnp.int8, name
    assert attn["q_norm"].dtype == attn["k_norm"].dtype == jnp.float32
    assert q["conv_blocks"]["moe"]["experts_fc_gate"]["kernel"].dtype == jnp.int8
    full = world.out["logits"]
    quant = jax.jit(lambda q: lm(q, ids, mask)["logits"])(q)
    err = float(jnp.sqrt(jnp.mean((full - quant) ** 2)) / jnp.sqrt(jnp.mean(full**2)))
    assert 0 < err < 0.1
    lm8 = TransformerLM(lm.cfg.replace(decode_weights_quant="int8"))
    g = jax.jit(lambda p: generate(lm8, p, ids[:, :8], jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(2),
                                   SamplerSettings(max_new_tokens=4)))(params)
    assert g["sequences"].shape == (2, 12)
    made = 2 * (8 + 3) * hf["num_experts_per_tok"] * 6  # prefill and three steps, six routed layers
    assert float(g["moe_stats"]["moe/assignments.sampler"]) == made


@pytest.mark.parametrize("bad, match", [
    (dict(kv_cache_quant="int8"), "short-convolution"),
    (dict(conv_taps=1), "conv_taps"),
    (dict(mixer_layers=("conv", "softmax", "conv", "delta", "softmax", "conv", "conv"),
          delta_heads=2, delta_head_dim=16), "short-convolution layers beside"),
    (dict(mixer_layers=("conv", "softmax", "conv", "ssm", "softmax", "conv", "conv"), ssm_heads=2,
          ssm_head_dim=16, ssm_state=16), "short-convolution layers beside"),
    (dict(ffn_layers=("dense", "routed", "none", "routed", "routed", "routed", "routed")),
     "short-convolution layer without a feed-forward"),
    (dict(first_k_dense=2), "leading dense layers of more than one mixer"),
    (dict(mixer_layers=("conv",) * 6), "mixer_layers"),
    (dict(mixer_layers=("conv", "softmax", "conv", "mamba", "softmax", "conv", "conv")), "mixer_layers"),
    (dict(attention_impl="ring"), "ring"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, n_kv_head=4, mixer_layers=None),
     "qk_norm"),
])
def test_what_the_family_does_not_reach_raises_at_configuration_time(bad, match):
    kw = dict(ref.system_config(toy()), n_positions=64)
    with pytest.raises((NotImplementedError, ValueError), match=match):
        TransformerConfig(**dict(kw, **bad))


@pytest.mark.parametrize("over", [
    dict(),  # the toy as it is
    dict(first_k_dense=0),  # conv + routed from the first layer
    dict(n_routed_experts=0, n_experts_held=None, n_experts_per_token=0, first_k_dense=0),  # dense throughout
    dict(mixer_layers=("conv",) * 7),  # no attention layer at all
])
def test_conv_beside_softmax_attention_is_accepted(over):
    kw = dict(ref.system_config(toy()), n_positions=64)
    cfg = TransformerConfig(**dict(kw, **over))
    assert cfg.hybrid and cfg.beyond_dense and "conv" in cfg.mixers
    assert cfg.state_elems_per_row == cfg.mixers.count("conv") * 2 * 64


def test_adapters_paged_engine_and_loader_raise_for_the_family(world):
    from trlx_tpu.models.hf import config_from_hf

    lm, params, ids = TransformerLM(world.cfg), world.base, world.ids
    with pytest.raises(NotImplementedError, match="prompt and prefix adapters"):
        jax.eval_shape(lambda p: lm(p, ids, prefix_embeds=jnp.zeros((2, lm.cfg.hidden_size))), params)
    with pytest.raises(NotImplementedError, match="paged decode engine"):
        lm._scan_segment(params["conv_blocks"], None, None, None, cache={"pk": None}, stack="conv_blocks")
    with pytest.raises(NotImplementedError, match="no loader for model_type .lfm2_moe"):
        config_from_hf(SimpleNamespace(model_type="lfm2_moe", conv_L_cache=3))


def test_the_memory_plan_counts_the_tails_beside_the_cache(world):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.utils.memdoctor import analytic_param_count, analytic_plan

    kw = dict(ref.system_config(PUBLISHED), n_positions=1024)
    assert analytic_param_count(kw) == ref.params_held(PUBLISHED)["total"] == 1_196_018_816
    assert analytic_param_count(dict(ref.system_config(world.hf), n_positions=128)) == sum(
        x.size for x in jax.tree_util.tree_leaves(world.base))
    config = default_ppo_config().evolve(
        train=dict(seq_length=1024, batch_size=8, remat_policy="full"),
        model=dict(model_path="random", model_extra_configs={"transformer": kw}),
        method=dict(chunk_size=32, num_rollouts=32))
    plan = analytic_plan(config, hbm_bytes=16 * 2**30)
    cache = [i for i in plan.items if i.component == "static_kv_cache"][0]
    assert cache.bytes == 3 * 32 * 1024 * 2 * 8 * 64 * 2 and "3 of 13 layers" in cache.note
    tails = [i for i in plan.items if i.component == "recurrent_state"][0]
    assert tails.bytes == 10 * 32 * 2 * 2 * 2048 and "10 short-convolution" in tails.note
    # a decode step reads and writes them once: 5.2 MB at 32 rows
    assert state_bytes_per_step(TransformerConfig(**kw), 32) == 2 * tails.bytes == 5_242_880


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import TPUPPOTrainer

    eager_init = TransformerLM.init
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(TransformerLM, "init", lambda self, key: jax.jit(lambda k: eager_init(self, k))(key))
        hf = toy()
        config = default_ppo_config().evolve(
            train=dict(batch_size=8, total_steps=1, seq_length=16, epochs=1, tracker=None,
                       checkpoint_dir=str(tmp_path_factory.mktemp("ckpt")), compute_dtype="float32"),
            model=dict(model_path="random", num_layers_unfrozen=2,
                       model_extra_configs={"transformer": ref.system_config(hf)}),
            tokenizer=dict(tokenizer_path="byte", tokenizer_extra_configs=dict(vocab_size=hf["vocab_size"])),
            method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                        gen_kwargs=dict(max_new_tokens=4, do_sample=True, eos_token_id=-1)))
        yield SimpleNamespace(hf=hf, trainer=TPUPPOTrainer(config, reward_fn=lambda **kw: [0.0] * 8))


def test_the_freeze_mask_trains_the_top_two_conv_layers(trainer):
    trainer = trainer.trainer
    mask = trainer.make_freeze_mask(trainer.params)["base"]
    assert np.asarray(mask["conv_blocks"]["conv"]["in_proj"]["kernel"]).ravel().tolist() == [0.0, 0.0, 1.0, 1.0]
    assert np.asarray(mask["conv_blocks"]["conv"]["conv_w"]).ravel().tolist() == [0.0, 0.0, 1.0, 1.0]
    assert np.asarray(mask["conv_blocks"]["moe"]["experts_fc_gate"]["kernel"]).ravel().tolist() == [
        0.0, 0.0, 1.0, 1.0]
    assert np.asarray(mask["blocks"]["attn"]["q_norm"]).max() == 0.0  # both attention layers lie below
    assert np.asarray(mask["dense_blocks"]["conv"]["out_proj"]["kernel"]).max() == 0.0
    assert float(mask["conv_blocks"]["moe"]["router_gate"]) == 0.0  # a share trains no router
    assert trainer.model.frozen_below() == 5


def test_gauges_and_the_tail_count_reach_the_flight_stream(trainer):
    """`model/conv_layers`, `model/cache_layers`, `model/routed_layers` and
    `model/state_elems_per_row` once per built train step; `state_bytes_carried` on the
    `tokens_wait` span: 3 decode steps of 8 rows through 5 conv layers; no gauge of a
    state-step kernel, which these layers do not take."""
    from trlx_tpu.obs.recorder import iter_rows

    hf, trainer = trainer.hf, trainer.trainer
    d = hf["hidden_size"]
    gauges = []
    real_gauge = trainer.obs.gauge
    trainer.obs.gauge = lambda **kw: gauges.append(kw)
    try:
        trainer._note_backward_depth()
    finally:
        trainer.obs.gauge = real_gauge
    g = gauges[0]
    assert (g["model/conv_layers"], g["model/cache_layers"], g["model/routed_layers"]) == (5, 2, 6)
    assert g["model/state_elems_per_row"] == 5 * 2 * d and g["model/layers"] == 7
    assert g["model/backward_layers"] == 2 and "model/ssm_layers" not in g and "model/delta_layers" not in g
    trainer.obs.start(step=0)
    out = trainer.generate(np.ones((8, 12), np.int32))
    trainer._pull_sampled_tokens(out, 8, {})
    trainer.obs.end_cycle(step=0)
    rows = list(iter_rows(os.path.join(trainer.config.train.checkpoint_dir, "flight")))
    assert not [r for r in rows if "gen/state_step_fused" in r]
    (cycle,) = [r for r in rows if r["kind"] == "cycle"]
    (counts,) = [c for name, *_, c in cycle["spans"] if name == "tokens_wait"]
    assert counts["state_bytes_carried"] == 3 * 2 * 8 * 5 * 2 * 4 * d  # float32 compute here
