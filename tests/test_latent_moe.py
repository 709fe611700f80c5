"""Latent attention with a latent cache, routed and shared experts as one chip's
share, a multi-stream residual path: the program against the plain reference
(`benchmark/reference/xing4_ref.py`, which imports nothing of the program) at toy
sizes from its `toy_sizes`, on seeded random weights.

Tolerances: compute is float32 on the CPU here, the reference float32 at the
highest matmul precision. Differences are reduction order alone: 2e-4 on logits of
order 3 and 1e-3 relative on gradients (20 Sinkhorn steps and a softmax chain
amplify the last bit a few hundred times; a wrong mask, scale, transpose or expert
is an error of order 0.1 to 1).

One toy stack serves every parity test: one leading dense layer, then three routed
ones, so that the cut at the top-2 branch point leaves a dense and a routed layer
frozen under two trainable routed ones. It is built, run forward and differentiated
ONCE, jitted (`world`, module scope), beside the reference's forward and `jax.grad`;
the tests read that one result, each for its own claim. The CPU's time here is
compiling, not computing: nothing runs op by op."""

import json
import math
import os
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4_ref as ref
from trlx_tpu.models.generation import SamplerSettings, generate
from trlx_tpu.models.transformer import (
    LatentAttention,
    RoutedMLP,
    TransformerConfig,
    TransformerLM,
    _join_stats,
    make_attention_bias,
    moe_counters,
    quantize_decode_weights,
    rope_inv_frequencies,
    sinkhorn,
)
from trlx_tpu.models.wrappers import CausalLMWithValueHead

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "xing4.0-29b-a4b.json")) as _f:
    PUBLISHED = json.load(_f)
LOGIT_TOL, GRAD_RTOL = 2e-4, 1e-3
LAYERS, LEAD = 4, 1  # one leading dense layer, then three routed ones


def toy(**over):
    layers, lead = LAYERS, LEAD
    hf = dict(PUBLISHED, **ref.toy_sizes(PUBLISHED))
    # 6 Sinkhorn steps where the published 20 prove nothing more about the parity
    # with the reference: the steps are unrolled and 20 of them, in 14 places and
    # again under jax.grad, are most of what the CPU compiles here (the test of
    # `sinkhorn` itself runs the published 20)
    hf.update(num_hidden_layers=layers, first_k_dense_replace=lead, hc_sinkhorn_iters=6,
              correct={"routing_margin": 1e-4})
    hf.update(over)
    return hf


def liven(params):
    """Seeded values for what initialises to constants (norm scales, the mixing
    scalars and biases, the router's selection bias) and larger weights, so that
    every term of every equation carries signal."""
    def one(path, x):
        name = path[-1].key
        key = jax.random.fold_in(jax.random.PRNGKey(7), zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31))
        if name == "alpha":
            return jax.random.uniform(key, x.shape, minval=0.05, maxval=0.3)
        if name in ("b_pre", "b_post", "router_bias"):
            return 0.3 * jax.random.normal(key, x.shape)
        if name == "b_res":
            return x + 0.5 * jax.random.normal(key, x.shape)
        if name in ("scale", "q_a_norm", "kv_a_norm"):
            return 1.0 + 0.2 * jax.random.normal(key, x.shape)
        return 5.0 * x

    return jax.tree_util.tree_map_with_path(one, params)


def batch(rows=2, seq=32, vocab=512, pad=3):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    return ids, jnp.ones((rows, seq), jnp.int32).at[0, :pad].set(0)  # row 0 left-padded


def top2(tree):
    """What `num_layers_unfrozen` 2 trains: the top two routed layers, the final
    norm and the head."""
    return {"blocks": jax.tree_util.tree_map(lambda x: x[-2:], tree["blocks"]),
            "ln_f": tree["ln_f"], "lm_head": tree["lm_head"]}


def assert_gradients_close(got, want):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.abs(g - w).max() <= GRAD_RTOL * np.abs(w).max() + 1e-6, jax.tree_util.keystr(path)


@pytest.fixture(scope="module")
def world():
    """The toy model under the hydra wrapper (policy branch at the top 2, value
    branch at the top 1), its teacher-forced pass and the gradient of a random
    linear functional of its logits, and the same from the reference: two jitted
    programs, run once."""
    hf = toy()
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32))
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
        base = dict(base, ln_f=trained["ln_f"], lm_head=trained["lm_head"], blocks=jax.tree_util.tree_map(
            lambda low, top: jnp.concatenate([low[:-2], top]), base["blocks"], trained["blocks"]))
        p = ref.params_from_system(base)
        hidden, decisive = ref.hidden_states(p, hf, ids, mask)
        logits = ref.logits(p, hidden)
        return jnp.sum(logits * cotangent), (logits, decisive)

    (_, out), got = jax.jit(jax.value_and_grad(system, has_aux=True))(params["base"])
    (_, (want_logits, decisive)), want = jax.jit(jax.value_and_grad(plain, has_aux=True))(
        top2(params["base"]), params["base"])
    return SimpleNamespace(hf=hf, cfg=cfg, model=model, lm=model.lm, params=params, base=params["base"],
                           ids=ids, mask=mask, out=out, grads=got, want_logits=want_logits,
                           want_grads=want, decisive=decisive)


def test_scorer_logits_match_the_reference(world):
    """The teacher-forced forward through the hydra capture against the
    reference's forward, on real positions."""
    real = np.asarray(world.mask) > 0
    assert np.abs(np.asarray(world.out["logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert float(world.decisive.mean()) > 0.9  # the margin 1e-4 leaves ties to chance alone


def test_trainable_gradients_match_the_reference_and_the_backward_stops_at_the_branch_point(world):
    """`frozen_below` at the top-2 branch point: the two trainable routed layers,
    the final norm and the head against the reference's `jax.grad`; below the
    branch point (a dense and a routed layer, the embedding) the backward never ran."""
    grads = jax.tree_util.tree_map(np.asarray, world.grads)
    assert_gradients_close(top2(grads), world.want_grads)
    frozen = [jax.tree_util.tree_map(lambda x: x[:-2], grads["blocks"]), grads["dense_blocks"], grads["embed"]]
    assert all(np.abs(x).max() == 0.0 for x in jax.tree_util.tree_leaves(frozen))


@pytest.mark.parametrize("attention_impl", ["xla", "pallas"])
def test_latent_attention_expanded_form_and_its_gradients_match_the_reference(attention_impl):
    """The attention layer alone, teacher-forced, against the reference's equations
    and their `jax.grad` (weights and input); under `pallas` the flash kernels run
    (interpreted) with keys 24 and values 16 wide, forward and backward."""
    hf = toy()
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32,
                                   attention_impl=attention_impl))
    layer = LatentAttention(cfg)
    ids, mask = batch()
    T = ids.shape[1]
    x = jax.random.normal(jax.random.PRNGKey(3), ids.shape + (hf["hidden_size"],))
    cotangent = jax.random.normal(jax.random.PRNGKey(4), x.shape) * mask[..., None]
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    bias = make_attention_bias(mask, jnp.arange(T), jnp.arange(T))
    p = liven(jax.jit(lambda k: layer.init(k, x, bias, positions, None, mask)["params"])(jax.random.PRNGKey(5)))

    def system(p, x):
        return jnp.sum(layer.apply({"params": p}, x, bias, positions, None, mask)[0] * cotangent)

    def plain(p, x):
        w = {"w_dq": p["q_a"]["kernel"], "q_norm": p["q_a_norm"], "w_uq": p["q_b"]["kernel"],
             "w_dkv": p["kv_a"]["kernel"], "kv_norm": p["kv_a_norm"], "w_ukv": p["kv_b"]["kernel"],
             "w_o": p["o"]["kernel"]}
        ang = positions[..., None].astype(jnp.float32) * ref.yarn_inv_freq(hf)
        visible = jnp.tril(jnp.ones((T, T), bool))[None, None] & (mask[:, None, None, :] > 0)
        with jax.default_matmul_precision("highest"):
            return jnp.sum(ref._attention(x, w, hf, jnp.cos(ang), jnp.sin(ang), visible) * cotangent)

    assert ("pallas_call" in str(jax.make_jaxpr(system)(p, x))) == (attention_impl == "pallas")
    got_y, got = jax.jit(jax.value_and_grad(system, (0, 1)))(p, x)
    want_y, want = jax.jit(jax.value_and_grad(plain, (0, 1)))(p, x)
    assert abs(float(got_y - want_y)) < 1e-4 * float(jnp.abs(cotangent).sum())
    assert_gradients_close(got, want)


def test_prefill_then_decode_steps_through_the_latent_cache_match_the_full_forward(world):
    """Prefill of 24 (row 0 left-padded), then 8 single-token steps in the absorbed
    form against the cache, against the reference's full forward: logits, not tokens."""
    hf, lm, ids, mask = world.hf, world.lm, world.ids, world.mask
    P = 24
    positions = jnp.maximum(jnp.cumsum(mask, axis=1) - 1, 0)
    cache = lm.init_cache(2, 32, mask)
    # the leading dense layers' rows and the routed layers' rows, a segment an array
    width = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    assert cache["c"].shape == (LAYERS - LEAD, 2, 32, width) and cache["c_lead"].shape == (LEAD, 2, 32, width)

    @jax.jit
    def prefill(base):
        return lm(base, ids[:, :P], mask[:, :P], positions=positions[:, :P], cache=lm.init_cache(2, 32, mask))

    @jax.jit
    def step(base, token, position, cache):
        return lm(base, token, positions=position, cache=cache)

    out = prefill(world.base)
    got, cache = [out["logits"][:, -1]], out["cache"]
    for t in range(P, P + 8 - 1):
        out = step(world.base, ids[:, t : t + 1], positions[:, t : t + 1], cache)
        got.append(out["logits"][:, 0])
        cache = out["cache"]
    got = jnp.stack(got, axis=1)
    assert float(jnp.abs(got - world.want_logits[:, P - 1 : P + 7]).max()) < LOGIT_TOL
    assert int(cache["index"]) == P + 7


def test_a_latent_prefill_in_pieces_raises(world):
    cache = dict(world.lm.init_cache(2, 32), index=jnp.int32(8), static_index=8)
    with pytest.raises(NotImplementedError, match="empty cache"):
        jax.eval_shape(lambda base: world.lm(base, world.ids[:, :8], cache=cache), world.base)


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed layer with 8 of 64 experts held, on
    each of the eight chips of the deployment, the shared expert counted once,
    against the reference's uncut layer (all 64 held)."""
    hf = toy(n_routed_experts_published=64, n_routed_experts=8)
    kw = dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32)
    whole_hf = dict(hf, n_routed_experts=64, first_expert_held=0)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 16, hf["hidden_size"]))
    whole = RoutedMLP(TransformerConfig(**dict(kw, n_experts_held=64)))
    p = jax.jit(lambda k: whole.init(k, x)["params"])(key)
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, p)
    p["router_bias"] = 0.3 * jax.random.normal(key, p["router_bias"].shape)
    names = {"w_r": p["router_gate"], "b": p["router_bias"],
             "w_g": p["experts_fc_in"]["kernel"], "w_u": p["experts_fc_gate"]["kernel"],
             "w_d": p["experts_fc_out"]["kernel"],
             "shared": {"w_g": p["shared"]["fc_in"]["kernel"], "w_u": p["shared"]["fc_gate"]["kernel"],
                        "w_d": p["shared"]["fc_out"]["kernel"]}}

    @jax.jit
    def uncut(names):
        with jax.default_matmul_precision("highest"):
            return ref._experts(x, names, whole_hf, 0.0)[0], ref._gated(x, names["shared"])

    @jax.jit
    def eight_chips(p):
        out = []
        for chip in range(8):
            cfg = TransformerConfig(**dict(kw, n_experts_held=8, first_expert_held=8 * chip))
            mine = dict(p, **{k: {"kernel": p[k]["kernel"][8 * chip : 8 * chip + 8]}
                              for k in ("experts_fc_in", "experts_fc_gate", "experts_fc_out")})
            out.append(RoutedMLP(cfg).apply({"params": mine}, x))
        return out

    want, shared = uncut(names)
    total, pairs = shared, 0.0
    for y, stats in eight_chips(p):
        total = total + (y - shared)
        pairs += float(stats["load"].sum())
    assert float(jnp.abs(total - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert pairs == 2 * 16 * hf["num_experts_per_tok"]  # every assignment computed on exactly one chip


def test_the_decode_form_and_the_sorted_form_choose_and_compute_alike():
    """Sampler and scorer route alike at equal weights: one row a sequence through
    the decode form (every held expert over every row) and through the sorted,
    grouped form gives the same result and the same rows per expert."""
    hf = toy()
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 1, hf["hidden_size"]))
    layer = RoutedMLP(cfg)
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, jax.jit(lambda k: layer.init(k, x)["params"])(jax.random.PRNGKey(5)))
    y_decode, s_decode = jax.jit(lambda p: layer.apply({"params": p}, x, decode=True))(p)
    y_sorted, s_sorted = jax.jit(lambda p: layer.apply({"params": p}, x, decode=False))(p)
    assert float(jnp.abs(y_decode - y_sorted).max()) < 1e-5
    assert np.array_equal(np.asarray(s_decode["load"]), np.asarray(s_sorted["load"]))
    assert float(s_decode["load"].sum()) > 0


def test_sinkhorn_is_doubly_stochastic_and_its_backward_finite_at_the_clamp():
    """Logits of the size the layer makes (a projection scaled by a_res of order 0.01
    to 0.3, plus a bias that starts at 2 I): rows and columns sum to 1 within 1e-4 after
    20 steps (a bias of 4 I, 0.94 on the diagonal, would leave rows 4e-3 off: near the
    identity a step shrinks the error by 0.85). Matrices at the clamp's ends (all 30, all -30, a diagonal far above the
    rest, uniform noise over +-40), which 20 steps need not balance (Sinkhorn's rate
    falls with the matrix's contrast): columns still sum to 1 (the last step), nothing
    overflows, and the backward is finite."""
    rng = np.random.RandomState(0)
    usual = jnp.asarray(0.3 * rng.randn(64, 4, 4) + 2.0 * np.eye(4), jnp.float32)
    ends = jnp.stack([jnp.full((4, 4), 30.0), jnp.full((4, 4), -30.0),
                      jnp.diag(jnp.full(4, 75.0)) - 40.0,
                      jnp.asarray(rng.uniform(-40, 40, (4, 4)), jnp.float32)])

    def mix(z):
        return sinkhorn(jnp.exp(jnp.clip(z, -30.0, 30.0)), 20, 1e-6)

    mix_and_grad = jax.jit(jax.value_and_grad(
        lambda z: (lambda m: (jnp.sum(m * jnp.arange(16.0).reshape(4, 4)), m))(mix(z)), has_aux=True))
    (_, m), grad_usual = mix_and_grad(usual)
    assert float(jnp.abs(m.sum(-1) - 1).max()) < 1e-4 and float(jnp.abs(m.sum(-2) - 1).max()) < 1e-4
    assert float(m.min()) >= 0.0
    (_, e), grad_ends = mix_and_grad(ends)
    assert bool(jnp.all(jnp.isfinite(e))) and float(jnp.abs(e.sum(-2) - 1).max()) < 1e-4
    np.testing.assert_allclose(np.asarray(e[0]), 0.25, atol=1e-5)
    assert bool(jnp.all(jnp.isfinite(grad_usual))) and bool(jnp.all(jnp.isfinite(grad_ends)))
    np.testing.assert_allclose(
        np.asarray(m), np.asarray(ref.sinkhorn(jnp.exp(jnp.clip(usual, -30.0, 30.0)), 20, 1e-6)), atol=1e-6)


def test_yarn_frequencies_and_score_scale_by_hand():
    """64 rotary channels, theta 10000, factor 64 over 4096 positions, beta 32 / 1:
    the correction dimensions are 64 ln(4096 / (32 * 2 pi)) / (2 ln 10000) = 10.47 -> 10
    and 64 ln(4096 / (2 pi)) / (2 ln 10000) = 22.52 -> 23, so channels 0-10 keep
    1/theta_i, channels 23-31 turn 64 times slower and channel 16 blends 6/13 of the way.
    m = 0.1 ln 64 + 1 = 1.4159, the score scale m^2 / sqrt(192) = 0.14468."""
    cfg = TransformerConfig(**dict(ref.system_config(PUBLISHED), n_positions=64))
    freq = np.asarray(rope_inv_frequencies(cfg))
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert freq.shape == (32,)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 64.0, rtol=1e-6)
    ramp = 6.0 / 13.0
    np.testing.assert_allclose(freq[16], plain[16] * (1 - ramp) + plain[16] / 64.0 * ramp, rtol=1e-6)
    np.testing.assert_allclose(freq, np.asarray(ref.yarn_inv_freq(PUBLISHED)), rtol=1e-6)
    m = 0.1 * math.log(64.0) + 1.0
    assert abs(m - 1.41589) < 1e-5
    assert abs(cfg.attn_softmax_scale - m * m / math.sqrt(192.0)) < 1e-9
    assert abs(cfg.attn_softmax_scale - ref.softmax_scale(PUBLISHED)) < 1e-9
    assert cfg.cache_elems_per_position == 576


def test_hydra_branch_and_forward_from_layer_agree_with_the_uncut_forward(world):
    """On the four-stream state: the capture at the branch point is [B, T, 4, E]; the
    reference branch run from it (`forward_from_layer`) with copied weights gives the
    uncut forward's logits (the reference's, which the policy's are held to above);
    the value head reads the summed, normed state; the value branch forks at its own depth."""
    hf, out, ids = world.hf, world.out, world.ids
    real = np.asarray(world.mask) > 0
    assert out["branch_hidden"].shape == ids.shape + (hf["hc_mult"], hf["hidden_size"])
    assert np.abs(np.asarray(out["ref_logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert np.abs(np.asarray(out["ref_logits"] - out["logits"]))[real].max() < 1e-5
    assert out["values"].shape == ids.shape and bool(jnp.all(jnp.isfinite(out["values"])))
    # counters: the policy's routed layers, and apart from them the branch's two
    # (the scorer joins them; a train step that reads only the policy's leaves the
    # branch dead code)
    routed = LAYERS - LEAD
    assert out["moe_stats"]["load"].shape == (routed, hf["n_routed_experts"])
    assert out["ref_moe_stats"]["load"].shape == (2, hf["n_routed_experts"])
    counters = moe_counters(_join_stats(out["moe_stats"], out["ref_moe_stats"]), "scorer")
    made = ids.size * hf["num_experts_per_tok"] * (routed + 2)
    assert float(counters["moe/assignments.scorer"]) == made
    assert 0 < float(counters["moe/assignments_here.scorer"]) < made
    assert float(counters["moe/load_max_over_mean.scorer"]) >= 1.0
    with pytest.raises(NotImplementedError, match="leading dense"):
        CausalLMWithValueHead(world.cfg, branch_at=0).make_ref_params(world.params)


def test_int8_rollout_weights_cover_experts_and_written_projections_only(world):
    hf, lm, params, ids, mask = world.hf, world.lm, world.base, world.ids, world.mask
    q = jax.jit(quantize_decode_weights)(params)
    moe, attn = q["blocks"]["moe"], q["blocks"]["attn"]
    held, width = hf["n_routed_experts"], hf["moe_intermediate_size"]
    assert moe["experts_fc_in"]["kernel"].dtype == jnp.int8
    assert moe["experts_fc_in"]["kernel_scale"].shape == (3, held, width)  # per expert and output channel
    assert moe["experts_fc_out"]["kernel_scale"].shape == (3, held, hf["hidden_size"])
    assert moe["shared"]["fc_in"]["kernel"].dtype == jnp.int8
    assert q["dense_blocks"]["mlp"]["fc_out"]["kernel"].dtype == jnp.int8
    for name in ("q_a", "q_b", "kv_a", "o"):
        assert attn[name]["kernel"].dtype == jnp.int8, name
    assert attn["kv_b"]["kernel"].dtype == jnp.float32 and "kernel_scale" not in attn["kv_b"]
    assert moe["router_gate"].dtype == jnp.float32
    # the quantised policy is the same function to int8's precision, teacher-forced
    # (against the policy's own logits, which the reference holds) and in the sampler
    full = world.out["logits"]
    quant = jax.jit(lambda q: lm(q, ids, mask)["logits"])(q)
    err = float(jnp.sqrt(jnp.mean((full - quant) ** 2)) / jnp.sqrt(jnp.mean(full**2)))
    assert 0 < err < 0.1
    lm8 = TransformerLM(lm.cfg.replace(decode_weights_quant="int8"))
    g = jax.jit(lambda p: generate(lm8, p, ids[:, :8], jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(2),
                                   SamplerSettings(max_new_tokens=4)))(params)
    assert g["sequences"].shape == (2, 12)
    made = 2 * (8 + 3) * hf["num_experts_per_tok"] * 3  # prefill and three steps, three routed layers
    assert float(g["moe_stats"]["moe/assignments.sampler"]) == made


@pytest.mark.parametrize("bad, match", [
    (dict(kv_cache_quant="int8"), "int8 latent cache"),
    (dict(attention_impl="ring"), "ring"),
    (dict(parallel_residual=True), "parallel_residual"),
    (dict(n_experts_held=8, first_expert_held=12), "held"),
])
def test_what_the_family_does_not_reach_raises_at_configuration_time(bad, match):
    kw = dict(ref.system_config(toy()), n_positions=64)
    with pytest.raises((NotImplementedError, ValueError), match=match):
        TransformerConfig(**dict(kw, **bad))


def test_adapters_paged_engine_pipeline_and_loader_raise_for_the_family(world):
    from trlx_tpu.models.hf import config_from_hf

    lm, params, ids = TransformerLM(world.cfg), world.base, world.ids
    with pytest.raises(NotImplementedError, match="adapters"):
        jax.eval_shape(lambda p: lm(p, ids, prefix_embeds=jnp.zeros((2, lm.cfg.hidden_size))), params)
    with pytest.raises(NotImplementedError, match="paged"):
        lm._scan_segment(params["blocks"], None, None, None, cache={"pk": None})
    lm._mesh = SimpleNamespace(shape={"pp": 2})
    with pytest.raises(NotImplementedError, match="pp > 1"):
        lm._pp_microbatches(2, None)
    with pytest.raises(NotImplementedError, match="no loader"):
        config_from_hf(SimpleNamespace(model_type="xing4_0", kv_lora_rank=512, n_routed_experts=64))


def test_the_memory_plan_counts_held_experts_latent_cache_and_streams(world):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.utils.memdoctor import analytic_param_count, analytic_plan

    kw = dict(ref.system_config(PUBLISHED), n_positions=1024)
    assert analytic_param_count(kw) == ref.params_held(PUBLISHED)["total"] == 1_016_199_162
    assert analytic_param_count(dict(ref.system_config(world.hf), n_positions=64)) == sum(
        x.size for x in jax.tree_util.tree_leaves(world.base))
    config = default_ppo_config().evolve(
        train=dict(seq_length=1024, batch_size=8, remat_policy="full"),
        model=dict(model_path="random", model_extra_configs={"transformer": kw}),
        method=dict(chunk_size=32, num_rollouts=32))
    plan = analytic_plan(config, hbm_bytes=16 * 2**30)
    cache = [i for i in plan.items if i.component == "static_kv_cache"][0]
    assert cache.bytes == 7 * 32 * 1024 * 576 * 2 and "latent" in cache.note
    assert "4 residual streams" in [i for i in plan.items if i.component == "activations"][0].note


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import TPUPPOTrainer

    # the trainer initialises its model op by op, which here is 13 s of small CPU
    # compiles; the weights' values are nothing to these tests
    eager_init = TransformerLM.init
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(TransformerLM, "init", lambda self, key: jax.jit(lambda k: eager_init(self, k))(key))
        hf = toy(router_bias_balance_steps=8)
        config = default_ppo_config().evolve(
            train=dict(batch_size=8, total_steps=1, seq_length=16, epochs=1, tracker=None,
                       checkpoint_dir=str(tmp_path_factory.mktemp("ckpt")), compute_dtype="float32"),
            model=dict(model_path="random", num_layers_unfrozen=2,
                       model_extra_configs={"transformer": ref.system_config(hf)}),
            tokenizer=dict(tokenizer_path="byte", tokenizer_extra_configs=dict(vocab_size=hf["vocab_size"])),
            method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                        gen_kwargs=dict(max_new_tokens=4, do_sample=True)))
        yield SimpleNamespace(hf=hf, trainer=TPUPPOTrainer(config, reward_fn=lambda **kw: [0.0] * 8))


def test_the_freeze_mask_covers_the_leading_dense_layers_and_the_router_bias(trainer):
    hf, trainer = trainer.hf, trainer.trainer
    mask = trainer.make_freeze_mask(trainer.params)["base"]
    assert float(np.max(mask["dense_blocks"]["attn"]["o"]["kernel"])) == 0.0
    assert np.asarray(mask["blocks"]["attn"]["o"]["kernel"]).ravel().tolist() == [0.0, 1.0, 1.0]
    assert float(mask["blocks"]["moe"]["router_bias"]) == 0.0
    # a share (4 of 16 experts here) trains no router: its gradient is the held experts' part alone
    assert float(mask["blocks"]["moe"]["router_gate"]) == 0.0
    assert np.asarray(mask["blocks"]["moe"]["experts_fc_in"]["kernel"]).ravel().tolist() == [0.0, 1.0, 1.0]
    assert float(mask["embed"]["wte"]) == 0.0 and float(mask["lm_head"]["kernel"]) == 1.0
    gauges = []
    trainer.obs.gauge = lambda **kw: gauges.append(kw)
    trainer._note_backward_depth()
    assert gauges[0]["model/experts_held"] == hf["n_routed_experts"]
    assert gauges[0]["model/cache_elems_per_position"] == hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    assert gauges[0]["model/residual_streams"] == 4 and gauges[0]["model/backward_layers"] == 2
    with pytest.raises(NotImplementedError, match="paged decode engine"):
        trainer._engine_eligible()


def skewed_ids(rows, seq, symbols=5):
    """Tokens of a few symbols, as the benchmark's prompts are: most positions
    then choose the same few experts."""
    return jax.random.randint(jax.random.PRNGKey(3), (rows, seq), 97, 97 + symbols)


def test_the_balancing_rule_evens_the_routers_choices_and_leaves_the_weights(world):
    from trlx_tpu.models.transformer import balance_router_bias

    ids = skewed_ids(4, 48)
    base = dict(world.base, blocks=dict(world.base["blocks"], moe=dict(
        world.base["blocks"]["moe"], router_bias=jnp.zeros_like(world.base["blocks"]["moe"]["router_bias"]))))
    out = jax.jit(lambda p: world.lm(p, ids, jnp.ones_like(ids), compute_logits=False))(base)
    choices = np.asarray(out["moe_stats"]["choices"])  # every choice the routers made, held here or not
    assert choices.shape == (LAYERS - LEAD, world.hf["n_routed_experts_published"])
    assert (choices.sum(-1) == ids.size * world.hf["num_experts_per_tok"]).all()
    assert (choices[:, :world.hf["n_routed_experts"]] == np.asarray(out["moe_stats"]["load"])).all()
    new, (before, after) = balance_router_bias(world.lm, base, ids, jnp.ones_like(ids), steps=16)
    # 4 of 16 experts a token: the fullest can hold 4 times the mean at most. Five
    # symbols start at 3.2, 2.0 and 1.6 in the three layers; sixteen steps leave
    # 1.08-1.13 (one expert's load moves in steps of a few positions of 192)
    assert float(jnp.max(before)) > 3.0 and (np.asarray(after) < 1.3).all(), (before, after)
    changed = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new), jax.tree_util.tree_leaves(base)) if a is not b]
    assert changed == ["['blocks']['moe']['router_bias']"]


def test_a_random_routed_model_is_balanced_on_the_first_prompts_and_every_copy_takes_the_bias(trainer):
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline

    trainer = trainer.trainer
    assert not np.asarray(trainer.params["base"]["blocks"]["moe"]["router_bias"]).any()
    weights = trainer.params["base"]["blocks"]["moe"]["router_gate"]
    prompts = ["".join(chr(c) for c in row) for row in np.asarray(skewed_ids(8, 12))]
    trainer.add_prompt_pipeline(PromptPipeline(prompts, 12, trainer.tokenizer))
    bias = np.asarray(trainer.params["base"]["blocks"]["moe"]["router_bias"])
    assert np.abs(bias).max(axis=-1).min() > 0.0  # every routed layer, frozen or not
    assert trainer.params["base"]["blocks"]["moe"]["router_gate"] is weights
    # the frozen reference is the top two layers as they stand when training starts
    np.testing.assert_array_equal(np.asarray(trainer.ref_params["blocks"]["moe"]["router_bias"]), bias[-2:])


@pytest.fixture(scope="module")
def blocks(trainer):
    """The fused block of two epochs over eight rows of the toy trainer (hydra top 2
    of 1 dense + 3 routed layers: the trunk is the dense layer and the first routed
    one), as built, the trunk's output held, and with the whole forward in every
    step: two programs, run once from the same state."""
    from tests.test_frozen_trunk import block_both_ways, block_perms, rollout_batch

    rows = rollout_batch(False, rows=8, p=12, vocab=trainer.hf["vocab_size"])
    held, whole = block_both_ways(trainer.trainer, rows, block_perms(8, 8, 2))
    return SimpleNamespace(rows=rows, held=held, whole=whole, steps=2)


def test_the_block_that_holds_the_routed_four_stream_trunk_equals_the_block_that_runs_it_in_every_step(trainer, blocks):
    """Parameters, optimizer state, loss and stats bit for bit; the captures are held
    in stream form. The two counters of pairs differ by design (the next test)."""
    from tests.test_frozen_trunk import assert_same_block

    t = trainer.trainer
    assert t.trunk_layers_held() == t.model.branch_at == LAYERS - 2
    (capture,), counters = jax.eval_shape(t.trunk_constants, t.params, blocks.rows)
    assert capture.shape == (8, 16, trainer.hf["hc_mult"], trainer.hf["hidden_size"])
    assert counters["load"].shape == (LAYERS - 2 - LEAD, trainer.hf["n_routed_experts"])
    assert np.isfinite(blocks.held[2])
    assert_same_block(blocks.held, blocks.whole, skip=("moe/assignments",))


def test_the_train_counters_count_the_held_trunks_pairs_once_a_block(trainer, blocks):
    """`moe/assignments_here.train` and `moe/assignments.train` stay "pairs the program
    ran, a step's mean": times the steps of the block they read the trunk's routed
    layer ONCE over all rows (counted by a whole forward over them) plus the steps' own
    for the trainable layers, where the block that runs the whole forward in every
    step reads the trunk's in every step. The fullest expert's share is a ratio and
    reads the same."""
    t, steps = trainer.trainer, blocks.steps
    stats = jax.jit(lambda base, ids, mask: t.model.lm(base, ids, mask, compute_logits=False)["moe_stats"])(
        t.params["base"], *t._policy_inputs(blocks.rows))
    routed, trunk = LAYERS - LEAD, LAYERS - 2 - LEAD
    assert stats["load"].shape[0] == routed
    once = {"moe/assignments_here.train": float(np.sum(stats["load"][:trunk])),
            "moe/assignments.train": float(stats["assignments"]) * trunk / routed}
    assert once["moe/assignments_here.train"] > 0
    held, whole = blocks.held[3], blocks.whole[3]
    for name, trunks in once.items():
        own = steps * (float(whole[name]) - trunks)  # every step of the whole block ran all eight rows
        assert own > 0
        np.testing.assert_allclose(float(held[name]) * steps, trunks + own, rtol=1e-6)
    assert float(held["moe/load_max_over_mean.train"]) == float(whole["moe/load_max_over_mean.train"])


def test_counters_reach_the_cycle_row():
    from trlx_tpu.obs.telemetry import TelemetryAggregator

    t = TelemetryAggregator(window=4)
    t.observe_stats({"moe/assignments_here.train": 4096.0, "losses/total_loss": 0.1})
    row = t.close_cycle(1.0, {}, step=4)
    assert row["counters"] == {"moe/assignments_here.train": 4096.0}
    assert "counters" not in t.close_cycle(1.0, {}, step=8)  # flushed once


# -- the kernels of the main path at the published widths, compiled for a v5e
# that is described and not attached (nothing runs) ---------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def test_mosaic_compiles_the_flash_kernels_at_keys_192_values_128(one_chip, monkeypatch):
    from trlx_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

        def step(q, k, v, mask):
            return jax.grad(lambda q, k, v: fa.flash_attention(q, k, v, mask, True, 0.1447)
                            .astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

        compiled = jax.jit(step).lower(shape(2, 32, 1024, 192), shape(2, 32, 1024, 192),
                                       shape(2, 32, 1024, 128), shape(2, 1024, dt=jnp.int32)).compile()
        text = compiled.as_text()
        assert all(name in text for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_mosaic_compiles_the_flash_kernels_at_head_64_over_grouped_heads(one_chip, monkeypatch):
    """The causal kernels at `lfm2-24b-a2b`'s attention: 32 query over 8 key-value
    heads of 64, forward and backward at a train step's 8 x 1024, and the prefill's
    896 queries at offset 0 into a cache of 1024 slots."""
    from trlx_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

        def step(q, k, v, mask):
            return jax.grad(lambda q, k, v: fa.flash_attention(q, k, v, mask, True, 0.125)
                            .astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)

        text = jax.jit(step).lower(shape(8, 32, 1024, 64), shape(8, 8, 1024, 64), shape(8, 8, 1024, 64),
                                   shape(8, 1024, dt=jnp.int32)).compile().as_text()
        assert all(name in text for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
        prefill = jax.jit(lambda q, k, v, mask: fa.flash_attention(q, k, v, mask, True, 0.125, q_offset=0))
        text = prefill.lower(shape(32, 32, 896, 64), shape(32, 8, 1024, 64), shape(32, 8, 1024, 64),
                             shape(32, 1024, dt=jnp.int32)).compile().as_text()
        assert "flash_fwd" in text
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_mosaic_compiles_the_int8_decode_kernel_at_the_cells_shapes(topo, one_chip, monkeypatch):
    """The fused decode kernel over the int8 cache, compiled by Mosaic at
    the shapes of the three Pythia cells: one chip at 16 heads x 1024 and
    2048 slots, and the four-chip cell's 16 rows x 32 heads under
    shard_map on a described 2x2 mesh (4 rows a chip, no collective)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from trlx_tpu.ops import decode_attention as da
    from trlx_tpu.parallel import make_mesh

    monkeypatch.setattr(da, "_interpret", lambda: False)
    mesh = make_mesh({"dp": 1, "fsdp": 4}, devices=topo.devices)
    rows = ("dp", "fsdp")
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for on, (L, B, H, S) in ((None, (22, 8, 16, 1024)), (None, (22, 8, 16, 2048)), (mesh, (12, 16, 32, 1024))):
            def shape(*s, dt, spec=P()):
                sharding = one_chip if on is None else NamedSharding(on, spec)
                return jax.ShapeDtypeStruct(s, dt, sharding=sharding)

            def step(q, ck, cv, ks, vs, mask, lx, w):
                return da.decode_attention_on_mesh(on, q, ck, cv, ks, vs, mask, lx, w, 128 ** -0.5)

            stacked = P(None, rows)
            text = jax.jit(step).lower(
                shape(B, H, 128, dt=jnp.bfloat16, spec=P(rows)),
                shape(L, B, H, S, 128, dt=jnp.int8, spec=stacked),
                shape(L, B, H, S, 128, dt=jnp.int8, spec=stacked),
                shape(L, B, H, S, dt=jnp.float32, spec=stacked),
                shape(B, H, 1, 128, dt=jnp.float32, spec=P(rows)),
                shape(B, S, dt=jnp.int32, spec=P(rows)),
                shape(dt=jnp.int32), shape(dt=jnp.int32),
            ).compile().as_text()
            assert "decode_attn" in text and "tpu_custom_call" in text
            assert "all-gather" not in text and "all-reduce" not in text
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_mosaic_compiles_the_state_step_kernel_at_the_cells_shapes(one_chip, monkeypatch):
    """The decode step of a recurrent mixer as one kernel (`ops/state_step.py`), compiled
    by Mosaic at the two longgen-b32 cells' shapes (delta rule: 6 layers x 32 rows x 32
    heads of 128 x 128; state space: 5 x 32 x 128 heads of 64 x 128 in 8 groups): the
    carry is the custom call's operand and its output, and nothing copies it."""
    import re

    from trlx_tpu.ops import state_step as ss

    monkeypatch.setattr(ss, "_interpret", lambda: False)
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    B = 32
    cases = {
        "delta": (ss.delta_state_step, (6, B, 32, 128, 128), (
            shape(B, 32, 128), shape(B, 32, 128), shape(B, 32, 128), shape(B, 32, 128), shape(B, 32))),
        "ssm": (ss.ssm_state_step, (5, B, 128, 64, 128), (
            shape(B, 128, 64, dt=jnp.bfloat16), shape(B, 8, 128, dt=jnp.bfloat16),
            shape(B, 8, 128, dt=jnp.bfloat16), shape(B, 128), shape(128))),
    }
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for kind, (step, carry, vectors) in cases.items():
            compiled = jax.jit(step, donate_argnums=0).lower(shape(*carry), shape(dt=jnp.int32), *vectors).compile()
            text = compiled.as_text()
            assert "state_step" in text and "tpu_custom_call" in text
            assert "output_to_operand_aliasing={{0}: (1, {})}" in text
            carried = "f32[" + ",".join(map(str, carry)) + "]"
            assert not re.findall(rf"= {re.escape(carried)}\S* (?:copy|dynamic-slice|dynamic-update-slice)\(", text)
            assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20  # no second carry, no layer's slice
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
