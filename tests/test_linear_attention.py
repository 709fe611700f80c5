"""Gated delta-rule linear attention (KDA) with a recurrent state in the sampler's
carry, beside a rotary-free latent cache, in a stack whose layers differ in their
MIXER: the program against the plain reference (`benchmark/reference/kimi_linear_ref.py`,
the delta rule as a recurrence over positions, which imports nothing of the program)
at toy sizes from its `toy_sizes`, on seeded random weights.

Tolerances: compute is float32 on the CPU here, the reference float32 at the highest
matmul precision. Differences are reduction order alone (the chunked form sums what
the recurrence accumulates): 2e-4 on logits of order 3 and 1e-3 relative on gradients.

One toy stack serves every parity test: a leading dense KDA layer, then KDA, KDA, MLA,
KDA with routed experts, so that the cut at the top-2 branch point leaves three KDA
layers frozen under one trainable MLA and one trainable KDA layer. It is built, run
forward and differentiated ONCE, jitted (`world`, module scope), beside the reference's
forward and `jax.grad`. Sequences are 80 long: three chunks of 32 of the chunked form,
the last padded, as are the prefill's (72) and the form's own test's (100)."""

import json
import logging
import os
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear_ref as ref
from trlx_tpu.models.generation import SamplerSettings, generate, state_bytes_per_step
from trlx_tpu.models.transformer import (
    KDA_CHUNK,
    RoutedMLP,
    TransformerConfig,
    TransformerLM,
    extract_branch_params,
    kda_chunked,
    kda_step,
    layer_stacks,
    quantize_decode_weights,
    state_step_unfused,
)
from trlx_tpu.models.wrappers import CausalLMWithValueHead

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json")) as _f:
    PUBLISHED = json.load(_f)
LOGIT_TOL, GRAD_RTOL = 2e-4, 1e-3
LAYERS, SEQ = 5, 80


def toy(**over):
    hf = dict(PUBLISHED, **ref.toy_sizes(PUBLISHED))
    hf.update(correct={"routing_margin": 1e-4})
    hf.update(over)
    return hf


def liven(params):
    """Seeded values for what initialises to constants (norm scales, the selection
    bias) and larger weights, so that every term of every equation carries signal.
    The decay's parameters keep their own seeded start."""
    def one(path, x):
        name = path[-1].key
        key = jax.random.fold_in(jax.random.PRNGKey(7), zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31))
        if name == "router_bias":
            return 0.3 * jax.random.normal(key, x.shape)
        if name in ("scale", "kv_a_norm", "o_norm"):
            return 1.0 + 0.2 * jax.random.normal(key, x.shape)
        if name in ("A_log", "dt_bias") or name.startswith("conv_"):
            return x
        return 5.0 * x

    return jax.tree_util.tree_map_with_path(one, params)


def batch(rows=2, seq=SEQ, vocab=512, pad=5):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    return ids, jnp.ones((rows, seq), jnp.int32).at[0, :pad].set(0)  # row 0 left-padded


def top2(tree):
    """What `num_layers_unfrozen` 2 trains: the MLA layer (published 4) and the KDA
    layer above it (5), the final norm and the head."""
    return {"blocks": tree["blocks"], "ln_f": tree["ln_f"], "lm_head": tree["lm_head"],
            "delta_blocks": jax.tree_util.tree_map(lambda x: x[-1:], tree["delta_blocks"])}


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
        base = dict(base, ln_f=trained["ln_f"], lm_head=trained["lm_head"], blocks=trained["blocks"],
                    delta_blocks=jax.tree_util.tree_map(
                        lambda low, top: jnp.concatenate([low[:-1], top]), base["delta_blocks"],
                        trained["delta_blocks"]))
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


def test_the_stack_is_segments_of_layers_equal_in_mixer_and_feed_forward(world):
    assert world.cfg.mixers == ("delta", "delta", "delta", "latent", "delta")
    assert layer_stacks(world.cfg) == (("dense_blocks", 0), ("delta_blocks", 0), ("delta_blocks", 1),
                                       ("blocks", 0), ("delta_blocks", 2))
    rows = {name: jax.tree_util.tree_leaves(world.base[name])[0].shape[0]
            for name in ("dense_blocks", "delta_blocks", "blocks")}
    assert rows == {"dense_blocks": 1, "delta_blocks": 3, "blocks": 1}
    assert "conv_q" in world.base["dense_blocks"]["attn"] and "kv_b" in world.base["blocks"]["attn"]
    assert "mlp" in world.base["dense_blocks"] and "moe" in world.base["delta_blocks"]
    # the published cut: KDA-dense, then KDA, KDA, MLA, KDA, KDA, KDA, MLA, KDA
    kw = ref.system_config(PUBLISHED)
    assert kw["mixer_layers"] == ("delta",) * 3 + ("latent",) + ("delta",) * 3 + ("latent", "delta")
    published = TransformerConfig(**dict(kw, n_positions=1024))
    assert published.cache_layers == 2 and published.cache_elems_per_position == 576
    assert published.state_elems_per_row == 7 * (32 * 128 * 128 + 3 * 12288)


def test_scorer_logits_match_the_reference(world):
    """The teacher-forced forward (chunked form in four KDA layers, expanded latent
    attention without rotation in the fourth layer) through the hydra capture against
    the reference's recurrence, on real positions."""
    real = np.asarray(world.mask) > 0
    assert np.abs(np.asarray(world.out["logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert float(world.decisive.mean()) > 0.9  # the margin 1e-4 leaves ties to chance alone


def test_trainable_gradients_match_the_reference_and_the_backward_stops_at_the_branch_point(world):
    """`frozen_below` at the top-2 branch point: one MLA and one KDA layer (through the
    checkpointed scan of the chunked form), the final norm and the head against the
    reference's `jax.grad`; below the branch point (three KDA layers, the embedding)
    the backward never ran."""
    grads = jax.tree_util.tree_map(np.asarray, world.grads)
    assert_gradients_close(top2(grads), world.want_grads)
    frozen = [jax.tree_util.tree_map(lambda x: x[:-1], grads["delta_blocks"]), grads["dense_blocks"],
              grads["embed"]]
    assert all(np.abs(x).max() == 0.0 for x in jax.tree_util.tree_leaves(frozen))
    moved = [np.abs(x).max() for x in jax.tree_util.tree_leaves(top2(grads)["delta_blocks"]["attn"])]
    assert min(moved) > 0.0  # every parameter of the trainable KDA mixer, A_log and the taps too


def _recurrence(q, k, v, g, beta, state):
    def step(s, x):
        o, s = kda_step(*x, s)
        return s, o

    state, o = jax.lax.scan(step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _value_and_grads(form):
    def f(q, k, v, g, beta, state):
        o, s = form(q, k, v, g, beta, state)
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(s * s), (o, s)
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True))


# two programs for the five cases: compiled once each
CHUNKED, RECURRENT = _value_and_grads(kda_chunked), _value_and_grads(_recurrence)


@pytest.mark.parametrize("gates", ["mild", "strong", "beta_zero", "beta_one", "mixed"])
def test_the_chunked_form_equals_the_recurrence(gates):
    """100 positions at the chunk the program runs (32, sub-chunks of 8: the last
    chunk is padded), from a non-zero state. `strong`: a decay of e^-8 a step in every
    channel, e^-256 across a chunk, whose inverse no float32 holds: the differences of
    cumulative log-gates are formed before they are exponentiated. `mixed`: every third
    position decays by up to e^-8, the rest hardly. Outputs, final state and the
    gradients of all five inputs."""
    B, T, H, D = 2, 100, 2, 8
    assert T % KDA_CHUNK
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, D))
    g = -0.5 * jax.random.uniform(ks[3], (B, T, H, D))
    beta = jax.random.uniform(ks[4], (B, T, H))
    if gates == "strong":
        g, beta = jnp.full_like(g, -8.0), jnp.ones_like(beta)
    elif gates == "beta_zero":
        beta = jnp.zeros_like(beta)
    elif gates == "beta_one":
        beta = jnp.ones_like(beta)
    elif gates == "mixed":
        g = 2.0 * g * jnp.where(jnp.arange(T) % 3 == 0, 8.0, 0.01)[None, :, None, None]
    state = jax.random.normal(ks[5], (B, H, D, D))

    (_, (o, s)), grads = CHUNKED(q, k, v, g, beta, state)
    (_, (want_o, want_s)), want = RECURRENT(q, k, v, g, beta, state)
    assert bool(jnp.all(jnp.isfinite(o))) and all(bool(jnp.all(jnp.isfinite(x))) for x in grads)
    assert float(jnp.abs(o - want_o).max()) < 1e-5 * max(float(jnp.abs(want_o).max()), 1.0)
    assert float(jnp.abs(s - want_s).max()) < 1e-5 * max(float(jnp.abs(want_s).max()), 1.0)
    for got, wanted in zip(grads, want):
        assert float(jnp.abs(got - wanted).max()) <= 1e-4 * float(jnp.abs(wanted).max()) + 3e-6
    if gates == "beta_zero":  # nothing is written: the state only decays, the output reads it
        np.testing.assert_allclose(np.asarray(s), np.asarray(state * jnp.exp(g.sum(1))[..., None]), rtol=1e-5)


def _decoder(lm, base):
    """Prefill of all but 8 tokens (row 0 left-padded), then 8 single-token steps: the
    chunked form's final state and last three convolution inputs handed to the recurrent
    form, the latent layer's rows beside them. Returns `run(ids, mask)`: the logits of
    each step and the cache at the end."""

    @jax.jit
    def prefill(base, ids, mask, cache_mask):
        return lm(base, ids, mask, cache=lm.init_cache(ids.shape[0], cache_mask.shape[1], cache_mask))

    @jax.jit
    def step(base, token, cache):
        return lm(base, token, cache=cache)

    def run(ids, mask):
        total = ids.shape[1]
        P = total - 8
        out = prefill(base, ids[:, :P], mask[:, :P], mask)
        got, cache = [out["logits"][:, -1]], out["cache"]
        for t in range(P, total - 1):
            out = step(base, ids[:, t : t + 1], cache)
            got.append(out["logits"][:, 0])
            cache = out["cache"]
        return jnp.stack(got, axis=1), cache

    return run


@pytest.fixture(scope="module")
def decoded(world):
    """`run`, and `result`, the world's batch through it, on one device (no mesh): a decode
    step of the recurrent layers takes the kernel of `ops/state_step.py`, interpreted."""
    assert state_step_unfused(world.cfg, world.lm.mesh) is None
    run = _decoder(world.lm, world.base)
    return SimpleNamespace(P=SEQ - 8, run=run, result=run(world.ids, world.mask))


def test_prefill_then_decode_steps_through_both_kinds_of_state_match_the_full_forward(world, decoded):
    hf, lm = world.hf, world.lm
    cache = lm.init_cache(2, SEQ, world.mask)
    H, D = hf["linear_attn_config"]["num_heads"], hf["linear_attn_config"]["head_dim"]
    # each stack its own arrays: the one latent layer's rows; the leading KDA layer's
    # state and the three routed KDA layers' apart
    assert cache["c"].shape == (1, 2, SEQ, hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) and "c_lead" not in cache
    assert cache["kda_s"].shape == (3, 2, H, D, D) and cache["kda_s"].dtype == jnp.float32
    assert cache["kda_s_lead"].shape == (1, 2, H, D, D)
    assert cache["kda_u"].shape == (3, 2, 3, 3 * H * D) and cache["kda_u_lead"].shape == (1, 2, 3, 3 * H * D)
    got, cache = decoded.result
    P = decoded.P
    assert float(jnp.abs(got - world.want_logits[:, P - 1 : SEQ - 1]).max()) < LOGIT_TOL
    assert int(cache["index"]) == SEQ - 1
    assert float(jnp.abs(cache["kda_s"]).max()) > 0 and float(jnp.abs(cache["kda_u_lead"]).max()) > 0


def test_a_left_padded_row_decodes_as_the_same_row_unpadded(world, decoded):
    """Row 0 has 5 pad slots in front: its state stays zero and its convolution window
    empty until its first token, so its logits are those of the 75 real tokens run
    alone, through prefill and through the decode steps."""
    pad = 5
    alone = world.ids[:1, pad:]
    want, cache = decoded.run(alone, jnp.ones_like(alone))
    got, padded = decoded.result
    assert float(jnp.abs(got[:1] - want).max()) < LOGIT_TOL
    np.testing.assert_allclose(np.asarray(padded["kda_s"][:, :1]), np.asarray(cache["kda_s"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(padded["kda_u_lead"][:, :1]), np.asarray(cache["kda_u_lead"]), atol=1e-5)


def test_decode_steps_on_a_mesh_of_two_devices_take_the_xla_branch_and_agree_with_the_kernels(world, decoded):
    """More than one device is the static condition that sends a decode step of the
    recurrent layers to `kda_step` on a slice of the carry (`state_step_unfused`): the same
    prefill and steps there give the kernel's logits and states to float32 rounding."""
    from trlx_tpu.parallel import make_mesh

    lm = TransformerLM(world.cfg)
    lm.mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    assert "2 devices" in state_step_unfused(world.cfg, lm.mesh)
    got, cache = _decoder(lm, world.base)(world.ids, world.mask)
    want, kernels = decoded.result
    assert float(jnp.abs(got - want).max()) < 2e-5
    np.testing.assert_allclose(np.asarray(cache["kda_s"]), np.asarray(kernels["kda_s"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache["kda_u_lead"]), np.asarray(kernels["kda_u_lead"]), atol=1e-5)


def test_thirty_two_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The share tied to the model: the routed layer with 8 of 256 experts held, on
    each of the 32 chips of the deployment, the shared expert counted once, against
    the reference's uncut layer (all 256 held)."""
    hf = toy(num_experts_published=256, num_experts=8, num_experts_per_token=8)
    kw = dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32)
    whole_hf = dict(hf, num_experts=256, first_expert_held=0)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 16, hf["hidden_size"]))
    whole = RoutedMLP(TransformerConfig(**dict(kw, n_experts_held=256)))
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
            weights, _ = ref.route(x, names, whole_hf)
            shared = ref._gated(x, names["shared"])
            # every expert's part at once: the toy is small enough
            h = jax.nn.silu(jnp.einsum("btd,edf->btef", x, names["w_g"])) * jnp.einsum(
                "btd,edf->btef", x, names["w_u"])
            return shared + jnp.einsum("btef,efd,bte->btd", h, names["w_d"], weights), shared

    def chip(first):
        cfg = TransformerConfig(**dict(kw, n_experts_held=8, first_expert_held=first))

        def apply(p, held):
            mine = dict(p, **{k: {"kernel": jax.lax.dynamic_slice_in_dim(p[k]["kernel"], held, 8)}
                              for k in ("experts_fc_in", "experts_fc_gate", "experts_fc_out")})
            return RoutedMLP(cfg).apply({"params": mine}, x)
        return jax.jit(apply)

    def rolled(p, shift):
        """The router's experts renumbered so that expert `shift` is expert 0: the
        choice of a top-k is the same under any numbering."""
        return dict(p, router_gate=jnp.roll(p["router_gate"], -shift, axis=1),
                    router_bias=jnp.roll(p["router_bias"], -shift))

    want, shared = uncut(names)
    first_chip = chip(0)  # one program for the 32 chips: chip c sees its experts as 0-7
    total, pairs = shared, 0.0
    for index in range(32):
        y, stats = first_chip(rolled(p, 8 * index), 8 * index)
        total = total + (y - shared)
        pairs += float(stats["load"].sum())
    assert float(jnp.abs(total - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert pairs == 2 * 16 * hf["num_experts_per_token"]  # every assignment computed on exactly one chip
    # a chip that holds experts 16-23 under their own numbers (`first_expert_held` 16) gives
    # what the renumbered router gave, and what the reference's held part gives
    y16 = chip(16)(p, 16)[0]
    assert float(jnp.abs(y16 - first_chip(rolled(p, 16), 16)[0]).max()) < 1e-5 * float(jnp.abs(want).max())
    held_hf = dict(hf, first_expert_held=16)
    mine = dict(names, **{k: names[k][16:24] for k in ("w_g", "w_u", "w_d")})
    with jax.default_matmul_precision("highest"):
        ref_part = jax.jit(lambda x, mine: ref._experts(x, mine, held_hf, 0.0)[0])(x, mine)
    assert float(jnp.abs(y16 - ref_part).max()) < 1e-4 * float(jnp.abs(want).max())


def test_hydra_branch_and_forward_from_layer_over_mixed_segments_agree_with_the_uncut_forward(world):
    """The reference branch (one row of `blocks`, the last row of `delta_blocks`) run from
    the capture at the branch point gives the uncut forward's logits; the value branch
    forks one layer higher and holds the top KDA layer alone."""
    hf, out, ids = world.hf, world.out, world.ids
    real = np.asarray(world.mask) > 0
    assert out["branch_hidden"].shape == ids.shape + (hf["hidden_size"],)
    assert np.abs(np.asarray(out["ref_logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert np.abs(np.asarray(out["ref_logits"] - out["logits"]))[real].max() < 1e-5
    assert out["values"].shape == ids.shape and bool(jnp.all(jnp.isfinite(out["values"])))
    ref_params = world.model.make_ref_params(world.params)
    rows = lambda tree, name: jax.tree_util.tree_leaves(tree[name])[0].shape[0]
    assert (rows(ref_params, "blocks"), rows(ref_params, "delta_blocks")) == (1, 1)
    assert "dense_blocks" not in ref_params
    v_branch = world.params["v_branch"]
    assert (rows(v_branch, "blocks"), rows(v_branch, "delta_blocks")) == (0, 1)
    np.testing.assert_array_equal(np.asarray(ref_params["delta_blocks"]["attn"]["A_log"][0]),
                                  np.asarray(world.base["delta_blocks"]["attn"]["A_log"][2]))
    # counters: four routed layers in the policy, and apart from them the branch's two
    assert out["moe_stats"]["load"].shape == (LAYERS - 1, hf["num_experts"])
    assert out["ref_moe_stats"]["load"].shape == (2, hf["num_experts"])
    # the whole forward from the embedding (`forward_from_layer` at layer 1: every
    # segment above the leading layer) is the uncut forward too
    lm = world.lm

    @jax.jit
    def from_layer_one(base):
        cap = lm.forward_with_branch_capture(base, ids, world.mask, 1)
        branch = extract_branch_params(base, 1, lm.cfg)
        return lm.forward_from_layer(branch, cap["branch_hidden"], cap["attn_bias"], cap["positions"],
                                     key_mask=cap["key_mask"])["logits"], cap["logits"]

    branch_logits, whole_logits = from_layer_one(world.base)
    assert np.abs(np.asarray(branch_logits - whole_logits))[real].max() < 1e-5
    assert np.abs(np.asarray(whole_logits - world.want_logits))[real].max() < LOGIT_TOL
    with pytest.raises(NotImplementedError, match="leading dense"):
        CausalLMWithValueHead(world.cfg, branch_at=0).make_ref_params(world.params)
    with pytest.raises(ValueError, match="needs its config"):
        extract_branch_params(world.base, 3)


def test_int8_rollout_weights_cover_the_delta_layers_written_projections(world):
    hf, lm, params, ids, mask = world.hf, world.lm, world.base, world.ids, world.mask
    q = jax.jit(quantize_decode_weights)(params)
    for stack in ("dense_blocks", "delta_blocks"):
        attn = q[stack]["attn"]
        for name in ("q", "k", "v", "o"):
            assert attn[name]["kernel"].dtype == jnp.int8, (stack, name)
        for name in ("f_a", "f_b", "g_a", "g_b", "b"):  # they feed float32 gates
            assert attn[name]["kernel"].dtype == jnp.float32 and "kernel_scale" not in attn[name]
        assert attn["conv_q"].dtype == jnp.float32 and attn["A_log"].dtype == jnp.float32
    assert q["delta_blocks"]["attn"]["q"]["kernel_scale"].shape == (3, 2, 16)
    assert q["blocks"]["attn"]["q_b"]["kernel"].dtype == jnp.int8
    assert q["blocks"]["attn"]["kv_b"]["kernel"].dtype == jnp.float32
    assert q["delta_blocks"]["moe"]["experts_fc_in"]["kernel"].dtype == jnp.int8
    full = world.out["logits"]
    quant = jax.jit(lambda q: lm(q, ids, mask)["logits"])(q)
    err = float(jnp.sqrt(jnp.mean((full - quant) ** 2)) / jnp.sqrt(jnp.mean(full**2)))
    assert 0 < err < 0.1
    lm8 = TransformerLM(lm.cfg.replace(decode_weights_quant="int8"))
    g = jax.jit(lambda p: generate(lm8, p, ids[:, :8], jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(2),
                                   SamplerSettings(max_new_tokens=4)))(params)
    assert g["sequences"].shape == (2, 12)
    made = 2 * (8 + 3) * hf["num_experts_per_token"] * 4  # prefill and three steps, four routed layers
    assert float(g["moe_stats"]["moe/assignments.sampler"]) == made


@pytest.mark.parametrize("bad, match", [
    (dict(kv_cache_quant="int8"), "KDA"),
    (dict(attention_impl="ring"), "ring"),
    (dict(parallel_residual=True), "parallel_residual"),
    (dict(pos_embed="learned"), "learned or alibi"),
    (dict(mixer_layers=("delta",) * 4), "mixer_layers"),
    (dict(mixer_layers=("delta", "delta", "softmax", "latent", "delta")), "mixer_layers"),
    (dict(delta_heads=0), "delta_heads"),
    (dict(first_k_dense=4), "more than one mixer"),
    (dict(delta_value_heads=3), "whole multiple of delta_heads"),
])
def test_what_the_family_does_not_reach_raises_at_configuration_time(bad, match):
    kw = dict(ref.system_config(toy()), n_positions=64)
    with pytest.raises((NotImplementedError, ValueError), match=match):
        TransformerConfig(**dict(kw, **bad))


def test_adapters_paged_engine_pipeline_loader_and_a_prefill_in_pieces_raise_for_the_family(world):
    from trlx_tpu.models.hf import config_from_hf

    lm, params, ids = TransformerLM(world.cfg), world.base, world.ids
    with pytest.raises(NotImplementedError, match="KDA"):
        jax.eval_shape(lambda p: lm(p, ids, prefix_embeds=jnp.zeros((2, lm.cfg.hidden_size))), params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        lm._scan_segment(params["delta_blocks"], None, None, None, cache={"pk": None}, stack="delta_blocks")
    lm._mesh = SimpleNamespace(shape={"pp": 2})
    with pytest.raises(NotImplementedError, match="KDA"):
        lm._pp_microbatches(2, None)
    with pytest.raises(NotImplementedError, match="no loader"):
        config_from_hf(SimpleNamespace(model_type="kimi_linear", linear_attn_config={"kda_layers": [1]}))
    cache = dict(world.lm.init_cache(2, SEQ), index=jnp.int32(8), static_index=8)
    with pytest.raises(NotImplementedError, match="empty cache"):
        jax.eval_shape(lambda base: world.lm(base, ids[:, :8], cache=cache), world.base)


def test_the_memory_plan_counts_the_state_beside_the_latent_cache(world):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.utils.memdoctor import analytic_param_count, analytic_plan

    kw = dict(ref.system_config(PUBLISHED), n_positions=1024)
    assert analytic_param_count(kw) == ref.params_held(PUBLISHED)["total"] == 1_007_274_848
    assert analytic_param_count(dict(ref.system_config(world.hf), n_positions=128)) == sum(
        x.size for x in jax.tree_util.tree_leaves(world.base))
    config = default_ppo_config().evolve(
        train=dict(seq_length=1024, batch_size=8, remat_policy="full"),
        model=dict(model_path="random", model_extra_configs={"transformer": kw}),
        method=dict(chunk_size=32, num_rollouts=32))
    plan = analytic_plan(config, hbm_bytes=16 * 2**30)
    cache = [i for i in plan.items if i.component == "static_kv_cache"][0]
    assert cache.bytes == 2 * 32 * 1024 * 576 * 2 and "2 of 9 layers" in cache.note
    state = [i for i in plan.items if i.component == "recurrent_state"][0]
    assert state.bytes == 7 * 32 * (4 * 32 * 128 * 128 + 2 * 3 * 12288)
    # a decode step reads and writes it once: 0.94 GB of float32 state at 32 rows
    published = TransformerConfig(**kw)
    assert state_bytes_per_step(published, 32) == 2 * state.bytes
    assert state_bytes_per_step(TransformerConfig(vocab_size=64, hidden_size=32, n_layer=2, n_head=2), 8) == 0


@pytest.fixture(scope="module")
def trainer(tmp_path_factory):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.trainer.ppo import TPUPPOTrainer

    eager_init = TransformerLM.init
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(TransformerLM, "init", lambda self, key: jax.jit(lambda k: eager_init(self, k))(key))
        hf = toy(router_bias_balance_steps=4)
        config = default_ppo_config().evolve(
            train=dict(batch_size=8, total_steps=1, seq_length=16, epochs=1, tracker=None,
                       checkpoint_dir=str(tmp_path_factory.mktemp("ckpt")), compute_dtype="float32"),
            model=dict(model_path="random", num_layers_unfrozen=2,
                       model_extra_configs={"transformer": ref.system_config(hf)}),
            tokenizer=dict(tokenizer_path="byte", tokenizer_extra_configs=dict(vocab_size=hf["vocab_size"])),
            method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                        gen_kwargs=dict(max_new_tokens=4, do_sample=True, eos_token_id=-1)))
        yield SimpleNamespace(hf=hf, trainer=TPUPPOTrainer(config, reward_fn=lambda **kw: [0.0] * 8))


def test_the_freeze_mask_addresses_layers_by_their_index_in_the_whole_stack(trainer):
    hf, trainer = trainer.hf, trainer.trainer
    mask = trainer.make_freeze_mask(trainer.params)["base"]
    assert float(np.max(mask["dense_blocks"]["attn"]["o"]["kernel"])) == 0.0
    # KDA layers 1, 2 and 4 of the stack: only the last is above the branch point at 3
    assert np.asarray(mask["delta_blocks"]["attn"]["o"]["kernel"]).ravel().tolist() == [0.0, 0.0, 1.0]
    assert np.asarray(mask["delta_blocks"]["attn"]["A_log"]).ravel().tolist() == [0.0, 0.0, 1.0]
    assert np.asarray(mask["blocks"]["attn"]["o"]["kernel"]).ravel().tolist() == [1.0]
    assert float(mask["blocks"]["moe"]["router_bias"]) == 0.0 and float(mask["delta_blocks"]["moe"]["router_gate"]) == 0.0
    assert float(mask["embed"]["wte"]) == 0.0 and float(mask["lm_head"]["kernel"]) == 1.0
    assert trainer.model.frozen_below() == 3
    with pytest.raises(NotImplementedError, match="paged decode engine"):
        trainer._engine_eligible()


def test_the_block_that_holds_the_delta_rule_trunk_equals_the_block_that_runs_it_in_every_step(trainer):
    """Hydra top 2 of 5: the trunk is the leading dense KDA layer and two routed KDA
    layers, in two stacks; the fused block holds its output across two epochs and
    leaves parameters, optimizer state, loss and stats as the block that runs the
    chunked form through it in every step does (the counters of pairs and of sorted
    rows count the trunk's once a block: `tests/test_latent_moe.py`)."""
    from tests.test_frozen_trunk import (COUNTED_ONCE_A_BLOCK, assert_same_block, block_both_ways,
                                         block_perms, rollout_batch)

    hf, trainer = trainer.hf, trainer.trainer
    assert trainer.trunk_layers_held() == trainer.model.frozen_below() == 3
    rows = rollout_batch(False, rows=8, p=12, vocab=hf["vocab_size"])
    (capture,), counters = jax.eval_shape(trainer.trunk_constants, trainer.params, rows)
    assert capture.shape == (8, 16, hf["hidden_size"]) and counters["load"].shape[0] == 2
    held, whole = block_both_ways(trainer, rows, block_perms(8, 8, 2))
    assert np.isfinite(held[2])
    assert_same_block(held, whole, skip=COUNTED_ONCE_A_BLOCK)


def test_gauges_and_the_state_count_reach_the_flight_stream(trainer, caplog):
    """`model/delta_layers`, `model/latent_layers`, `model/state_elems_per_row` beside
    `model/cache_elems_per_position` once per built train step; `state_bytes_carried`
    on the `tokens_wait` span: 3 decode steps of 8 rows through 4 KDA layers."""
    from trlx_tpu.models.transformer import _warn_state_step_unfused
    from trlx_tpu.obs.recorder import iter_rows

    hf, trainer = trainer.hf, trainer.trainer
    H, D = hf["linear_attn_config"]["num_heads"], hf["linear_attn_config"]["head_dim"]
    gauges = []
    real_gauge = trainer.obs.gauge
    trainer.obs.gauge = lambda **kw: gauges.append(kw)
    try:
        trainer._note_backward_depth()
    finally:
        trainer.obs.gauge = real_gauge
    per_row = 4 * (H * D * D + 3 * 3 * H * D)
    assert gauges[0]["model/delta_layers"] == 4 and gauges[0]["model/latent_layers"] == 1
    assert gauges[0]["model/state_elems_per_row"] == per_row
    assert gauges[0]["model/cache_elems_per_position"] == hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    assert gauges[0]["model/experts_held"] == hf["num_experts"] and gauges[0]["model/backward_layers"] == 2
    trainer.obs.start(step=0)
    _warn_state_step_unfused.cache_clear()  # once a process and reason: another file's may have been first
    logger = logging.getLogger("trlx_tpu.models.transformer")  # the library's loggers do not propagate
    logger.addHandler(caplog.handler)
    try:
        out = trainer.generate(np.ones((8, 12), np.int32))
    finally:
        logger.removeHandler(caplog.handler)
    trainer._pull_sampled_tokens(out, 8, {})
    trainer.obs.end_cycle(step=0)
    rows = list(iter_rows(os.path.join(trainer.config.train.checkpoint_dir, "flight")))
    # the trainer's mesh is the eight CPU devices: the recurrent layers' decode step takes
    # the XLA branch and says why, once for all its layers; on one device it is the kernel
    assert [r["gen/state_step_fused"] for r in rows if "gen/state_step_fused" in r] == [0]
    assert [r.getMessage() for r in caplog.records if "recurrent state" in r.getMessage()] == [
        "a decode step passes over the recurrent state in XLA ops, not in the fused kernel "
        "(a mesh of 8 devices: the kernel is one chip's program)"]
    mesh, trainer._lm().mesh = trainer._lm().mesh, None
    try:
        trainer.obs.gauge = lambda **kw: gauges.append(kw)
        trainer._note_decode_attn((trainer.generate_experience_settings, (8, 12), ()))
    finally:
        trainer.obs.gauge, trainer._lm().mesh = real_gauge, mesh
    assert gauges[-1]["gen/state_step_fused"] == 1 and gauges[-1]["gen/decode_attn_fused"] == 0
    (cycle,) = [r for r in rows if r["kind"] == "cycle"]
    (counts,) = [c for name, *_, c in cycle["spans"] if name == "tokens_wait"]
    assert counts["state_bytes_carried"] == 3 * 2 * 8 * 4 * (4 * H * D * D + 4 * 3 * 3 * H * D)
    assert counts["rows"] == 8 and counts["tokens"] == 32 and counts["moe/assignments.sampler"] > 0


def test_a_random_hybrid_model_is_balanced_in_every_routed_stack_and_every_copy_takes_the_bias(trainer):
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline

    trainer = trainer.trainer
    base = trainer.params["base"]
    assert not np.asarray(base["delta_blocks"]["moe"]["router_bias"]).any()
    prompts = ["".join(chr(c) for c in row) for row in
               np.asarray(jax.random.randint(jax.random.PRNGKey(3), (8, 12), 97, 102))]
    trainer.add_prompt_pipeline(PromptPipeline(prompts, 12, trainer.tokenizer))
    base = trainer.params["base"]
    delta, latent = (np.asarray(base[name]["moe"]["router_bias"]) for name in ("delta_blocks", "blocks"))
    assert delta.shape[0] == 3 and latent.shape[0] == 1
    assert np.abs(delta).max(axis=-1).min() > 0.0 and np.abs(latent).max() > 0.0
    # the frozen reference is the top two layers: the MLA layer and the last KDA layer
    np.testing.assert_array_equal(np.asarray(trainer.ref_params["blocks"]["moe"]["router_bias"]), latent)
    np.testing.assert_array_equal(np.asarray(trainer.ref_params["delta_blocks"]["moe"]["router_bias"]), delta[-1:])
