"""Mamba-2 state-space layers with a recurrent state in the sampler's carry, beside
grouped-query softmax attention with a per-head key and value cache and latent-space
routed experts, in a stack whose every layer is ONE sub-layer: the program against the
plain reference (`benchmark/reference/nemotron_h_ref.py`, the state space as a
recurrence over positions, which imports nothing of the program) at toy sizes from its
`toy_sizes`, on seeded random weights.

Tolerances: compute is float32 on the CPU here, the reference float32 at the highest
matmul precision. Differences are reduction order alone (the chunked form sums what
the recurrence accumulates): 2e-4 on logits of order 3 and 1e-3 relative on gradients.

One toy stack serves every parity test: M E M * E M E, so that the cut at the top-2
branch point leaves two `M`, two `E` and the attention layer frozen under one trainable
`M` and one trainable `E` layer. It is built, run forward and differentiated ONCE,
jitted (`world`, module scope), beside the reference's forward and `jax.grad`.
Sequences are 44 long: three chunks of 16 of the chunked form, the last padded, as are
the prefill's (36) and the form's own test's (50)."""

import json
import logging
import os
import zlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h_ref as ref
from trlx_tpu.models.generation import SamplerSettings, generate, state_bytes_per_step
from trlx_tpu.models.transformer import (
    RoutedMLP,
    TransformerConfig,
    TransformerLM,
    extract_branch_params,
    layer_stacks,
    quantize_decode_weights,
    ssm_chunked,
    ssm_step,
    state_step_unfused,
)
from trlx_tpu.models.wrappers import CausalLMWithValueHead

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs", "nemotron-3-super-120b-a12b.json")) as _f:
    PUBLISHED = json.load(_f)
LOGIT_TOL, GRAD_RTOL = 2e-4, 1e-3
LAYERS, SEQ = 7, 44


def toy(**over):
    hf = dict(PUBLISHED, **ref.toy_sizes(PUBLISHED))
    hf.update(correct={"routing_margin": 1e-4})
    hf.update(over)
    return hf


def liven(params):
    """Seeded values for what initialises to constants (norm scales, D, the selection
    bias) and larger weights, so that every term of every equation carries signal.
    The decay's parameters, the taps and their bias keep their own seeded start."""
    def one(path, x):
        name = path[-1].key
        key = jax.random.fold_in(jax.random.PRNGKey(7), zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31))
        if name == "router_bias":
            return 0.3 * jax.random.normal(key, x.shape)
        if name in ("scale", "norm", "D"):
            return 1.0 + 0.2 * jax.random.normal(key, x.shape)
        if name in ("A_log", "dt_bias") or name.startswith("conv_"):
            return x
        return 5.0 * x

    return jax.tree_util.tree_map_with_path(one, params)


def batch(rows=2, seq=SEQ, vocab=512, pad=5):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    return ids, jnp.ones((rows, seq), jnp.int32).at[0, :pad].set(0)  # row 0 left-padded


def top2(tree):
    """What `num_layers_unfrozen` 2 trains: the `M` layer (published 6 of the toy's 7)
    and the `E` layer above it, the final norm and the head."""
    last = lambda name: jax.tree_util.tree_map(lambda x: x[-1:], tree[name])
    return {"ssm_blocks": last("ssm_blocks"), "moe_blocks": last("moe_blocks"),
            "ln_f": tree["ln_f"], "lm_head": tree["lm_head"]}


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
        with_top = lambda name: jax.tree_util.tree_map(
            lambda low, top: jnp.concatenate([low[:-1], top]), base[name], trained[name])
        base = dict(base, ln_f=trained["ln_f"], lm_head=trained["lm_head"],
                    ssm_blocks=with_top("ssm_blocks"), moe_blocks=with_top("moe_blocks"))
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


def test_the_stack_is_segments_of_layers_that_are_one_sub_layer_each(world):
    assert world.cfg.mixers == ("ssm", "none", "ssm", "softmax", "none", "ssm", "none")
    assert world.cfg.ffns == ("none", "routed", "none", "none", "routed", "none", "routed")
    assert layer_stacks(world.cfg) == (
        ("ssm_blocks", 0), ("moe_blocks", 0), ("ssm_blocks", 1), ("attn_blocks", 0), ("moe_blocks", 1),
        ("ssm_blocks", 2), ("moe_blocks", 2))
    rows = {name: jax.tree_util.tree_leaves(world.base[name])[0].shape[0]
            for name in ("ssm_blocks", "attn_blocks", "moe_blocks")}
    assert rows == {"ssm_blocks": 3, "attn_blocks": 1, "moe_blocks": 3}
    # a layer has the norm of what it has, and nothing of what it has not
    assert set(world.base["ssm_blocks"]) == {"ln_1", "ssm"} and set(world.base["attn_blocks"]) == {"ln_1", "attn"}
    assert set(world.base["moe_blocks"]) == {"ln_2", "moe"}
    assert "experts_fc_gate" not in world.base["moe_blocks"]["moe"]  # two products an expert, no gate
    assert world.base["moe_blocks"]["moe"]["experts_fc_in"]["kernel"].shape == (3, 4, 32, 32)  # in the latent space
    assert world.base["moe_blocks"]["moe"]["shared"]["fc_in"]["kernel"].shape == (3, 64, 48)  # reads x, its own width
    # the published cut: layers 1-11, M : E : * = 5 : 5 : 1, the top two one `M` and one `E`
    kw = ref.system_config(PUBLISHED)
    published = TransformerConfig(**dict(kw, n_positions=1024))
    assert [name for name, _ in layer_stacks(published)] == [
        {"M": "ssm_blocks", "E": "moe_blocks", "*": "attn_blocks"}[c] for c in "MEMEMEM*EME"]
    assert published.cache_layers == 1 and published.cache_elems_per_position == 512
    assert published.state_elems_per_row == 5 * (128 * 64 * 128 + 3 * 10240)
    assert (published.n_routed_experts, published.n_experts_held, published.n_experts_per_token) == (512, 8, 22)


def test_scorer_logits_match_the_reference(world):
    """The teacher-forced forward (chunked form in three `M` layers, grouped-query
    attention without positions in the fourth layer, latent-space experts in three)
    through the hydra capture against the reference's recurrence, on real positions."""
    real = np.asarray(world.mask) > 0
    assert np.abs(np.asarray(world.want_logits))[real].max() > 1.0
    assert np.abs(np.asarray(world.out["logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert float(world.decisive.mean()) > 0.9  # the margin 1e-4 leaves ties to chance alone


def test_trainable_gradients_match_the_reference_and_the_backward_stops_at_the_branch_point(world):
    """`frozen_below` at the top-2 branch point: one `M` layer (through the checkpointed
    scan of the chunked form) and one `E` layer, the final norm and the head against the
    reference's `jax.grad`; below the branch point the backward never ran."""
    grads = jax.tree_util.tree_map(np.asarray, world.grads)
    assert_gradients_close(top2(grads), world.want_grads)
    below = lambda name: jax.tree_util.tree_map(lambda x: x[:-1], grads[name])
    frozen = [below("ssm_blocks"), below("moe_blocks"), grads["attn_blocks"], grads["embed"]]
    assert all(np.abs(x).max() == 0.0 for x in jax.tree_util.tree_leaves(frozen))
    moved = [np.abs(x).max() for x in jax.tree_util.tree_leaves(top2(grads)["ssm_blocks"]["ssm"])]
    assert min(moved) > 0.0  # every parameter of the trainable mixer: A_log, D, dt_bias, taps and bias too


def _recurrence(x, b, c, dt, a, state):
    def step(s, at):
        y, s = ssm_step(*at, a, s)
        return s, y

    state, y = jax.lax.scan(step, state, tuple(jnp.moveaxis(v, 1, 0) for v in (x, b, c, dt)))
    return jnp.moveaxis(y, 0, 1), state


def _value_and_grads(form):
    def f(x, b, c, dt, a, state):
        y, s = form(x, b, c, dt, a, state)
        return jnp.sum(y * jnp.cos(y)) + jnp.sum(s * s), (y, s)
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True))


# two programs for the four cases: compiled once each
CHUNKED = _value_and_grads(lambda *args: ssm_chunked(*args, chunk=16))
RECURRENT = _value_and_grads(_recurrence)


@pytest.mark.parametrize("steps", ["mild", "strong", "dt_zero", "mixed"])
def test_the_chunked_form_equals_the_recurrence(steps):
    """50 positions in chunks of 16 (the last chunk is padded), from a non-zero state,
    8 heads in 2 groups. `strong`: a decay of e^-8 a step in every head, e^-128 across a
    chunk, whose inverse no float32 holds: the differences of cumulative log-decays are
    formed before they are exponentiated. `dt_zero`: nothing is written and nothing
    decays (what a masked position does). `mixed`: every third position decays by up to
    e^-5, the rest hardly. Outputs, final state and the gradients of x, B, C, dt and a."""
    B, T, H, P, G, N = 2, 50, 8, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    b = jax.random.normal(ks[1], (B, T, G, N))
    c = jax.random.normal(ks[2], (B, T, G, N))
    dt = 0.5 * jax.random.uniform(ks[3], (B, T, H))
    a = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=1.0))
    if steps == "strong":
        dt, a = jnp.full_like(dt, 1.0), jnp.full_like(a, -8.0)
    elif steps == "dt_zero":
        dt = jnp.zeros_like(dt)
    elif steps == "mixed":
        dt = dt * jnp.where(jnp.arange(T) % 3 == 0, 4.0, 0.01)[None, :, None]
    state = jax.random.normal(ks[5], (B, H, P, N))

    (_, (y, s)), grads = CHUNKED(x, b, c, dt, a, state)
    (_, (want_y, want_s)), want = RECURRENT(x, b, c, dt, a, state)
    assert bool(jnp.all(jnp.isfinite(y))) and all(bool(jnp.all(jnp.isfinite(g))) for g in grads)
    assert float(jnp.abs(y - want_y).max()) < 1e-5 * max(float(jnp.abs(want_y).max()), 1.0)
    assert float(jnp.abs(s - want_s).max()) < 1e-5 * max(float(jnp.abs(want_s).max()), 1.0)
    # a gradient is held to 1e-4 of its own size and to float32's rounding of the terms it
    # sums: under `strong` the gradient of `a` is 0.1, a sum of 800 terms of e^-8 each,
    # beside gradients of dt of 300
    scale = max(float(jnp.abs(wanted).max()) for wanted in want)
    for got, wanted in zip(grads, want):
        assert float(jnp.abs(got - wanted).max()) <= 1e-4 * float(jnp.abs(wanted).max()) + 3e-6 + 2e-7 * scale
    if steps == "dt_zero":  # the state is left as it was, the output only reads it
        np.testing.assert_allclose(np.asarray(s), np.asarray(state), rtol=1e-6)


def _decoder(lm, base):
    """Prefill of all but 8 tokens (row 0 left-padded), then 8 single-token steps: the
    chunked form's final state and last three convolution inputs handed to the one-step
    form, the attention layer's k and v rows beside them. Returns `run(ids, mask)`: the
    logits of each step and the cache at the end."""

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


def test_prefill_then_decode_steps_through_state_tail_and_kv_rows_match_the_full_forward(world, decoded):
    hf, lm = world.hf, world.lm
    cache = lm.init_cache(2, SEQ, world.mask)
    H, P, N = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"]
    conv = H * P + 2 * hf["n_groups"] * N
    # each stack its own arrays: the three `M` layers' float32 state and convolution tail,
    # the one attention layer's k and v rows; an `E` layer keeps nothing
    assert cache["ssm_s"].shape == (3, 2, H, P, N) and cache["ssm_s"].dtype == jnp.float32
    assert cache["ssm_u"].shape == (3, 2, 3, conv)
    assert cache["k"].shape == cache["v"].shape == (1, 2, SEQ, hf["num_key_value_heads"], hf["head_dim"])
    assert set(cache) == {"ssm_s", "ssm_u", "k", "v", "index", "static_index", "key_mask"}
    got, cache = decoded.result
    P0 = decoded.P
    assert P0 % hf["chunk_size"]  # the prefill is not whole chunks
    assert float(jnp.abs(got - world.want_logits[:, P0 - 1 : SEQ - 1]).max()) < LOGIT_TOL
    assert int(cache["index"]) == SEQ - 1
    assert float(jnp.abs(cache["ssm_s"]).min(axis=(1, 2, 3, 4)).max()) >= 0 and float(jnp.abs(cache["ssm_s"][2]).max()) > 0
    assert float(jnp.abs(cache["ssm_u"]).max()) > 0 and float(jnp.abs(cache["k"][0, :, SEQ - 2]).max()) > 0


def test_a_left_padded_row_decodes_as_the_same_row_unpadded(world, decoded):
    """Row 0 has 5 pad slots in front: its state stays zero and its convolution window
    empty until its first token, so its logits are those of the 39 real tokens run
    alone, through prefill and through the decode steps."""
    pad = 5
    alone = world.ids[:1, pad:]
    want, cache = decoded.run(alone, jnp.ones_like(alone))
    got, padded = decoded.result
    assert float(jnp.abs(got[:1] - want).max()) < LOGIT_TOL
    np.testing.assert_allclose(np.asarray(padded["ssm_s"][:, :1]), np.asarray(cache["ssm_s"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(padded["ssm_u"][:, :1]), np.asarray(cache["ssm_u"]), atol=1e-5)


def test_decode_steps_on_a_mesh_of_two_devices_take_the_xla_branch_and_agree_with_the_kernels(world, decoded):
    """More than one device is the static condition that sends a decode step of the
    recurrent layers to `ssm_step` on a slice of the carry (`state_step_unfused`): the same
    prefill and steps there give the kernel's logits and states to float32 rounding."""
    from trlx_tpu.parallel import make_mesh

    lm = TransformerLM(world.cfg)
    lm.mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
    assert "2 devices" in state_step_unfused(world.cfg, lm.mesh)
    got, cache = _decoder(lm, world.base)(world.ids, world.mask)
    want, kernels = decoded.result
    assert float(jnp.abs(got - want).max()) < 2e-5
    np.testing.assert_allclose(np.asarray(cache["ssm_s"]), np.asarray(kernels["ssm_s"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache["ssm_u"]), np.asarray(kernels["ssm_u"]), atol=1e-5)


def test_sixty_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The share tied to the model: the `E` layer with 8 of 512 experts held, top-22, on
    each of the 64 chips of the deployment (each share's r W_up), the shared expert
    counted once, against the reference's uncut layer (all 512 held)."""
    hf = toy(n_routed_experts_published=512, n_routed_experts=8, num_experts_per_tok=22)
    kw = dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32)
    whole_hf = dict(hf, n_routed_experts=512, first_expert_held=0)
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 16, hf["hidden_size"]))
    whole = RoutedMLP(TransformerConfig(**dict(kw, n_experts_held=512)))
    p = jax.jit(lambda k: whole.init(k, x)["params"])(key)
    p = jax.tree_util.tree_map(lambda a: 5.0 * a, p)
    p["router_bias"] = 0.3 * jax.random.normal(key, p["router_bias"].shape)
    names = {"w_r": p["router_gate"], "b": p["router_bias"],
             "w_down": p["latent_in"]["kernel"], "w_up": p["latent_out"]["kernel"],
             "w_1": p["experts_fc_in"]["kernel"], "w_2": p["experts_fc_out"]["kernel"],
             "shared_1": p["shared"]["fc_in"]["kernel"], "shared_2": p["shared"]["fc_out"]["kernel"]}

    @jax.jit
    def uncut(names):
        with jax.default_matmul_precision("highest"):
            return ref._experts(x, names, whole_hf, 0.0)[0], ref.shared_expert(x, names)

    def chip(first):
        cfg = TransformerConfig(**dict(kw, n_experts_held=8, first_expert_held=first))

        def apply(p, held):
            mine = dict(p, **{k: {"kernel": jax.lax.dynamic_slice_in_dim(p[k]["kernel"], held, 8)}
                              for k in ("experts_fc_in", "experts_fc_out")})
            return RoutedMLP(cfg).apply({"params": mine}, x)
        return jax.jit(apply)

    def rolled(p, shift):
        """The router's experts renumbered so that expert `shift` is expert 0: the
        choice of a top-k is the same under any numbering."""
        return dict(p, router_gate=jnp.roll(p["router_gate"], -shift, axis=1),
                    router_bias=jnp.roll(p["router_bias"], -shift))

    want, shared = uncut(names)
    first_chip = chip(0)  # one program for the 64 chips: chip c sees its experts as 0-7
    total, pairs = shared, 0.0
    for index in range(64):
        y, stats = first_chip(rolled(p, 8 * index), 8 * index)
        total = total + (y - shared)
        pairs += float(stats["load"].sum())
    assert float(jnp.abs(want - shared).max()) > 0.02 * float(jnp.abs(want).max())  # the routed part is there
    assert float(jnp.abs(total - want).max()) < 1e-4 * float(jnp.abs(want).max())
    assert pairs == 2 * 16 * 22  # every assignment computed on exactly one chip
    # a chip that holds experts 16-23 under their own numbers (`first_expert_held` 16) gives
    # what the renumbered router gave, and what the reference's held part gives
    y16 = chip(16)(p, 16)[0]
    assert float(jnp.abs(y16 - first_chip(rolled(p, 16), 16)[0]).max()) < 1e-5 * float(jnp.abs(want).max())
    held_hf = dict(hf, first_expert_held=16)
    mine = dict(names, **{k: names[k][16:24] for k in ("w_1", "w_2")})
    with jax.default_matmul_precision("highest"):
        ref_part = jax.jit(lambda x, mine: ref._experts(x, mine, held_hf, 0.0)[0])(x, mine)
    assert float(jnp.abs(y16 - ref_part).max()) < 1e-4 * float(jnp.abs(want).max())


def test_hydra_branch_and_forward_from_layer_over_one_sub_layer_layers_agree_with_the_uncut_forward(world):
    """The reference branch (the last row of `ssm_blocks` and of `moe_blocks`, no row of
    `attn_blocks`) run from the capture at the branch point gives the uncut forward's
    logits; the value branch forks one layer higher and holds the top `E` layer alone."""
    hf, out, ids = world.hf, world.out, world.ids
    real = np.asarray(world.mask) > 0
    assert out["branch_hidden"].shape == ids.shape + (hf["hidden_size"],)
    assert np.abs(np.asarray(out["ref_logits"] - world.want_logits))[real].max() < LOGIT_TOL
    assert np.abs(np.asarray(out["ref_logits"] - out["logits"]))[real].max() < 1e-5
    assert out["values"].shape == ids.shape and bool(jnp.all(jnp.isfinite(out["values"])))
    ref_params = world.model.make_ref_params(world.params)
    rows = lambda tree, name: jax.tree_util.tree_leaves(tree[name])[0].shape[0]
    assert [rows(ref_params, n) for n in ("ssm_blocks", "attn_blocks", "moe_blocks")] == [1, 0, 1]
    v_branch = world.params["v_branch"]
    assert [rows(v_branch, n) for n in ("ssm_blocks", "attn_blocks", "moe_blocks")] == [0, 0, 1]
    np.testing.assert_array_equal(np.asarray(ref_params["ssm_blocks"]["ssm"]["A_log"][0]),
                                  np.asarray(world.base["ssm_blocks"]["ssm"]["A_log"][2]))
    # counters: three `E` layers in the policy, and apart from them the branch's one
    assert out["moe_stats"]["load"].shape == (3, hf["n_routed_experts"])
    assert out["ref_moe_stats"]["load"].shape == (1, hf["n_routed_experts"])
    # a branch from layer 2 (M * E M E above an `M` and an `E` layer) is the uncut forward too
    lm = world.lm

    @jax.jit
    def from_layer_two(base):
        cap = lm.forward_with_branch_capture(base, ids, world.mask, 2)
        branch = extract_branch_params(base, 2, lm.cfg)
        return lm.forward_from_layer(branch, cap["branch_hidden"], cap["attn_bias"], cap["positions"],
                                     key_mask=cap["key_mask"])["logits"], cap["logits"]

    branch_logits, whole_logits = from_layer_two(world.base)
    assert np.abs(np.asarray(branch_logits - whole_logits))[real].max() < 1e-5
    assert np.abs(np.asarray(whole_logits - world.want_logits))[real].max() < LOGIT_TOL
    with pytest.raises(ValueError, match="needs its config"):
        extract_branch_params(world.base, 3)


def test_trunk_constants_resume_at_the_branch_point_over_one_sub_layer_layers(world):
    """What PR 34's fused block holds: the capture entering layer 5 (the hidden state
    after M E M * E), and the forward resumed there gives the uncut forward's logits."""
    lm, ids, mask = world.lm, world.ids, world.mask
    assert lm.resume_point((5,), 5) == 5

    @jax.jit
    def both(base):
        trunk = lm.trunk_constants(base, ids, mask, (5,), 5)
        resumed = lm.forward_with_multi_capture(base, ids, mask, (5,), frozen_below=5, trunk=trunk)
        whole = lm.forward_with_multi_capture(base, ids, mask, (5,), frozen_below=5)
        return trunk, resumed, whole

    (captures, counters), resumed, whole = both(world.base)
    assert len(captures) == 1 and captures[0].shape == ids.shape + (world.hf["hidden_size"],)
    assert counters["load"].shape == (2, world.hf["n_routed_experts"])  # the trunk's two `E` layers
    real = np.asarray(mask) > 0
    assert np.abs(np.asarray(resumed["logits"] - whole["logits"]))[real].max() < 1e-5
    np.testing.assert_allclose(np.asarray(resumed["captures"][0]), np.asarray(whole["captures"][0]), atol=1e-6)
    assert resumed["moe_stats"]["load"].shape == (3, world.hf["n_routed_experts"])


def test_int8_rollout_weights_cover_both_projections_attention_the_latent_pair_and_the_experts(world):
    hf, lm, params, ids, mask = world.hf, world.lm, world.base, world.ids, world.mask
    q = jax.jit(quantize_decode_weights)(params)
    ssm = q["ssm_blocks"]["ssm"]
    for name in ("in_proj", "out_proj"):
        assert ssm[name]["kernel"].dtype == jnp.int8 and ssm[name]["kernel_scale"].shape[0] == 3
    for name in ("A_log", "D", "dt_bias", "conv_w", "conv_b", "norm"):
        assert ssm[name].dtype == jnp.float32, name
    for name in ("q", "k", "v", "o"):
        assert q["attn_blocks"]["attn"][name]["kernel"].dtype == jnp.int8, name
    moe = q["moe_blocks"]["moe"]
    for name in ("latent_in", "latent_out", "experts_fc_in", "experts_fc_out"):
        assert moe[name]["kernel"].dtype == jnp.int8, name
    assert moe["shared"]["fc_in"]["kernel"].dtype == moe["shared"]["fc_out"]["kernel"].dtype == jnp.int8
    assert moe["experts_fc_in"]["kernel_scale"].shape == (3, 4, 32)  # an expert and output channel
    assert moe["router_gate"].dtype == jnp.float32 and q["moe_blocks"]["ln_2"]["scale"].dtype == jnp.float32
    full = world.out["logits"]
    quant = jax.jit(lambda q: lm(q, ids, mask)["logits"])(q)
    err = float(jnp.sqrt(jnp.mean((full - quant) ** 2)) / jnp.sqrt(jnp.mean(full**2)))
    assert 0 < err < 0.1
    lm8 = TransformerLM(lm.cfg.replace(decode_weights_quant="int8"))
    g = jax.jit(lambda p: generate(lm8, p, ids[:, :8], jnp.ones((2, 8), jnp.int32), jax.random.PRNGKey(2),
                                   SamplerSettings(max_new_tokens=4)))(params)
    assert g["sequences"].shape == (2, 12)
    made = 2 * (8 + 3) * hf["num_experts_per_tok"] * 3  # prefill and three steps, three `E` layers
    assert float(g["moe_stats"]["moe/assignments.sampler"]) == made


@pytest.mark.parametrize("bad, match", [
    (dict(kv_cache_quant="int8"), "state-space"),
    (dict(attention_impl="ring"), "ring"),
    (dict(parallel_residual=True), "parallel_residual"),
    (dict(mixer_layers=("ssm",) * 6), "mixer_layers"),
    (dict(ffn_layers=None), "states ffn_layers"),
    (dict(ffn_layers=("none",) * 7), "mixer 'none' with feed-forward 'none'"),
    (dict(ffn_layers=("routed",) * 7), "mixer 'ssm' with feed-forward 'routed'"),
    (dict(ffn_layers=("none", "dense", "none", "none", "routed", "none", "routed")), "is 'none' or 'routed'"),
    (dict(ssm_heads=0), "ssm_heads"),
    (dict(ssm_groups=3), "whole groups"),
    (dict(residual_streams=4), "one sub-layer under several residual streams"),
    (dict(mixer_layers=("ssm", "none", "delta", "softmax", "none", "ssm", "none"), delta_heads=2, delta_head_dim=16),
     "state-space .Mamba-2. layers beside delta-rule layers"),
])
def test_what_the_family_does_not_reach_raises_at_configuration_time(bad, match):
    kw = dict(ref.system_config(toy()), n_positions=64)
    with pytest.raises((NotImplementedError, ValueError), match=match):
        TransformerConfig(**dict(kw, **bad))


def test_adapters_paged_engine_pipeline_and_loader_raise_for_the_family(world):
    from trlx_tpu.models.hf import config_from_hf

    lm, params, ids = TransformerLM(world.cfg), world.base, world.ids
    with pytest.raises(NotImplementedError, match="Mamba-2"):
        jax.eval_shape(lambda p: lm(p, ids, prefix_embeds=jnp.zeros((2, lm.cfg.hidden_size))), params)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        lm._scan_segment(params["ssm_blocks"], None, None, None, cache={"pk": None}, stack="ssm_blocks")
    lm._mesh = SimpleNamespace(shape={"pp": 2})
    with pytest.raises(NotImplementedError, match="Mamba-2"):
        lm._pp_microbatches(2, None)
    with pytest.raises(NotImplementedError, match="no loader"):
        config_from_hf(SimpleNamespace(model_type="nemotron_h", hybrid_override_pattern="M*E"))


def test_the_memory_plan_counts_the_state_beside_the_cache(world):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.utils.memdoctor import analytic_param_count, analytic_plan

    kw = dict(ref.system_config(PUBLISHED), n_positions=1024)
    assert analytic_param_count(kw) == ref.params_held(PUBLISHED)["total"] == 1_210_931_584
    assert analytic_param_count(dict(ref.system_config(world.hf), n_positions=128)) == sum(
        x.size for x in jax.tree_util.tree_leaves(world.base))
    config = default_ppo_config().evolve(
        train=dict(seq_length=1024, batch_size=8, remat_policy="full"),
        model=dict(model_path="random", model_extra_configs={"transformer": kw}),
        method=dict(chunk_size=32, num_rollouts=32))
    plan = analytic_plan(config, hbm_bytes=16 * 2**30)
    cache = [i for i in plan.items if i.component == "static_kv_cache"][0]
    assert cache.bytes == 1 * 32 * 1024 * 512 * 2 and "1 of 11 layers" in cache.note
    state = [i for i in plan.items if i.component == "recurrent_state"][0]
    assert state.bytes == 5 * 32 * (4 * 128 * 64 * 128 + 2 * 3 * 10240) and "5 state-space layers" in state.note
    # a decode step reads and writes it once: 1.36 GB at 32 rows (4.26 MB a row and layer)
    published = TransformerConfig(**kw)
    assert state_bytes_per_step(published, 32) == 2 * state.bytes == 1_361_838_080
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


def test_the_freeze_mask_counts_published_layers_each_one_sub_layer(trainer):
    trainer = trainer.trainer
    mask = trainer.make_freeze_mask(trainer.params)["base"]
    # `num_layers_unfrozen` 2 is two PUBLISHED layers: the `M` layer 5 and the `E` layer 6
    assert np.asarray(mask["ssm_blocks"]["ssm"]["out_proj"]["kernel"]).ravel().tolist() == [0.0, 0.0, 1.0]
    assert np.asarray(mask["ssm_blocks"]["ssm"]["A_log"]).ravel().tolist() == [0.0, 0.0, 1.0]
    assert np.asarray(mask["ssm_blocks"]["ln_1"]["scale"]).ravel().tolist() == [0.0, 0.0, 1.0]
    assert np.asarray(mask["attn_blocks"]["attn"]["o"]["kernel"]).ravel().tolist() == [0.0]
    assert np.asarray(mask["moe_blocks"]["moe"]["latent_in"]["kernel"]).ravel().tolist() == [0.0, 0.0, 1.0]
    assert np.asarray(mask["moe_blocks"]["moe"]["experts_fc_in"]["kernel"]).ravel().tolist() == [0.0, 0.0, 1.0]
    assert float(mask["moe_blocks"]["moe"]["router_bias"]) == 0.0 and float(mask["moe_blocks"]["moe"]["router_gate"]) == 0.0
    assert float(mask["embed"]["wte"]) == 0.0 and float(mask["lm_head"]["kernel"]) == 1.0
    assert trainer.model.frozen_below() == 5
    with pytest.raises(NotImplementedError, match="paged decode engine"):
        trainer._engine_eligible()


def test_the_block_that_holds_the_state_space_trunk_equals_the_block_that_runs_it_in_every_step(trainer):
    """Hydra top 2 of 7: the trunk is M E M * E, five segments in three stacks; the
    fused block holds its output across two epochs and leaves parameters, optimizer
    state, loss and stats as the block that runs the chunked form through it in every
    step does (the counters of pairs and of sorted rows count the trunk's once a
    block: `tests/test_latent_moe.py`)."""
    from tests.test_frozen_trunk import (COUNTED_ONCE_A_BLOCK, assert_same_block, block_both_ways,
                                         block_perms, rollout_batch)

    hf, trainer = trainer.hf, trainer.trainer
    assert trainer.trunk_layers_held() == trainer.model.frozen_below() == 5
    rows = rollout_batch(False, rows=8, p=12, vocab=hf["vocab_size"])
    (capture,), counters = jax.eval_shape(trainer.trunk_constants, trainer.params, rows)
    assert capture.shape == (8, 16, hf["hidden_size"]) and counters["load"].shape[0] == 2
    held, whole = block_both_ways(trainer, rows, block_perms(8, 8, 2))
    assert np.isfinite(held[2])
    assert_same_block(held, whole, skip=COUNTED_ONCE_A_BLOCK)


def test_gauges_and_the_state_count_reach_the_flight_stream(trainer, caplog):
    """`model/ssm_layers`, `model/cache_layers`, `model/routed_layers`,
    `model/state_elems_per_row` beside `model/cache_elems_per_position` and
    `model/experts_held` once per built train step; `state_bytes_carried` on the
    `tokens_wait` span: 3 decode steps of 8 rows through 3 `M` layers."""
    from trlx_tpu.models.transformer import _warn_state_step_unfused
    from trlx_tpu.obs.recorder import iter_rows

    hf, trainer = trainer.hf, trainer.trainer
    H, P, N = hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"]
    conv = H * P + 2 * hf["n_groups"] * N
    gauges = []
    real_gauge = trainer.obs.gauge
    trainer.obs.gauge = lambda **kw: gauges.append(kw)
    try:
        trainer._note_backward_depth()
    finally:
        trainer.obs.gauge = real_gauge
    assert (gauges[0]["model/ssm_layers"], gauges[0]["model/cache_layers"], gauges[0]["model/routed_layers"]) == (3, 1, 3)
    assert gauges[0]["model/state_elems_per_row"] == 3 * (H * P * N + 3 * conv)
    assert gauges[0]["model/cache_elems_per_position"] == 2 * hf["num_key_value_heads"] * hf["head_dim"]
    assert gauges[0]["model/experts_held"] == hf["n_routed_experts"] and gauges[0]["model/backward_layers"] == 2
    assert gauges[0]["model/layers"] == 7 and "model/delta_layers" not in gauges[0]
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
    assert counts["state_bytes_carried"] == 3 * 2 * 8 * 3 * (4 * H * P * N + 4 * 3 * conv)
    assert counts["rows"] == 8 and counts["tokens"] == 32 and counts["moe/assignments.sampler"] > 0


def test_a_random_model_is_balanced_in_its_routed_stack_and_every_copy_takes_the_bias(trainer):
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline

    trainer = trainer.trainer
    assert not np.asarray(trainer.params["base"]["moe_blocks"]["moe"]["router_bias"]).any()
    prompts = ["".join(chr(c) for c in row) for row in
               np.asarray(jax.random.randint(jax.random.PRNGKey(3), (8, 12), 97, 102))]
    trainer.add_prompt_pipeline(PromptPipeline(prompts, 12, trainer.tokenizer))
    bias = np.asarray(trainer.params["base"]["moe_blocks"]["moe"]["router_bias"])
    assert bias.shape == (3, 16) and np.abs(bias).max(axis=-1).min() > 0.0
    # the frozen reference is the top two layers: its one `E` layer is the policy's last
    np.testing.assert_array_equal(np.asarray(trainer.ref_params["moe_blocks"]["moe"]["router_bias"]), bias[-1:])
