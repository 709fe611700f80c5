"""The decode step of a recurrent mixer as one kernel (`ops/state_step.py`) against
`kda_step` and `ssm_step`, interpreted on the CPU at toy sizes, and what the sampler's
program holds of it: one aliased `pallas_call` a recurrent segment, no slice of the
carry taken in XLA."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.generation import SamplerSettings, generate
from trlx_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    kda_step,
    layer_stacks,
    ssm_step,
    state_step_unfused,
)
from trlx_tpu.ops.state_step import delta_state_step, head_block, ssm_state_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, ROWS, LAYER = 4, 3, 2  # the stepped layer is neither first nor last; row 1 is masked


def _delta_case(H):
    dk, dv = 8, 16
    ks = jax.random.split(jax.random.PRNGKey(H), 6)
    live = jnp.ones((ROWS,)).at[1].set(0.0)
    q, k = (jax.random.normal(ks[i], (ROWS, H, dk)) for i in (0, 1))
    v = jax.random.normal(ks[2], (ROWS, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (ROWS, H, dk))) * live[:, None, None]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (ROWS, H))) * live[:, None]
    state = jax.random.normal(ks[5], (LAYERS, ROWS, H, dk, dv))
    return (delta_state_step, kda_step, state, (q, k, v, g, beta), lambda fit: head_block(H, dk, dv, 1, fit * dk * dv * 4))


def _ssm_case(H, G):
    P, N = 8, 16
    ks = jax.random.split(jax.random.PRNGKey(H), 6)
    live = jnp.ones((ROWS,)).at[1].set(0.0)
    x = jax.random.normal(ks[0], (ROWS, H, P))
    Bm, Cm = (jax.random.normal(ks[i], (ROWS, G, N)) for i in (1, 2))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (ROWS, H))) * live[:, None]
    a = -jnp.exp(jax.random.normal(ks[4], (H,)))
    state = jax.random.normal(ks[5], (LAYERS, ROWS, H, P, N))
    return (ssm_state_step, ssm_step, state, (x, Bm, Cm, dt, a), lambda fit: head_block(H, P, N, H // G, fit * P * N * 4))


@pytest.mark.parametrize("case, fit, block", [
    # (heads a cell may hold, heads it gets): all of H; a block that divides H; the most
    # that divides H where what would fit (4 of 6; 8 of 12 in groups of 4) does not
    (lambda: _delta_case(4), 4, 4), (lambda: _delta_case(4), 2, 2), (lambda: _delta_case(6), 4, 3),
    (lambda: _ssm_case(8, 2), 8, 8), (lambda: _ssm_case(8, 2), 4, 4), (lambda: _ssm_case(12, 3), 8, 4),
    (lambda: _ssm_case(4, 4), 1, 1),
], ids=["delta-whole", "delta-halves", "delta-4-of-6", "ssm-whole", "ssm-a-group", "ssm-8-of-12", "ssm-a-head"])
def test_the_kernel_steps_one_layer_of_the_carry_as_the_reference_steps_its_slice(case, fit, block):
    kernel, reference, state, vectors, block_of = case()
    assert block_of(fit) == block
    tile_bytes = state.shape[3] * state.shape[4] * 4
    step = jax.jit(lambda s, ix, *v: kernel(s, ix, *v, cell_bytes=fit * tile_bytes))
    out, carried = step(state, jnp.int32(LAYER), *vectors)
    want_out, want = jax.jit(reference)(*vectors, state[LAYER])
    scale = float(jnp.abs(want).max())
    # float32 rounding: the sums run in another order than XLA's (1e-6 of the largest term)
    assert float(jnp.abs(carried[LAYER] - want).max()) <= 2e-6 * scale
    assert float(jnp.abs(out - want_out).max()) <= 1e-5 * float(jnp.abs(want_out).max())
    assert out.dtype == jnp.float32 and carried.dtype == jnp.float32 and carried.shape == state.shape
    others = np.array([l for l in range(LAYERS) if l != LAYER])
    np.testing.assert_array_equal(np.asarray(carried)[others], np.asarray(state)[others])
    # the masked row (g = 0 and beta = 0; dt = 0) keeps its state bit for bit
    np.testing.assert_array_equal(np.asarray(carried[LAYER, 1]), np.asarray(state[LAYER, 1]))
    assert float(jnp.abs(carried[LAYER, 0] - state[LAYER, 0]).max()) > 0


def _toy_lm(family):
    if family == "kimi":
        from benchmark.reference import kimi_linear_ref as ref
        name, carried = "kimi-linear-48b-a3b", ("kda_s", "kda_s_lead")
    else:
        from benchmark.reference import nemotron_h_ref as ref
        name, carried = "nemotron-3-super-120b-a12b", ("ssm_s",)
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        published = json.load(f)
    hf = dict(published, **ref.toy_sizes(published))
    cfg = TransformerConfig(**dict(ref.system_config(hf), n_positions=64, dtype=jnp.float32))
    # segments of recurrent layers, each one scan: KDA-dense | KDA KDA | MLA | KDA and M E M * E M E
    kinds = [stack if mixer in ("delta", "ssm") else None
             for (stack, _), mixer in zip(layer_stacks(cfg), cfg.mixers)]
    segments = sum(kind is not None and kind != before for kind, before in zip(kinds, [None] + kinds))
    assert segments == 3
    return TransformerLM(cfg), carried, segments


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


@pytest.mark.parametrize("family", ["kimi", "nemotron"])
def test_the_sampler_holds_one_aliased_kernel_a_recurrent_segment_and_no_slice_of_the_carry(family, monkeypatch):
    lm, carried, segments = _toy_lm(family)
    rows, prompt, new = 2, 8, 4
    settings = SamplerSettings(max_new_tokens=new, do_sample=True, eos_token_id=-1, pad_token_id=0)
    params = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    ids = jax.ShapeDtypeStruct((rows, prompt), jnp.int32)
    states = {tuple(v.shape) for k, v in lm.init_cache(rows, prompt + new).items() if k in carried}

    def program():
        traced = jax.make_jaxpr(lambda p, i, m, key: generate(lm, p, i, m, key, settings))(
            params, ids, ids, jax.random.PRNGKey(0))
        (loop,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "while"]
        return list(_walk(loop.params["body_jaxpr"].jaxpr))

    assert state_step_unfused(lm.cfg, lm.mesh) is None
    body = program()
    kernels = [e for e in body if e.primitive.name == "pallas_call"]
    # one call a segment of recurrent layers, inside that segment's scan over its layers
    assert len(kernels) == segments
    for call in kernels:
        (alias,) = call.params["input_output_aliases"]
        assert tuple(call.invars[alias[0]].aval.shape) in states  # the carry, whole
        assert call.outvars[alias[1]].aval.shape == call.invars[alias[0]].aval.shape
    assert not [e for e in body if e.primitive.name == "optimization_barrier"]
    assert not [e for e in body if e.primitive.name in ("dynamic_slice", "dynamic_update_slice")
                and tuple(e.invars[0].aval.shape) in states]

    # the XLA branch, where the static function gives a reason: the slice, no kernel
    monkeypatch.setattr("trlx_tpu.models.transformer.state_step_unfused", lambda cfg, mesh: "asked to")
    body = program()
    assert not [e for e in body if e.primitive.name == "pallas_call"]
    assert len([e for e in body if e.primitive.name == "dynamic_update_slice"
                and tuple(e.invars[0].aval.shape) in states]) == segments
