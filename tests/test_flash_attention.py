"""Pallas fused attention: numerics vs the XLA path (interpreter mode on
CPU; compiled on TPU) and gradient flow through the custom VJP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
from trlx_tpu.ops.flash_attention import _attention_reference, flash_attention


def test_kernel_matches_reference():
    B, H, T, D = 2, 2, 16, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    mask = jnp.ones((B, T), jnp.int32).at[0, :5].set(0)  # left padding

    ref = _attention_reference(q, k, v, mask, causal=True, sm_scale=D**-0.5)
    out = flash_attention(q, k, v, mask)
    # fully-masked (padded) query rows may differ; compare real rows only
    real = np.asarray(mask, bool)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out)[b, :, real[b]], np.asarray(ref)[b, :, real[b]],
            atol=2e-5, rtol=2e-4,
        )


def test_kernel_gradients_flow():
    B, H, T, D = 1, 2, 8, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    mask = jnp.ones((B, T), jnp.int32)

    def loss_flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, mask).sum()

    def loss_ref(q_, k_, v_):
        return _attention_reference(q_, k_, v_, mask, True, D**-0.5).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4)


@pytest.mark.slow
def test_model_forward_parity_pallas_vs_xla():
    kw = dict(vocab_size=64, hidden_size=16, n_layer=2, n_head=2,
              n_positions=64, dtype=jnp.float32)
    lm_x = TransformerLM(TransformerConfig(**kw))
    lm_p = TransformerLM(TransformerConfig(attention_impl="pallas", **kw))
    params = lm_x.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
    mask = jnp.ones((2, 12), jnp.int32).at[0, :3].set(0)
    out_x = lm_x(params, ids, mask)["logits"]
    out_p = lm_p(params, ids, mask)["logits"]
    real = np.asarray(mask, bool)
    np.testing.assert_allclose(
        np.asarray(out_p)[real], np.asarray(out_x)[real], atol=2e-4, rtol=2e-3
    )


def test_kernel_gradients_with_padding_and_fully_masked_rows():
    # left-padded batch: causal + pad creates query rows whose every key
    # is masked — the regime where a logsumexp-based backward silently
    # diverges from the reference (fp32 absorbs log(l) at m = -1e30)
    B, H, T, D = 2, 2, 64, 32
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    m = np.ones((B, T), np.int32)
    m[:, :19] = 0  # 19 leading pad slots
    mask = jnp.asarray(m)

    def loss_flash(q_, k_, v_):
        return (flash_attention(q_, k_, v_, mask) * jnp.arange(D)).sum()

    def loss_ref(q_, k_, v_):
        return (_attention_reference(q_, k_, v_, mask, True, D**-0.5) * jnp.arange(D)).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


def test_kernel_cross_attention_shapes():
    # T != S (decode-style / cross attention), non-causal, half-masked
    B, H, T, S, D = 1, 3, 32, 64, 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    m = np.ones((B, S), np.int32)
    m[:, :10] = 0
    mask = jnp.asarray(m)
    out = flash_attention(q, k, v, mask, causal=False)
    ref = _attention_reference(q, k, v, mask, False, D**-0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("n_kv_head", [1, 2, 4])
def test_kernel_gqa_matches_reference(n_kv_head):
    """GQA: kv heads passed UNREPEATED ([B, Hkv, S, D]) match the
    repeat-then-attend XLA reference, forward and backward, across
    group sizes (Hkv=H is the MHA degenerate case)."""
    B, H, T, D = 2, 4, 32, 16
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, n_kv_head, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, n_kv_head, T, D)), jnp.float32)
    m = np.ones((B, T), np.int32)
    m[0, :7] = 0
    mask = jnp.asarray(m)

    def loss_flash(q_, k_, v_):
        return (flash_attention(q_, k_, v_, mask) * jnp.arange(D)).sum()

    def loss_ref(q_, k_, v_):
        return (
            _attention_reference(q_, k_, v_, mask, True, D**-0.5) * jnp.arange(D)
        ).sum()

    out = flash_attention(q, k, v, mask)
    ref = _attention_reference(q, k, v, mask, True, D**-0.5)
    real = np.asarray(mask, bool)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(out)[b, :, real[b]], np.asarray(ref)[b, :, real[b]],
            atol=2e-5, rtol=2e-4,
        )
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        assert a.shape == b.shape  # dk/dv stay at Hkv heads
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


@pytest.mark.slow
def test_model_gqa_pallas_vs_xla():
    """A GQA model config routes teacher-forced forwards through the
    pallas kernel with unrepeated kv and matches the XLA path."""
    kw = dict(vocab_size=64, hidden_size=32, n_layer=2, n_head=4,
              n_kv_head=2, n_positions=64, pos_embed="rotary",
              use_attn_bias=False, dtype=jnp.float32)
    lm_x = TransformerLM(TransformerConfig(**kw))
    lm_p = TransformerLM(TransformerConfig(attention_impl="pallas", **kw))
    params = lm_x.init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    mask = jnp.ones((2, 16), jnp.int32).at[0, :4].set(0)
    out_x = lm_x(params, ids, mask)["logits"]
    out_p = lm_p(params, ids, mask)["logits"]
    real = np.asarray(mask, bool)
    np.testing.assert_allclose(
        np.asarray(out_p)[real], np.asarray(out_x)[real], atol=2e-4, rtol=2e-3
    )


def test_generation_prefill_pallas_vs_xla():
    """Rollout generation with attention_impl='pallas' routes the PREFILL
    through the kernel (static cache offset 0) and greedy-decodes the
    same tokens as the XLA path — the long-context rollout gap: an 8k
    prompt prefill is a full-length attention pass."""
    from trlx_tpu.models.generation import SamplerSettings, make_generate_fn

    kw = dict(vocab_size=64, hidden_size=32, n_layer=2, n_head=4,
              n_kv_head=2, n_positions=128, pos_embed="rotary",
              use_attn_bias=False, dtype=jnp.float32)
    lm_x = TransformerLM(TransformerConfig(**kw))
    lm_p = TransformerLM(TransformerConfig(attention_impl="pallas", **kw))
    params = lm_x.init(jax.random.PRNGKey(0))
    settings = SamplerSettings(max_new_tokens=8, do_sample=False,
                               eos_token_id=-1, pad_token_id=0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    mask = jnp.ones((2, 16), jnp.int32).at[0, :5].set(0)  # left padding
    rng = jax.random.PRNGKey(2)
    out_x = make_generate_fn(lm_x, settings)(params, ids, mask, rng)
    out_p = make_generate_fn(lm_p, settings)(params, ids, mask, rng)
    np.testing.assert_array_equal(
        np.asarray(out_x["sequences"]), np.asarray(out_p["sequences"])
    )


@pytest.mark.slow
def test_generation_prefill_pallas_nonzero_offset():
    """Adapter generation (kv-prefix / soft-prompt warm segments) prefills
    at a NONZERO static cache offset — the only path where the kernels'
    q_offset differs from both 0 and S-T, pinning their causal coordinate
    arithmetic against the XLA path."""
    from trlx_tpu.models.generation import SamplerSettings, generate

    kw = dict(vocab_size=64, hidden_size=32, n_layer=2, n_head=4,
              n_kv_head=2, n_positions=128, pos_embed="rotary",
              use_attn_bias=False, dtype=jnp.float32)
    lm_x = TransformerLM(TransformerConfig(**kw))
    lm_p = TransformerLM(TransformerConfig(attention_impl="pallas", **kw))
    params = lm_x.init(jax.random.PRNGKey(0))
    settings = SamplerSettings(max_new_tokens=8, do_sample=False,
                               eos_token_id=-1, pad_token_id=0)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    mask = jnp.ones((2, 16), jnp.int32).at[0, :5].set(0)
    rng = jax.random.PRNGKey(2)
    cfgp = lm_p.cfg
    prefix = {
        "k": jnp.asarray(
            np.random.default_rng(5).normal(
                size=(kw["n_layer"], 8, cfgp.n_kv_head, cfgp.head_dim)),
            jnp.float32),
        "v": jnp.asarray(
            np.random.default_rng(6).normal(
                size=(kw["n_layer"], 8, cfgp.n_kv_head, cfgp.head_dim)),
            jnp.float32),
    }
    soft = jnp.asarray(
        np.random.default_rng(7).normal(size=(8, kw["hidden_size"])), jnp.float32
    )
    for adapter in [dict(kv_prefix=prefix), dict(soft_prompt=soft)]:
        out_x = jax.jit(
            lambda p, i, m, r: generate(lm_x, p, i, m, r, settings, **adapter)
        )(params, ids, mask, rng)
        out_p = jax.jit(
            lambda p, i, m, r: generate(lm_p, p, i, m, r, settings, **adapter)
        )(params, ids, mask, rng)
        np.testing.assert_array_equal(
            np.asarray(out_x["sequences"]), np.asarray(out_p["sequences"])
        )


def _bias_reference(q, k, v, key_mask, bias, causal):
    """XLA oracle for the bias-carrying kernel (T5 semantics: additive
    learned bias, no 1/sqrt(d) scale)."""
    from trlx_tpu.ops.flash_attention import NEG_INF

    T, S = q.shape[2], k.shape[2]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) + bias[None]
    if causal:
        s = jnp.where(
            jnp.arange(T)[:, None] >= jnp.arange(S)[None, :], s, NEG_INF
        )
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


@pytest.mark.parametrize("causal", [False, True])
def test_bias_kernel_matches_reference(causal):
    """flash_attention_bias (T5 rel-bias variant): values AND all four
    gradients — q, k, v and the batch-summed dbias that trains the
    rel_bias table — against the XLA oracle, with padding masks."""
    from trlx_tpu.ops.flash_attention import flash_attention_bias

    B, H, T, D = 2, 3, 128, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(H, T, T)), jnp.float32)
    mask = jnp.asarray(rng.random((B, T)) > 0.2, jnp.int32).at[:, :4].set(1)
    ct = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)

    out = flash_attention_bias(q, k, v, mask, bias, causal=causal)
    ref = _bias_reference(q, k, v, mask, bias, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    gk = jax.grad(
        lambda a: (
            flash_attention_bias(a[0], a[1], a[2], mask, a[3], causal=causal)
            * ct
        ).sum()
    )((q, k, v, bias))
    gr = jax.grad(
        lambda a: (_bias_reference(a[0], a[1], a[2], mask, a[3], causal) * ct).sum()
    )((q, k, v, bias))
    for a, b, name in zip(gk, gr, ("dq", "dk", "dv", "dbias")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name
        )


def _pallas_names(fn, *args):
    """Names of the pallas_calls in fn's jaxpr, in order."""
    return [
        e.params["name"]
        for e in jax.make_jaxpr(fn)(*args).eqns
        if e.primitive.name == "pallas_call"
    ]


def test_kernels_carry_fixed_names():
    """A kernel is a functools.partial, so only `name=` on its
    pallas_call names it: on the chip that name is the instruction's
    (`%flash_fwd.1`), which a trace reduction finds after any refactor;
    without it the instruction is named after the flax scope and a
    counter (`%attn.89`)."""
    from trlx_tpu.ops.flash_attention import flash_attention_bias

    q = jnp.ones((1, 2, 128, 16), jnp.float32)
    mask = jnp.ones((1, 128), jnp.int32)
    bias = jnp.zeros((2, 128, 128), jnp.float32)
    assert _pallas_names(
        jax.grad(lambda x: flash_attention(x, x, x, mask).sum()), q
    ) == ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    assert _pallas_names(
        jax.grad(lambda x: flash_attention_bias(x, x, x, mask, bias).sum()), q
    ) == ["flash_bias_fwd", "flash_bias_bwd_dq", "flash_bias_bwd_dkv"]


def test_every_pallas_call_in_ops_has_a_name_of_its_own():
    """All nine, the decode kernels included, without running them."""
    import ast
    import glob
    import os

    import trlx_tpu.ops

    names = []
    for path in glob.glob(os.path.join(os.path.dirname(trlx_tpu.ops.__file__), "*.py")):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and getattr(node.func, "attr", "") == "pallas_call":
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{path}:{node.lineno} pallas_call without name="
                names.append(kw["name"].value)
    assert sorted(names) == sorted(set(names)) and {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "decode_attn", "paged_decode_attn", "state_step",
    } <= set(names)


def test_flash_attention_on_mesh_matches_single_device():
    """GSPMD cannot partition a Mosaic call, so on a multi-device mesh
    the kernel runs under shard_map (batch over dp x fsdp, heads over
    tp): values and gradients equal the unmeshed call, and shapes the
    mesh does not divide raise instead of replicating."""
    from trlx_tpu.ops.flash_attention import flash_attention_on_mesh
    from trlx_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    B, H, Hkv, T, D = 8, 4, 2, 16, 8
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, T, D)), jnp.float32)
    mask = jnp.ones((B, T), jnp.int32).at[0, :5].set(0)

    def loss(on_mesh):
        def f(q_, k_, v_):
            return (flash_attention_on_mesh(on_mesh, q_, k_, v_, mask) ** 2).sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    (lm, gm), (l1, g1) = loss(mesh)(q, k, v), loss(None)(q, k, v)
    np.testing.assert_allclose(float(lm), float(l1), rtol=1e-5)
    for a, b in zip(gm, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-4)
    with pytest.raises(ValueError, match="must divide"):
        flash_attention_on_mesh(mesh, q[:6], k[:6], v[:6], mask[:6])
