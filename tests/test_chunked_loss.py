"""Chunked-from-hidden logprob/CE path (train.logit_chunks) and the
bf16-gradient view (train.grads_dtype): numerical parity against the
full-logits losses, plus an end-to-end PPO run on the at-scale recipe
knobs. This is the machinery that makes the 1.3B training recipe
reachable through trlx_tpu.train() instead of a hand-rolled bench step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    logit_projection,
)
from trlx_tpu.ops.common import chunked_logprobs, logprobs_of_labels

B, T, E, V = 2, 11, 16, 37  # T deliberately not divisible by n_chunks


def _hidden_labels(seed=0, t=T):
    rng = np.random.default_rng(seed)
    hidden = jnp.asarray(rng.normal(size=(B, t, E)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, V, size=(B, t)), jnp.int32)
    return hidden, labels


@pytest.mark.parametrize("n_chunks", [1, 3, 4])
def test_chunked_logprobs_matches_full_tied(n_chunks):
    cfg = TransformerConfig(vocab_size=V, hidden_size=E, n_layer=1, n_head=2)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    hidden, labels = _hidden_labels()
    proj = logit_projection(params)
    full = logprobs_of_labels(proj(hidden), labels)
    chunked = chunked_logprobs(proj, hidden, labels, n_chunks)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked), atol=1e-5)


def test_chunked_logprobs_matches_full_untied():
    cfg = TransformerConfig(
        vocab_size=V, hidden_size=E, n_layer=1, n_head=2,
        tie_word_embeddings=False,
    )
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    assert "lm_head" in params
    hidden, labels = _hidden_labels(1)
    proj = logit_projection(params)
    full = logprobs_of_labels(proj(hidden), labels)
    chunked = chunked_logprobs(proj, hidden, labels, 4)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked), atol=1e-5)


def test_chunked_logprobs_grad_matches():
    """The jax.checkpoint chunk scan must backprop identically to the
    full projection (the whole point: same grads, no [B,T,V] residual)."""
    cfg = TransformerConfig(vocab_size=V, hidden_size=E, n_layer=1, n_head=2)
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    hidden, labels = _hidden_labels(2)
    hidden = hidden.astype(jnp.float32)

    def loss_full(h, wte):
        p = logit_projection({"embed": {"wte": wte}})
        return logprobs_of_labels(p(h), labels).mean()

    def loss_chunked(h, wte):
        p = logit_projection({"embed": {"wte": wte}})
        return chunked_logprobs(p, h, labels, 3).mean()

    wte = params["embed"]["wte"]
    gf_h, gf_w = jax.grad(loss_full, argnums=(0, 1))(hidden, wte)
    gc_h, gc_w = jax.grad(loss_chunked, argnums=(0, 1))(hidden, wte)
    np.testing.assert_allclose(np.asarray(gf_h), np.asarray(gc_h), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gf_w), np.asarray(gc_w), atol=1e-5)


def test_t5_projection_matches_model_logits():
    from trlx_tpu.models.seq2seq import Seq2SeqConfig, T5LM, t5_logit_projection

    for tie in (True, False):
        cfg = Seq2SeqConfig(
            vocab_size=V, d_model=E, n_layer=1, n_decoder_layer=1, n_head=2,
            d_ff=32, tie_word_embeddings=tie,
        )
        lm = T5LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        hidden = _hidden_labels(3)[0]
        full = lm._logits(params, hidden)
        via_proj = t5_logit_projection(params, cfg)(hidden)
        np.testing.assert_allclose(
            np.asarray(full), np.asarray(via_proj), atol=1e-6
        )


def test_sft_loss_from_hidden_matches():
    from trlx_tpu.trainer.sft import sft_loss, sft_loss_from_hidden

    cfg = TransformerConfig(vocab_size=V, hidden_size=E, n_layer=2, n_head=2)
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    ids = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)
    labels = jnp.asarray(
        np.where(rng.random((B, T)) < 0.3, -100, np.asarray(ids)), jnp.int32
    )

    def full(p):
        out = lm(p, ids)
        return sft_loss(out["logits"], labels)[0]

    def chunked(p):
        out = lm(p, ids, compute_logits=False)
        assert out["logits"] is None
        return sft_loss_from_hidden(
            out["hidden_states"], logit_projection(p), labels, 3
        )[0]

    lf, gf = jax.value_and_grad(full)(params)
    lc, gc = jax.value_and_grad(chunked)(params)
    np.testing.assert_allclose(float(lf), float(lc), atol=1e-5)
    # bf16 forward + differing fp32 reduction orders (log_softmax gather
    # vs picked-minus-logsumexp): grads agree to bf16-noise level
    for a, b in zip(jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-3
        )


def _make_ppo_trainer(num_layers_unfrozen=-1, tie_word_embeddings=True, **train_kw):
    from trlx_tpu.data.default_configs import default_ppo_config
    from trlx_tpu.utils.loading import get_trainer

    config = default_ppo_config().evolve(
        train=dict(
            batch_size=8, total_steps=2, eval_interval=10, seq_length=12,
            checkpoint_interval=10, epochs=1, tracker=None, **train_kw,
        ),
        model=dict(
            model_path="random",
            num_layers_unfrozen=num_layers_unfrozen,
            model_extra_configs={
                "transformer": dict(
                    hidden_size=16, n_layer=2, n_head=2, n_positions=64,
                    tie_word_embeddings=tie_word_embeddings,
                )
            },
        ),
        tokenizer=dict(tokenizer_path="byte"),
        method=dict(
            num_rollouts=8, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=4, top_k=0, top_p=1.0, do_sample=True),
        ),
    )
    trainer_cls = get_trainer(config.train.trainer)
    return trainer_cls(
        config=config,
        reward_fn=lambda samples, prompts, outputs, **kw: [1.0] * len(outputs),
    )


def _fake_rollout_batch(trainer, P=4, N=4):
    from trlx_tpu.data import PPORolloutBatch

    rng = np.random.default_rng(7)
    vocab = trainer.model.cfg.vocab_size
    B_ = 8
    return PPORolloutBatch(
        query_tensors=jnp.asarray(rng.integers(1, vocab, (B_, P)), jnp.int32),
        response_tensors=jnp.asarray(rng.integers(1, vocab, (B_, N)), jnp.int32),
        logprobs=jnp.asarray(rng.normal(size=(B_, N)), jnp.float32),
        values=jnp.asarray(rng.normal(size=(B_, N)), jnp.float32),
        rewards=jnp.asarray(rng.normal(size=(B_, N)), jnp.float32),
        response_mask=jnp.ones((B_, N), jnp.float32),
    )


@pytest.mark.parametrize("num_layers_unfrozen", [-1, 1])
def test_ppo_loss_chunked_matches_full(num_layers_unfrozen):
    """trainer.loss with logit_chunks>0 == the full-logits loss (value
    AND gradients), in both hydra and full-reference modes."""
    trainer = _make_ppo_trainer(num_layers_unfrozen)
    batch = _fake_rollout_batch(trainer)

    trainer.config.train.logit_chunks = 0
    (lf, _), gf = jax.value_and_grad(trainer.loss, has_aux=True)(
        trainer.params, batch
    )
    trainer.config.train.logit_chunks = 3
    (lc, _), gc = jax.value_and_grad(trainer.loss, has_aux=True)(
        trainer.params, batch
    )
    np.testing.assert_allclose(float(lf), float(lc), rtol=2e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gc)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=3e-4
        )


def test_ppo_experience_fwd_chunked_matches_full():
    """The rollout experience forward (policy+ref logprobs, KL penalty)
    under logit_chunks == the full-logits one."""
    trainer = _make_ppo_trainer(1)
    rng = np.random.default_rng(9)
    vocab = trainer.model.cfg.vocab_size
    P = N = 4
    tokens = jnp.asarray(rng.integers(1, vocab, (8, P + N)), jnp.int32)
    mask = jnp.ones_like(tokens)
    rmask = jnp.ones((8, N), jnp.int32)

    outs = {}
    for chunks in (0, 3):
        trainer.config.train.logit_chunks = chunks
        fn = trainer._get_experience_fwd_fn(P, N)
        batch, kl = fn(
            trainer.params, trainer.ref_params, tokens, mask, rmask,
            jnp.float32(0.1), jnp.ones((8,), jnp.float32),
        )
        outs[chunks] = (batch, kl)
    b0, kl0 = outs[0]
    b1, kl1 = outs[3]
    np.testing.assert_allclose(
        np.asarray(b0.logprobs), np.asarray(b1.logprobs), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(b0.rewards), np.asarray(b1.rewards), atol=1e-5
    )
    np.testing.assert_allclose(
        float(kl0["mean_kl"]), float(kl1["mean_kl"]), rtol=1e-4, atol=1e-6
    )


@pytest.mark.slow
def test_ppo_learn_at_scale_recipe_knobs(tmp_path):
    """End-to-end trlx_tpu.train() with the full at-scale recipe config:
    logit_chunks + grads_dtype=bfloat16 + fused int8 AdamW + save_attn
    remat — the exact knob set the 1.3B bench drives (here on a tiny
    model so CI covers the wiring)."""
    import trlx_tpu
    from trlx_tpu.data.default_configs import default_ppo_config

    config = default_ppo_config().evolve(
        train=dict(
            batch_size=8, total_steps=2, eval_interval=2, checkpoint_interval=2,
            seq_length=12, epochs=2, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpts"),
            logit_chunks=2, grads_dtype="bfloat16", remat_policy="full",
        ),
        model=dict(
            model_path="random", num_layers_unfrozen=-1,
            model_extra_configs={
                "transformer": dict(
                    hidden_size=16, n_layer=2, n_head=2, n_positions=64
                )
            },
        ),
        tokenizer=dict(tokenizer_path="byte"),
        optimizer=dict(name="adamw_8bit_fused", kwargs=dict(lr=1e-4)),
        method=dict(
            num_rollouts=8, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=4, top_k=0, top_p=1.0, do_sample=True),
        ),
    )
    prompts = ["hello world", "the cat", "a b", "xyz", "what is", "I am", "go", "ok"]
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [
            float(len(o)) for o in outputs
        ],
        prompts=prompts,
        config=config,
    )
    assert trainer.iter_count == 2
    assert all(
        np.isfinite(np.asarray(x, np.float32)).all()
        for x in jax.tree_util.tree_leaves(trainer.params)
    )


@pytest.mark.slow
def test_sft_grads_dtype_bf16_with_accumulation(tmp_path):
    """grads_dtype with minibatch accumulation: per-microbatch grads ride
    bf16 but the running sum stays fp32 (base._step_update)."""
    import trlx_tpu
    from trlx_tpu.data.default_configs import default_sft_config

    config = default_sft_config().evolve(
        train=dict(
            batch_size=8, minibatch_size=4, total_steps=2, eval_interval=4,
            checkpoint_interval=4, seq_length=16, epochs=2, tracker=None,
            checkpoint_dir=str(tmp_path / "ckpts"),
            logit_chunks=2, grads_dtype="bfloat16",
            mesh=dict(dp=2, fsdp=2, tp=2, sp=1),
        ),
        model=dict(
            model_path="random",
            model_extra_configs={
                "transformer": dict(
                    hidden_size=16, n_layer=2, n_head=2, n_positions=64
                )
            },
        ),
        tokenizer=dict(tokenizer_path="byte"),
        method=dict(gen_kwargs=dict(max_new_tokens=4)),
    )
    samples = ["hello world"] * 8 + ["the quick brown fox"] * 8
    trainer = trlx_tpu.train(samples=samples, config=config)
    assert trainer.iter_count == 2
    assert all(
        np.isfinite(np.asarray(x, np.float32)).all()
        for x in jax.tree_util.tree_leaves(trainer.params)
    )


# -- an untied head split over fsdp: gathered once per call -----------------

_HB, _HT, _HE, _HV = 8, 37, 64, 96  # rows over fsdp 4; T not whole chunks


def _head_mesh(axes):
    from trlx_tpu.parallel import make_mesh

    return make_mesh({"dp": 1, **axes}, devices=jax.devices()[:4])


def _head_case(dtype, seed=0):
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _head_mesh({"fsdp": 4})
    rng = np.random.default_rng(seed)
    kernel = jax.device_put(
        jnp.asarray(rng.normal(size=(_HE, _HV)) * 0.2, dtype),
        NamedSharding(mesh, P("fsdp", None)))
    hidden = jax.device_put(
        jnp.asarray(rng.normal(size=(_HB, _HT, _HE)), dtype),
        NamedSharding(mesh, P(("dp", "fsdp"), None, None)))
    labels = jnp.asarray(rng.integers(0, _HV, (_HB, _HT)), jnp.int32)
    weights = jnp.asarray(rng.normal(size=(_HB, _HT)), jnp.float32)

    def loss(k, h, on_mesh):
        proj = logit_projection({"lm_head": {"kernel": k}}, mesh if on_mesh else None)
        return (chunked_logprobs(proj, h, labels, 8) * weights).sum()

    return mesh, kernel, hidden, loss


@pytest.mark.parametrize("ring", [None, (0, 1, 3, 2)], ids=["axis_order", "ring_order"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gathered_head_matches_the_closure_form(dtype, ring, monkeypatch):
    """On fsdp 4 with 8 logit chunks the head gathered once gives the
    closure form's log-probabilities and gradients for the head and the
    hidden states: float32 to rounding, bfloat16 to its own precision
    (the chips now sum a chunk's head gradient in float32 before they
    reduce it, where the closure form reduced each chunk's bf16 part).
    With the head's blocks in a ring order (how a 2x2 TPU host lists
    them) every chip still gets its own block of the gradient."""
    import trlx_tpu.ops.common as oc

    monkeypatch.setattr(oc, "_fsdp_ring", lambda mesh: ring)
    _, kernel, hidden, loss = _head_case(dtype)
    closure = jax.jit(jax.value_and_grad(lambda k, h: loss(k, h, False), argnums=(0, 1)))
    once = jax.jit(jax.value_and_grad(lambda k, h: loss(k, h, True), argnums=(0, 1)))
    want, got = closure(kernel, hidden), once(kernel, hidden)
    assert got[1][0].sharding.spec == kernel.sharding.spec
    for w, g in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        w, g = np.asarray(w, np.float32), np.asarray(g, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
        else:
            assert np.linalg.norm(g - w) <= 1e-2 * np.linalg.norm(w)


def _collectives_by_computation(hlo: str):
    """{computation: [(op, dims)]} of every all-gather, all-reduce and
    reduce-scatter in optimised HLO text, and the while bodies' names."""
    import re

    found, bodies, comp = {}, set(), None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            comp = head.group(1)
        bodies.update(re.findall(r"body=%?([\w.\-]+)", line))
        m = re.search(r"= \(?([a-z]+\d*\[[\d,]*\])[^=]*? (all-gather|all-reduce|reduce-scatter)(?:-start)?\(", line)
        if m:
            dims = tuple(int(d) for d in re.search(r"\[([\d,]*)\]", m.group(1)).group(1).split(",") if d)
            found.setdefault(comp, []).append((m.group(2), dims))
    return found, bodies


def test_gathered_head_moves_the_head_once_per_call():
    """In the compiled value_and_grad no collective of the head's shape
    (whole, or a chip's `E / 4` rows of it, either way round) sits in a
    while body: one all-gather of it and one reduce-scatter of its
    gradient, per call. The closure form all-reduces the gradient inside
    the chunk loop (XLA:CPU hoists its gather, XLA:TPU does not)."""
    _, kernel, hidden, loss = _head_case(jnp.float32)
    head = {(_HE, _HV), (_HE // 4, _HV), (_HV, _HE), (_HV, _HE // 4)}

    def moved(on_mesh):
        fn = jax.jit(jax.value_and_grad(lambda k, h: loss(k, h, on_mesh), argnums=(0, 1)))
        found, bodies = _collectives_by_computation(fn.lower(kernel, hidden).compile().as_text())
        in_loops = [c for comp in bodies for c in found.get(comp, []) if c[1] in head]
        return in_loops, sorted(c for cs in found.values() for c in cs if c[1] in head)

    in_loops, every = moved(True)
    assert in_loops == []
    assert every == [("all-gather", (_HE, _HV)), ("reduce-scatter", (_HE // 4, _HV))]
    in_loops, _ = moved(False)
    assert "all-reduce" in {op for op, _ in in_loops}


@pytest.mark.parametrize("axes, rows, tied, want", [
    (None, 8, False, False),
    ({"fsdp": 1}, 8, False, False),
    ({"fsdp": 2, "tp": 2}, 8, False, False),
    ({"fsdp": 2, "pp": 2}, 8, False, False),
    ({"fsdp": 2, "sp": 2}, 8, False, False),
    ({"fsdp": 4}, 8, True, False),
    ({"fsdp": 4}, 6, False, False),
    ({"fsdp": 4}, 8, False, True),
    ({"dp": 2, "fsdp": 2}, 8, False, True),
], ids=["no_mesh", "one_device", "tp2", "pp2", "sp2", "tied", "rows6", "fsdp4", "dp2xfsdp2"])
def test_head_gathered_once_predicate(axes, rows, tied, want):
    """Only an untied head on a mesh whose fsdp axis splits E, with no tp,
    pp or sp axis and rows that divide over dp x fsdp, takes the form."""
    from trlx_tpu.ops.common import head_gathered_once

    mesh = None if axes is None else _head_mesh(axes)
    params = {"embed": {"wte": jnp.zeros((_HV, _HE))}}
    if not tied:
        params["lm_head"] = {"kernel": jnp.zeros((_HE, _HV))}
    assert head_gathered_once(logit_projection(params, mesh), rows) is want


@pytest.mark.parametrize("axes", [{"dp": 1}, {"dp": 8}], ids=["one_device", "dp8"])
def test_ppo_loss_off_fsdp_lowers_to_the_closure_form(axes):
    """Wherever no fsdp axis splits the head, a PPO loss over an untied
    head lowers to the text it lowers to with the closure projection."""
    trainer = _make_ppo_trainer(1, tie_word_embeddings=False, logit_chunks=3, mesh=axes)
    batch = _fake_rollout_batch(trainer)

    def lowered():
        return jax.jit(jax.value_and_grad(trainer.loss, has_aux=True)).lower(
            trainer.params, batch).as_text()

    assert "lm_head" in trainer.params["base"]
    text = lowered()
    trainer.model.logit_project_fn = lambda params: logit_projection(params["base"])
    assert lowered() == text
