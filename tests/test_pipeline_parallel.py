"""Pipeline parallelism (`pp` mesh axis, parallel/pipeline.py).

The reference's model-parallel backend pipelines Megatron stages
(ref: configs/nemo_configs/megatron_20b.yaml
`pipeline_model_parallel_size`); here the same strategy is a GPipe
microbatch schedule over the scan-stacked layer axis. These tests pin
the invariant that makes it safe to enable: pipelined forwards, hydra
captures, and gradients are numerically identical to the sequential
scan on the virtual 8-device CPU mesh.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import trlx_tpu
from trlx_tpu.data.default_configs import default_ppo_config, default_sft_config
from trlx_tpu.models.transformer import TransformerConfig, TransformerLM
from trlx_tpu.models.wrappers import CausalLMWithValueHead
from trlx_tpu.parallel import make_mesh, shard_params
from trlx_tpu.parallel.mesh import data_sharding


def tiny_cfg(**kw):
    base = dict(
        vocab_size=97, hidden_size=32, n_layer=4, n_head=2, n_positions=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    base.update(kw)
    return TransformerConfig(**base)


def padded_batch(B=8, T=16, vocab=97, pad=3):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (B, T)).astype(np.int32)
    mask = np.ones((B, T), np.int32)
    mask[: B // 2, :pad] = 0  # left padding on half the rows
    return ids, mask


@pytest.mark.slow
@pytest.mark.parametrize("axes", [{"pp": 2, "dp": 2, "tp": 2}, {"pp": 4, "dp": 2}])
def test_pp_forward_matches_sequential(axes):
    cfg = tiny_cfg()
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ids, mask = padded_batch()

    ref = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(params, ids, mask)

    mesh = make_mesh(axes)
    lm.mesh = mesh
    with mesh:
        sp = shard_params(mesh, params)
        di = jax.device_put(ids, data_sharding(mesh))
        dm = jax.device_put(mask, data_sharding(mesh))
        out = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(sp, di, dm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n_microbatch", [2, 4, 8])
def test_pp_microbatch_counts(n_microbatch):
    cfg = tiny_cfg(pp_microbatches=n_microbatch)
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ids, mask = padded_batch()

    ref = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(params, ids, mask)
    mesh = make_mesh({"pp": 2, "dp": 2})
    lm.mesh = mesh
    with mesh:
        out = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(
            shard_params(mesh, params), ids, mask
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_pp_multi_capture_parity():
    """Hydra + value-branch fork hiddens out of the pipelined pass equal
    the segmented sequential scan's captures."""
    cfg = tiny_cfg()
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ids, mask = padded_batch()
    points = (1, 3)

    lm.mesh = None
    ref = jax.jit(
        lambda p, i, m: lm.forward_with_multi_capture(p, i, m, points)
    )(params, ids, mask)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    lm.mesh = mesh
    with mesh:
        out = jax.jit(
            lambda p, i, m: lm.forward_with_multi_capture(p, i, m, points)
        )(shard_params(mesh, params), ids, mask)
    for k in range(len(points)):
        np.testing.assert_allclose(
            np.asarray(out["captures"][k]), np.asarray(ref["captures"][k]),
            atol=1e-5, rtol=1e-5,
        )
    np.testing.assert_allclose(
        np.asarray(out["logits"]), np.asarray(ref["logits"]), atol=1e-5, rtol=1e-5
    )


@pytest.mark.slow
@pytest.mark.parametrize("remat", [False, True])
def test_pp_grad_parity(remat):
    cfg = tiny_cfg()
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ids, mask = padded_batch()

    def loss(p):
        return (lm(p, ids, mask, remat=remat)["logits"] ** 2).mean()

    lm.mesh = None
    g_ref = jax.grad(loss)(params)

    mesh = make_mesh({"pp": 2, "dp": 2, "fsdp": 2})
    lm.mesh = mesh
    with mesh:
        g_pp = jax.jit(jax.grad(loss))(shard_params(mesh, params))
    for a, b in zip(
        jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_pp)
    ):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5, rtol=1e-4)


def test_pp_forward_train_hydra_parity():
    """The PPO teacher-forced pass (policy logits + values + frozen
    reference logits) is invariant to pipelining."""
    cfg = tiny_cfg()
    model = CausalLMWithValueHead(cfg, branch_at=cfg.n_layer - 1)
    params = model.init_params(jax.random.PRNGKey(0))
    ref_params = model.make_ref_params(params)
    ids, mask = padded_batch()

    model.lm.mesh = None
    ref = jax.jit(
        lambda p, r, i, m: model.forward_train(p, r, i, m)
    )(params, ref_params, ids, mask)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    model.lm.mesh = mesh
    with mesh:
        out = jax.jit(lambda p, r, i, m: model.forward_train(p, r, i, m))(
            shard_params(mesh, params), shard_params(mesh, ref_params), ids, mask
        )
    for key in ("logits", "values", "ref_logits"):
        np.testing.assert_allclose(
            np.asarray(out[key]), np.asarray(ref[key]), atol=1e-5, rtol=1e-5,
            err_msg=key,
        )


def test_pp_alibi_local_window_flags():
    """Per-layer global/local attention flags (gpt-neo) ride the stacked
    xs into the pipeline stages; alibi biases are per-microbatch ctx."""
    cfg = tiny_cfg(
        pos_embed="alibi",
        local_window=4,
        attn_layers=("global", "local", "global", "local"),
        use_attn_bias=False,
    )
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ids, mask = padded_batch()

    lm.mesh = None
    ref = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(params, ids, mask)
    mesh = make_mesh({"pp": 2, "dp": 2})
    lm.mesh = mesh
    with mesh:
        out = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(
            shard_params(mesh, params), ids, mask
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_pp_sp_mutually_exclusive():
    """Enforced at make_mesh — the chokepoint every config path goes
    through — because the trainer flips sp>1 to ring attention, which
    would otherwise silently bypass the pipelined path while params stay
    pp-sharded (duplicated compute, no error)."""
    with pytest.raises(ValueError, match="mutually exclusive"):
        make_mesh({"pp": 2, "sp": 2, "dp": 2})

    # a hand-built Mesh that skips make_mesh still raises at trace time
    import numpy as _np
    from jax.sharding import Mesh

    cfg = tiny_cfg()
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    devs = _np.array(jax.devices()[:8]).reshape(2, 2, 1, 1, 2)
    lm.mesh = Mesh(devs, ("pp", "dp", "fsdp", "tp", "sp"))
    ids, mask = padded_batch()
    with pytest.raises(ValueError, match="mutually exclusive"):
        lm(params, ids, mask)


def test_pp_out_of_range_capture_points_omitted():
    """points >= n_layer are omitted under pp, matching the sequential
    path (which never captures them), not returned as zeros."""
    cfg = tiny_cfg()
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ids, mask = padded_batch()
    mesh = make_mesh({"pp": 2, "dp": 2})
    lm.mesh = mesh
    with mesh:
        out = jax.jit(
            lambda p: lm.forward_with_multi_capture(p, ids, mask, (1, cfg.n_layer))
        )(shard_params(mesh, params))
    assert len(out["captures"]) == 1


def test_pp_indivisible_falls_back():
    """n_layer=3 doesn't split over pp=2: warn and run sequentially."""
    cfg = tiny_cfg(n_layer=3)
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    ids, mask = padded_batch()
    lm.mesh = None
    ref = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(params, ids, mask)
    lm.mesh = make_mesh({"pp": 2, "dp": 2})
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = jax.jit(lambda p, i, m: lm(p, i, m)["logits"])(params, ids, mask)
    assert any("falling back" in str(w.message) for w in caught)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_pp_param_layer_axis_sharded():
    """The stacked layer axis lands on pp so each stage owns its slice."""
    cfg = tiny_cfg()
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    sharded = shard_params(mesh, params)
    assert sharded["blocks"]["attn"]["q"]["kernel"].sharding.spec[0] == "pp"
    assert sharded["blocks"]["ln_1"]["scale"].sharding.spec[0] == "pp"


@pytest.mark.slow
def test_sft_learn_on_pp_mesh(tmp_path):
    """End-to-end SFT learn() on a pp=2 x dp=2 x tp=2 mesh."""
    config = default_sft_config().evolve(
        train=dict(
            batch_size=8, total_steps=3, eval_interval=3, seq_length=16,
            epochs=3, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"),
            mesh={"pp": 2, "dp": 2, "tp": 2, "fsdp": 1},
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
    samples = ["hello world", "the cat sat", "a b c", "xyz uvw", "one two",
               "three four", "五 六", "alpha beta"]
    trainer = trlx_tpu.train(samples=samples, config=config)
    assert trainer.iter_count == 3


@pytest.mark.slow
def test_ppo_learn_on_pp_mesh(tmp_path):
    """End-to-end PPO learn() (rollout generation runs the sequential
    decode with pp-sharded weights; experience + train steps pipeline)."""
    config = default_ppo_config().evolve(
        train=dict(
            batch_size=8, total_steps=2, eval_interval=2, seq_length=12,
            epochs=2, tracker=None, checkpoint_dir=str(tmp_path / "ckpts"),
            mesh={"pp": 2, "dp": 2, "tp": 1, "fsdp": 1},
        ),
        model=dict(
            model_path="random",
            num_layers_unfrozen=1,
            model_extra_configs={
                "transformer": dict(
                    hidden_size=16, n_layer=2, n_head=2, n_positions=64
                )
            },
        ),
        tokenizer=dict(tokenizer_path="byte"),
        method=dict(
            num_rollouts=8, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=4, top_k=0, top_p=1.0, do_sample=True),
        ),
    )
    prompts = ["hello world", "the cat", "a b", "xyz", "what is", "I am", "go", "ok"]
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [
            float(len(o.split())) for o in outputs
        ],
        prompts=prompts,
        config=config,
    )
    assert trainer.iter_count == 2


def test_pp_ilql_forward_parity():
    """ILQL's head group reads the final hidden out of the pipelined
    trunk; Q/V head outputs must be pipelining-invariant."""
    from trlx_tpu.models.wrappers import CausalLMWithILQLHeads

    cfg = tiny_cfg()
    model = CausalLMWithILQLHeads(cfg, two_qs=True)
    params = model.init_params(jax.random.PRNGKey(0))
    ids, mask = padded_batch()
    n_actions, n_states = 4, 5
    rng = np.random.default_rng(1)
    actions_ixs = np.sort(rng.integers(0, 15, (8, n_actions)), axis=-1).astype(np.int32)
    states_ixs = np.sort(rng.integers(0, 16, (8, n_states)), axis=-1).astype(np.int32)

    model.lm.mesh = None
    ref_logits, (ref_qs, ref_tqs, ref_vs) = jax.jit(
        lambda p: model.forward(p, ids, mask, states_ixs, actions_ixs)
    )(params)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    model.lm.mesh = mesh
    with mesh:
        logits, (qs, tqs, vs) = jax.jit(
            lambda p: model.forward(p, ids, mask, states_ixs, actions_ixs)
        )(shard_params(mesh, params))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), atol=1e-5, rtol=1e-5)
    for a, b in zip(tuple(ref_qs) + (ref_vs,), tuple(qs) + (vs,)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5, rtol=1e-5)


class _FakeDev:
    """Duck-typed device: data_group_info only reads .process_index."""

    def __init__(self, p):
        self.process_index = p


class _FakeMesh:
    def __init__(self, devices, axis_names):
        self.devices = devices
        self.axis_names = axis_names


def test_data_group_info(monkeypatch):
    """Row-distribution grouping (the pp x multihost contract): processes
    on different pp stages of the same (dp, fsdp) blocks form ONE data
    group (replica rows); processes on distinct blocks form separate
    groups; inconsistent overlaps raise. The end-to-end version runs as a
    real 2-process jax.distributed test (tests/test_multihost.py)."""
    import trlx_tpu.parallel.multihost as mh

    monkeypatch.setattr(mh, "is_multihost", lambda: True)
    monkeypatch.setattr(mh.jax, "process_index", lambda: 0)
    names = ("pp", "dp", "fsdp", "tp", "sp")

    def mesh_of(proc_of_idx, shape):
        devs = np.empty(shape, dtype=object)
        for idx in np.ndindex(*shape):
            devs[idx] = _FakeDev(proc_of_idx(idx))
        return _FakeMesh(devs, names)

    # pp=2 spanning 2 processes: one group, rows replicated, rep = 0
    m = mesh_of(lambda idx: idx[0], (2, 2, 1, 2, 1))  # proc = pp stage
    assert mh.data_group_info(m) == (0, 1)
    assert mh.group_representatives(m) == [0]

    # dp=2 split across 2 processes: two groups (the historical layout)
    m = mesh_of(lambda idx: idx[1], (1, 2, 1, 2, 1))  # proc = dp block
    assert mh.data_group_info(m) == (0, 2)
    assert mh.group_representatives(m) == [0, 1]

    # pp=2 x dp=2 over 4 processes: 2 groups of 2 stage-processes each
    m = mesh_of(lambda idx: idx[0] * 2 + idx[1], (2, 2, 1, 1, 1))
    assert mh.data_group_info(m)[1] == 2

    # inconsistent: a row block split across two processes that otherwise
    # own different blocks (overlapping, non-identical block sets)
    def bad(idx):
        dp, fsdp, tp = idx[1], idx[2], idx[3]
        block = dp * 2 + fsdp
        if block == 0:
            return 0
        if block == 1:
            return tp  # straddles processes 0 and 1
        return 1

    m = mesh_of(bad, (1, 2, 2, 2, 1))
    with pytest.raises(ValueError, match="row blocks"):
        mh.data_group_info(m)


@pytest.mark.slow
def test_pp_t5_forward_parity():
    """Encoder and decoder stacks of the seq2seq (T5) family pipeline
    over pp with identical teacher-forced outputs, including the hydra
    branch capture."""
    from trlx_tpu.models.seq2seq import Seq2SeqConfig, T5LM

    cfg = Seq2SeqConfig(
        vocab_size=97, d_model=32, d_kv=8, d_ff=64, n_layer=4,
        n_decoder_layer=4, n_head=4, relative_attention_num_buckets=8,
        relative_attention_max_distance=20,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    lm = T5LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S, T = 8, 7, 5
    enc_ids = rng.integers(0, 97, (B, S)).astype(np.int32)
    enc_mask = np.ones((B, S), np.int32)
    enc_mask[: B // 2, -2:] = 0
    dec_ids = rng.integers(0, 97, (B, T)).astype(np.int32)
    dec_ids[:, 0] = 0

    lm.mesh = None
    ref = jax.jit(lambda p: lm(p, enc_ids, enc_mask, dec_ids))(params)
    ref_cap = jax.jit(
        lambda p: lm.forward_with_branch_capture(p, enc_ids, enc_mask, dec_ids, None, 2)
    )(params)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    lm.mesh = mesh
    with mesh:
        sp = shard_params(mesh, params)
        out = jax.jit(lambda p: lm(p, enc_ids, enc_mask, dec_ids))(sp)
        out_cap = jax.jit(
            lambda p: lm.forward_with_branch_capture(
                p, enc_ids, enc_mask, dec_ids, None, 2
            )
        )(sp)
    np.testing.assert_allclose(
        np.asarray(out["logits"]), np.asarray(ref["logits"]), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(out_cap["branch_hidden"]), np.asarray(ref_cap["branch_hidden"]),
        atol=1e-5, rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(out_cap["logits"]), np.asarray(ref_cap["logits"]),
        atol=1e-5, rtol=1e-5,
    )


@pytest.mark.slow
def test_pp_t5_bf16_grad_compiles():
    """bf16 ctx leaves (T5 encoder_hidden) cross the shard_map boundary:
    their cotangent psum must not hit the XLA CPU bf16 AllReducePromotion
    crash (regression: teacher-forced T5 training under pp aborted the
    process on CPU meshes in bf16)."""
    from trlx_tpu.models.seq2seq import Seq2SeqConfig, T5LM

    cfg = Seq2SeqConfig(
        vocab_size=97, d_model=32, d_kv=8, d_ff=64, n_layer=2,
        n_decoder_layer=2, n_head=4, relative_attention_num_buckets=8,
        relative_attention_max_distance=20, dtype=jnp.bfloat16,
    )
    lm = T5LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    enc_ids = rng.integers(0, 97, (8, 6)).astype(np.int32)
    enc_mask = np.ones((8, 6), np.int32)
    dec_ids = rng.integers(0, 97, (8, 4)).astype(np.int32)

    mesh = make_mesh({"pp": 2, "dp": 2, "tp": 2})
    lm.mesh = mesh

    def loss(p):
        out = lm(p, enc_ids, enc_mask, dec_ids)
        return (out["logits"].astype(jnp.float32) ** 2).mean()

    with mesh:
        g = jax.jit(jax.grad(loss))(shard_params(mesh, params))
    assert all(
        np.isfinite(np.asarray(x, np.float32)).all()
        for x in jax.tree_util.tree_leaves(g)
    )


def test_pp_prompt_tuning_parity():
    """Teacher-forced prompt tuning (soft tokens as leading positions)
    rides through the pipelined forward unchanged."""
    cfg = tiny_cfg()
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    soft = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (4, cfg.hidden_size)), np.float32
    )
    ids, mask = padded_batch()

    lm.mesh = None
    ref = jax.jit(lambda p: lm(p, ids, mask, prefix_embeds=soft)["logits"])(params)
    mesh = make_mesh({"pp": 2, "dp": 2})
    lm.mesh = mesh
    with mesh:
        out = jax.jit(lambda p: lm(p, ids, mask, prefix_embeds=soft)["logits"])(
            shard_params(mesh, params)
        )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# 1F1B schedule (parallel/pipeline.py:_run_1f1b)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pp_1f1b_grad_parity_with_captures():
    """pp_schedule='1f1b' (custom-VJP backward: recompute + cotangent
    pipelines interleaved, O(pp) boundary liveness) produces the same
    loss and grads as the sequential scan — including capture-point
    cotangents (the hydra/value-branch fork inputs)."""
    kw = dict(vocab_size=64, hidden_size=32, n_layer=4, n_head=2,
              n_positions=32, dtype=jnp.float32, pp_microbatches=4)
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, 64)
    mask = jnp.ones_like(ids)
    lm_seq = TransformerLM(TransformerConfig(**kw))
    params = lm_seq.init(jax.random.PRNGKey(0))

    def loss_of(lm):
        def loss(p):
            out = lm.forward_with_multi_capture(p, ids, mask, points=(2,))
            return jnp.mean(out["logits"] ** 2) + jnp.mean(out["captures"][0] ** 2)
        return loss

    l0, g0 = jax.value_and_grad(loss_of(lm_seq))(params)
    mesh = make_mesh({"pp": 2, "dp": 2, "fsdp": 2})
    lm = TransformerLM(TransformerConfig(pp_schedule="1f1b", **kw))
    lm.mesh = mesh
    with mesh:
        l1, g1 = jax.jit(jax.value_and_grad(loss_of(lm)))(shard_params(mesh, params))
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        g1, g0,
    )


@pytest.mark.slow
def test_pp_1f1b_t5_grad_parity():
    """Seq2seq under 1f1b: the encoder_hidden ctx cotangent (accumulated
    per microbatch across stages, then psum-merged) matches sequential."""
    from trlx_tpu.models.seq2seq import Seq2SeqConfig, T5LM

    kw = dict(vocab_size=64, d_model=32, d_ff=64, n_layer=2,
              n_decoder_layer=4, n_head=2, relative_attention_num_buckets=8,
              dtype=jnp.float32, pp_microbatches=4)
    enc = jax.random.randint(jax.random.PRNGKey(1), (8, 10), 0, 64)
    dec = jax.random.randint(jax.random.PRNGKey(2), (8, 6), 0, 64)
    m = jnp.ones_like(enc)
    lm0 = T5LM(Seq2SeqConfig(**kw))
    params = lm0.init(jax.random.PRNGKey(0))

    def loss_of(lm):
        return lambda p: jnp.mean(lm(p, enc, m, dec)["logits"] ** 2)

    l0, g0 = jax.value_and_grad(loss_of(lm0))(params)
    mesh = make_mesh({"pp": 2, "dp": 2, "fsdp": 2})
    lm = T5LM(Seq2SeqConfig(pp_schedule="1f1b", **kw))
    lm.mesh = mesh
    with mesh:
        l1, g1 = jax.jit(jax.value_and_grad(loss_of(lm)))(shard_params(mesh, params))
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=2e-6
        ),
        g1, g0,
    )


@pytest.mark.slow
def test_pp_1f1b_memory_bound():
    """The point of 1f1b: backward temp memory is bounded by O(pp)
    rolling buffers, not O(M) stored tick boundaries. At M=16
    microbatches the compiled temp footprint must be a small fraction of
    no-remat GPipe's (measured ~12x on this geometry)."""
    kw = dict(vocab_size=64, hidden_size=128, n_layer=4, n_head=4,
              n_positions=128, dtype=jnp.float32, pp_microbatches=16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (32, 128), 0, 64)
    mask = jnp.ones_like(ids)
    mesh = make_mesh({"pp": 2, "dp": 2, "fsdp": 2})
    params = TransformerLM(TransformerConfig(**kw)).init(jax.random.PRNGKey(0))
    temps = {}
    for sched in ["gpipe", "1f1b"]:
        lm = TransformerLM(TransformerConfig(pp_schedule=sched, **kw))
        lm.mesh = mesh

        def loss(p, lm=lm):
            return jnp.mean(lm(p, ids, mask)["logits"] ** 2)

        with mesh:
            comp = jax.jit(jax.value_and_grad(loss)).lower(
                shard_params(mesh, params)
            ).compile()
        temps[sched] = comp.memory_analysis().temp_size_in_bytes
    assert temps["1f1b"] < 0.25 * temps["gpipe"], temps


def test_pp_bad_schedule_is_loud():
    from trlx_tpu.parallel.pipeline import pipelined_layers

    mesh = make_mesh({"pp": 2})
    with pytest.raises(ValueError, match="pp_schedule"):
        pipelined_layers(
            mesh, lambda l, h, c: h, {"w": jnp.zeros((2, 3))},
            jnp.zeros((4, 8)), (), n_microbatch=2, schedule="interleaved",
        )


@pytest.mark.slow
def test_pp4_1f1b_grad_parity():
    """pp=4 single-layer stages: the deepest mesh the 8-device CI box
    allows — exercises the 2*pp-1=7 slot ring with wraparound and the
    multi-hop cotangent ppermute chain."""
    kw = dict(vocab_size=64, hidden_size=32, n_layer=4, n_head=2,
              n_positions=32, dtype=jnp.float32, pp_microbatches=8)
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, 64)
    mask = jnp.ones_like(ids)
    lm_seq = TransformerLM(TransformerConfig(**kw))
    params = lm_seq.init(jax.random.PRNGKey(0))

    def loss_of(lm):
        return lambda p: jnp.mean(lm(p, ids, mask)["logits"] ** 2)

    l0, g0 = jax.value_and_grad(loss_of(lm_seq))(params)
    mesh = make_mesh({"pp": 4, "dp": 2})
    lm = TransformerLM(TransformerConfig(pp_schedule="1f1b", **kw))
    lm.mesh = mesh
    with mesh:
        l1, g1 = jax.jit(jax.value_and_grad(loss_of(lm)))(shard_params(mesh, params))
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
        ),
        g1, g0,
    )
