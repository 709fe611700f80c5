"""The backward pass stops at the hydra branch point.

Under `num_layers_unfrozen: k` the freeze mask (`make_freeze_mask`,
`make_seq2seq_freeze_mask`) zeroes the updates of the trunk below the
branch point; `forward_with_multi_capture(frozen_below=...)` (and its T5
twin) makes that trunk a constant of the differentiated forward, so its
backward never runs. These tests hold the two statements together:
same trainable gradients as the full backward, exactly-zero gradients
where the mask is 0 and nowhere else, bit-identical frozen leaves after
a step, no transposed scan over the trunk, and the bypass
configurations trace to the program they traced to before.

The forward through that trunk is a loop invariant of the fused block
(`make_fused_train_steps`): it runs once before the scan over the
optimizer steps (`_block_trunk`), every step gathers its rows of the
output and resumes at the branch point. The second half of the file
holds that block to the block that runs the whole forward in every
step (parameters, optimizer state, loss and stats), counts its FLOPs,
and holds every configuration that must keep the whole forward to the
parent's program."""

from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data import PPORolloutBatch
from trlx_tpu.data.default_configs import default_ppo_config

ROWS, P, N = 8, 8, 4
CAUSAL = dict(hidden_size=16, n_layer=8, n_head=2, n_positions=64)
# the shape the benchmark's cells run (GPT-NeoX): untied head, rotary,
# parallel residual
NEOX = dict(CAUSAL, tie_word_embeddings=False, pos_embed="rotary",
            parallel_residual=True)
T5 = dict(d_model=16, n_layer=2, n_decoder_layer=4, n_head=2, d_kv=8,
          d_ff=32, relative_attention_num_buckets=8)

# name -> (model_extra_configs, num_layers_unfrozen, remat_policy,
#          num_value_layers_unfrozen, peft_config)
FROZEN_CASES = {
    "hydra-remat_none": ({"transformer": CAUSAL}, 2, "none", 0, None),
    "hydra-remat_full": ({"transformer": CAUSAL}, 2, "full", 0, None),
    "neox-remat_full": ({"transformer": NEOX}, 2, "full", 0, None),
    # value_branch_at 4 < branch_at 6: its capture is a constant
    "value_deeper-remat_full": ({"transformer": CAUSAL}, 2, "full", 4, None),
    # value_branch_at 7 > branch_at 6: its capture carries gradient
    "value_shallower-remat_none": ({"transformer": CAUSAL}, 2, "none", 1, None),
    "t5-remat_full": ({"seq2seq": T5}, 2, "full", 0, None),
}
BYPASS_CASES = {
    "all_trainable": ({"transformer": CAUSAL}, -1, "full", 0, None),
    "all_trainable-value_branch": ({"transformer": CAUSAL}, -1, "full", 2, None),
    "lora": ({"transformer": CAUSAL}, 2, "full", 0,
             {"peft_type": "LORA", "r": 4, "lora_alpha": 8}),
    "embed_layernorm": (
        {"transformer": dict(CAUSAL, embed_layernorm=True)}, 2, "full", 0, None,
    ),
    "t5-all_trainable": ({"seq2seq": T5}, -1, "full", 0, None),
}


def build_trainer(ckpt_dir, case, **train):
    from trlx_tpu.trainer.ppo import TPUPPOTrainer

    extra, unfrozen, remat, value_layers, peft = case
    config = default_ppo_config().evolve(
        train=dict(
            dict(batch_size=ROWS, total_steps=2, seq_length=P + N, epochs=1,
                 tracker=None, checkpoint_dir=str(ckpt_dir),
                 # fp32 compute: the tolerance below is a float32 one
                 compute_dtype="float32", remat_policy=remat),
            **train,
        ),
        model=dict(
            model_path="random", num_layers_unfrozen=unfrozen,
            model_arch_type="seq2seq" if "seq2seq" in extra else "causal",
            model_extra_configs=extra, peft_config=peft,
        ),
        tokenizer=dict(tokenizer_path="byte"),
        method=dict(
            num_rollouts=ROWS, chunk_size=ROWS, ppo_epochs=1,
            num_value_layers_unfrozen=value_layers,
            gen_kwargs=dict(max_new_tokens=N, do_sample=True),
        ),
    )
    return TPUPPOTrainer(config, reward_fn=lambda **kw: [0.0])


def rollout_batch(seq2seq: bool, rows: int = ROWS, p: int = P, vocab: int = 250) -> PPORolloutBatch:
    """A synthetic store batch with ragged response masks."""
    rng = np.random.RandomState(0)
    lens = np.resize([4, 2, 3, 4, 1, 3, 2, 4], rows)
    mask = (np.arange(N)[None, :] < lens[:, None]).astype(np.float32)
    # seq2seq responses are decoder ids: start token + N sampled tokens
    n_resp = N + 1 if seq2seq else N
    return PPORolloutBatch(
        query_tensors=jnp.asarray(rng.randint(1, vocab, (rows, p)), jnp.int32),
        response_tensors=jnp.asarray(rng.randint(1, vocab, (rows, n_resp)), jnp.int32),
        logprobs=jnp.asarray(rng.randn(rows, N) * 0.1, jnp.float32),
        values=jnp.asarray(rng.randn(rows, N) * 0.1, jnp.float32),
        rewards=jnp.asarray(rng.randn(rows, N) * 0.1, jnp.float32),
        response_mask=jnp.asarray(mask),
    )


class full_backward:
    """The parent's path: force `frozen_below=0` on the capture forward
    (what every caller got before the stop existed)."""

    def __init__(self, trainer):
        self.lm = trainer.model.lm
        self.name = (
            "forward_with_branch_capture" if trainer.seq2seq
            else "forward_with_multi_capture"
        )

    def __enter__(self):
        real = getattr(self.lm, self.name)
        setattr(self.lm, self.name,
                lambda *a, **k: real(*a, **dict(k, frozen_below=0)))

    def __exit__(self, *exc):
        delattr(self.lm, self.name)  # the instance attribute shadowing the method


def loss_grad(trainer, batch):
    """A FRESH function each call: jit and make_jaxpr cache on identity,
    and the two paths differ only in a patched method."""
    return lambda p: jax.grad(lambda q: trainer.loss(q, batch), has_aux=True)(p)[0]


def scans_of(jaxpr, found=None):
    """(length, reverse) of every scan in a jaxpr, sub-jaxprs included.
    A transposed scan (the backward of a scanned layer stack) runs in
    reverse."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append((eqn.params["length"], eqn.params["reverse"]))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    scans_of(sub, found)
    return found


def matmul_flops(jaxpr, times=1):
    """dot_general FLOPs of a jaxpr, a scan's body counted `length`
    times (XLA's `cost_analysis()` counts a while body once, whatever
    its trip count, so it cannot see the depth of a scanned stack)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            k = int(np.prod([lhs[i] for i in contract]))
            total += times * 2 * k * int(np.prod(eqn.outvars[0].aval.shape))
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += matmul_flops(sub, inner)
    return total


def mask_of(trainer, grads):
    """The freeze mask broadcast to each gradient leaf, as booleans."""
    return jax.tree_util.tree_map(
        lambda g, m: np.broadcast_to(np.asarray(m), g.shape) > 0,
        grads, trainer._update_mask,
    )


@pytest.fixture(scope="module", params=list(FROZEN_CASES))
def frozen(request, tmp_path_factory):
    """One trainer per case, with the loss gradient through the new path
    and through the parent's (full backward), computed once."""
    trainer = build_trainer(
        tmp_path_factory.mktemp(request.param), FROZEN_CASES[request.param]
    )
    batch = rollout_batch(trainer.seq2seq)
    with trainer.mesh:
        grads = jax.jit(loss_grad(trainer, batch))(trainer.params)
        jaxpr = jax.make_jaxpr(loss_grad(trainer, batch))(trainer.params)
        with full_backward(trainer):
            grads_full = jax.jit(loss_grad(trainer, batch))(trainer.params)
            jaxpr_full = jax.make_jaxpr(loss_grad(trainer, batch))(trainer.params)
    to_np = lambda t: jax.tree_util.tree_map(np.asarray, t)  # noqa: E731
    return dict(
        trainer=trainer, batch=batch, grads=to_np(grads),
        grads_full=to_np(grads_full), jaxpr=jaxpr.jaxpr,
        jaxpr_full=jaxpr_full.jaxpr,
    )


def test_trainable_gradients_equal_the_full_backward(frozen):
    """Every trainable entry has the gradient the full backward gives.
    Float32, same order of operations above the stop: 1e-6 relative to
    the leaf's largest entry."""
    trainable = mask_of(frozen["trainer"], frozen["grads"])
    checked = 0
    for g, g_full, m in zip(*map(
        jax.tree_util.tree_leaves,
        (frozen["grads"], frozen["grads_full"], trainable),
    )):
        if m.any():
            scale = np.abs(g_full[m]).max()
            np.testing.assert_allclose(g[m], g_full[m], rtol=0, atol=1e-6 * scale)
            checked += 1
    assert checked


def test_zero_gradient_exactly_where_the_mask_freezes(frozen):
    """Mask/stop agreement: the entries the freeze mask zeroes are the
    entries whose gradient is identically zero. The full backward gives
    those entries a gradient (and the mask throws it away)."""
    trainer = frozen["trainer"]
    assert trainer._update_mask is not None
    trainable = mask_of(trainer, frozen["grads"])
    frozen_leaves = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(frozen["grads"])
    for (path, g), g_full, m in zip(
        flat, jax.tree_util.tree_leaves(frozen["grads_full"]),
        jax.tree_util.tree_leaves(trainable),
    ):
        name = jax.tree_util.keystr(path)
        assert not g[~m].any(), f"{name}: gradient under a zero mask"
        if m.any():
            assert g[m].any(), f"{name}: trainable but no gradient"
        if (~m).any():
            frozen_leaves += 1
            assert g_full[~m].any(), f"{name}: the parent had none either"
    assert frozen_leaves


def test_step_leaves_frozen_leaves_bit_identical(frozen):
    trainer, batch = frozen["trainer"], frozen["batch"]
    before = jax.tree_util.tree_map(np.asarray, trainer.params)
    params = jax.tree_util.tree_map(jnp.copy, trainer.params)
    opt_state = jax.tree_util.tree_map(jnp.copy, trainer.opt_state)
    with trainer.mesh:
        new_params, _, loss, _ = jax.jit(trainer._step_update)(
            params, opt_state, batch
        )
    assert np.isfinite(float(loss))
    trainable = mask_of(trainer, before)
    moved = 0
    for old, new, m in zip(*map(
        jax.tree_util.tree_leaves, (before, new_params, trainable)
    )):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[~m], old[~m])
        moved += int((new[m] != old[m]).any())
    assert moved


def test_no_transposed_scan_over_the_frozen_trunk(frozen):
    """The gradient's jaxpr scans each frozen segment forward and never
    in reverse. Against the parent's, exactly the transposed scans of
    the frozen segments (and of the T5 encoder) are gone."""
    trainer = frozen["trainer"]
    model = trainer.model
    below = model.frozen_below()
    assert below == model.branch_at > 0
    if trainer.seq2seq:
        segments = [model.cfg.n_layer, below]  # the encoder is frozen too
    else:
        cuts = [0] + [p for p in model._capture_points() if p <= below]
        segments = [b - a for a, b in zip(cuts, cuts[1:])]

    def reverse_scans(jaxpr):
        return Counter(n for n, reverse in scans_of(jaxpr) if reverse)

    forward = Counter(n for n, reverse in scans_of(frozen["jaxpr"]) if not reverse)
    assert not Counter(segments) - forward
    ours, parents = reverse_scans(frozen["jaxpr"]), reverse_scans(frozen["jaxpr_full"])
    assert parents - ours == Counter(segments)
    assert not ours - parents


# -- the fused block: the trunk's forward once a block ------------------------


class whole_forward:
    """The block that runs the whole forward in every optimizer step: what
    a trainer that holds nothing (`trunk_layers_held()` 0) builds."""

    def __init__(self, trainer):
        self.trainer = trainer

    def __enter__(self):
        self.trainer.trunk_layers_held = lambda: 0

    def __exit__(self, *exc):
        del self.trainer.trunk_layers_held  # the instance attribute shadowing the method


def block_perms(rows: int, batch: int, epochs: int) -> np.ndarray:
    """[steps, batch] minibatch rows, `rows // batch` steps an epoch, a
    fresh shuffle each epoch (what `_epoch_perms` hands the block)."""
    per = rows // batch
    return np.concatenate([
        np.random.RandomState(e).permutation(rows)[: per * batch].reshape(per, batch)
        for e in range(epochs)
    ]).astype(np.int32)


def run_block(trainer, batch, perms):
    """A newly built fused block run from the trainer's own state, which
    it leaves alone: (params, opt_state, mean loss, mean stats)."""
    fused = trainer.make_fused_train_steps()
    state = jax.tree_util.tree_map(jnp.copy, (trainer.params, trainer.opt_state))
    with trainer.mesh:
        out = fused(*state, batch, jnp.asarray(perms))
    return jax.tree_util.tree_map(np.asarray, out)


def block_both_ways(trainer, batch, perms):
    """(the block as built, the block that runs the whole forward in
    every step), from the same state on the same rows."""
    held = run_block(trainer, batch, perms)
    with whole_forward(trainer):
        whole = run_block(trainer, batch, perms)
    return held, whole


def assert_same_block(got, want, rtol=0.0, skip=()):
    """Parameters, optimizer state, loss and stats, leaf by leaf: bit for
    bit, or within `rtol` of the leaf's largest entry."""
    got, _ = jax.tree_util.tree_flatten_with_path(got)
    want = jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for (path, g), w in zip(got, want):
        name = jax.tree_util.keystr(path)
        if any(key in name for key in skip):
            continue
        if rtol:
            np.testing.assert_allclose(g, w, rtol=0, atol=rtol * max(np.abs(w).max(), 1e-30), err_msg=name)
        else:
            np.testing.assert_array_equal(g, w, err_msg=name)


def parents_block(trainer):
    """The parent's `fused_train_step`, as it stood before a block held
    anything: the scan of the optimizer step over the permutations."""

    def fused_train_step(params, opt_state, full_batch, perms):
        def body(carry, perm):
            p, o = carry
            mb = jax.tree_util.tree_map(lambda x: x[perm], full_batch)
            p, o, loss, stats = trainer._step_update(p, o, mb)
            return (p, o), (loss, stats)

        (params, opt_state), (losses, stats) = jax.lax.scan(body, (params, opt_state), perms)
        mean_stats = jax.tree_util.tree_map(lambda x: jnp.mean(x, axis=0), stats)
        return params, opt_state, jnp.mean(losses), mean_stats

    return fused_train_step


def block_jaxpr(trainer, fn, batch, perms):
    with trainer.mesh:
        return jax.make_jaxpr(fn)(trainer.params, trainer.opt_state, batch, jnp.asarray(perms))


def gauges_of(trainer, build):
    """The gauge rows a builder of train steps writes."""
    rows, real = [], trainer.obs.gauge
    trainer.obs.gauge = lambda **kw: rows.append(kw)
    try:
        build()
    finally:
        trainer.obs.gauge = real
    return rows


def test_the_block_that_holds_the_trunk_equals_the_block_that_runs_it_in_every_step(frozen):
    """Three epochs over the batch (rows == batch: one group). The causal
    cases hold the trunk up to the branch point (with the value branch
    deeper both captures are held; shallower, its capture carries gradient
    and stays in the step) and come out bit for bit: the same operations on
    the same rows, the trunk's only moved out of the loop. T5 keeps the
    whole forward in the block."""
    trainer, batch = frozen["trainer"], frozen["batch"]
    perms = block_perms(ROWS, ROWS, 3)
    if trainer.seq2seq:
        assert trainer.trunk_layers_held() == 0
        assert str(block_jaxpr(trainer, trainer.make_fused_train_steps().__wrapped__, batch, perms)) == str(
            block_jaxpr(trainer, parents_block(trainer), batch, perms))
        return
    model = trainer.model
    assert trainer.trunk_layers_held() == model.branch_at == 6
    with trainer.mesh:
        captures, counters = jax.eval_shape(trainer.trunk_constants, trainer.params, batch)
    held_points = [p for p in model._capture_points() if p <= model.branch_at]
    assert len(captures) == len(held_points) and counters is None
    assert all(c.shape == (ROWS, P + N, 16) for c in captures)
    held, whole = block_both_ways(trainer, batch, perms)
    assert np.isfinite(held[2]) and any(
        (a != b).any() for a, b in zip(*map(jax.tree_util.tree_leaves, (held[0], trainer.params))))
    assert_same_block(held, whole)


@pytest.mark.parametrize("rows", [16, 12])
def test_rows_beyond_the_batch_are_held_in_groups_of_the_batch(frozen, rows):
    """`batch < rollouts`: the trunk runs over the block's rows in groups
    of the step's batch size (`lax.map`; a ragged last group wraps round),
    and a step gathers its rows from all of them."""
    trainer = frozen["trainer"]
    if trainer.seq2seq:  # T5 holds nothing, whatever the rows
        assert trainer._block_trunk(trainer.params, rollout_batch(True, rows=rows), 2) is None
        return
    batch = rollout_batch(False, rows=rows)
    held, whole = block_both_ways(trainer, batch, block_perms(rows, ROWS, 2))
    assert_same_block(held, whole)


def test_the_held_captures_split_with_the_microbatches(tmp_path):
    """`num_mb > 1` over rows in two groups: each microbatch resumes from
    its own rows of the captures."""
    trainer = build_trainer(tmp_path, FROZEN_CASES["value_deeper-remat_full"],
                            batch_size=16, minibatch_size=8)
    assert (trainer.num_mb, trainer.mb_size, trainer.trunk_layers_held()) == (2, 8, 6)
    batch = rollout_batch(False, rows=32)
    held, whole = block_both_ways(trainer, batch, block_perms(32, 16, 2))
    assert_same_block(held, whole)


BLOCK_BYPASS = dict(
    {name: (case, {}) for name, case in BYPASS_CASES.items()},
    # the pipelined forward keeps the whole backward, and the whole forward
    pp2=(FROZEN_CASES["hydra-remat_full"], dict(mesh={"pp": 2, "dp": 4, "fsdp": 1, "tp": 1})),
)


@pytest.mark.parametrize("name", list(BLOCK_BYPASS))
def test_bypass_configurations_trace_to_the_parents_program(name, tmp_path):
    """All layers trainable, a peft adapter, an embedding LayerNorm
    (which trains, under every layer): no stop, the same jaxpr as with
    `frozen_below=0` forced, which is the parent's. Those, and `pp > 1`,
    hold nothing in the fused block either: it traces to the parent's
    block, and both builders write `model/trunk_layers_hoisted` 0."""
    case, train = BLOCK_BYPASS[name]
    trainer = build_trainer(tmp_path, case, **train)
    batch = rollout_batch(trainer.seq2seq)
    if name in BYPASS_CASES:
        assert trainer.model.frozen_below() == 0
        with trainer.mesh:
            jaxpr = jax.make_jaxpr(loss_grad(trainer, batch))(trainer.params)
            with full_backward(trainer):
                jaxpr_full = jax.make_jaxpr(loss_grad(trainer, batch))(trainer.params)
        assert str(jaxpr) == str(jaxpr_full)
    assert trainer.trunk_layers_held() == 0
    perms = block_perms(ROWS, ROWS, 2)
    built = {}
    rows = gauges_of(trainer, lambda: built.update(
        fused=trainer.make_fused_train_steps(), step=trainer.make_train_step()))
    assert [row["model/trunk_layers_hoisted"] for row in rows] == [0, 0]
    assert str(block_jaxpr(trainer, built["fused"].__wrapped__, batch, perms)) == str(
        block_jaxpr(trainer, parents_block(trainer), batch, perms))


def test_the_per_step_program_keeps_the_whole_forward(frozen):
    """`make_train_step` has nowhere to keep a trunk's output: on a frozen
    trunk it traces to the parent's step (the trunk's scan inside it), and
    its gauge reads 0 where the fused block's reads the trunk's layers."""
    trainer, batch = frozen["trainer"], frozen["batch"]
    built = {}
    rows = gauges_of(trainer, lambda: built.update(
        step=trainer.make_train_step(), fused=trainer.make_fused_train_steps()))
    held = 0 if trainer.seq2seq else trainer.model.branch_at
    assert [row["model/trunk_layers_hoisted"] for row in rows] == [0, held]
    assert all(row["model/backward_layers"] == 2 for row in rows)
    with trainer.mesh:
        step = jax.make_jaxpr(built["step"].__wrapped__)(trainer.params, trainer.opt_state, batch)
        parents = jax.make_jaxpr(trainer._step_update)(trainer.params, trainer.opt_state, batch)
    assert str(step) == str(parents)
    # the trunk's first segment (T5: the encoder) is scanned forward inside the step
    first = trainer.model.cfg.n_layer if trainer.seq2seq else min(trainer.model._capture_points())
    assert (first, False) in scans_of(step.jaxpr)


def test_the_memory_plan_has_a_row_for_the_held_captures(frozen):
    """`_extra_plan_items`: what lives across the block's scan, a device's
    rows of every held capture in the compute dtype; T5 holds nothing."""
    trainer = frozen["trainer"]
    if trainer.seq2seq:
        assert trainer.trunk_layers_held() == 0
        return
    rows = [item for item in trainer._extra_plan_items() if item.component == "trunk_constants"]
    model = trainer.model
    held = sum(p <= model.branch_at for p in model._capture_points())
    (row,) = rows
    assert row.phase == "train"
    assert row.bytes == held * (ROWS // trainer.data_ways()) * (P + N) * 16 * 4


def outer_scan(jaxpr, length):
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "scan" and e.params["length"] == length]
    return eqn.params["jaxpr"].jaxpr


def test_gradient_flops_of_top2_of_8(tmp_path):
    """Structural, and cannot pass by accident: the parent's path reads
    1.0 here. Counted in units of one layer's forward F (38.3 MFLOP at
    width 128, 8 x 12 positions), `remat: full`: the parent runs F + F +
    1.67 F (the transposed body) in each of 8 layers = 29.4; now 6 F
    under the stop and 3.67 F in each of the top 2 = 13.3. On both sides
    ride the heads (the value head is 4 x width wide: 1.3 F with its
    backward) and, in the jaxpr as traced, the reference branch's
    forward (2.2 F of dead code that XLA removes): 16.8 / 32.9 = 0.51,
    where ISSUE 26 reckoned 14 / 32 for the layers alone.

    The BLOCK of e epochs: one trunk forward and e times the step's rest,
    where the block that runs the whole forward pays e times both; the
    trunk's scan of 6 layers stands before the scan over the steps and
    nowhere inside it."""
    wide = dict(CAUSAL, hidden_size=128, n_head=4)
    trainer = build_trainer(tmp_path, ({"transformer": wide}, 2, "full", 0, None))
    batch = rollout_batch(False)
    with trainer.mesh:
        jaxpr = jax.make_jaxpr(loss_grad(trainer, batch))(trainer.params)
        with full_backward(trainer):
            jaxpr_full = jax.make_jaxpr(loss_grad(trainer, batch))(trainer.params)
        trunk = matmul_flops(jax.make_jaxpr(trainer.trunk_constants)(trainer.params, batch).jaxpr)
    ratio = matmul_flops(jaxpr.jaxpr) / matmul_flops(jaxpr_full.jaxpr)
    assert 0.45 < ratio < 0.53, ratio

    def block_flops(epochs):
        perms = block_perms(ROWS, ROWS, epochs)
        return block_jaxpr(trainer, trainer.make_fused_train_steps().__wrapped__, batch, perms).jaxpr

    e = 3
    held_1, held_e = block_flops(1), block_flops(e)
    with whole_forward(trainer):
        whole_e = block_flops(e)
    step = matmul_flops(held_1) - trunk  # a step's rest: the top two layers, the heads, their backward
    layer = trunk / 6
    assert 0.9 * 38.3e6 < layer < 1.1 * 38.3e6
    assert matmul_flops(held_e) == trunk + e * step
    assert matmul_flops(whole_e) == e * (trunk + step)
    assert 0.3 < trunk / (trunk + step) < 0.45  # 6 F of 6 + 2 x 3.67 + the heads and the dead branch
    assert scans_of(held_e).count((6, False)) == 1
    assert (6, False) not in scans_of(outer_scan(held_e, e))
    assert (6, False) in scans_of(outer_scan(whole_e, e))
