"""The optimizer step walks the rows it trains (`ops/trainable_view.py`).

A freeze mask is concrete when the step is traced, so what each leaf needs
of the optimizer is static: the whole, a run of rows, nothing, or (where
the cut is not possible) the whole leaf with its mask, the path every leaf
took before. These tests hold the step on the view to that full walk, bit
for bit, on both optimizer paths (`fused_apply` over int8 moments, optax
`update` + `apply_updates`), for parameters and for the state, whose tree
and shapes stay what `tx.init` gives; count what the built step streams;
and keep the NaN guard's promise on the view."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.test_frozen_trunk import (
    assert_same_block, block_perms, gauges_of, rollout_batch, run_block)
from trlx_tpu.data.default_configs import default_ppo_config
from trlx_tpu.ops import adam8bit, trainable_view as tv
from trlx_tpu.ops.adam8bit import FusedAdamW8bit, Q8
from trlx_tpu.trainer.base import _mask_updates

LR, WD, STEPS = 1e-2, 0.1, 3
ROWS6 = np.float32([0, 0, 0, 0, 1, 1])


def rows_mask(rows, ndim):
    return jnp.asarray(rows, jnp.float32).reshape((-1,) + (1,) * (ndim - 1))


# name -> (shape, mask as a function of the shape, the mark it must get)
LEAVES = {
    "suffix": ((6, 2, 256), lambda s: rows_mask(ROWS6, 3), tv.Rows(4, 6)),
    "suffix_2d": ((6, 512), lambda s: rows_mask(ROWS6, 2), tv.Rows(4, 6)),
    "middle_run": ((6, 256), lambda s: rows_mask([0, 1, 1, 1, 0, 0], 2), tv.Rows(1, 4)),
    "all_rows": ((6, 100), lambda s: rows_mask(np.ones(6), 2), tv.WHOLE),
    "no_rows": ((6, 2, 256), lambda s: rows_mask(np.zeros(6), 3), tv.NOTHING),
    "no_rows_odd_tail": ((6, 100), lambda s: rows_mask(np.zeros(6), 2), tv.NOTHING),
    "scalar_1": ((8, 40), lambda s: np.float32(1.0), tv.WHOLE),
    "scalar_0": ((10, 256), lambda s: np.float32(0.0), tv.NOTHING),
    # the three that must fall back to the whole walk with their mask
    "tail_not_blocks": ((6, 3, 100), lambda s: rows_mask(ROWS6, 3), tv.MASKED),
    "elementwise": ((4, 256), lambda s: jnp.asarray(np.arange(4 * 256).reshape(s) % 3 == 0, jnp.float32), tv.MASKED),
    "rows_not_one_run": ((6, 256), lambda s: rows_mask([1, 0, 1, 1, 0, 1], 2), tv.MASKED),
    "stacked_vector": ((6,), lambda s: rows_mask(ROWS6, 1), tv.MASKED),
}
FALLBACKS = ["tail_not_blocks", "elementwise", "rows_not_one_run"]


def toy(names, seed=0):
    rng = np.random.default_rng(seed)
    params = {n: jnp.asarray(rng.normal(size=LEAVES[n][0]), jnp.float32) for n in names}
    mask = {n: LEAVES[n][1](LEAVES[n][0]) for n in names}
    return params, mask


def toy_grads(params, step):
    rng = np.random.default_rng(100 + step)
    return {n: jnp.asarray(rng.normal(size=p.shape), jnp.float32) for n, p in params.items()}


@pytest.mark.parametrize("name", list(LEAVES))
def test_marks(name):
    params, mask = toy([name])
    assert tv.trainable_view(mask, params) == {name: LEAVES[name][2]}
    assert tv.trainable_view(None, params) is None


# -- both optimizer paths, as functions of (params, grads, state) -------------


def make_tx(path, mask):
    """(tx, the mask the step hands it) as `_assemble_optimizer` builds them."""
    if path == "fused":
        return FusedAdamW8bit(LR, weight_decay=WD), mask
    return optax.chain(optax.adamw(LR, weight_decay=WD), _mask_updates(mask)), None


def apply(tx, params, grads, state, mask):
    if hasattr(tx, "fused_apply"):
        return tx.fused_apply(params, grads, state, mask=mask)
    updates, state = tx.update(grads, state, params)
    return optax.apply_updates(params, updates), state


def full_walk(path, params, mask, grads_by_step):
    """The walk every leaf took before the view: every row of every leaf
    through the optimizer, the step multiplied by the mask."""
    tx, mask = make_tx(path, mask)
    state = tx.init(params)
    for grads in grads_by_step:
        params, state = jax.jit(lambda p, g, s: apply(tx, p, g, s, mask))(params, grads, state)
    return params, state


def step_on_view(tx, view, left):
    """The step `_step_update` builds: cut, run the optimizer, write back."""

    def step(params, grads, state):
        marks = tv.state_view(tx, state, view)
        new_params, new_state = apply(
            tx, tv.cut(params, view), tv.cut(grads, view), tv.cut(state, marks), left)
        return tv.paste(params, new_params, view), tv.paste(state, new_state, marks)

    return step


def view_walk(path, params, mask, grads_by_step):
    view = tv.trainable_view(mask, params)
    tx, left = make_tx(path, tv.view_mask(mask, view))
    state = tx.init(params)
    for grads in grads_by_step:
        params, state = jax.jit(step_on_view(tx, view, left))(params, grads, state)
    return params, state


def ones(mask, leaf):
    """Elements of `leaf` whose (broadcast) mask is 1."""
    return int(np.broadcast_to(np.asarray(mask), leaf.shape).sum())


def assert_bit_identical(got, want):
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=jax.tree_util.keystr(path))


CASES = {
    "a-suffix_rows": ["suffix", "suffix_2d", "middle_run", "scalar_1", "scalar_0", "no_rows"],
    **{f"c-{name}": [name, "suffix", "all_rows"] for name in FALLBACKS},
    "everything": list(LEAVES),
}


@pytest.mark.parametrize("path", ["fused", "adamw"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_view_leaves_what_the_full_walk_leaves(case, path, monkeypatch):
    """(a), (b), (c): three steps with weight decay, parameters and moments
    (int8 payloads and scales, or float32 `mu` / `nu`) bit for bit, the
    state's tree and shapes those of `init`."""
    monkeypatch.setattr(adam8bit, "_FUSED_CHUNK_ELEMS", 512)  # several chunks a leaf
    params, mask = toy(CASES[case])
    grads = [toy_grads(params, i) for i in range(STEPS)]
    # a frozen row's gradient is zero where the trunk is a constant of the loss
    grads = [jax.tree_util.tree_map(lambda g, m: g * m, g, mask) for g in grads]
    got, want = view_walk(path, params, mask, grads), full_walk(path, params, mask, grads)
    assert_bit_identical(got, want)
    moved = [n for n in params if (np.asarray(got[0][n]) != np.asarray(params[n])).any()]
    assert set(moved) == {n for n in params if LEAVES[n][2] != tv.NOTHING}


@pytest.mark.parametrize("path", ["fused", "adamw"])
def test_an_all_zero_row_mask_walks_nothing(path):
    """(d): parameter and moments untouched, and no work in the program: no
    scan over chunks on the fused path, no square root on either."""
    params, mask = toy(["no_rows", "scalar_0", "no_rows_odd_tail"])
    view = tv.trainable_view(mask, params)
    assert set(view.values()) == {tv.NOTHING}
    tx, left = make_tx(path, tv.view_mask(mask, view))
    state = tx.init(params)
    grads, step = toy_grads(params, 0), step_on_view(tx, view, left)
    jaxpr = jax.make_jaxpr(step)(params, grads, state)
    assert not {"scan", "sqrt", "dynamic_update_slice"} & {e.primitive.name for e in jaxpr.jaxpr.eqns}
    new_params, new_state = jax.jit(step)(params, grads, state)
    assert_bit_identical(new_params, params)
    for new, old in zip(*map(jax.tree_util.tree_leaves, (new_state, state))):
        if new.ndim:  # the moments; the step counts move on
            np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


# -- the trainer's step: a stacked toy, top 2 of 6 layers ---------------------

WIDE = dict(hidden_size=256, n_layer=6, n_head=2, n_positions=64)  # every row whole blocks
NARROW = dict(hidden_size=16, n_layer=6, n_head=2, n_positions=64)  # norms and biases fall back
OPTIMIZERS = {
    "fused": dict(name="adamw_8bit_fused", kwargs=dict(lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.01)),
    "adamw": dict(name="adamw", kwargs=dict(lr=1e-3, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.01)),
}


def build_trainer(ckpt_dir, path, transformer=NARROW, **train):
    from trlx_tpu.trainer.ppo import TPUPPOTrainer

    path, _, sharded = path.partition("-")
    if sharded:  # float32 moments sharded with their parameters, as the four-chip cell runs
        train["mesh"] = {"dp": 1, "fsdp": 4, "tp": 1, "pp": 1}
    config = default_ppo_config().evolve(
        train=dict(dict(batch_size=8, total_steps=2, seq_length=12, epochs=1, tracker=None,
                        checkpoint_dir=str(ckpt_dir), compute_dtype="float32"), **train),
        model=dict(model_path="random", num_layers_unfrozen=2,
                   model_extra_configs={"transformer": transformer}),
        tokenizer=dict(tokenizer_path="byte"),
        optimizer=OPTIMIZERS[path],
        method=dict(num_rollouts=8, chunk_size=8, ppo_epochs=1,
                    gen_kwargs=dict(max_new_tokens=4, do_sample=True)),
    )
    return TPUPPOTrainer(config, reward_fn=lambda **kw: [0.0])


class full_walk_step:
    """The step as it was before the view: every leaf whole, the freeze mask
    multiplied in (streamed through `fused_apply`, or chained behind the
    optax transformation)."""

    def __init__(self, trainer):
        self.trainer = trainer
        self.saved = trainer._view, trainer.tx

    def __enter__(self):
        t = self.trainer
        t._view = None  # no view: the mask is applied as it is
        t.tx, _ = t._assemble_optimizer(t.config.optimizer, t.config.scheduler)

    def __exit__(self, *exc):
        self.trainer._view, self.trainer.tx = self.saved


@pytest.fixture(scope="module", params=[*OPTIMIZERS, "adamw-fsdp4"])
def narrow(request, tmp_path_factory):
    path = request.param.partition("-")[0]
    return build_trainer(tmp_path_factory.mktemp(request.param), request.param), path


@pytest.fixture(scope="module")
def block(narrow):
    """(rows, permutations, what the fused block on the view leaves), run once
    a trainer: two epochs on the int8 path, one step on the optax path."""
    trainer, path = narrow
    batch, perms = rollout_batch(False), block_perms(8, 8, 2 if path == "fused" else 1)
    return batch, perms, run_block(trainer, batch, perms)


def test_a_block_on_the_view_equals_the_block_that_walks_every_row(narrow, block):
    """The fused block: parameters, optimizer state, loss and stats bit for
    bit over two epochs on the int8 path; the state's tree and shapes are
    `tx.init`'s. On the optax path the moments and the loss are bit for bit
    and a parameter may differ in the last place of its leaf's largest: with
    no multiplication by the mask between them XLA:CPU contracts `p + (-lr)
    * step` into one fused multiply-add, which rounds once where the full
    walk rounds twice (one step here; the functional cases above hold three
    to the bit)."""
    trainer, path = narrow
    marks = set(jax.tree_util.tree_leaves(trainer._view))
    assert tv.MASKED in marks and tv.NOTHING in marks and tv.WHOLE in marks
    assert any(isinstance(m, tv.Rows) for m in marks)
    batch, perms, on_view = block
    with full_walk_step(trainer):
        whole = run_block(trainer, batch, perms)
    assert_same_block(on_view[1:], whole[1:])
    # the last place of a leaf's largest entry: a float32 has 24 bits
    assert_same_block(on_view[0], whole[0], rtol=0.0 if path == "fused" else 2.0 ** -23)
    init = jax.eval_shape(trainer.tx.init, trainer.params)
    assert jax.tree_util.tree_structure(on_view[1]) == jax.tree_util.tree_structure(init)
    assert [x.shape for x in jax.tree_util.tree_leaves(on_view[1])] == [
        x.shape for x in jax.tree_util.tree_leaves(init)]


def sqrt_elements(jaxpr, scope=None, times=1):
    """Elements that pass through a square root (Adam's denominator; the
    int8 code's companding) in the equations under `scope`, a scan's body
    counted `length` times."""
    total = 0
    for eqn in jaxpr.eqns:
        if scope is not None and scope not in str(eqn.source_info.name_stack):
            continue
        if eqn.primitive.name == "sqrt":
            total += times * math.prod(eqn.outvars[0].aval.shape)
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += sqrt_elements(sub, None, inner)
    return total


@pytest.mark.parametrize("path", list(OPTIMIZERS))
def test_the_step_streams_the_trained_rows(path, tmp_path, monkeypatch):
    """(e): at a width where every row is whole blocks the optimizer part of
    the step's jaxpr streams `optim/params_walked` elements, which is the
    count of the trained ones: top 2 of 6 layers, the heads, no embedding."""
    monkeypatch.setattr(adam8bit, "_FUSED_CHUNK_ELEMS", 256)  # a chunk a block: no pad rows
    trainer = build_trainer(tmp_path, path, WIDE)
    (row,) = gauges_of(trainer, trainer.make_train_step)
    walked, trained = row["optim/params_walked"], row["optim/params_trained"]
    leaves = jax.tree_util.tree_leaves(trainer.params)
    total = sum(x.size for x in leaves)
    assert tv.MASKED not in jax.tree_util.tree_leaves(trainer._view)
    assert walked == trained == sum(
        ones(m, p) for m, p in zip(jax.tree_util.tree_leaves(trainer._update_mask), leaves))
    assert 0.25 * total < trained < 0.45 * total  # 2 of 6 layers and the heads
    batch = rollout_batch(False)
    with trainer.mesh:
        jaxpr = jax.make_jaxpr(trainer._step_update)(trainer.params, trainer.opt_state, batch)
    # an element: once through Adam's denominator; its two int8 codes once each
    per_element = 3 if path == "fused" else 1
    padded = sum(-(-x.size // 256) * 256 for x in jax.tree_util.tree_leaves(
        tv.cut(trainer.params, trainer._view)))
    assert sqrt_elements(jaxpr.jaxpr, "optimizer_update") == per_element * (
        padded if path == "fused" else walked)
    with full_walk_step(trainer):
        with trainer.mesh:
            before = jax.make_jaxpr(trainer._step_update)(trainer.params, trainer.opt_state, batch)
    assert sqrt_elements(before.jaxpr, "optimizer_update") > 2 * per_element * walked


def test_fallback_leaves_are_the_gap_between_walked_and_trained(narrow):
    trainer, _ = narrow
    (row,) = gauges_of(trainer, trainer.make_train_step)
    frozen_in_fallbacks = sum(
        p.size - ones(m, p)
        for mark, m, p in zip(*map(jax.tree_util.tree_leaves, (
            trainer._view, trainer._update_mask, trainer.params)))
        if mark == tv.MASKED)
    assert frozen_in_fallbacks > 0
    assert row["optim/params_walked"] == row["optim/params_trained"] + frozen_in_fallbacks


def test_a_nan_gradient_reaches_neither_parameters_nor_moments(narrow):
    """(f): `skip_nan_updates` on the view. The optax path commits the old
    state; the fused path takes a step of weight decay alone."""
    trainer, path = narrow
    assert trainer.config.train.skip_nan_updates
    batch = rollout_batch(False)
    batch = batch.replace(rewards=batch.rewards.at[0, 0].set(jnp.nan))
    state = jax.tree_util.tree_map(jnp.copy, (trainer.params, trainer.opt_state))
    with trainer.mesh:
        params, opt_state, loss, _ = jax.jit(trainer._step_update)(*state, batch)
    assert np.isnan(float(loss))
    for leaf in jax.tree_util.tree_leaves((params, opt_state)):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    if path == "adamw":
        assert_bit_identical((params, opt_state), (trainer.params, trainer.opt_state))
    frozen = jax.tree_util.tree_map(
        lambda p, m: np.asarray(p)[np.broadcast_to(np.asarray(m), p.shape) == 0],
        (params, trainer.params), (trainer._update_mask,) * 2)
    assert_bit_identical(*frozen)


def test_the_optimizer_state_survives_a_save_and_a_restore(narrow, block, tmp_path):
    """The state a block on the view leaves is the tree a checkpoint holds
    today: saved, restored into a fresh trainer, equal leaf for leaf."""
    trainer, path = narrow
    params, opt_state, _, _ = block[2]
    saved = trainer.params, trainer.opt_state
    trainer.params, trainer.opt_state = jax.tree_util.tree_map(
        lambda new, old: jax.device_put(new, old.sharding), (params, opt_state), saved)
    try:
        trainer.save(str(tmp_path / "ckpt"))
    finally:
        trainer.params, trainer.opt_state = saved
    fresh = build_trainer(tmp_path / "fresh", path, mesh=trainer.config.train.mesh)
    fresh.load(str(tmp_path / "ckpt"))
    assert_bit_identical(
        jax.tree_util.tree_map(np.asarray, (fresh.params, fresh.opt_state)), (params, opt_state))
