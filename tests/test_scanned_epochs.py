"""Scanned-epoch (fused lax.scan) golden equivalence + rollout-overlap
tests: the dispatch-free PPO cycle must be a pure performance change.

- the scanned optimization path (train.fused_inner_loop, default ON)
  must produce the SAME minibatch sequence and numerically matching
  losses/params as the per-step loop (the golden check the default
  rests on),
- `pipeline.epoch_shuffle_order` is the single shuffle source all three
  consumers (host loader, device-gather loader, scanned perms) agree on,
- `ppo.overlap_rollouts` must train to completion with correct prompt
  cursor bookkeeping and deferred (one-cycle-delayed) metrics staying
  monotonic. Runs under tier-1 (CPU, not slow).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trlx_tpu
from tests.test_trainers import (
    PPO_PROMPTS,
    ppo_tiny_config,
    read_metrics,
    word_count_reward,
)


def _build_ppo(tmp_path, **kw):
    """A tiny PPO trainer wired to the prompt pipeline by hand (the
    api.train path minus learn()), so tests can drive make_experience
    and the train steps directly."""
    from trlx_tpu.pipeline.offline_pipeline import PromptPipeline
    from trlx_tpu.utils.loading import get_trainer

    config = ppo_tiny_config(str(tmp_path / "ckpts"), **kw)
    trainer = get_trainer(config.train.trainer)(
        config=config, reward_fn=word_count_reward
    )
    max_prompt_length = (
        config.train.seq_length - config.method.gen_kwargs["max_new_tokens"]
    )
    trainer.add_prompt_pipeline(
        PromptPipeline(PPO_PROMPTS, max_prompt_length, trainer.tokenizer)
    )
    return trainer, config


def _copy(tree):
    """Deep copy a device pytree preserving shardings (so both the
    looped and scanned runs start from bit-identical state and neither
    donation invalidates the trainer's own params)."""
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(np.asarray(x), x.sharding), tree
    )


def test_epoch_shuffle_order_matches_loaders():
    """Both loader flavors' first-iteration order IS epoch_shuffle_order
    — the contract the scanned path's permutations are built on."""
    from trlx_tpu.pipeline import DataLoader, epoch_shuffle_order
    from trlx_tpu.pipeline.ppo_pipeline import _DeviceGatherLoader

    n, bs, seed = 16, 8, 1234
    order = epoch_shuffle_order(n, seed)

    dev_loader = _DeviceGatherLoader(
        {"ix": jnp.arange(n)}, bs, shuffle=True, drop_last=True, seed=seed
    )
    got_dev = np.concatenate([np.asarray(b["ix"]) for b in dev_loader])
    np.testing.assert_array_equal(got_dev, order)

    host_loader = DataLoader(
        list(range(n)), bs, collate_fn=np.asarray, shuffle=True,
        drop_last=True, seed=seed,
    )
    got_host = np.concatenate(list(host_loader))
    np.testing.assert_array_equal(got_host, order)


def test_scanned_epoch_matches_looped(tmp_path):
    """Golden check: the fused lax.scan over minibatch permutations and
    the per-step loop produce matching mean loss AND matching final
    params from the same rollout store (same seeds, same minibatch
    order) — numerical tolerance only covers compilation differences."""
    trainer, config = _build_ppo(
        tmp_path, method=dict(num_rollouts=16, chunk_size=8, ppo_epochs=2)
    )
    trainer.n_inner_epochs = 2
    trainer.make_experience(16)
    full, n = trainer._fused_epoch_batch()
    assert n == 16
    perms = trainer._epoch_perms(n)
    bs = config.train.batch_size
    assert perms.shape == (2 * (16 // bs), bs)

    # the scanned perms must BE the per-epoch loader orders (same seed
    # stream): minibatch composition is identical, not just similar
    from trlx_tpu.pipeline import epoch_shuffle_order

    want = np.concatenate([
        epoch_shuffle_order(n, config.train.seed + 0)[: len(perms) // 2 * bs],
        epoch_shuffle_order(n, config.train.seed + 2)[: len(perms) // 2 * bs],
    ])
    np.testing.assert_array_equal(perms.reshape(-1), want)

    device_full = trainer.place_batch(full)
    # build both jitted fns BEFORE any donation touches trainer state
    fused = trainer.make_fused_train_steps()
    step = trainer.make_train_step()

    # looped: the exact _learn inner-loop semantics — a fresh reshuffled
    # loader per inner epoch, seeded by train.seed + iter_count
    p_l, o_l = _copy(trainer.params), _copy(trainer.opt_state)
    losses = []
    it = 0
    for _ in range(2):
        loader = trainer.store.create_loader(
            bs, shuffle=True, drop_last=True, seed=config.train.seed + it
        )
        for batch in loader:
            db = trainer.place_batch(batch)
            with trainer.mesh:
                p_l, o_l, loss, _ = step(p_l, o_l, db)
            losses.append(float(loss))
            it += 1
    assert it == len(perms)

    # the FIRST step sees bit-identical params and rows in both programs:
    # its loss is the tight pin (the scan body and the standalone step
    # differ by an ulp, 6e-8 relative here)
    p_1, o_1 = _copy(trainer.params), _copy(trainer.opt_state)
    with trainer.mesh:
        _, _, first_loss, _ = fused(p_1, o_1, device_full, jnp.asarray(perms[:1]))
    np.testing.assert_allclose(float(first_loss), losses[0], rtol=1e-6)

    p_s, o_s = _copy(trainer.params), _copy(trainer.opt_state)
    with trainer.mesh:
        p_s, o_s, mean_loss, _ = fused(p_s, o_s, device_full, jnp.asarray(perms))

    # NOT the order of a float32 mean over the steps (float32 against
    # float64 over these four losses is 5e-8): each later step starts
    # from params the two programs rounded differently, which AdamW
    # amplifies (see below), so the per-step losses drift apart as the
    # steps go: 6e-8, 1e-6, 6e-5, 1e-5 relative here, 1.35e-5 on the
    # mean. The bound is about ten times the drift read; the minibatch
    # order is pinned exactly above (perms), the arithmetic by the
    # first step.
    np.testing.assert_allclose(
        float(mean_loss), float(np.mean(losses)), rtol=1e-4, atol=1e-6
    )
    # params: the two compiled programs (scan body vs standalone step)
    # may round differently at the last bit, and AdamW's m/sqrt(v)
    # normalization amplifies that to ~lr scale where gradients are near
    # zero — so the param check is absolute at a fraction of the total
    # update budget, while the first step's loss above pins the tight match
    for a, b in zip(
        jax.tree_util.tree_leaves(p_l), jax.tree_util.tree_leaves(p_s)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=0
        )


def test_overlap_rollouts_learns_and_cleans_up(tmp_path):
    """A full learn() with overlap_rollouts on: trains to total_steps,
    leaves no dangling prefetch, accounts every trained chunk in the
    prompt cursor, and the deferred metrics stay step-monotonic with one
    finite loss record per optimizer step."""
    ckpt_dir = str(tmp_path / "ckpts")
    config = ppo_tiny_config(
        ckpt_dir,
        train=dict(total_steps=4, epochs=4, eval_interval=100,
                   checkpoint_interval=100, save_best=False),
        method=dict(overlap_rollouts=True, num_rollouts=8, chunk_size=8),
    )
    trainer = trlx_tpu.train(
        reward_fn=word_count_reward, prompts=PPO_PROMPTS, config=config
    )
    assert trainer.iter_count == 4
    assert trainer._prefetched_gen is None
    # 1 initial cycle + 3 post-epoch cycles, every one trained: the
    # cursor counts them all and no prefetch is left half-charged
    assert trainer._prompt_batches_consumed == 4
    assert trainer._extra_state()["prompt_batches_consumed"] == 4

    recs = read_metrics(ckpt_dir)
    steps = [r["_step"] for r in recs]
    assert steps == sorted(steps), f"non-monotonic tracker steps: {steps}"
    losses = [
        (r["_step"], r["losses/total_loss"])
        for r in recs if "losses/total_loss" in r
    ]
    assert [s for s, _ in losses] == [1, 2, 3, 4]
    assert all(np.isfinite(l) for _, l in losses)


def test_prefetch_cursor_excluded_until_trained(tmp_path):
    """An in-flight prefetched chunk must NOT count in the persisted
    prompt cursor (it has not trained), and abandoning it rewinds the
    live cursor."""
    trainer, _ = _build_ppo(tmp_path, method=dict(overlap_rollouts=True))
    trainer.make_experience(8)
    assert trainer._prompt_batches_consumed == 1
    assert trainer._extra_state()["prompt_batches_consumed"] == 1

    trainer.pre_optimization_hook(will_continue=True)
    assert trainer._prefetched_gen is not None
    assert trainer._prompt_batches_consumed == 2  # live cursor advanced
    assert trainer._extra_state()["prompt_batches_consumed"] == 1  # persisted: not yet

    trainer._abandon_prefetch()
    assert trainer._prefetched_gen is None
    assert trainer._prompt_batches_consumed == 1

    # will_continue=False (final block) must not prefetch at all
    trainer.pre_optimization_hook(will_continue=False)
    assert trainer._prefetched_gen is None


def test_async_metrics_off_restores_immediate_flush(tmp_path):
    """train.async_metrics=false: every fused block flushes its stats
    synchronously (no deferral), and the run still matches the step
    budget — the escape hatch for exact per-block observability. Rides
    along: checkpoint_interval=0 with save_best off commits nothing, not
    even the final checkpoint."""
    ckpt_dir = str(tmp_path / "ckpts")
    config = ppo_tiny_config(
        ckpt_dir,
        train=dict(total_steps=2, epochs=2, eval_interval=100,
                   checkpoint_interval=0, save_best=False,
                   async_metrics=False),
    )
    trainer = trlx_tpu.train(
        reward_fn=word_count_reward, prompts=PPO_PROMPTS, config=config
    )
    assert trainer.iter_count == 2
    assert not trainer._deferred_train
    assert not [d for d in os.listdir(ckpt_dir) if "checkpoint" in d]
    losses = [
        r["losses/total_loss"] for r in read_metrics(ckpt_dir)
        if "losses/total_loss" in r
    ]
    assert len(losses) == 2 and all(np.isfinite(l) for l in losses)
