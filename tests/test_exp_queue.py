"""Experience transport (trlx_tpu/exp/): queue ordering + dedup, lease
expiry/reclaim on a fake clock, the staleness admission gate, the
delivery-interleaving property (any mix of duplicate / expired /
reordered deliveries consumes the fault-free sequence), and the
end-to-end golden check: ``ppo.exp.enabled`` fault-free is BIT-EQUAL
(store contents + loss stream + consumed prompt order) to the direct
rollout path on CPU.

Tier-1 budget: 70s (tests/test_marker_audit.py) — the learn() runs of
the golden / clip / reject-regeneration checks dominate; everything
else is host-side units.
"""

import json
import os
import random
import shutil

import numpy as np
import pytest

from trlx_tpu.exp import (
    ExpConfig,
    ExperienceChunk,
    ExperienceQueue,
    ExperienceTransport,
    LeaseTable,
    StalenessConfig,
)
from trlx_tpu.exp.queue import (
    OFFER_ACCEPTED,
    OFFER_DUPLICATE,
    OFFER_FULL,
    OFFER_STALE_EPOCH,
)
from trlx_tpu.exp import transport as exp_transport


def chunk(seq, epoch=0, version=0, payload=None):
    return ExperienceChunk(
        chunk_id=(epoch, seq), policy_version=version,
        payload=seq if payload is None else payload,
    )


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -- config ------------------------------------------------------------


def test_expconfig_validation():
    cfg = ExpConfig.from_dict(
        {"enabled": True, "max_depth": 2,
         "staleness": {"mode": "clip", "max_staleness": 3}}
    )
    assert cfg.enabled and cfg.max_depth == 2
    assert cfg.staleness.mode == "clip" and cfg.staleness.max_staleness == 3
    assert ExpConfig.from_dict(None).enabled is False
    with pytest.raises(ValueError, match="unknown keys"):
        ExpConfig.from_dict({"depth": 3})
    with pytest.raises(ValueError, match="unknown keys"):
        ExpConfig.from_dict({"staleness": {"modes": "reject"}})
    with pytest.raises(ValueError, match="mode must be"):
        StalenessConfig.from_dict({"mode": "drop"})
    with pytest.raises(ValueError, match="max_depth"):
        ExpConfig.from_dict({"max_depth": 0})


# -- queue -------------------------------------------------------------


def test_queue_in_order_consumption_and_dedup():
    q = ExperienceQueue(max_depth=4)
    # out-of-order arrival buffers until the gap fills
    assert q.offer(chunk(2)) == OFFER_ACCEPTED
    assert q.poll() is None  # waiting on seq 1
    assert q.offer(chunk(1)) == OFFER_ACCEPTED
    got = q.poll()
    assert got.seq == 1
    q.commit(got)
    assert q.cursor == 1
    # redelivery of a committed seq AND of a buffered seq both dedup
    assert q.offer(chunk(1)) == OFFER_DUPLICATE
    assert q.offer(chunk(2)) == OFFER_DUPLICATE
    got = q.poll()
    assert got.seq == 2
    # commit must be in-order
    with pytest.raises(ValueError, match="out-of-order"):
        q.commit(chunk(4))
    q.commit(got)
    assert q.cursor == 2 and q.depth == 0


def test_queue_backpressure_and_epoch():
    q = ExperienceQueue(max_depth=2)
    assert q.offer(chunk(1)) == OFFER_ACCEPTED
    assert q.offer(chunk(2)) == OFFER_ACCEPTED
    assert q.offer(chunk(3)) == OFFER_FULL  # back-pressure
    assert q.stats["full_rejections"] == 1
    # a chunk from an older epoch is dropped, not buffered
    q.advance_epoch()
    assert q.cursor == 0 and q.depth == 0
    assert q.offer(chunk(1, epoch=0)) == OFFER_STALE_EPOCH
    assert q.offer(chunk(1, epoch=1)) == OFFER_ACCEPTED
    # resume restores the committed position
    q.load_cursor(epoch=3, cursor=17)
    assert q.epoch == 3 and q.cursor == 17 and q.depth == 0
    assert q.next_undelivered() == 18


# -- leases ------------------------------------------------------------


def test_lease_expiry_and_reclaim_on_fake_clock():
    clock = FakeClock()
    table = LeaseTable(ttl_s=1.0, clock=clock)
    lease = table.acquire((0, 1), "w0", meta={"x": 1})
    # a live lease cannot be double-acquired or reclaimed
    with pytest.raises(ValueError, match="already leased"):
        table.acquire((0, 1), "w1")
    with pytest.raises(ValueError, match="still live"):
        table.reclaim((0, 1), "w1")
    # heartbeats keep it alive past the raw TTL
    clock.advance(0.8)
    table.heartbeat((0, 1))
    clock.advance(0.8)
    assert table.expired() == []
    # silence past the TTL expires it; reclaim keeps the replay meta
    clock.advance(1.1)
    assert [l.chunk_id for l in table.expired()] == [(0, 1)]
    fresh = table.reclaim((0, 1), "w1")
    assert fresh.attempt == 2 and fresh.meta == {"x": 1}
    assert table.expired() == []  # fresh heartbeat clock
    # a dead producer's beats are ignored — death = beats stop
    table.mark_dead((0, 1))
    table.heartbeat((0, 1))
    clock.advance(1.1)
    assert [l.chunk_id for l in table.expired()] == [(0, 1)]
    table.release((0, 1))
    assert table.outstanding == 0
    assert lease.attempt == 1  # the original object is unchanged


# -- transport ---------------------------------------------------------


def _transport(clock=None, **over):
    cfg = ExpConfig.from_dict(
        {"enabled": True, "lease_ttl_s": 1.0, "wait_poll_s": 0.0,
         "offer_timeout_s": 5.0, **over}
    )
    return ExperienceTransport(
        cfg, clock=clock or FakeClock(), sleep=lambda s: None
    )


def test_transport_produce_deliver_consume_cycle():
    t = _transport()
    lease = t.begin_chunk(snapshot={"cursor": 0})
    assert lease.chunk_id == (0, 1) and lease.meta == {"cursor": 0}
    assert t.deliver(lease, 0, payload="p1") == OFFER_ACCEPTED
    assert t.leases.outstanding == 0
    got = t.poll()
    verdict, staleness = t.admit(got, current_version=0)
    assert (verdict, staleness) == (exp_transport.ADMIT, 0)
    t.committed(got)
    assert t.queue.cursor == 1
    assert t.state_dict() == {"epoch": 0, "cursor": 1}


def test_transport_wedge_rides_backpressure_then_times_out():
    clock = FakeClock()
    waits = []

    def wait(poll_s):
        waits.append(poll_s)
        clock.advance(0.5)

    t = _transport(clock=clock, offer_timeout_s=2.0)
    t.wedge(offers=2)
    lease = t.begin_chunk()
    assert t.deliver(lease, 0, payload="p", wait=wait) == OFFER_ACCEPTED
    assert len(waits) == 2 and t.stats["backpressure_waits"] == 2
    # a wedge that never clears blows the bounded wait
    t2 = _transport(clock=clock, offer_timeout_s=2.0)
    t2.wedge(offers=10_000)
    with pytest.raises(RuntimeError, match="back-pressure"):
        t2.deliver(t2.begin_chunk(), 0, payload="p", wait=wait)


def test_transport_staleness_gate_reject_and_clip():
    t = _transport(staleness={"mode": "reject", "max_staleness": 1})
    lease = t.begin_chunk()
    t.deliver(lease, policy_version=0, payload="p")
    got = t.poll()
    # staleness 1 (the overlap prefetch) is admitted untouched
    assert t.admit(got, current_version=1) == (exp_transport.ADMIT, 1)
    # past the max: rejected, dropped from the buffer, cursor unmoved
    verdict, staleness = t.admit(got, current_version=5)
    assert (verdict, staleness) == (exp_transport.REJECT, 5)
    assert t.poll() is None and t.queue.cursor == 0
    # re-dispatch re-leases the SAME seq for regeneration
    redo = t.redispatch_rejected(got)
    assert redo.chunk_id == got.chunk_id
    t.deliver(redo, policy_version=5, payload="p2")
    got2 = t.poll()
    assert t.admit(got2, current_version=5) == (exp_transport.ADMIT, 0)
    t.committed(got2)
    assert t.queue.cursor == 1

    tc = _transport(staleness={"mode": "clip", "max_staleness": 1})
    lease = tc.begin_chunk()
    tc.deliver(lease, policy_version=0, payload="p")
    got = tc.poll()
    assert tc.admit(got, current_version=4) == (exp_transport.ADMIT_CLIP, 4)
    assert tc.stats["staleness_clips"] == 1


def test_transport_abort_epoch_voids_inflight():
    t = _transport()
    l1 = t.begin_chunk()
    t.deliver(l1, 0, payload="a")
    t.begin_chunk()  # an outstanding (undelivered) lease
    assert t.queue.depth == 1 and t.leases.outstanding == 1
    epoch = t.abort_epoch()
    assert epoch == 1
    assert t.queue.depth == 0 and t.leases.outstanding == 0
    # seqs restart under the new epoch
    assert t.begin_chunk().chunk_id == (1, 1)


# -- the delivery-interleaving property --------------------------------


def _fuzz_one(seed: int, n_chunks: int = 12) -> None:
    """One fuzz episode: producers generate chunks 1..n (payload = seq);
    a seeded adversary interleaves deliveries out of order, duplicates
    them, and kills producers mid-lease (expiry -> reclaim ->
    regeneration, which by the replay-snapshot contract reproduces the
    same payload). Whatever the interleaving, the consumer must commit
    payloads exactly [1..n] — the fault-free sequence."""
    rng = random.Random(seed)
    clock = FakeClock()
    t = _transport(clock=clock, max_depth=3)
    consumed = []
    ready = []  # produced-but-undelivered (lease, payload) pairs
    while len(consumed) < n_chunks:
        moves = ["consume"]
        # keep produced-in-flight (undelivered + buffered) within the
        # queue depth so a delivery can always eventually land
        if (
            t._produced_seq < n_chunks
            and t.queue.depth + len(ready) < t.cfg.max_depth
        ):
            moves += ["produce"] * 2
        if ready:
            moves += ["deliver", "deliver"]
        if t._produced_seq:
            moves += ["duplicate"]
        move = rng.choice(moves)
        if move == "produce":
            lease = t.begin_chunk(snapshot={"seq": t._produced_seq})
            if rng.random() < 0.3:
                # producer death mid-lease: TTL expiry, reclaim, and a
                # deterministic regeneration of the same payload. The
                # clock jump may expire OTHER outstanding leases too
                # (slow producers) — swap every reclaimed lease back
                # into the ready set under its chunk id.
                t.producer_died(lease)
                clock.advance(t.cfg.lease_ttl_s + 0.1)
                by_id = {
                    l.chunk_id: l for l in t.reclaim_expired()
                }
                ready = [
                    (by_id.get(l.chunk_id, l), p) for (l, p) in ready
                ]
                lease = by_id[lease.chunk_id]
            ready.append((lease, lease.chunk_id[1]))
            rng.shuffle(ready)  # deliveries may reorder
        elif move == "deliver" and ready:
            lease, payload = ready.pop()
            status = t.deliver(lease, 0, payload=payload)
            assert status in (OFFER_ACCEPTED, OFFER_DUPLICATE)
        elif move == "duplicate":
            # redeliver a random already-produced seq verbatim (a
            # retry racing its own success); landing one for a seq
            # whose real delivery is still pending is fine — dedup
            # drops whichever copy arrives second
            seq = rng.randint(1, t._produced_seq)
            dup = ExperienceChunk(
                chunk_id=(t.queue.epoch, seq), policy_version=0,
                payload=seq,
            )
            assert t.queue.offer(dup) in (
                OFFER_DUPLICATE, OFFER_FULL, OFFER_ACCEPTED
            )
        else:
            got = t.poll()
            if got is None:
                continue
            verdict, _ = t.admit(got, current_version=0)
            assert verdict == exp_transport.ADMIT
            consumed.append(got.payload)
            t.committed(got)
    assert consumed == list(range(1, n_chunks + 1)), (
        f"seed {seed}: consumed {consumed}"
    )


def test_delivery_interleaving_matches_fault_free_sequence():
    # property-style seeded fuzz (hypothesis drives it when installed;
    # the seeded loop is the floor either way)
    for seed in range(40):
        _fuzz_one(seed)


try:  # optional: let hypothesis explore beyond the seeded floor
    from hypothesis import given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_delivery_interleaving_hypothesis(seed):
        _fuzz_one(seed)
except ImportError:  # pragma: no cover - hypothesis not installed
    pass


# -- the staleness correction in the surrogate -------------------------


def test_ppo_loss_is_weight_scales_policy_term_only():
    import jax.numpy as jnp

    from trlx_tpu.ops.ppo import ppo_loss

    rng = np.random.default_rng(0)
    shape = (4, 6)
    kw = dict(
        logprobs=jnp.asarray(rng.normal(size=shape), jnp.float32),
        values=jnp.asarray(rng.normal(size=shape), jnp.float32),
        old_logprobs=jnp.asarray(rng.normal(size=shape), jnp.float32),
        old_values=jnp.asarray(rng.normal(size=shape), jnp.float32),
        advantages=jnp.asarray(rng.normal(size=shape), jnp.float32),
        returns=jnp.asarray(rng.normal(size=shape), jnp.float32),
        mask=jnp.ones(shape, jnp.float32),
        cliprange=0.2, cliprange_value=0.2, vf_coef=1.0,
    )
    base_loss, base_stats = ppo_loss(**kw)
    ones_loss, _ = ppo_loss(**kw, is_weight=jnp.ones(shape, jnp.float32))
    # weight 1 == no weight, bit-for-bit
    assert float(base_loss) == float(ones_loss)
    half_loss, half_stats = ppo_loss(
        **kw, is_weight=jnp.full(shape, 0.5, jnp.float32)
    )
    # the policy term scales; the value term must not
    assert np.isclose(
        float(half_stats["losses/policy_loss"]),
        0.5 * float(base_stats["losses/policy_loss"]), rtol=1e-6,
    )
    assert float(half_stats["losses/value_loss"]) == float(
        base_stats["losses/value_loss"]
    )


# -- state.json invariants ---------------------------------------------


def test_check_cursor_invariants():
    from trlx_tpu.utils.checkpointing import check_cursor_invariants

    ok = {"prompt_batches_consumed": 7, "exp_queue": {"cursor": 7, "epoch": 0}}
    assert check_cursor_invariants(ok) == []
    assert check_cursor_invariants({"iter_count": 3}) == []  # exp off
    torn = {"prompt_batches_consumed": 3, "exp_queue": {"cursor": 9, "epoch": 0}}
    problems = check_cursor_invariants(torn)
    assert problems and "PAST" in problems[0]
    bad = {"exp_queue": {"cursor": -1, "epoch": 0}}
    assert check_cursor_invariants(bad)
    assert check_cursor_invariants({"exp_queue": {"cursor": 1, "epoch": -2}})


# -- end-to-end golden: exp.enabled == direct path ---------------------


def _tiny_ppo_config(ckpt_dir, exp):
    from trlx_tpu.data.default_configs import default_ppo_config

    return default_ppo_config().evolve(
        train=dict(
            batch_size=8, total_steps=3, eval_interval=100,
            checkpoint_interval=100, seq_length=24, epochs=64,
            tracker="jsonl", checkpoint_dir=ckpt_dir, save_best=False,
        ),
        model=dict(
            model_path="random", num_layers_unfrozen=-1,
            model_extra_configs={
                "transformer": dict(
                    vocab_size=258, hidden_size=32, n_layer=2, n_head=2,
                    n_positions=64,
                )
            },
        ),
        tokenizer=dict(tokenizer_path="byte"),
        method=dict(
            num_rollouts=8, chunk_size=8, ppo_epochs=1,
            overlap_rollouts=True, exp=exp,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0,
                            do_sample=True),
        ),
    )


def _run_tiny(tmp_path, tag, exp):
    import trlx_tpu

    ckpt_dir = os.path.join(str(tmp_path), tag)
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    prompts = ["hello world", "the cat", "a b", "xyz",
               "what is", "I am", "go", "ok"]

    def reward(samples, prompts, outputs, **kw):
        return [float(len(o.split())) for o in outputs]

    trainer = trlx_tpu.train(
        reward_fn=reward, prompts=prompts,
        config=_tiny_ppo_config(ckpt_dir, exp),
    )
    with open(os.path.join(ckpt_dir, "logs", "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    stream = [
        {k: v for k, v in r.items()
         if k.startswith("losses/") or k == "reward/mean"}
        for r in recs
    ]
    # the LAST cycle's consumed rollouts, as host arrays: consumed
    # prompt order AND every derived tensor must match bit-for-bit
    store = None
    if trainer.store.history is not None:
        store = {
            "queries": np.asarray(trainer.store.history.query_tensors),
            "responses": np.asarray(trainer.store.history.response_tensors),
            "logprobs": np.asarray(trainer.store.history.logprobs),
            "rewards": np.asarray(trainer.store.history.rewards),
        }
    return trainer, [s for s in stream if s], store


def test_exp_enabled_fault_free_bit_equal_to_direct(tmp_path):
    direct, stream_direct, store_direct = _run_tiny(tmp_path, "direct", {})
    exp, stream_exp, store_exp = _run_tiny(
        tmp_path, "exp", {"enabled": True}
    )
    assert stream_exp == stream_direct, (
        f"loss/reward streams diverged:\n{stream_direct}\n{stream_exp}"
    )
    assert (store_direct is None) == (store_exp is None)
    if store_direct is not None:
        for key in store_direct:
            np.testing.assert_array_equal(
                store_direct[key], store_exp[key], err_msg=key,
            )
    # the transport actually carried the chunks (not silently bypassed)
    summary = exp._exp.stats_summary()
    assert summary["queue_committed"] >= 3
    assert summary["lease_released"] == summary["lease_acquired"]
    # and the prompt cursors marched in lockstep
    assert (
        exp._prompt_batches_consumed == direct._prompt_batches_consumed
    )


@pytest.mark.parametrize("chunks_per_cycle", [1, 2])
def test_clip_mode_trains_over_stale_chunk(tmp_path, chunks_per_cycle):
    """``staleness.mode: clip`` end to end: a stale_flood-corrupted
    chunk is ADMITTED with the IMPACT proximal recompute + per-token
    clipped importance weights, the ``staleness`` signal trips, the
    weights ride the store into the fused loss, and the run completes.
    With two chunks a cycle the stale one is the FIRST of its cycle: its
    ``exp/staleness_clipped`` must survive the cycle's aggregation over
    the union of the chunks' keys (the fresh final chunk has no such
    key)."""
    import trlx_tpu

    ckpt_dir = os.path.join(str(tmp_path), "clip")
    config = _tiny_ppo_config(
        ckpt_dir,
        {"enabled": True, "lease_ttl_s": 0.5, "wait_poll_s": 0.02,
         "staleness": {"mode": "clip", "max_staleness": 1, "clip_c": 0.3}},
    ).evolve(
        train=dict(
            guardrails=dict(enabled=True, loss_spike_sigma=0.0),
            chaos=dict(seed=0, faults=[
                {"fault": "stale_flood", "at": chunks_per_cycle + 1}
            ]),
        ),
        method=dict(num_rollouts=8 * chunks_per_cycle),
    )
    prompts = ["hello world", "the cat", "a b", "xyz",
               "what is", "I am", "go", "ok"]
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [
            float(len(o.split())) for o in outputs
        ],
        prompts=prompts, config=config,
    )
    assert trainer.iter_count >= config.train.total_steps
    summary = trainer._exp.stats_summary()
    assert summary["staleness_clips"] == 1
    assert "staleness" in trainer.guardrails.trip_history
    # every batch of a clip-mode run carries weights (ones when fresh),
    # and the stale chunk's weights were actually clipped into [1±c]
    w = np.asarray(trainer.store.history.is_weight)
    assert w.shape == np.asarray(trainer.store.history.logprobs).shape
    assert np.all(w >= 0.7 - 1e-6) and np.all(w <= 1.3 + 1e-6)
    # the cycle's logged mean counts the clipped chunk among all of its
    # chunks, wherever in the cycle it came
    with open(os.path.join(ckpt_dir, "logs", "metrics.jsonl")) as f:
        clipped = [
            rec["exp/staleness_clipped"] for rec in map(json.loads, f)
            if "exp/staleness_clipped" in rec
        ]
    assert clipped == [1.0 / chunks_per_cycle]


def test_reject_regenerates_prefetch_chunk_without_livelock(tmp_path):
    """max_staleness=0 makes every overlap_rollouts prefetch chunk
    (staleness 1 by construction) a REAL rejection: the retained
    prefetch samples must NOT be redelivered verbatim (same version ->
    infinite reject loop) — the chunk regenerates with the live policy
    and admits at staleness 0, and the run completes."""
    import trlx_tpu

    ckpt_dir = os.path.join(str(tmp_path), "reject0")
    config = _tiny_ppo_config(
        ckpt_dir,
        {"enabled": True, "lease_ttl_s": 0.5, "wait_poll_s": 0.02,
         "staleness": {"mode": "reject", "max_staleness": 0}},
    )
    prompts = ["hello world", "the cat", "a b", "xyz",
               "what is", "I am", "go", "ok"]
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [
            float(len(o.split())) for o in outputs
        ],
        prompts=prompts, config=config,
    )
    assert trainer.iter_count >= config.train.total_steps
    summary = trainer._exp.stats_summary()
    # every post-prefetch cycle rejected its prefetch chunk exactly once
    assert summary["staleness_rejects"] >= 1
    assert summary["redispatches"] == summary["staleness_rejects"]
    assert summary["queue_committed"] >= 3


def test_exp_cursor_persists_and_torn_commit_detected(tmp_path):
    exp, _, _ = _run_tiny(tmp_path, "persist", {"enabled": True})
    ckpt = os.path.join(str(tmp_path), "persist", "checkpoint_3")
    with open(os.path.join(ckpt, "state.json")) as f:
        state = json.load(f)
    eq = state["exp_queue"]
    assert eq["cursor"] == exp._exp.queue.cursor > 0
    assert eq["cursor"] <= state["prompt_batches_consumed"]
    assert eq["staleness_mode"] == "reject"
    # the offline validator reads the same fields and rejects a torn pair
    from trlx_tpu.utils.checkpointing import check_cursor_invariants

    state["exp_queue"]["cursor"] = state["prompt_batches_consumed"] + 5
    assert check_cursor_invariants(state)
