"""The online experience core: `TPUOnlineTrainer`, the trainer every
on-policy method (PPO, GRPO) is built on, and the topology-invariant
prompt loader it streams from.

What lives here is what COLLECTS experience, whatever algorithm scores
it: the prompt stream and its cursors, ONE rollout collection loop
(`_make_experience`) fed by a chunk source, the function that produces
one chunk (`_produce_chunk`: generate, then the method's
`_score_and_assemble`), the cross-cycle prefetch, the reward moments
and the rollout accounting. The train step, `generate()` and the
checkpoint cycle are `trainer/base.py`'s.

Layering (arrows one way): this module builds the experience transport
(`trlx_tpu/exp/`, `method.exp.*`) and the rollout fleet
(`trlx_tpu/fleet/`, `method.fleet.*`) and hands them callbacks; the
lease protocol and the staleness verdicts are `exp/rollout.py`'s, the
worker dispatch protocol `fleet/dispatch.py`'s, and neither imports
anything from `trlx_tpu/trainer/`.
"""

from __future__ import annotations

import json
import os
from abc import abstractmethod
from time import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data import PromptBatch
from trlx_tpu.exp import ExpConfig, ExperienceTransport
from trlx_tpu.exp.rollout import LeasedChunks
from trlx_tpu.fleet.config import FleetConfig
from trlx_tpu.models.generation import chunks_streamed
from trlx_tpu.models.transformer import balance_router_bias, router_bias, with_router_bias
from trlx_tpu.ops.common import running_moments_init, running_moments_update
from trlx_tpu.parallel import multihost as mh
from trlx_tpu.parallel.mesh import replicated_sharding, vector_sharding
from trlx_tpu.pipeline import DataLoader
from trlx_tpu.trainer.base import TPUBaseTrainer
from trlx_tpu.utils import Clock, infinite_loader, logging
from trlx_tpu.utils.trackers import DeferredStats

logger = logging.get_logger(__name__)


class _GroupChunkLoader(DataLoader):
    """Per-data-group view of the GLOBAL prompt-chunk order: every
    process draws the SAME shuffle stream a plain ``DataLoader`` over
    the full prompt list would (one shuffle of the global index order
    per epoch, same RNG consumption), chunks it at the global chunk
    size, then collates ONLY this group's strided rows of each chunk.

    This is what makes the prompt stream topology-invariant: the chunk
    composition is fixed by (seed, chunk_size) alone, so a checkpoint
    cursor saved under G data groups replays the exact same prompts
    under G' groups — while each host still pays only 1/G of the
    per-pull collation (the index slice happens BEFORE collate).
    Groups are padded to equal row counts by wrapping within the chunk
    (SPMD lockstep needs equal-shape pulls; the repeated row is the
    same compromise `shard_list` made)."""

    def __init__(
        self, dataset, batch_size, collate_fn, group, group_count,
        seed, shuffle=True, drop_last=True,
    ):
        super().__init__(
            dataset, batch_size, collate_fn=collate_fn, shuffle=shuffle,
            drop_last=drop_last, seed=seed,
        )
        self.group = group
        self.group_count = group_count

    def _select_rows(self, idxs) -> List[int]:
        # DataLoader.__iter__ hook: shuffle/chunking stay the base
        # class's (the parity-critical RNG stream is written ONCE);
        # only the row selection differs
        local = [int(i) for i in idxs[self.group :: self.group_count]]
        want = (len(idxs) + self.group_count - 1) // self.group_count
        i = 0
        while len(local) < want:
            local.append(int(idxs[(self.group + i * self.group_count) % len(idxs)]))
            i += 1
        return local


class _Lookahead:
    """The direct chunk source of the collection loop: a one-chunk
    lookahead. Generation for chunk i+1 is DISPATCHED before chunk i's
    host work (decode + reward_fn), so the device samples while the
    host scores — the reference's rollout loop is fully serial here
    (SURVEY §7 "host-device choreography"). With cycle-level overlap
    (``method.overlap_rollouts``) chunk 0 was dispatched ahead of the
    previous cycle's fused optimization block and sampled under it
    on-device (``pre_optimization_hook``): it is consumed first.

    Same four methods as the transport's source
    (``trlx_tpu.exp.rollout.LeasedChunks``); nothing here outlives the
    cycle, so there is nothing to commit, abandon or report."""

    def __init__(self, trainer: "TPUOnlineTrainer", num_rollouts: int):
        self.trainer, self.num_rollouts = trainer, num_rollouts
        self.ahead = trainer._take_prefetch() or trainer._dispatch_generation(
            trainer._next_prompt_batch()
        )
        self.groups = mh.data_group_count(trainer.mesh)
        self.chunk_rows = len(self.ahead[0].input_ids) * self.groups
        self.n_collected = 0

    def next_chunk(self, iter_count: int, clock: Clock):
        t, generation = self.trainer, self.ahead
        self.ahead = (
            t._dispatch_generation(t._next_prompt_batch())
            if self.n_collected + self.chunk_rows < self.num_rollouts
            else None
        )
        payload, _ = t._produce_chunk(iter_count, clock, generation=generation)
        self.n_collected += payload[2] * self.groups
        return payload

    def committed(self) -> None:
        pass

    def abandon(self) -> None:
        pass

    def cycle_stats(self) -> Dict[str, float]:
        return {}


class TPUOnlineTrainer(TPUBaseTrainer):
    """The trainer-agnostic online experience core.

    Everything an on-policy RLHF trainer needs to COLLECT experience
    lives here, independent of the algorithm that scores it: the
    topology-invariant prompt stream + cursors, ONE rollout collection
    loop fed by a chunk source (the direct one-chunk lookahead, or the
    experience transport's leased queue, ``method.exp.*``, with the
    rollout fleet, ``method.fleet.*``, behind it), the cross-cycle
    prefetch (``method.overlap_rollouts``), the decode engine seam
    (``method.gen_engine.*``, inherited from the base generate()),
    plus the reward running-moment machinery and the honest rollout
    accounting.

    Subclasses provide exactly one method-specific seam:
    ``_score_and_assemble`` — decode + reward + the algorithm's
    experience assembly for one generated chunk — and the usual
    ``loss``/``setup_model``. PPO and GRPO are both this class plus a
    seam; neither copies a line of the transport/fleet/prefetch
    machinery.
    """

    def __init__(self, config, **kwargs):
        super().__init__(config, **kwargs)

        data_ways = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        if config.method.chunk_size % data_ways:
            raise ValueError(
                f"method.chunk_size {config.method.chunk_size} must be divisible "
                f"by dp*fsdp={data_ways}"
            )
        self.store = self._make_store()
        self.running_moments = running_moments_init()
        self.ref_mean = getattr(config.method, "ref_mean", None)
        self.ref_std = getattr(config.method, "ref_std", None)

        self._deferred_rollout = DeferredStats()
        # rollout-data cursor: how many prompt chunks this run has pulled
        # off the (deterministically shuffled) prompt stream. Saved in
        # state.json so a resumed run fast-forwards to the exact position
        # instead of replaying the stream from its start.
        self._prompt_batches_consumed = 0
        self._resume_prompt_cursor = 0
        # cross-cycle rollout prefetch (method.overlap_rollouts): the
        # next cycle's first chunk, generated ahead of the current fused
        # optimization block (`_dispatch_generation`'s tuple: it carries
        # the policy version it was generated at, since the chunk is
        # consumed one optimizer cycle later), plus the prompt cursor it
        # must rewind to if it never trains (preemption / run end)
        self._prefetched_gen: Optional[Tuple] = None
        self._prefetch_cursor_start: Optional[int] = None
        self.log_rollouts = config.train.rollout_logging_dir is not None
        if self.log_rollouts:
            self.setup_rollout_logging(config)
        # resilient experience transport (method.exp.*, trlx_tpu/exp/):
        # rollout chunks travel through a leased, deduplicating queue
        # with a staleness admission gate; default off = the direct
        # rollout loop, and fault-free the transport path is golden-
        # checked bit-equal to it (tests/test_exp_queue.py)
        self._exp_cfg = ExpConfig.from_dict(getattr(config.method, "exp", None))
        self._exp: Optional[ExperienceTransport] = None
        if self._exp_cfg.enabled:
            self._exp = ExperienceTransport(
                self._exp_cfg, owner=f"proc{mh.process_index()}"
            )
        # fault-tolerant rollout fleet (method.fleet.*, trlx_tpu/fleet/):
        # chunk production routed to cross-process workers behind the
        # transport seam — membership heartbeats, versioned weight
        # broadcast, degraded-mode fallback to the in-process path
        self._fleet_cfg = FleetConfig.from_dict(
            getattr(config.method, "fleet", None)
        )
        self._fleet = None
        if self._fleet_cfg.enabled:
            if self._exp is None:
                raise ValueError(
                    "method.fleet.enabled requires method.exp.enabled: the "
                    "fleet produces chunks BEHIND the experience "
                    "transport (delivery/dedup/staleness stay its job)"
                )
            if mh.process_count() > 1:
                raise NotImplementedError(
                    "method.fleet with a multi-process learner mesh is not "
                    "supported yet (run one learner process; workers "
                    "scale horizontally instead)"
                )
            from trlx_tpu.fleet.coordinator import FleetCoordinator

            self._fleet = FleetCoordinator(
                self._fleet_cfg,
                self._fleet_cfg.resolved_dir(config.train.checkpoint_dir),
                owner=f"learner-{mh.process_index()}",
            )
        # the transport's consumer and in-process producer (its lease
        # and staleness protocol is exp/rollout.py's, the fleet's
        # dispatch protocol fleet/dispatch.py's): the collection loop's
        # chunk source in place of the direct lookahead
        self._leased_chunks: Optional[LeasedChunks] = None
        if self._exp is not None:
            ours = dict(
                snapshot=self._exp_snapshot,
                restore=self._exp_restore_snapshot,
                policy_version=lambda: self._policy_version,
                watchdog=self.watchdog, chaos=self.chaos,
                trip=self.guardrails.trip,
            )
            dispatcher = None
            if self._fleet is not None:
                from trlx_tpu.fleet.dispatch import ChunkDispatcher

                dispatcher = ChunkDispatcher(
                    coordinator=self._fleet, params=lambda: self.params,
                    **ours,
                )
            self._leased_chunks = LeasedChunks(
                exp=self._exp, produce=self._produce_chunk,
                next_batch=self._next_prompt_batch,
                take_prefetch=self._take_prefetch,
                staleness_weights=self._staleness_weights,
                fleet=dispatcher, **ours,
            )

    # -- method-specific seams -------------------------------------------

    def _make_store(self):
        """The rollout store. Default: the rectangular device-resident
        pytree store (works for any flax.struct batch with a
        ``query_tensors`` leading field)."""
        from trlx_tpu.pipeline.ppo_pipeline import PPORolloutStorage

        return PPORolloutStorage(
            pad_token_id=self.generate_settings.pad_token_id
        )

    def _inner_epochs(self) -> int:
        """Optimization epochs per collected rollout batch (PPO:
        ``method.ppo_epochs``)."""
        raise NotImplementedError

    @abstractmethod
    def _score_and_assemble(
        self, batch: PromptBatch, gen_out, stats: Dict[str, Any],
        iter_count: int, clock: Clock,
    ):
        """The method-specific half of one rollout chunk: decode +
        reward_fn, the algorithm's experience assembly (teacher-forced
        forwards, advantages, ...), running-moment update and the
        chunk's stats (mutated into ``stats``). Shared verbatim by the
        direct rollout loop, the experience-transport producer AND the
        fleet worker, so the paths cannot numerically diverge. Returns
        ``(rollout_batch, rows_local)``."""

    def _apply_staleness_clip(self, rollout_batch):
        """IMPACT-style admission correction for an over-stale chunk
        (``exp.staleness.mode: clip``): recompute behavior terms with
        the CURRENT policy and thread the mismatch into the surrogate
        as a clipped per-token importance weight. Method-specific."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement "
            "exp.staleness.mode='clip'; use mode='reject'"
        )

    def _rollout_stage_meta(self):
        """Metadata staged with each cycle's deferred rollout stats
        (PPO: the adaptive KL controller value at collection time)."""
        return None

    # -- rollout engine --------------------------------------------------

    def make_experience(self, num_rollouts: int = 1024, iter_count: int = 0) -> None:
        """Collect `num_rollouts` rollouts into the store (parity:
        reference make_experience :251-525; §3.2 call stack)."""
        # hang doctor: the rollout phase heartbeats per chunk inside the
        # loop, so a many-chunk collection stays healthy while a single
        # wedged generate/score goes silent past the rollout deadline
        with self.watchdog.phase("rollout", step=iter_count):
            self._make_experience(num_rollouts, iter_count)

    def _make_experience(self, num_rollouts: int, iter_count: int) -> None:
        logger.info("Collecting rollouts")
        self._rollout_abandoned = False
        # snapshot the prompt cursor: an abandoned (preempted) rollout
        # discards its partial store, so the cursor must rewind to here
        # or the resumed run would skip prompts that never trained. When
        # the cycle starts from a prefetched chunk (overlap_rollouts),
        # the rewind target is the cursor BEFORE that chunk's prompts
        # were pulled — the prefetch pull already advanced it.
        prompt_cursor_start = (
            self._prefetch_cursor_start
            if self._prefetched_gen is not None
            else self._prompt_batches_consumed
        )
        # guardrail `requeue` rewinds to here: the whole cycle's prompts
        # replay when its rollout batch turns out poisoned
        self._cycle_cursor_start = prompt_cursor_start
        self._finish_rollout_stats()  # flush any deferred previous-cycle stats
        clock = Clock()
        n_collected = 0
        accumulated_stats: List[Dict[str, float]] = []

        pbar = logging.progress(total=num_rollouts, desc="rollouts")
        # where finished chunks come from: the transport's leased queue
        # (method.exp.*), or the one-chunk lookahead below
        chunks = self._leased_chunks
        if chunks is None:
            chunks = _Lookahead(self, num_rollouts)
        while n_collected < num_rollouts:
            self.watchdog.beat("rollout", step=iter_count)
            # lane-refill decision point: pending serve requests outrank
            # the next training chunk's dispatch (bounded allowance)
            self._serve_tick(iter_count)
            if self.chaos is not None:
                # chaos: the sampler wedges at the top of this chunk —
                # the rollout phase goes silent and the watchdog's
                # deadline (not the scheduler) must end the run
                self.chaos.stall("stall_rollout")
            # rollout collection dominates on-policy wall-clock: a
            # preemption landing here must not wait out the remaining
            # chunks (the grace period would expire before the final
            # save). Abandon the rollout — learn()'s epoch-top check
            # saves and exits. Forced sync: every host runs this loop in
            # lockstep.
            if self._should_stop(force=True):
                logger.warning(
                    "preemption during rollout collection: abandoning "
                    "after %d/%d rollouts", n_collected, num_rollouts,
                )
                # flags the store as truncated: the total_steps that
                # prepare_learning derives from it must not be persisted
                # as this run's real budget. The cursor rewinds to the
                # cycle start — this cycle's chunks never train, so the
                # resumed run must replay them.
                self._rollout_abandoned = True
                self._prompt_batches_consumed = prompt_cursor_start
                chunks.abandon()
                break
            rollout_batch, stats, rows_local = chunks.next_chunk(
                iter_count, clock
            )
            accumulated_stats.append(stats)

            with self.obs.span("store_push"):
                self.push_to_store(rollout_batch)
            chunks.committed()
            n_collected += rows_local * mh.data_group_count(self.mesh)
            if hasattr(pbar, "update"):
                pbar.update(rows_local * mh.data_group_count(self.mesh))
            logger.info("[rollout %d / %d]", n_collected, num_rollouts)

        # flight recorder: this cycle's collected samples — the SAME
        # n_collected the trainer's own rollout accounting advances, so
        # telemetry samples/s cannot drift from it
        self.obs.note_samples(n_collected)
        if hasattr(pbar, "close"):
            pbar.close()
        if not accumulated_stats:
            # rollout abandoned before the first chunk completed
            # (preemption): nothing to log, nothing pending
            return
        # aggregate over the UNION of keys: conditional keys (a clip
        # admission mid-cycle) must not vanish just because the final
        # chunk was fresh — that telemetry is exactly what the
        # staleness ledger exists to surface
        agg = {
            k: sum(xs.get(k, 0.0) for xs in accumulated_stats) / len(accumulated_stats)
            for k in dict.fromkeys(k for xs in accumulated_stats for k in xs)
        }
        agg.update(chunks.cycle_stats())
        # ONE packed async device->host copy for every accumulated device
        # scalar, materialized lazily (post_backward / next
        # make_experience): a blocking read would wait for the device to
        # drain; this way the copy overlaps the train step instead of
        # extending the rollout phase
        self._deferred_rollout.stage(
            agg, step=iter_count, meta=self._rollout_stage_meta()
        )

    def _dispatch_generation(self, batch: PromptBatch) -> Tuple:
        """Dispatch the sampler on one prompt batch. Returns ``(batch,
        gen_out, wall of the dispatch, policy version at generation)``:
        what `_produce_chunk` scores, now or a chunk (or, prefetched, a
        cycle) later."""
        t0 = time()
        gen_out = self._generate_rollout(batch.input_ids, batch.attention_mask)
        return batch, gen_out, time() - t0, self._policy_version

    def _produce_chunk(
        self, iter_count: int, clock: Clock, batch: Optional[PromptBatch] = None,
        generation: Optional[Tuple] = None, after_generate=None,
    ):
        """Produce ONE rollout chunk: ``generate`` on ``batch`` (or take
        ``generation``, dispatched earlier by `_dispatch_generation`),
        then the method's `_score_and_assemble`. The direct lookahead,
        the experience transport's in-process producer and the fleet
        worker all call this, so the paths cannot numerically diverge.
        ``after_generate`` runs between the two halves (a lease
        heartbeat; the worker's mid-chunk chaos site). Returns
        ``((rollout_batch, stats, rows_local), policy_version)``, the
        version being the one the samples were generated at."""
        batch, gen_out, gen_time, version = (
            generation or self._dispatch_generation(batch)
        )
        if after_generate is not None:
            after_generate()
        stats: Dict[str, Any] = {"time/rollout_generate": gen_time}
        rollout_batch, rows_local = self._score_and_assemble(
            batch, gen_out, stats, iter_count, clock
        )
        return (rollout_batch, stats, rows_local), version

    def _staleness_weights(self, rollout_batch, over_stale: bool):
        """``exp.staleness.mode: clip``: every batch of the run carries
        importance weights, so that the store's pytree is uniform — the
        method's clipped recompute for an over-stale chunk, ones for a
        fresh one."""
        if over_stale:
            return self._apply_staleness_clip(rollout_batch)
        return rollout_batch.replace(
            is_weight=jnp.ones_like(rollout_batch.response_mask)
        )

    # -- shared score/assemble helpers -----------------------------------

    def _pull_sampled_tokens(self, gen_out, rows: int, stats) -> np.ndarray:
        """ONE packed device->host transfer for the three generation
        outputs (one sync instead of three): ``[B, seq + 2N]`` =
        sequences | response_ids | response_mask, this host's rows.
        ``local_rows`` returns a numpy array, so the pull BLOCKS until
        the sampler has finished: it is the sync at which the rollout's
        device time ends (the ``tokens_wait`` span), and it adds its
        wait to ``time/rollout_generate`` — the caller put the
        sampler's dispatch wall there, so the key reads the time its
        name says. ``rows`` = the real (non-pad) local rows."""
        t0 = time()
        with self.obs.span("tokens_wait", rows=rows) as counts:
            packed = mh.local_rows(
                jnp.concatenate(
                    [
                        gen_out["sequences"],
                        gen_out["response_ids"],
                        gen_out["response_mask"].astype(
                            gen_out["sequences"].dtype
                        ),
                    ],
                    axis=1,
                )
            )
            n_new = gen_out["response_ids"].shape[1]
            counts["tokens"] = int(packed[:rows, -n_new:].sum())
            # the sampler has finished: its routed layers' counters are a
            # free read here, and ride this span's counts into the cycle row
            for name, value in (gen_out.get("moe_stats") or {}).items():
                counts[name] = float(value)
            cells, state = gen_out.get("decode_cells"), gen_out.get("decode_state_bytes")
            if cells or state:
                # the decode loop stops once every row has finished: its
                # steps are the response columns any row still wrote
                steps = int(packed[:rows, -n_new:].any(axis=0).sum()) - 1
                if cells:
                    counts.update(chunks_streamed(steps=steps, **cells))
                if state:  # what the delta-rule layers' state cost the loop to carry
                    counts["state_bytes_carried"] = max(steps, 0) * state
        stats["time/rollout_generate"] = (
            stats.get("time/rollout_generate", 0.0) + time() - t0
        )
        return packed

    def _update_reward_moments(self, scores, scores_mask, stats):
        """Fold one chunk's host-computed scores into the running reward
        moments and pick the reward-scaling divisor (``method.
        scale_reward``). Local per-row sums -> one GLOBAL vector; the
        running-moment update then reduces over every host's rows
        in-graph (the reference all-gathers scores to rank 0 instead).
        A short final chunk (prompt dataset smaller than chunk_size)
        may not divide dp*fsdp — keep the tiny vector replicated then
        (padding would bias the running reward moments). Multi-host
        replication of per-group-DIFFERENT rows needs a host-side
        allgather first, so every process places the same full vector
        (parity: the reference pads across processes,
        accelerate_ppo_trainer.py:292-300). Returns ``scale_div`` (a
        device scalar)."""
        method = self.config.method
        local_sums = (scores * scores_mask).sum(axis=1)
        rows = len(local_sums) * mh.data_group_count(self.mesh)
        if rows % self.data_ways() == 0:
            score_sums = mh.global_from_local(
                local_sums, vector_sharding(self.mesh)
            )
        elif mh.is_multihost():
            score_sums = jax.device_put(
                np.asarray(
                    mh.allgather_group_rows(
                        local_sums.astype(np.float32), self.mesh
                    ),
                    np.float32,
                ),
                replicated_sharding(self.mesh),
            )
        else:
            score_sums = mh.global_from_local(
                local_sums, replicated_sharding(self.mesh)
            )
        if self.ref_mean is None:
            self.ref_mean = float(score_sums.mean())
            self.ref_std = float(score_sums.std())
        new_moments, scores_mean, scores_std = running_moments_update(
            self.running_moments, score_sums
        )
        # a NaN-poisoned chunk must not permanently poison the
        # running reward moments (they scale every later reward and
        # persist across checkpoints): keep the pre-chunk moments
        # when the chunk's sums are non-finite. The chunk's OWN
        # stats still report the poison, so the guardrails see it.
        keep = jnp.all(jnp.isfinite(score_sums))
        self.running_moments = jax.tree_util.tree_map(
            lambda n, o: jnp.where(keep, n, o),
            new_moments, self.running_moments,
        )
        # stats stay DEVICE scalars until the single packed fetch at
        # the end of make_experience (each host read is a device sync)
        stats["rollout_scores/mean"] = scores_mean
        stats["rollout_scores/std"] = scores_std
        stats["rollout_scores/running_mean"] = self.running_moments.mean
        stats["rollout_scores/running_std"] = self.running_moments.std

        # reward scaling happens inside the experience fn: pass the
        # divisor as a device scalar instead of fetching the running
        # std to the host
        scale_reward = getattr(method, "scale_reward", None)
        if scale_reward == "running":
            return self.running_moments.std
        if scale_reward == "ref":
            return jnp.float32(max(self.ref_std, 1e-8))
        return jnp.float32(1.0)

    def _rollout_accounting_stats(
        self, response_ids, response_mask, gen_out, stats, iter_count,
    ) -> None:
        """Honest rollout accounting: pad emissions from finished rows
        are NOT generated tokens — report mask-weighted real tokens
        plus batch occupancy, and a truncation rate (rows that ran to
        max_new_tokens without an EOS: a degenerate policy that stops
        emitting EOS shows up here, and the guardrails can trip on it
        via truncation_max). Plus the decode-engine per-chunk ledger
        when ``gen_stats`` rode along."""
        rm_np = np.asarray(response_mask)
        ri_np = np.asarray(response_ids)
        N_resp = rm_np.shape[1]
        real_toks = float(rm_np.sum())
        stats["rollout/real_tokens"] = real_toks
        # flight recorder: the honest (mask-weighted) token ledger —
        # telemetry's tokens/s numerator reuses THIS number, so pad
        # emissions can never inflate the trajectory artifact
        self.obs.note_tokens(real_toks * mh.data_group_count(self.mesh))
        stats["rollout/token_occupancy"] = real_toks / max(
            rm_np.shape[0] * N_resp, 1
        )
        eos_id = self.generate_settings.eos_token_id
        full_rows = rm_np.sum(axis=1) >= N_resp
        hit_eos = (
            ((ri_np == eos_id) & (rm_np > 0)).any(axis=1)
            if eos_id >= 0
            else np.zeros(len(full_rows), bool)
        )
        stats["rollout/truncation_rate"] = (
            float((full_rows & ~hit_eos).mean()) if len(full_rows) else 0.0
        )
        gstats = gen_out.get("gen_stats")
        if gstats is not None:
            g = {k: float(np.asarray(v)) for k, v in gstats.items()}
            # per-refill heartbeat accounting (host-side,
            # post-dispatch): with the decode engine a chunk is ONE
            # device dispatch, so the refills all land at once —
            # batch them into a single annotated beat (count=N)
            # instead of N same-instant beats that would evict the
            # other phases from the watchdog's bounded timeline
            refills = int(g.get("refills", 0))
            if refills:
                self.watchdog.beat(
                    "rollout", step=iter_count, count=refills
                )
            stats["rollout/engine_occupancy"] = g.get("occupancy", 0.0)
            stats["rollout/engine_refills"] = g.get("refills", 0.0)
            stats["rollout/engine_decode_steps"] = g.get("decode_steps", 0.0)
            # prompt-pad page compaction: pages that held nothing but
            # left-pad KV, released back to the pool at refill (lowers
            # the engine's HBM floor on ragged prompt mixes)
            stats["rollout/engine_reclaimed_pages"] = g.get(
                "reclaimed_pages", 0.0
            )
            if "drafted" in g:
                stats["rollout/spec_accept_rate"] = g["accepted"] / max(
                    g["drafted"], 1.0
                )
            if g.get("oom_truncated") or g.get("unserved"):
                logger.warning(
                    "gen_engine: page pool exhausted (%d lanes "
                    "truncated, %d prompts unserved) — raise "
                    "method.gen_engine.pool_pages",
                    int(g.get("oom_truncated", 0)),
                    int(g.get("unserved", 0)),
                )

    # -- experience transport and fleet (method.exp.*, method.fleet.*) ---

    def _exp_snapshot(self) -> Dict[str, Any]:
        """Replay state for a production lease, taken BEFORE the chunk
        touches anything: the trainer RNG key and the host-side reward
        accounting (running moments, ref stats). jax arrays are
        immutable, so holding references is free; restoring them makes
        a re-dispatched production bit-identical to the original
        attempt (same key -> same samples, same moments -> same reward
        scaling), which is what lets a producer death leave the
        consumed stream untouched. (The prompt batch itself is stashed
        on the lease at pull time — ``snap["batch"]`` — so a replay
        never re-pulls the stream.)"""
        return {
            "rng": self.rng,
            "running_moments": self.running_moments,
            "ref_mean": self.ref_mean,
            "ref_std": self.ref_std,
        }

    def _exp_restore_snapshot(self, snap: Dict[str, Any]) -> None:
        self.rng = snap["rng"]
        self.running_moments = snap["running_moments"]
        self.ref_mean = snap["ref_mean"]
        self.ref_std = snap["ref_std"]

    def _shutdown_producers(self) -> None:
        """learn()-exit hook: the fleet is told whether the step budget
        is done (a clean finish) or the learner is only going away."""
        if self._leased_chunks is None or self._leased_chunks.fleet is None:
            return
        total = getattr(self, "total_steps", None)
        self._leased_chunks.fleet.finish(
            self.iter_count,
            self.config.train.total_steps if total is None else total,
        )

    def _extra_consistency_checks(self) -> None:
        """Every host must hold the SAME experience-transport consumer
        cursor — a drifted cursor means hosts silently trained
        different chunks. Asserted through ``multihost.cursor_consensus``
        at the guardrails consistency cadence; disagreement trips the
        ladder like any other divergence."""
        if self._exp is None or not self.guardrails.enabled:
            return
        result = mh.cursor_consensus(
            "exp", self._exp.queue.epoch, self._exp.queue.cursor
        )
        if not result.agree:
            self.guardrails.trip(
                "consistency",
                f"experience-transport cursor diverged at step "
                f"{self.iter_count}: {result.detail}",
            )

    def _finish_rollout_stats(self) -> None:
        """Materialize + log the deferred make_experience stats, feeding
        the guardrails the rollout-side health signals. Trainers with
        controller state riding the flush (PPO's adaptive KL) override.
        Idempotent."""
        for stats, step, meta in self._deferred_rollout.flush():
            if meta is not None:
                stats["kl_coef"] = float(meta)
            if self.guardrails.enabled:
                kl = stats.get("policy/sqrt_kl")
                self.guardrails.observe_rollout(
                    kl=None if kl is None else float(kl) ** 2,
                    kl_target=None,
                    reward_mean=stats.get("rollout_scores/mean"),
                    running_mean=stats.get("rollout_scores/running_mean"),
                    running_std=stats.get("rollout_scores/running_std"),
                    truncation_rate=stats.get("rollout/truncation_rate"),
                )
            self._tracker_log(stats, step=step)

    # -- loop hooks ------------------------------------------------------

    def setup_rollout_logging(self, config) -> None:
        import uuid

        assert os.path.isdir(config.train.rollout_logging_dir)
        self.run_id = f"run-{uuid.uuid4()}"
        self.rollout_logging_dir = os.path.join(
            config.train.rollout_logging_dir, self.run_id
        )
        os.mkdir(self.rollout_logging_dir)
        with open(os.path.join(self.rollout_logging_dir, "config.json"), "w") as f:
            f.write(json.dumps(config.to_dict(), indent=2))

    def add_prompt_pipeline(self, pipeline) -> None:
        # the pipeline is retained so guardrail interventions (requeue /
        # rollback) can rebuild the stream and replay untrained prompts
        self._prompt_pipeline = pipeline
        self._balance_router_bias(pipeline)
        self._build_prompt_iterator()
        self._fast_forward_prompts()

    def _balance_router_bias(self, pipeline) -> None:
        """A routed model initialised at random, whose configuration asks
        for it (`router_balance_steps`), has its selection bias balanced on
        the first chunk of prompts before anything is sampled
        (`balance_router_bias`). Every copy of the routed layers made at
        set-up (the frozen reference, a value branch: the top layers of the
        main segment) takes the same bias; the weights stay as they are."""
        cfg = getattr(self.model, "cfg", None)
        steps = getattr(cfg, "router_balance_steps", 0)
        if not (steps and getattr(cfg, "routed", False) and getattr(self, "_random_init", False)):
            return
        with self.obs.span("router_balance", steps=steps):
            rows = min(len(pipeline), self._prompt_chunk_rows())
            batch = pipeline.collate([pipeline[i] for i in range(rows)])
            with self.mesh:
                base, ratios = balance_router_bias(
                    self.model.lm, self.params["base"], jnp.asarray(batch.input_ids),
                    jnp.asarray(batch.attention_mask), steps)
            bias = router_bias(base)

            def top_layers(tree):  # a branch holds the top rows of each stack
                return with_router_bias(tree, {
                    name: jax.device_put(jnp.array(bias[name][len(bias[name]) - old.shape[0]:]), old.sharding)
                    for name, old in router_bias(tree).items()})

            self.params = dict(self.params, base=top_layers(base))
            if "v_branch" in self.params:
                self.params["v_branch"] = top_layers(self.params["v_branch"])
            if getattr(self, "ref_params", None) is not None:
                self.ref_params = top_layers(self.ref_params)
            before, after = ([round(float(r), 2) for r in row] for row in np.asarray(ratios))
            logger.info(
                f"router bias balanced on {rows} prompts in {steps} steps: fullest expert over the "
                f"mean, by layer, {before} -> {after}")

    def _prompt_chunk_rows(self) -> int:
        """Prompts pulled from the stream per chunk (GRPO pulls
        chunk_size/group_size prompts and repeats each one)."""
        return self.config.method.chunk_size

    def _build_prompt_iterator(self) -> None:
        """(Re)create the prompt stream from position zero. The loader
        draws its shuffles from the config seed, so a rebuild replays
        the exact chunk sequence — fast-forwarding then restores any
        cursor, including one BEHIND the live position (streams only
        advance; rewind = rebuild + replay).

        TOPOLOGY-INVARIANT: the stream is one GLOBAL shuffle over the
        full prompt list, chunked at the global chunk_size; each data
        group then collates only its own rows of every global chunk
        (`_GroupChunkLoader`). The chunk sequence — and therefore the
        saved `prompt_batches_consumed` cursor — means the SAME prompts
        regardless of how many hosts/data groups the run has, so an
        elastic resume onto a different topology neither drops nor
        double-trains a prompt. (The previous scheme shuffled each
        group's strided slice independently, which re-partitioned the
        stream whenever the group count changed.) Single-group runs are
        byte-identical to the old behavior: same loader, same RNG
        stream, no slicing."""
        pipeline = self._prompt_pipeline
        # drop_last keeps chunk shapes static: one compiled sampler;
        # a prompt list smaller than one chunk degrades to a single
        # kept-ragged chunk (the historical len(loader)==0 fallback)
        chunk, drop_last = self._prompt_chunk_rows(), True
        if len(pipeline) < chunk:
            chunk, drop_last = len(pipeline), False
        group, group_count = mh.data_group_info(self.mesh)
        if group_count > 1:
            loader = _GroupChunkLoader(
                pipeline, chunk, pipeline.collate, group, group_count,
                seed=self.config.train.seed, drop_last=drop_last,
            )
        else:
            loader = pipeline.create_loader(
                chunk, shuffle=True, drop_last=drop_last,
                seed=self.config.train.seed,
            )
        self.prompt_iterator = infinite_loader(loader)
        self._prompt_batches_consumed = 0

    def _rewind_prompt_stream(self, cursor: int) -> None:
        """Rebuild the stream and advance it so the NEXT pull is chunk
        ``cursor`` — the replay path for prompts whose rollouts never
        trained (host-side batch pulls only: no generation, no scoring)."""
        self._build_prompt_iterator()
        for _ in range(cursor):
            next(self.prompt_iterator)
        self._prompt_batches_consumed = cursor

    def _reset_data_stream(self) -> None:
        """Guardrail-rollback hook: stream back to zero; the subsequent
        load() fast-forwards to the checkpoint's saved cursor."""
        if getattr(self, "_prompt_pipeline", None) is None:
            return
        self._resume_prompt_cursor = 0
        if self._exp is not None:
            # in-flight transport chunks belong to the discarded live
            # state; the load() that follows restores the committed
            # cursor on top of the bumped epoch
            self._exp.abort_epoch()
        self._build_prompt_iterator()

    def _requeue_poisoned_batch(self) -> bool:
        """Guardrail `requeue` rung: drop the poisoned rollout store and
        rewind the prompt stream to the cycle start, so the same prompts
        are re-collected with the CURRENT policy (their poisoned
        rollouts never train; recomputed importance ratios make the
        replay sound — IMPACT, arXiv:1912.00167)."""
        start = getattr(self, "_cycle_cursor_start", None)
        if len(self.store) == 0 or start is None:
            return False
        self._abandon_prefetch()
        if self._exp is not None:
            # the rebuilt stream replays this cycle's prompts: void the
            # transport's in-flight chunks/leases under a new epoch so
            # an old delivery can never shadow a replayed one
            self._exp.abort_epoch()
        self.store.clear_history()
        self._rewind_prompt_stream(start)
        logger.warning(
            "guardrails: discarded the poisoned rollout batch; prompt "
            "stream rewound to chunk %d for replay", start,
        )
        return True

    def _reward_fallback_value(self) -> float:
        """`resilient_io.fallback_reward: hold_mean` — substitute the
        running-moments mean while the reward service is down, keeping
        the reward distribution stationary instead of injecting zeros."""
        try:
            v = float(np.asarray(self.running_moments.mean))
        except Exception:
            return 0.0
        return v if np.isfinite(v) else 0.0

    def _next_prompt_batch(self) -> PromptBatch:
        batch = next(self.prompt_iterator)
        self._prompt_batches_consumed += 1
        return batch

    # -- cross-cycle rollout prefetch (method.overlap_rollouts) ----------

    def pre_optimization_hook(self, will_continue: bool) -> None:
        """Dispatch the FIRST chunk of the next cycle's generation ahead
        of the fused optimization block, with the pre-update params.
        Device FIFO runs the generation before the train scan — whose
        buffer donation invalidates these params for any LATER dispatch
        — and the host decodes+scores the chunk while the block trains.
        The samples are one policy update stale, which the clipped
        surrogate absorbs: the teacher-forced scorer recomputes
        old_logprobs with the updated params when the chunk is
        consumed, so the ratio stays self-consistent with the
        optimization epoch's start."""
        if not self.config.method.overlap_rollouts or not will_continue:
            return
        if self._prefetched_gen is not None or not hasattr(self, "prompt_iterator"):
            return
        cursor0 = self._prompt_batches_consumed
        batch = self._next_prompt_batch()
        # staleness metadata: the tuple carries the PRE-update policy's
        # version — the chunk is consumed one optimizer cycle later at
        # exactly staleness 1 (which the admission gate's default
        # max_staleness admits untouched)
        with self.watchdog.phase("rollout", step=self.iter_count):
            self._prefetched_gen = self._dispatch_generation(batch)
        self._prefetch_cursor_start = cursor0

    def _take_prefetch(self) -> Optional[Tuple]:
        """The prefetched generation, if the cycle starts from one; it
        is the taker's from here on."""
        generation, self._prefetched_gen = self._prefetched_gen, None
        self._prefetch_cursor_start = None
        return generation

    def _abandon_prefetch(self) -> None:
        """Drop an in-flight prefetched chunk and rewind the prompt
        cursor: its rollouts never train (run ending / preempted), so a
        resumed run must replay those prompts."""
        if self._prefetched_gen is None:
            return
        self._prefetched_gen = None
        self._prompt_batches_consumed = self._prefetch_cursor_start
        self._prefetch_cursor_start = None

    def _fast_forward_prompts(self) -> None:
        """Resume: advance the prompt stream to the saved cursor. The
        loader's shuffle RNG is stateful per epoch, so replaying `skip`
        host-side batch pulls (cheap: pre-tokenized collation, no
        generation) reproduces the exact data order the killed run would
        have continued with."""
        skip = self._resume_prompt_cursor - self._prompt_batches_consumed
        if skip <= 0 or not hasattr(self, "prompt_iterator"):
            return
        logger.info(
            "resume: fast-forwarding the prompt stream by %d chunks to "
            "restore the rollout data order", skip,
        )
        for _ in range(skip):
            next(self.prompt_iterator)
        self._prompt_batches_consumed += skip

    def _extra_fingerprint(self):
        """Consistency-watchdog extras: the rollout-data cursor (host-
        side online-trainer state that MUST advance in lockstep across
        hosts — a drifted cursor silently trains different prompts per
        host); subclasses layer their controller state on top."""
        out = {
            "prompt_cursor": float(self._prompt_batches_consumed),
        }
        if self._exp is not None:
            # the transport's committed consumer position must advance
            # in lockstep too (a drifted cursor = hosts training
            # different chunks); also asserted dedicatedly through
            # multihost.cursor_consensus in _extra_consistency_checks
            out["exp_epoch"] = float(self._exp.queue.epoch)
            out["exp_cursor"] = float(self._exp.queue.cursor)
        return out

    # -- resumable state -------------------------------------------------

    def _extra_state(self):
        rm = self.running_moments
        state = {
            "ref_mean": None if self.ref_mean is None else float(self.ref_mean),
            "ref_std": None if self.ref_std is None else float(self.ref_std),
            "running_moments": {
                "mean": float(rm.mean), "var": float(rm.var),
                "std": float(rm.std), "count": float(rm.count),
            },
            # an in-flight prefetched chunk has NOT trained: persist the
            # cursor from before its pull, so a resume from this
            # checkpoint replays those prompts instead of skipping them
            "prompt_batches_consumed": (
                self._prefetch_cursor_start
                if self._prefetched_gen is not None
                else self._prompt_batches_consumed
            ),
            # the cursor counts GLOBAL chunks of the topology-invariant
            # stream (this marker lets a restore distinguish cursors
            # saved under the old per-group-shuffle scheme)
            "prompt_stream": "global-chunks-v1",
        }
        if self._exp is not None:
            # the experience-transport consumer cursor, committed INSIDE
            # the atomic checkpoint (state.json rides the integrity
            # manifest): a resume replays exactly the unconsumed chunks
            # — produced-but-unconsumed ones regenerate from the
            # group-invariant prompt stream. Invariant (verify_ckpt.py's
            # torn-commit detector): cursor <= prompt_batches_consumed,
            # every committed chunk consumed a prompt pull.
            state["exp_queue"] = {
                **self._exp.state_dict(),
                "policy_version": self._policy_version,
                "staleness_mode": self._exp_cfg.staleness.mode,
            }
        if self._fleet is not None:
            # membership epoch + last broadcast version, committed by
            # the SAME atomic state.json write as the exp cursor —
            # verify_ckpt.py's torn-commit detector holds the pair to
            # the publish-cadence invariant (a cursor referencing a
            # policy the committed snapshot never broadcast is torn)
            state["fleet"] = self._fleet.state()
        return state

    def _restore_extra_state(self, state) -> None:
        from trlx_tpu.ops.common import RunningMoments

        self.ref_mean = state.get("ref_mean", self.ref_mean)
        self.ref_std = state.get("ref_std", self.ref_std)
        rm = state.get("running_moments")
        if rm:
            self.running_moments = RunningMoments(
                mean=jnp.float32(rm["mean"]), var=jnp.float32(rm["var"]),
                std=jnp.float32(rm["std"]), count=jnp.float32(rm["count"]),
            )
        eq = state.get("exp_queue")
        if eq and self._exp is not None:
            self._exp.load_state_dict(eq)
            self._policy_version = int(eq.get("policy_version", 0))
        if self._fleet is not None:
            # the restore may have moved _policy_version backwards
            # (rollback): drop the publish cursor so the next cycle
            # rebroadcasts the restored params — otherwise workers keep
            # the rolled-back-over weights and their chunks admit as
            # non-stale (generation version ahead of the learner's)
            self._fleet.reset_published()
        self._resume_prompt_cursor = state.get("prompt_batches_consumed", 0)
        if (
            self._resume_prompt_cursor
            and state.get("prompt_stream") != "global-chunks-v1"
            and mh.data_group_count(self.mesh) > 1
        ):
            # pre-elastic multihost checkpoints counted chunks of
            # per-group shuffled streams; the invariant stream replays
            # a (deterministic) different partitioning from the same
            # cursor — continue, but say so
            logger.warning(
                "restored prompt cursor %d predates the "
                "topology-invariant stream: the replayed chunk "
                "composition differs from the saving run's on multi-"
                "group meshes", self._resume_prompt_cursor,
            )
        self._fast_forward_prompts()

    def prepare_learning(self) -> None:
        self.eval_dataloader = mh.shard_pipeline(self.eval_pipeline, self.mesh).create_loader(
            max(self.config.method.chunk_size // mh.data_group_count(self.mesh), 1)
        )
        # the restored iter_count keys the deferred rollout-stats flush:
        # without it a resumed run logs its first rollout at step 0 and
        # breaks tracker-step monotonicity
        self.make_experience(self.config.method.num_rollouts, self.iter_count)
        self.n_inner_epochs = self._inner_epochs()
        n_batches = len(self.store) // self.config.train.batch_size
        self.total_steps = min(
            self.config.train.epochs * self.n_inner_epochs * max(n_batches, 1),
            self.config.train.total_steps,
        )

    def create_train_dataloader(self):
        return self.store.create_loader(
            self.config.train.batch_size, shuffle=True, drop_last=True,
            seed=self.config.train.seed + self.iter_count,
        )

    def post_backward_callback(self) -> None:
        # flush the deferred rollout stats (by now the async device->
        # host copy has landed under the train step: a free read)
        self._finish_rollout_stats()

    def _fused_epoch_batch(self):
        # the rollout store is a rectangular (device-resident) pytree:
        # the whole inner-epochs x minibatch loop can run as one fused scan
        return self.store.fused_epoch_source()

    def post_epoch_callback(self) -> None:
        if self.log_rollouts:
            self.store.export_history(self.rollout_logging_dir, self.tokenizer)
        self.store.clear_history()
        self.make_experience(self.config.method.num_rollouts, self.iter_count)
