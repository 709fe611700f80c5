"""GRPO trainer: critic-free online preference RL on the shared
experience core (Group Relative Policy Optimization, arXiv:2402.03300).

The rollout ENGINE — prompt stream + cursors, chunked generate() with
one-chunk lookahead, cross-cycle prefetch (`method.overlap_rollouts`),
the decode engine (`method.gen_engine.*`), experience transport
(`method.exp.*`) and rollout fleet (`method.fleet.*`) — is inherited
verbatim from `trainer.online.TPUOnlineTrainer`; this module contributes
only what is GRPO:

- the PROMPT TILING: each chunk pulls ``chunk_size / group_size``
  prompts off the shared stream and repeats each one ``group_size``
  times, so a group's N samples are consecutive rows of one chunk
  (sampler RNG is per-row, so the repeats decode differently);
- the score/assemble seam: teacher-forced policy+reference logprob
  forward (NO value head, no value forward), host reward scoring, and
  per-group reward z-scores as sequence-level advantages
  (ops/grpo.py `group_relative_advantages`);
- the loss: PPO's clipped surrogate with the group advantage and an
  in-loss KL regularizer against the frozen reference
  (ops/grpo.py `grpo_loss`) — no value loss, and the optimizer carries
  no critic state because there is no critic to carry;
- the IMPACT-style staleness clip recompute for the transport's
  ``exp.staleness.mode: clip`` admission.

Relative to PPO this halves the method-specific train-phase state: the
rollout store drops the `values`/`rewards` columns for one advantage
scalar per row, and the loss runs one policy forward instead of
policy+value(+GAE).
"""

from __future__ import annotations

from time import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data import GRPORolloutBatch, PromptBatch
from trlx_tpu.data.method_configs import GRPOConfig
from trlx_tpu.models.transformer import logit_projection
from trlx_tpu.models.wrappers import CausalLM
from trlx_tpu.ops.common import chunked_logprobs, logprobs_of_labels
from trlx_tpu.ops.grpo import group_relative_advantages, grpo_loss
from trlx_tpu.ops.remat import resolve_remat
from trlx_tpu.parallel import data_sharding, shard_params
from trlx_tpu.parallel import multihost as mh
from trlx_tpu.parallel.mesh import replicated_sharding, vector_sharding
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.online import TPUOnlineTrainer
from trlx_tpu.trainer.ppo import _masked_kl_stats
from trlx_tpu.utils import Clock, logging

logger = logging.get_logger(__name__)


@register_trainer("TPUGRPOTrainer")
class TPUGRPOTrainer(TPUOnlineTrainer):
    def __init__(self, config, **kwargs):
        if not isinstance(config.method, GRPOConfig):
            raise ValueError("config.method must be GRPOConfig")
        super().__init__(config, **kwargs)
        if not config.method.gen_kwargs.get("do_sample", False):
            # a greedy group is group_size identical completions: every
            # group degenerates to zero advantage and nothing trains
            logger.warning(
                "grpo.gen_kwargs.do_sample is off — identical group "
                "members give zero group-relative advantage; enable "
                "sampling for GRPO to learn anything"
            )
        self._experience_fns: Dict[Any, Any] = {}

    def _drop_traced_fns(self) -> None:
        # the teacher-forced experience fns trace train.remat_policy in
        # — the memory doctor's remat escalation must retrace them
        super()._drop_traced_fns()
        self._experience_fns.clear()

    def _pre_accum_batch(self, batch):
        """Split-microbatch compensation for GRPO: no whitening to
        precompute (advantages are stored per-sequence), but the
        loss's mask-count normalizer is batch-coupled — fix it to
        full_total/num_mb so a doctor split reproduces the unsplit
        normalization exactly with ragged masks (same contract as
        PPO's hook)."""
        if self.memdoctor.accum_factor <= 1 or not isinstance(
            batch, GRPORolloutBatch
        ):
            return batch
        rows = batch.response_mask.shape[0]
        norm = jnp.full(
            (rows,),
            batch.response_mask.astype(jnp.float32).sum() / self.num_mb,
            jnp.float32,
        )
        return batch.replace(norm_n=norm)

    # -- model -----------------------------------------------------------

    def setup_model(self) -> None:
        if self.config.model.model_arch_type == "seq2seq":
            raise NotImplementedError("seq2seq GRPO is not implemented (causal only)")
        self.seq2seq = False
        cfg, base_params, self.model_type = self.load_base_model()
        self.model = CausalLM(cfg)
        self.rng, key = jax.random.split(self.rng)
        params = self.model.init_params(key, base_params)
        params.update(getattr(self, "_loaded_aux", None) or {})
        params = self.attach_peft(params)
        self.params = shard_params(self.mesh, params)
        # frozen reference for the in-loss KL: the initial policy's base
        # tree, DEEP-COPIED — the train step donates self.params buffers
        # every step, so the reference must not alias them. (With LoRA
        # the adapter-disabled base is the reference, peft convention.)
        self.ref_params = jax.tree_util.tree_map(jnp.copy, self.params["base"])

    def trainable_mask(self):
        return self.lora_freeze_mask(self.params) or self.make_freeze_mask(self.params)

    # -- loss ------------------------------------------------------------

    def loss(self, params, batch: GRPORolloutBatch):
        """Recompute policy logprobs on stored rollouts; clipped
        surrogate on the stored group advantage + in-loss reference KL.
        One forward — no value head, no GAE, no value loss."""
        method = self.config.method
        pad = self.generate_settings.pad_token_id
        remat = resolve_remat(self.config.train.remat_policy)
        chunks = self.config.train.logit_chunks
        P = batch.query_tensors.shape[1]
        N = batch.response_tensors.shape[1]
        tokens = jnp.concatenate([batch.query_tensors, batch.response_tensors], axis=1)
        attention_mask = (tokens != pad).astype(jnp.int32)
        # response positions count even where response==pad (mask handles it)
        attention_mask = attention_mask.at[:, P:].set(
            jnp.maximum(attention_mask[:, P:], batch.response_mask.astype(jnp.int32))
        )
        out = self.model.forward(
            params, tokens, attention_mask, remat=remat, compute_logits=chunks == 0
        )
        if chunks:
            logprobs = chunked_logprobs(
                self.model.logit_project_fn(params),
                out["hidden_states"][:, P - 1 : P + N - 1],
                tokens[:, P : P + N], chunks,
            )
        else:
            logprobs = logprobs_of_labels(
                out["logits"][:, P - 1 : P + N - 1], tokens[:, P : P + N]
            )
        return grpo_loss(
            logprobs=logprobs,
            old_logprobs=batch.logprobs,
            ref_logprobs=batch.ref_logprobs,
            advantages=batch.advantages,
            mask=batch.response_mask,
            cliprange=method.cliprange,
            kl_coef=method.kl_coef,
            # experience-transport staleness correction (exp.staleness.
            # mode: clip); None on every other path = weight 1
            is_weight=batch.is_weight,
            # split-microbatch normalizer compensation (_pre_accum_batch)
            norm_n=None if batch.norm_n is None else batch.norm_n[0],
        )

    # -- the method-specific score/assemble seam -------------------------

    def _inner_epochs(self) -> int:
        return self.config.method.grpo_epochs

    def _prompt_chunk_rows(self) -> int:
        # the stream yields PROMPTS; tiling to group_size samples per
        # prompt happens in _next_prompt_batch, so one chunk of the
        # stream is chunk_size/group_size prompts = chunk_size samples
        return self.config.method.chunk_size // self.config.method.group_size

    def _next_prompt_batch(self) -> PromptBatch:
        """Pull one chunk of prompts and tile each ``group_size`` times:
        a group's members are consecutive rows, local to this data
        group (the z-score baseline never crosses hosts). The sampler's
        RNG is per-row, so identical tiled prompts decode into
        different completions."""
        batch = super()._next_prompt_batch()
        gs = self.config.method.group_size
        metadata = None
        if batch.metadata:
            metadata = {
                k: [x for x in v for _ in range(gs)]
                for k, v in batch.metadata.items()
            }
        return PromptBatch(
            input_ids=np.repeat(np.asarray(batch.input_ids), gs, axis=0),
            attention_mask=np.repeat(np.asarray(batch.attention_mask), gs, axis=0),
            metadata=metadata,
        )

    def _get_experience_fwd_fn(self, P: int, N: int):
        """Jitted score-independent half of the experience step:
        teacher-forced policy AND frozen-reference logprob forward (no
        value head) + per-token KL stats. Dispatched right after
        generation so it overlaps decode + reward_fn, exactly like
        PPO's fast path; the advantage injection completes the batch
        once the host scores return."""
        key = ("fwd", P, N, self.config.train.logit_chunks)
        if key in self._experience_fns:
            return self._experience_fns[key]
        model = self.model
        chunks = self.config.train.logit_chunks

        def grpo_experience_fwd(params, ref_params, tokens, attention_mask, response_mask, row_valid):
            out = model.forward(
                params, tokens, attention_mask, compute_logits=chunks == 0
            )
            ref_out = model.lm(
                ref_params, tokens, attention_mask, compute_logits=chunks == 0
            )
            if chunks:
                logprobs_full = chunked_logprobs(
                    model.logit_project_fn(params),
                    out["hidden_states"][:, :-1], tokens[:, 1:], chunks,
                )
                ref_logprobs_full = chunked_logprobs(
                    logit_projection(ref_params, model.lm.mesh),
                    ref_out["hidden_states"][:, :-1], tokens[:, 1:], chunks,
                )
            else:
                logprobs_full = logprobs_of_labels(out["logits"][:, :-1], tokens[:, 1:])
                ref_logprobs_full = logprobs_of_labels(
                    ref_out["logits"][:, :-1], tokens[:, 1:]
                )

            full_mask = attention_mask[:, 1:].astype(jnp.float32)
            log_ratio_full = (logprobs_full - ref_logprobs_full) * full_mask
            kl = jnp.exp(log_ratio_full) - 1 - log_ratio_full
            mean_kl, mean_kl_per_token = _masked_kl_stats(kl, row_valid)

            mask = response_mask.astype(jnp.float32)
            sl = slice(P - 1, P + N - 1)
            batch_out = GRPORolloutBatch(
                query_tensors=tokens[:, :P],
                response_tensors=tokens[:, P:],
                logprobs=logprobs_full[:, sl] * mask,
                ref_logprobs=ref_logprobs_full[:, sl] * mask,
                # advantages injected once the host scores return
                advantages=jnp.zeros((tokens.shape[0],), jnp.float32),
                response_mask=mask,
            )
            return batch_out, {
                "mean_kl": mean_kl, "mean_kl_per_token": mean_kl_per_token,
            }

        self._experience_fns[key] = jax.jit(grpo_experience_fwd)
        return self._experience_fns[key]

    def _get_adv_inject_fn(self):
        key = "adv_inject"
        if key not in self._experience_fns:

            def grpo_adv_inject(batch, adv):
                return batch.replace(advantages=adv)

            self._experience_fns[key] = jax.jit(grpo_adv_inject)
        return self._experience_fns[key]

    def _group_advantages(self, scores: np.ndarray, stats: Dict[str, Any]):
        """Per-group z-scores over this host's rows (groups are local by
        construction: tiling happens after the per-group stream slice).
        Degenerate all-equal groups get exactly zero advantage."""
        gs = self.config.method.group_size
        if len(scores) % gs:
            raise RuntimeError(
                f"rollout chunk of {len(scores)} rows is not whole groups "
                f"of {gs} — the prompt tiling invariant broke"
            )
        adv = np.asarray(group_relative_advantages(jnp.asarray(scores), gs))
        g = scores.reshape(-1, gs)
        group_std = g.std(axis=1)
        stats["grpo/group_reward_std"] = float(group_std.mean())
        stats["grpo/zero_adv_groups"] = float((group_std <= 1e-6).mean())
        return adv.astype(np.float32)

    def _score_and_assemble(
        self, batch: PromptBatch, gen_out, stats: Dict[str, Any],
        iter_count: int, clock: Clock,
    ):
        """The score half of one rollout chunk: decode + reward_fn, the
        teacher-forced policy+reference logprob forward, per-group
        z-score advantages, running-moment update and the chunk's stats
        (mutated into ``stats``). Shared verbatim by the direct rollout
        loop, the experience-transport producer AND the fleet worker,
        so the paths cannot numerically diverge. Returns
        ``(rollout_batch, rows_local)``."""
        method = self.config.method
        prompt_tensors = np.asarray(batch.input_ids)
        seq_w = gen_out["sequences"].shape[1]
        N = gen_out["response_ids"].shape[1]
        P_width = prompt_tensors.shape[1]
        real_local = gen_out.get("real_rows")
        B_local = (
            real_local
            if real_local is not None
            else gen_out["sequences"].shape[0] // mh.data_group_count(self.mesh)
        )

        # the sampler's device time ends here (same seam as PPO's): the
        # pull blocks, the experience forward is dispatched after it
        packed_dev = self._pull_sampled_tokens(gen_out, B_local, stats)

        # fast path: the score-independent policy+ref logprob forward is
        # dispatched NOW on the sampler's device tensors; it executes
        # while the host decodes and scores. Falls back when host-side
        # token rewrites (stop sequences) or pad rows are needed.
        device_gen = (
            not self.stop_sequences
            and B_local % self.local_ways() == 0
            and real_local is None
        )
        pre_batch = pre_kl_stats = None
        if device_gen:
            with self.mesh, self.obs.span("score_dispatch"):
                fwd_fn = self._get_experience_fwd_fn(P_width, N)
                pre_batch, pre_kl_stats = self._dispatch_experience(
                    fwd_fn,
                    self.params,
                    self.ref_params,
                    gen_out["sequences"].astype(jnp.int32),
                    jnp.concatenate(
                        [
                            gen_out["prompt_mask"].astype(jnp.int32),
                            gen_out["response_mask"].astype(jnp.int32),
                        ],
                        axis=1,
                    ),
                    gen_out["response_mask"].astype(jnp.int32),
                    jnp.ones((gen_out["sequences"].shape[0],), jnp.float32),
                )

        packed = packed_dev[:B_local]  # drop per-group pad rows
        sequences = packed[:, :seq_w]
        response_ids = packed[:, seq_w : seq_w + N]
        response_mask = packed[:, seq_w + N :]
        P = prompt_tensors.shape[1]

        prompt_sizes = [P] * len(sequences)
        with self.obs.span("detokenize"):
            str_samples, str_prompts, str_outputs = self.decode(
                prompt_tensors, sequences, prompt_sizes, append_eos_token=True
            )

        rollout_score_time = time()
        all_scores = self._call_reward_fn(
            samples=str_samples,
            prompts=str_prompts,
            outputs=str_outputs,
            tokenizer=self.tokenizer,
            **(batch.metadata or {}),
        )
        stats["time/rollout_score"] = time() - rollout_score_time

        # GRPO's baseline is per-SEQUENCE: dense reward vectors fold to
        # their sum (the group z-score needs one scalar per sample)
        scores = np.asarray(
            [float(np.asarray(s, np.float32).sum()) for s in all_scores],
            np.float32,
        )
        if method.cliprange_reward:
            scores = np.clip(
                scores, -method.cliprange_reward, method.cliprange_reward
            )

        # running reward moments ride the shared online-core helper for
        # telemetry/guardrails parity with PPO; the returned scaling
        # divisor is irrelevant here — z-scores are scale-invariant
        self._update_reward_moments(
            scores[:, None], np.ones_like(scores)[:, None], stats
        )
        advantages = self._group_advantages(scores, stats)

        if self.stop_sequences:
            # stop-sequence trimming changed the outputs: rebuild the
            # response tokens from the trimmed strings (the fallback
            # forward below recomputes logprobs on the rebuilt rows)
            outputs = self.tokenizer(str_outputs, add_special_tokens=False)["input_ids"]
            response_ids = np.full(
                (len(outputs), N), self.generate_settings.pad_token_id, np.int32
            )
            response_mask = np.zeros((len(outputs), N), np.int32)
            for i, o in enumerate(outputs):
                o = o[:N]
                response_ids[i, : len(o)] = o
                response_mask[i, : len(o)] = 1
            sequences = np.concatenate([prompt_tensors, response_ids], axis=1)

        # pad rows to the data-parallel multiple for sharding; pad rows
        # carry zero advantage and are excluded from KL stats via the
        # row-validity vector, then trimmed before the store push
        B = len(sequences)
        target = B + (-B) % self.local_ways()
        sharding = data_sharding(self.mesh)
        if device_gen:
            # the forward half has been executing since right after
            # generation; complete it with the host-computed advantages
            # (device_gen implies B % local_ways == 0, so the advantage
            # vector shards cleanly)
            with self.mesh, self.obs.span("score_inject"):
                inject_fn = self._get_adv_inject_fn()
                rollout_batch = inject_fn(
                    pre_batch,
                    mh.global_from_local(advantages, vector_sharding(self.mesh)),
                )
            kl_stats = pre_kl_stats
        else:
            attention_mask = np.concatenate(
                [np.asarray(batch.attention_mask, np.int32), response_mask],
                axis=1,
            )

            def rpad(x):
                return self.pad_rows(x, target)

            adv_padded = np.concatenate(
                [advantages, np.zeros(target - B, np.float32)]
            )
            with self.mesh, self.obs.span("score_dispatch"):
                fwd_fn = self._get_experience_fwd_fn(P, N)
                pre_batch, kl_stats = self._dispatch_experience(
                    fwd_fn,
                    self.params,
                    self.ref_params,
                    mh.global_from_local(rpad(sequences.astype(np.int32)), sharding),
                    mh.global_from_local(rpad(attention_mask), sharding),
                    mh.global_from_local(rpad(response_mask), sharding),
                    # per-ROW validity (pad rows sit inside each data
                    # group's block of the global batch)
                    mh.global_from_local(
                        np.concatenate(
                            [np.ones(B, np.float32),
                             np.zeros(target - B, np.float32)]
                        ),
                        vector_sharding(self.mesh),
                    ),
                )
                inject_fn = self._get_adv_inject_fn()
                rollout_batch = inject_fn(
                    pre_batch,
                    mh.global_from_local(adv_padded, vector_sharding(self.mesh)),
                )
        if target != B and mh.is_multihost():
            # each group's pad rows sit inside the global batch; a flat
            # [:B] can't drop them (same choreography as PPO's seam)
            rollout_batch = jax.tree_util.tree_map(
                lambda x: jax.device_put(
                    np.asarray(
                        mh.allgather_group_rows(
                            mh.local_rows(x)[:B], self.mesh
                        )
                    ),
                    replicated_sharding(self.mesh),
                ),
                rollout_batch,
            )
        elif target != B:
            rollout_batch = jax.tree_util.tree_map(
                lambda x: x[:B], rollout_batch
            )

        # honest rollout accounting + decode-engine ledger (shared
        # online-core helper)
        self._rollout_accounting_stats(
            response_ids, response_mask, gen_out, stats, iter_count
        )
        stats["time/rollout_time"] = clock.tick()
        stats["policy/sqrt_kl"] = jnp.sqrt(
            jnp.maximum(kl_stats["mean_kl"], 0.0)
        )
        stats["policy/kl_per_token"] = jnp.sqrt(
            jnp.maximum(kl_stats["mean_kl_per_token"], 0.0)
        )
        return rollout_batch, len(sequences)

    def _apply_staleness_clip(self, rollout_batch: GRPORolloutBatch):
        """IMPACT-style admission correction for an over-stale chunk
        (``exp.staleness.mode: clip``, arXiv:1912.00167): recompute
        behavior logprobs with the CURRENT policy (the proximal
        recompute) and thread the mismatch into the surrogate as a
        per-token clipped importance weight (``ops/grpo.py``
        ``is_weight``). The stored reference logprobs and group
        advantages are policy-independent and keep their values."""
        pad = self.generate_settings.pad_token_id
        q = jnp.asarray(rollout_batch.query_tensors, jnp.int32)
        r = jnp.asarray(rollout_batch.response_tensors, jnp.int32)
        P, N = q.shape[1], r.shape[1]
        tokens = jnp.concatenate([q, r], axis=1)
        attention_mask = (tokens != pad).astype(jnp.int32)
        resp_mask = jnp.asarray(rollout_batch.response_mask)
        attention_mask = attention_mask.at[:, P:].set(
            jnp.maximum(attention_mask[:, P:], resp_mask.astype(jnp.int32))
        )
        with self.mesh:
            fwd_fn = self._get_experience_fwd_fn(P, N)
            pre_batch, _ = fwd_fn(
                self.params, self.ref_params, tokens, attention_mask,
                resp_mask.astype(jnp.int32),
                jnp.ones((tokens.shape[0],), jnp.float32),
            )
        c = self._exp_cfg.staleness.clip_c
        mask = resp_mask.astype(jnp.float32)
        rho = jnp.exp(pre_batch.logprobs - rollout_batch.logprobs)
        is_weight = jnp.clip(rho, 1.0 - c, 1.0 + c) * mask + (1.0 - mask)
        return rollout_batch.replace(
            logprobs=pre_batch.logprobs,
            is_weight=is_weight,
        )
