"""PPO trainer: the method-specific half of the online experience core.

Parity: /root/reference/trlx/trainer/accelerate_ppo_trainer.py:35-553 and
the KL controllers from modeling_ppo.py:35-67. Metric keys match
(`time/rollout_generate`, `time/rollout_score`, `rollout_scores/*`,
`policy/sqrt_kl`, `kl_ctl_value`, ...), as does the running-moments
reward scaling and the adaptive KL schedule, so reward curves are
directly comparable.

The rollout ENGINE — prompt stream + cursors, chunked generate() with
one-chunk lookahead, cross-cycle prefetch, the experience transport
(`exp/`) and rollout fleet (`fleet/`) — lives in the trainer-agnostic
`trainer.online.TPUOnlineTrainer`; this module contributes only what is
PPO: the value-headed model, the teacher-forced score/assemble seam
(policy+ref+value forward, per-token KL penalty, reward injection), the
adaptive KL controller, GAE + the clipped surrogate loss, and the
IMPACT-style staleness clip recompute.

TPU re-design of the rollout loop (reference §3.2 call stack):
- Generation, the teacher-forced policy+ref+value forward, the KL
  penalty and reward assembly are TWO jitted calls per chunk (sample,
  then score+assemble); the reference interleaves ~10 host/device
  syncs and a rank0 broadcast/scatter round-trip per chunk.
- Reward scoring stays host-side (arbitrary user Python), computed once
  per host over its own shard — the NeMo-style per-host pattern
  (nemo_ppo_trainer.py:195-197), not the rank0-scatter one.
- Rollouts are born as rectangular PPORolloutBatch pytrees; no ragged
  tensor lists, no pad-at-collate.
"""

from __future__ import annotations

from time import time
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trlx_tpu.data import PPORolloutBatch, PromptBatch
from trlx_tpu.data.method_configs import PPOConfig
from trlx_tpu.models.transformer import _join_stats, moe_counters
from trlx_tpu.models.wrappers import CausalLMWithValueHead, Seq2SeqLMWithValueHead
from trlx_tpu.obs.telemetry import tree_param_count
from trlx_tpu.ops.common import chunked_logprobs, logprobs_of_labels
from trlx_tpu.ops.ppo import gae_advantages_and_returns, ppo_loss
from trlx_tpu.ops.remat import resolve_remat
from trlx_tpu.parallel import data_sharding, shard_params
from trlx_tpu.parallel import multihost as mh
from trlx_tpu.parallel.mesh import replicated_sharding, vector_sharding
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.online import (  # noqa: F401  (_GroupChunkLoader re-export)
    TPUOnlineTrainer,
    _GroupChunkLoader,
)
from trlx_tpu.utils import Clock, logging

logger = logging.get_logger(__name__)


def _masked_kl_stats(kl, row_valid):
    """(mean_kl, mean_kl_per_token) over the rows row_valid marks 1:
    rows appended by pad_rows for dp-divisibility are excluded so they
    cannot bias the adaptive KL controller. A VECTOR (not a prefix
    count): on multi-host each data group's pad rows sit inside the
    global batch, so "the first n rows" would keep some groups' pad
    rows and drop other groups' real ones."""
    row_valid = row_valid.astype(jnp.float32)
    n_valid = jnp.maximum(row_valid.sum(), 1.0)
    mean_kl = (kl.sum(axis=1) * row_valid).sum() / n_valid
    mean_kl_per_token = (kl * row_valid[:, None]).sum() / (n_valid * kl.shape[1])
    return mean_kl, mean_kl_per_token


class AdaptiveKLController:
    """Ziegler-style proportional KL coefficient controller
    (parity: reference modeling_ppo.py:35-57)."""

    def __init__(self, init_kl_coef: float, target: float, horizon: int):
        self.value = init_kl_coef
        self.target = target
        self.horizon = horizon

    def update(self, current: float, n_steps: int) -> None:
        proportional_error = np.clip(current / self.target - 1, -0.2, 0.2)
        mult = 1 + proportional_error * n_steps / self.horizon
        self.value *= mult


class FixedKLController:
    """(parity: reference modeling_ppo.py:60-67)"""

    def __init__(self, kl_coef: float):
        self.value = kl_coef

    def update(self, current: float, n_steps: int) -> None:
        pass


@register_trainer("TPUPPOTrainer")
class TPUPPOTrainer(TPUOnlineTrainer):
    def __init__(self, config, **kwargs):
        if not isinstance(config.method, PPOConfig):
            raise ValueError("config.method must be PPOConfig")
        super().__init__(config, **kwargs)

        if (
            self.seq2seq
            and self._exp_cfg.enabled
            and self._exp_cfg.staleness.mode == "clip"
        ):
            raise NotImplementedError(
                "exp.staleness.mode='clip' needs the causal "
                "experience forward for the proximal recompute; "
                "use mode='reject' with seq2seq models"
            )

        if config.method.target:
            self.kl_ctl: Any = AdaptiveKLController(
                config.method.init_kl_coef, config.method.target, config.method.horizon
            )
        else:
            self.kl_ctl = FixedKLController(config.method.init_kl_coef)

        self.mean_kl = 0.0
        self._experience_fns: Dict[Tuple, Any] = {}

    # -- model -----------------------------------------------------------

    def setup_model(self) -> None:
        cfg, base_params, self.model_type = self.load_base_model()
        self.seq2seq = self.config.model.model_arch_type == "seq2seq"
        k = self.config.model.num_layers_unfrozen
        if self.config.model.peft_config is not None:
            from trlx_tpu.models.peft import normalize_peft_config

            pc = normalize_peft_config(self.config.model.peft_config)
            if self.seq2seq and pc["peft_type"] != "LORA":
                # matches the reference matrix: its own peft tests skip
                # seq2seq x {PROMPT,PREFIX} (peft 0.3.0 bugs)
                raise NotImplementedError(
                    "seq2seq supports peft_type='LORA' only"
                )
            # with adapters the reference model is the disabled-adapter
            # base, not a hydra branch (reference peft contract)
            k = -1
            if (
                pc["peft_type"] in ("PROMPT_TUNING", "PREFIX_TUNING")
                and self.config.method.num_value_layers_unfrozen
            ):
                raise NotImplementedError(
                    "num_value_layers_unfrozen with prompt/prefix tuning is "
                    "not supported (the value-branch capture forward does "
                    "not thread virtual-token adapters)"
                )
        at = None
        if self.seq2seq:
            if k is not None and 0 < k < cfg.n_decoder_layer:
                at = cfg.n_decoder_layer - k
            self.model = Seq2SeqLMWithValueHead(cfg, branch_at=at)
        else:
            if k is not None and 0 < k < cfg.n_layer:
                at = cfg.n_layer - k
            nv = self.config.method.num_value_layers_unfrozen
            value_at = cfg.n_layer - nv if nv and 0 < nv < cfg.n_layer else None
            self.model = CausalLMWithValueHead(
                cfg, branch_at=at, value_branch_at=value_at
            )
        self.rng, key = jax.random.split(self.rng)
        params = self.model.init_params(key, base_params)
        params.update(getattr(self, "_loaded_aux", None) or {})
        params = self.attach_peft(params)
        self.params = shard_params(self.mesh, params)
        # frozen in-process reference: the top-k branch (hydra) or a full
        # copy when everything is trainable (reference :74-77); with LoRA
        # the disabled-adapter base IS the reference (peft parity)
        with self.obs.span("ref_init") as counts:
            self.ref_params = shard_params(self.mesh, self.model.make_ref_params(self.params))
            counts["params"] = tree_param_count(self.ref_params)

    def trainable_mask(self):
        lora_mask = self.lora_freeze_mask(self.params)
        if lora_mask is not None:
            return lora_mask
        if self.seq2seq:
            return self.make_seq2seq_freeze_mask(self.params)
        return self.make_freeze_mask(self.params)

    # -- loss ------------------------------------------------------------

    def _policy_inputs(self, batch: PPORolloutBatch):
        """(tokens, attention_mask) of the causal policy's teacher-forced
        pass over stored rollouts: query then response."""
        P = batch.query_tensors.shape[1]
        tokens = jnp.concatenate([batch.query_tensors, batch.response_tensors], axis=1)
        attention_mask = (tokens != self.generate_settings.pad_token_id).astype(jnp.int32)
        # response positions count even where response==pad (mask handles it)
        attention_mask = attention_mask.at[:, P:].set(
            jnp.maximum(attention_mask[:, P:], batch.response_mask.astype(jnp.int32))
        )
        return tokens, attention_mask

    def trunk_layers_held(self) -> int:
        # T5 keeps the whole forward in the block (`models/seq2seq.py` has
        # its own `frozen_below` and no split at the constants)
        return 0 if self.seq2seq else self.model.trunk_layers_held()

    def trunk_constants(self, params, batch: PPORolloutBatch):
        return self.model.trunk_constants(params, *self._policy_inputs(batch))

    def loss(self, params, batch: PPORolloutBatch, trunk=None):
        """Recompute logprobs/values on stored rollouts, GAE on the fly,
        clipped PPO objective (parity: reference loss :127-204). `trunk`:
        the frozen trunk's output for these rows where the fused block
        holds it (`trunk_constants`); the forward resumes above it."""
        method = self.config.method
        if batch.advantages is not None:
            # gradient-accumulation compensation (_pre_accum_batch):
            # advantages were whitened over the FULL minibatch before
            # the microbatch split — recomputing here would whiten per
            # microbatch and diverge from the unsplit step
            advantages, returns = batch.advantages, batch.returns
        else:
            advantages, returns = gae_advantages_and_returns(
                batch.values, batch.rewards, gamma=method.gamma, lam=method.lam
            )
        pad = self.generate_settings.pad_token_id
        remat = resolve_remat(self.config.train.remat_policy)
        # chunked-from-hidden logprobs (train.logit_chunks): the full
        # [B, T, V] fp32 logits never materialize — the at-scale recipe
        chunks = self.config.train.logit_chunks
        if self.seq2seq:
            # query = encoder prompt; response = decoder ids (start token
            # + sampled tokens), parity: reference loss :146-173
            dec = batch.response_tensors
            enc_mask = (batch.query_tensors != pad).astype(jnp.int32)
            dec_mask = jnp.concatenate(
                [jnp.ones_like(dec[:, :1]), batch.response_mask.astype(jnp.int32)],
                axis=1,
            )
            out = self.model.forward_train(
                params, self.ref_params, batch.query_tensors, enc_mask, dec,
                dec_mask, remat=remat, compute_logits=chunks == 0,
            )
            if chunks:
                logprobs = chunked_logprobs(
                    self.model.logit_project_fn(params),
                    out["hidden_states"][:, :-1], dec[:, 1:], chunks,
                )
            else:
                logprobs = logprobs_of_labels(out["logits"][:, :-1], dec[:, 1:])
            values_pred = out["values"][:, :-1]
            return ppo_loss(
                logprobs=logprobs,
                values=values_pred,
                old_logprobs=batch.logprobs,
                old_values=batch.values,
                advantages=advantages,
                returns=returns,
                mask=batch.response_mask,
                cliprange=method.cliprange,
                cliprange_value=method.cliprange_value,
                vf_coef=method.vf_coef,
                is_weight=batch.is_weight,
                norm_n=None if batch.norm_n is None else batch.norm_n[0],
            )
        P = batch.query_tensors.shape[1]
        N = batch.response_tensors.shape[1]
        tokens, attention_mask = self._policy_inputs(batch)
        out = self.model.forward_train(
            params, self.ref_params, tokens, attention_mask, remat=remat,
            compute_logits=chunks == 0, trunk=trunk,
        )
        if chunks:
            # only response positions need logprobs: slice hidden BEFORE
            # projecting, so even the chunked vocab matmul runs over N
            # rows, not P+N
            logprobs = chunked_logprobs(
                self.model.logit_project_fn(params),
                out["hidden_states"][:, P - 1 : P + N - 1],
                tokens[:, P : P + N], chunks,
            )
        else:
            logprobs = logprobs_of_labels(out["logits"][:, P - 1 : P + N - 1], tokens[:, P : P + N])
        values_pred = out["values"][:, P - 1 : P + N - 1]
        loss, stats = ppo_loss(
            logprobs=logprobs,
            values=values_pred,
            old_logprobs=batch.logprobs,
            old_values=batch.values,
            advantages=advantages,
            returns=returns,
            mask=batch.response_mask,
            cliprange=method.cliprange,
            cliprange_value=method.cliprange_value,
            vf_coef=method.vf_coef,
            # experience-transport staleness correction (exp.staleness.
            # mode: clip); None on every other path = weight 1
            is_weight=batch.is_weight,
            # split-microbatch normalizer compensation (_pre_accum_batch)
            norm_n=None if batch.norm_n is None else batch.norm_n[0],
        )
        # a routed model's counters ride the step's stats (per optimizer
        # step; the fused block's flush carries their mean over its steps)
        return loss, dict(stats, **moe_counters(out.get("moe_stats"), "train"))

    # -- the method-specific score/assemble seam -------------------------

    def _inner_epochs(self) -> int:
        return self.config.method.ppo_epochs

    def _rollout_stage_meta(self):
        # the adaptive KL coefficient at collection time rides the
        # deferred stats so the flush logs the value the chunk trained at
        return self.kl_ctl.value

    def _get_experience_fn(self, P: int, N: int, S: int):
        """Jitted score+assemble step: teacher-forced policy/ref/value
        forward, per-token KL penalty, terminal (or dense) reward add."""
        # logit_chunks is baked into the traced fn: it keys the cache
        key = (P, N, S, self.config.train.logit_chunks)
        if key in self._experience_fns:
            return self._experience_fns[key]
        model = self.model

        chunks = self.config.train.logit_chunks

        def ppo_experience_seq2seq(params, ref_params, enc_ids, enc_mask, dec_ids, response_mask, scores, scores_mask, kl_coef, row_valid, scale_div):
            scores = scores / jnp.maximum(scale_div, 1e-8)
            mask = response_mask.astype(jnp.float32)
            dec_mask = jnp.concatenate(
                [jnp.ones_like(dec_ids[:, :1]), response_mask.astype(jnp.int32)], axis=1
            )
            out = model.forward_train(
                params, ref_params, enc_ids, enc_mask, dec_ids, dec_mask,
                compute_logits=chunks == 0,
            )
            if chunks:
                from trlx_tpu.models.seq2seq import t5_logit_projection

                logprobs = chunked_logprobs(
                    model.logit_project_fn(params),
                    out["hidden_states"][:, :-1], dec_ids[:, 1:], chunks,
                ) * mask
                ref_logprobs = chunked_logprobs(
                    t5_logit_projection(ref_params, model.cfg),
                    out["ref_hidden"][:, :-1], dec_ids[:, 1:], chunks,
                ) * mask
            else:
                logprobs = logprobs_of_labels(out["logits"][:, :-1], dec_ids[:, 1:]) * mask
                ref_logprobs = logprobs_of_labels(out["ref_logits"][:, :-1], dec_ids[:, 1:]) * mask
            log_ratio = logprobs - ref_logprobs
            kl = jnp.exp(log_ratio) - 1 - log_ratio
            mean_kl, mean_kl_per_token = _masked_kl_stats(kl, row_valid)
            values = out["values"][:, :-1] * mask

            rewards = -kl_coef * log_ratio
            if S == 1:
                last = jnp.maximum(mask.sum(axis=1).astype(jnp.int32) - 1, 0)
                rewards = rewards + scores[:, 0:1] * jax.nn.one_hot(last, N, dtype=rewards.dtype)
            else:
                padded = jnp.zeros_like(rewards)
                padded = padded.at[:, :S].set(scores * scores_mask)
                rewards = rewards + padded
            rewards = rewards * mask

            batch_out = PPORolloutBatch(
                query_tensors=enc_ids,
                response_tensors=dec_ids,
                logprobs=logprobs,
                values=values,
                rewards=rewards,
                response_mask=mask,
            )
            return batch_out, {"mean_kl": mean_kl, "mean_kl_per_token": mean_kl_per_token}

        if self.seq2seq:
            self._experience_fns[key] = jax.jit(ppo_experience_seq2seq)
            return self._experience_fns[key]

        # causal path: composed from the SAME two jitted halves the
        # overlapped fast path uses (fwd + score inject), so the fallback
        # cannot numerically diverge from it
        fwd_fn = self._get_experience_fwd_fn(P, N)
        inject_fn = self._get_score_inject_fn(N, S)

        def ppo_experience(params, ref_params, tokens, attention_mask, response_mask, scores, scores_mask, kl_coef, row_valid, scale_div):
            # no envelope here: this composed fn is itself dispatched
            # through _dispatch_experience at its call site — wrapping
            # both layers would classify one OOM twice
            pre_batch, kl_stats = fwd_fn(
                params, ref_params, tokens, attention_mask, response_mask,
                kl_coef, row_valid,
            )
            return inject_fn(pre_batch, scores, scores_mask, scale_div), kl_stats

        self._experience_fns[key] = ppo_experience
        return self._experience_fns[key]

    def _get_experience_fwd_fn(self, P: int, N: int):
        """The score-independent half of the experience step: teacher-forced
        policy/ref/value forward + per-token KL penalty. Dispatched BEFORE
        host scoring (it only reads device tensors the sampler produced),
        so the heaviest rollout compute overlaps decode + reward_fn — with
        a slow reward model the whole forward hides under scoring. The
        score half is `_get_score_inject_fn`."""
        key = ("fwd", P, N, self.config.train.logit_chunks)
        if key in self._experience_fns:
            return self._experience_fns[key]
        model = self.model

        chunks = self.config.train.logit_chunks

        def ppo_experience_fwd(params, ref_params, tokens, attention_mask, response_mask, kl_coef, row_valid):
            out = model.forward_train(
                params, ref_params, tokens, attention_mask,
                compute_logits=chunks == 0,
            )
            if chunks:
                from trlx_tpu.models.transformer import logit_projection

                logprobs_full = chunked_logprobs(
                    model.logit_project_fn(params),
                    out["hidden_states"][:, :-1], tokens[:, 1:], chunks,
                )
                ref_logprobs_full = chunked_logprobs(
                    logit_projection(ref_params, model.lm.mesh),
                    out["ref_hidden"][:, :-1], tokens[:, 1:], chunks,
                )
            else:
                logprobs_full = logprobs_of_labels(out["logits"][:, :-1], tokens[:, 1:])
                ref_logprobs_full = logprobs_of_labels(out["ref_logits"][:, :-1], tokens[:, 1:])

            full_mask = attention_mask[:, 1:].astype(jnp.float32)
            log_ratio_full = (logprobs_full - ref_logprobs_full) * full_mask
            kl = jnp.exp(log_ratio_full) - 1 - log_ratio_full
            mean_kl, mean_kl_per_token = _masked_kl_stats(kl, row_valid)

            mask = response_mask.astype(jnp.float32)
            sl = slice(P - 1, P + N - 1)
            logprobs = logprobs_full[:, sl] * mask
            values = out["values"][:, sl] * mask
            log_ratio = log_ratio_full[:, sl] * mask

            batch_out = PPORolloutBatch(
                query_tensors=tokens[:, :P],
                response_tensors=tokens[:, P:],
                logprobs=logprobs,
                values=values,
                rewards=-kl_coef * log_ratio,  # scores injected later
                response_mask=mask,
            )
            # a routed model's counters: the policy's layers and the reference branch's
            moe_stats = _join_stats(out.get("moe_stats"), out.get("ref_moe_stats"))
            return batch_out, {"mean_kl": mean_kl, "mean_kl_per_token": mean_kl_per_token,
                               **moe_counters(moe_stats, "scorer")}

        self._experience_fns[key] = jax.jit(ppo_experience_fwd)
        return self._experience_fns[key]

    def _get_score_inject_fn(self, N: int, S: int):
        """Apply host-computed scores to a KL-only rollout batch: scale,
        add terminal (S=1) or dense (S>1) rewards, re-mask."""
        key = ("inject", N, S)
        if key in self._experience_fns:
            return self._experience_fns[key]

        def ppo_score_inject(batch_out, scores, scores_mask, scale_div):
            scores = scores / jnp.maximum(scale_div, 1e-8)
            mask = batch_out.response_mask
            rewards = batch_out.rewards
            if S == 1:
                last = jnp.maximum(mask.sum(axis=1).astype(jnp.int32) - 1, 0)
                rewards = rewards + scores[:, 0:1] * (
                    jax.nn.one_hot(last, N, dtype=rewards.dtype)
                )
            else:
                padded = jnp.zeros_like(rewards)
                padded = padded.at[:, :S].set(scores * scores_mask)
                rewards = rewards + padded
            return batch_out.replace(rewards=rewards * mask)

        self._experience_fns[key] = jax.jit(ppo_score_inject)
        return self._experience_fns[key]

    def _score_and_assemble(
        self, batch: PromptBatch, gen_out, stats: Dict[str, Any],
        iter_count: int, clock: Clock,
    ):
        """The score half of one rollout chunk: decode + reward_fn, the
        teacher-forced policy/ref/value forward, KL penalty + reward
        assembly, running-moment update and the chunk's stats (mutated
        into ``stats``). Shared verbatim by the direct rollout loop and
        the experience-transport producer, so the two paths cannot
        numerically diverge. Returns ``(rollout_batch, rows_local)``."""
        method = self.config.method
        prompt_tensors = np.asarray(batch.input_ids)
        seq_w = gen_out["sequences"].shape[1]
        N = gen_out["response_ids"].shape[1]
        P_width = prompt_tensors.shape[1]
        # a ragged multi-host chunk comes back PADDED per data group
        # with real_rows marking the group's real count — all row
        # bookkeeping below runs on real rows; the pad rows only
        # exist inside device arrays until the local slice
        real_local = gen_out.get("real_rows")
        B_local = (
            real_local
            if real_local is not None
            else gen_out["sequences"].shape[0] // mh.data_group_count(self.mesh)
        )

        # the sampler's device time ends here: the pull blocks, so the
        # experience forward below is dispatched only after it
        packed_dev = self._pull_sampled_tokens(gen_out, B_local, stats)

        # fast path: the score-INDEPENDENT half of the experience step
        # (policy/ref/value forward + KL penalty — the heaviest rollout
        # compute) is dispatched NOW, on the device tensors the sampler
        # just produced. It executes while the host decodes and scores
        # the samples; the tiny score-injection jit below completes the
        # rollout batch once reward_fn returns. Falls back to the
        # fused experience fn when host-side token rewrites (stop
        # sequences, seq2seq) or pad rows are needed.
        device_gen = (
            not self.seq2seq
            and not self.stop_sequences
            and B_local % self.local_ways() == 0
            # a padded multihost chunk (real_rows set — including the
            # divisible-but-widened case, where generate() padded up
            # to an already-compiled wider shape) must take the
            # host-scored path: the device fast path would build
            # pre_batch over the pad rows and mismatch the real-row
            # scores at injection
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
                    jnp.float32(self.kl_ctl.value),
                    # device_gen only runs on unpadded batches: every
                    # row is valid
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

        scores_list = [np.atleast_1d(np.asarray(s, np.float32)) for s in all_scores]
        S = max(len(s) for s in scores_list)
        if S > N:
            # a dense reward vector longer than the response window (a
            # char-level reward_fn over a decode that appended the EOS
            # string, say) cannot be scattered onto [B, N] rewards: fold
            # the tail into the final in-window entry so no reward mass
            # is silently dropped
            scores_list = [
                np.concatenate([s[: N - 1], [s[N - 1 :].sum()]])
                if len(s) > N else s
                for s in scores_list
            ]
            S = N
        scores = np.zeros((len(scores_list), S), np.float32)
        scores_mask = np.zeros((len(scores_list), S), np.float32)
        for i, s in enumerate(scores_list):
            scores[i, : len(s)] = s
            scores_mask[i, : len(s)] = 1.0

        if self.stop_sequences:
            # stop-sequence trimming changed the outputs: rebuild the
            # response tokens from the trimmed strings (the reference
            # re-tokenizes unconditionally, :345-365 — lossy for some
            # tokenizers, so here only when actually needed)
            outputs = self.tokenizer(str_outputs, add_special_tokens=False)["input_ids"]
            response_ids = np.full((len(outputs), N), self.generate_settings.pad_token_id, np.int32)
            response_mask = np.zeros((len(outputs), N), np.int32)
            for i, o in enumerate(outputs):
                o = o[:N]
                response_ids[i, : len(o)] = o
                response_mask[i, : len(o)] = 1
            if self.seq2seq:
                start = sequences[:, :1]  # decoder start token column
                sequences = np.concatenate([start, response_ids], axis=1)
            else:
                sequences = np.concatenate([prompt_tensors, response_ids], axis=1)

        if method.cliprange_reward:
            scores = np.clip(scores, -method.cliprange_reward, method.cliprange_reward)

        # running reward moments + the reward-scaling divisor (shared
        # online-core helper — one implementation for PPO and GRPO)
        scale_div = self._update_reward_moments(scores, scores_mask, stats)

        # pad rows to the data-parallel multiple for sharding; the
        # extra rows are trimmed off the rollout batch afterwards
        # (multi-host: every group pads the same B -> target, so the
        # global batch stays rectangular; pad rows repeat the last
        # real row, are excluded from KL stats via the row-validity
        # vector below, and are dropped before the store push)
        B = len(sequences)
        target = B + (-B) % self.local_ways()

        def rpad(x):
            return self.pad_rows(x, target)

        sharding = data_sharding(self.mesh)
        if device_gen:
            # the forward half has been executing since right after
            # generation; complete it with the host-computed scores
            with self.mesh, self.obs.span("score_inject"):
                inject_fn = self._get_score_inject_fn(N, S)
                rollout_batch = inject_fn(
                    pre_batch,
                    mh.global_from_local(scores, sharding),
                    mh.global_from_local(scores_mask, sharding),
                    scale_div,
                )
            kl_stats = pre_kl_stats
        else:
            exp_fn = self._get_experience_fn(P, N, S)
            if self.seq2seq:
                args = (
                    rpad(prompt_tensors.astype(np.int32)),
                    rpad(np.asarray(batch.attention_mask, np.int32)),
                    rpad(sequences.astype(np.int32)),
                )
            else:
                attention_mask = np.concatenate(
                    [np.asarray(batch.attention_mask, np.int32), response_mask],
                    axis=1,
                )
                args = (
                    rpad(sequences.astype(np.int32)),
                    rpad(attention_mask),
                )
            with self.mesh, self.obs.span("score_dispatch"):
                rollout_batch, kl_stats = self._dispatch_experience(
                    exp_fn,
                    self.params,
                    self.ref_params,
                    *[mh.global_from_local(a, sharding) for a in args],
                    mh.global_from_local(rpad(response_mask), sharding),
                    mh.global_from_local(rpad(scores), sharding),
                    mh.global_from_local(rpad(scores_mask), sharding),
                    jnp.float32(self.kl_ctl.value),
                    # per-ROW validity (pad rows sit inside each data
                    # group's block of the global batch, so a prefix
                    # count can't mark them)
                    mh.global_from_local(
                        np.concatenate(
                            [np.ones(B, np.float32),
                             np.zeros(target - B, np.float32)]
                        ),
                        vector_sharding(self.mesh),
                    ),
                    scale_div,
                )
        if target != B and mh.is_multihost():
            # each group's pad rows sit inside the global batch; a
            # flat [:B] can't drop them. The chunk is tiny (only a
            # short FINAL chunk is ragged), so take the host
            # round-trip: local real rows -> allgather -> one
            # replicated, consistent global batch for the store
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
            # trim the sharding-pad rows ON DEVICE (the store keeps
            # device-resident rollouts; no host round-trip here)
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
        # the scorer's routed-layer counters: device scalars, flushed with
        # the rest of the chunk's deferred stats
        stats.update({k: v for k, v in kl_stats.items() if k.startswith("moe/")})
        return rollout_batch, len(sequences)

    def _apply_staleness_clip(self, rollout_batch: PPORolloutBatch):
        """IMPACT-style admission correction for an over-stale chunk
        (``exp.staleness.mode: clip``, arXiv:1912.00167): recompute
        logprobs/values with the CURRENT policy (the proximal recompute
        — the PPO ratio is then measured against the policy the
        optimization epoch actually starts from) and thread the
        behavior mismatch into the surrogate as a per-token CLIPPED
        importance weight rho = clip(pi_now/pi_behavior, 1±clip_c)
        (``ops/ppo.py`` ``is_weight``). The stored rewards keep their
        generation-time KL penalty (the terminal score is
        policy-independent)."""
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
            pre_batch, _ = self._dispatch_experience(
                fwd_fn,
                self.params, self.ref_params, tokens, attention_mask,
                resp_mask.astype(jnp.int32),
                jnp.float32(self.kl_ctl.value),
                jnp.ones((tokens.shape[0],), jnp.float32),
            )
        c = self._exp_cfg.staleness.clip_c
        mask = resp_mask.astype(jnp.float32)
        rho = jnp.exp(pre_batch.logprobs - rollout_batch.logprobs)
        is_weight = jnp.clip(rho, 1.0 - c, 1.0 + c) * mask + (1.0 - mask)
        return rollout_batch.replace(
            logprobs=pre_batch.logprobs,
            values=pre_batch.values,
            is_weight=is_weight,
        )

    def _finish_rollout_stats(self) -> None:
        """Materialize + log the deferred make_experience stats (sets
        self.mean_kl for the KL controller; feeds the guardrails the
        rollout-side health signals). Idempotent."""
        for stats, step, kl_ctl_value in self._deferred_rollout.flush():
            stats["kl_ctl_value"] = kl_ctl_value
            self.mean_kl = stats["policy/sqrt_kl"] ** 2
            if self.guardrails.enabled:
                self.guardrails.observe_rollout(
                    kl=self.mean_kl,
                    kl_target=getattr(self.kl_ctl, "target", None),
                    reward_mean=stats.get("rollout_scores/mean"),
                    running_mean=stats.get("rollout_scores/running_mean"),
                    running_std=stats.get("rollout_scores/running_std"),
                    truncation_rate=stats.get("rollout/truncation_rate"),
                )
            self._tracker_log(stats, step=step)

    # -- memory doctor hooks ---------------------------------------------

    def _pre_accum_batch(self, batch):
        """Gradient-accumulation compensation for the memory doctor's
        split_microbatch rung: GAE + advantage whitening are computed
        over the FULL step batch before the scan splits it, so the
        whitening statistics (batch mean/std) are num_mb-INVARIANT —
        an unsplit (num_mb=1) baseline is reproduced exactly
        (reduction-order tolerance, tests/test_memdoctor.py golden),
        and any further doctor split preserves numerics. A config that
        already accumulated (train.minibatch_size) whitened per
        microbatch pre-doctor; its first split switches to this
        full-batch scope with a logged warning (_apply_accum_factor) —
        no compensation can reproduce the old statistics from smaller
        microbatches. Outside a doctor split the batch passes through
        untouched: the pre-doctor minibatch path keeps its
        reference-parity per-microbatch whitening."""
        if self.memdoctor.accum_factor <= 1 or not isinstance(
            batch, PPORolloutBatch
        ):
            return batch
        method = self.config.method
        advantages, returns = gae_advantages_and_returns(
            batch.values, batch.rewards, gamma=method.gamma, lam=method.lam
        )
        # the loss's mask-count normalizer, fixed to full_total/num_mb:
        # each microbatch then divides by the same constant, so the
        # accumulated mean equals the unsplit sum/N_total exactly even
        # when ragged response masks make per-microbatch counts unequal
        rows = batch.response_mask.shape[0]
        norm = jnp.full(
            (rows,),
            batch.response_mask.astype(jnp.float32).sum() / self.num_mb,
            jnp.float32,
        )
        return batch.replace(advantages=advantages, returns=returns, norm_n=norm)

    def _drop_traced_fns(self) -> None:
        # the teacher-forced experience fns trace train.remat_policy
        # in too — a remat escalation must retrace them
        super()._drop_traced_fns()
        self._experience_fns.clear()

    def _extra_plan_items(self):
        """Preflight plan rows for PPO's method half: the teacher-forced
        experience forward materializes one chunk's activations at
        [chunk, P+N] on top of the rollout phase (it shares the phase
        with generation — they run back-to-back per chunk)."""
        from trlx_tpu.utils.memdoctor import PlanItem, _dtype_size

        train = self.config.train
        chunk = int(self.config.method.chunk_size)
        rows_dev = max(chunk // self.data_ways(), 1)
        cfg = self._lm().cfg
        S = train.seq_length
        # forward-only: residency is ~2 live layer activations, not the
        # whole saved-for-backward stack (unless logits materialize)
        act_b = int(rows_dev * S * cfg.hidden_size * 2
                    * _dtype_size(train.compute_dtype))
        chunks = max(int(train.logit_chunks or 0), 0)
        logit_rows = S if chunks == 0 else -(-S // chunks)
        logits_b = int(2 * rows_dev * logit_rows * cfg.vocab_size * 4)
        items = [
            PlanItem("rollout", "experience_fwd", act_b + logits_b,
                     "teacher-forced policy+ref forward per chunk"),
        ]
        held = self.trunk_layers_held() if train.fused_inner_loop else 0
        if held:
            # the frozen trunk's output for every row of the block, live
            # across the scan over the optimizer steps (`_block_trunk`)
            points = [p for p in self.model._capture_points() if p <= held]
            rows = max(int(self.config.method.num_rollouts) // self.data_ways(), 1)
            items.append(PlanItem(
                "train", "trunk_constants",
                int(len(points) * rows * S * cfg.hidden_size * cfg.residual_streams
                    * _dtype_size(train.compute_dtype)),
                f"the {held} frozen layers' output, held across the fused block: "
                f"{len(points)} capture(s) of every rollout",
            ))
        return items

    # -- controller state layered on the online-core hooks ---------------

    def _extra_fingerprint(self):
        """Consistency-watchdog extras: the online core's cursors plus
        the KL controller (host-side PPO state that MUST advance in
        lockstep across hosts)."""
        out = super()._extra_fingerprint()
        out["kl_ctl"] = float(self.kl_ctl.value)
        return out

    def _extra_state(self):
        state = super()._extra_state()
        state["kl_ctl_value"] = float(self.kl_ctl.value)
        state["mean_kl"] = float(self.mean_kl)
        return state

    def _restore_extra_state(self, state) -> None:
        if "kl_ctl_value" in state:
            self.kl_ctl.value = state["kl_ctl_value"]
        self.mean_kl = state.get("mean_kl", 0.0)
        super()._restore_extra_state(state)

    def post_backward_callback(self) -> None:
        # flush the deferred rollout stats first: they carry the mean KL
        # this controller update consumes (by now the async device->host
        # copy has landed under the train step, so this is a free read)
        super().post_backward_callback()
        self.kl_ctl.update(self.mean_kl, n_steps=self.config.train.batch_size)
