"""DPO trainer: offline direct preference optimization
(Rafailov et al., arXiv:2305.18290).

Offline like ILQL/SFT: `trlx_tpu.train(samples=[(prompt, chosen,
rejected), ...], config=...)` builds a pairwise store
(pipeline/dpo_pipeline.py) and the per-step loop (or the fused scan)
minimizes the sigmoid preference loss over policy-vs-frozen-reference
logprob margins (ops/dpo.py). The frozen reference is a deep copy of
the INITIAL policy (with LoRA, the adapter-disabled base — the peft
DPO convention), captured at setup so the train step's buffer donation
can never alias it.

Each step runs chosen and rejected rows as ONE stacked forward (the
pair storage collates both sides to a shared static width), plus one
reference forward of the same shape whose gradient is never taken.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from trlx_tpu.data import DPOBatch
from trlx_tpu.data.method_configs import DPOConfig
from trlx_tpu.models.transformer import logit_projection
from trlx_tpu.models.wrappers import CausalLM
from trlx_tpu.ops.common import chunked_logprobs, logprobs_of_labels
from trlx_tpu.ops.dpo import dpo_loss
from trlx_tpu.ops.remat import resolve_remat
from trlx_tpu.parallel import shard_params
from trlx_tpu.pipeline.dpo_pipeline import DPOPairStorage
from trlx_tpu.trainer import register_trainer
from trlx_tpu.trainer.base import TPUBaseTrainer
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


@register_trainer("TPUDPOTrainer")
class TPUDPOTrainer(TPUBaseTrainer):
    def __init__(self, config, **kwargs):
        if not isinstance(config.method, DPOConfig):
            raise ValueError("config.method must be DPOConfig")
        super().__init__(config, **kwargs)

    def setup_model(self) -> None:
        if self.config.model.model_arch_type == "seq2seq":
            raise NotImplementedError("seq2seq DPO is not implemented (causal only)")
        self.seq2seq = False
        cfg, base_params, self.model_type = self.load_base_model()
        self.model = CausalLM(cfg)
        self.rng, key = jax.random.split(self.rng)
        params = self.attach_lora(self.model.init_params(key, base_params))
        self.params = shard_params(self.mesh, params)
        # frozen reference = the initial policy's base tree, DEEP-COPIED:
        # the train step donates self.params buffers every step, so the
        # reference must not alias them. With LoRA the adapter-disabled
        # base IS the reference (peft DPO convention) and stays frozen
        # for free — the copy still guards against donation.
        self.ref_params = jax.tree_util.tree_map(jnp.copy, self.params["base"])

    def trainable_mask(self):
        return self.lora_freeze_mask(self.params) or self.make_freeze_mask(self.params)

    def _sequence_logprobs(self, params, ref_params, ids, mask, resp_mask, remat):
        """Policy and frozen-reference summed response logprobs for one
        stacked [chosen; rejected] row block."""
        chunks = self.config.train.logit_chunks
        resp = resp_mask[:, 1:].astype(jnp.float32)
        out = self.model.forward(
            params, ids, mask, remat=remat, compute_logits=chunks == 0
        )
        ref_out = self.model.lm(
            ref_params, ids, mask, remat=remat, compute_logits=chunks == 0
        )
        if chunks:
            lp = chunked_logprobs(
                self.model.logit_project_fn(params),
                out["hidden_states"][:, :-1], ids[:, 1:], chunks,
            )
            ref_lp = chunked_logprobs(
                logit_projection(ref_params, self.model.lm.mesh),
                ref_out["hidden_states"][:, :-1], ids[:, 1:], chunks,
            )
        else:
            lp = logprobs_of_labels(out["logits"][:, :-1], ids[:, 1:])
            ref_lp = logprobs_of_labels(ref_out["logits"][:, :-1], ids[:, 1:])
        return (lp * resp).sum(axis=-1), (ref_lp * resp).sum(axis=-1)

    def loss(self, params, batch: DPOBatch):
        method = self.config.method
        remat = resolve_remat(self.config.train.remat_policy)
        B = batch.chosen_ids.shape[0]
        ids = jnp.concatenate([batch.chosen_ids, batch.rejected_ids], axis=0)
        mask = jnp.concatenate(
            [batch.chosen_attention_mask, batch.rejected_attention_mask], axis=0
        )
        resp = jnp.concatenate(
            [batch.chosen_response_mask, batch.rejected_response_mask], axis=0
        )
        seq_lp, ref_seq_lp = self._sequence_logprobs(
            params, self.ref_params, ids, mask, resp, remat
        )
        return dpo_loss(
            seq_lp[:B], seq_lp[B:], ref_seq_lp[:B], ref_seq_lp[B:],
            beta=method.beta, label_smoothing=method.label_smoothing,
        )

    def make_experience(
        self,
        samples: List,
        rewards: Optional[List[float]] = None,
        seq_length: int = 1024,
    ) -> None:
        """Build the pairwise store from (prompt, chosen, rejected)
        triples. ``rewards`` must be None — DPO's signal is the pair
        ordering itself (pass preference pairs, not scored samples)."""
        if rewards is not None:
            raise ValueError(
                "DPO takes no rewards: pass samples as (prompt, chosen, "
                "rejected) triples — the preference ordering IS the signal"
            )
        # hang doctor: tokenization is host-bound but can still wedge on
        # a slow/remote tokenizer backend — heartbeat it as its own phase
        with self.watchdog.phase("experience"):
            self.store = DPOPairStorage(
                samples, self.tokenizer, max_length=seq_length
            )

    def prepare_learning(self) -> None:
        self.eval_dataloader = self.eval_pipeline.create_loader(
            self.config.train.batch_size
        )
        self.n_inner_epochs = 1
        n_batches = len(self.store) // self.config.train.batch_size
        self.total_steps = min(
            self.config.train.epochs * max(n_batches, 1),
            self.config.train.total_steps,
        )

    def create_train_dataloader(self):
        return self.store.create_loader(
            self.config.train.batch_size, shuffle=True, drop_last=True,
            seed=self.config.train.seed + self.iter_count,
        )
