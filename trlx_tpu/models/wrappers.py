"""Model wrappers: causal LM + value head (PPO, with in-process frozen
reference branch) and causal LM + ILQL heads.

Parity: /root/reference/trlx/models/modeling_ppo.py:244-499
(`AutoModelForCausalLMWith{Value,HydraValue}Head`) and
modeling_ilql.py:262-479 (`AutoModelForCausalLMWithILQLHeads`). The
reference's per-architecture `ModelBranch` classes (modeling_ppo.py:502-1637)
are unnecessary here: the frozen reference branch is a slice of the stacked
layer stack re-run from the captured hidden state
(`TransformerLM.forward_with_branch_capture` / `forward_from_layer`).

Wrappers are functional: `params` trees in, activation dicts out, so the
trainers can jit/shard/donate them directly.

LoRA: when a params tree carries a "lora" overlay ({path: {a, b}}, see
trlx_tpu.models.lora), `_effective_base` merges it onto a
gradient-stopped base — so only the adapters (and heads) train, matching
the reference's peft contract (tests/test_peft.py: backprop touches
adapters only; the reference model is the disabled-adapter forward).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.models.heads import (
    apply_head,
    apply_ilql_heads,
    init_head,
    init_ilql_heads,
    sync_target_q_heads,
)
from trlx_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    extract_branch_params,
    logit_projection,
)

Array = jnp.ndarray


def _effective_base(wrapper, params: Dict) -> Dict:
    """Resolve the base param tree, merging a LoRA overlay if present.
    With any peft adapter the base is stop-gradiented: only the adapter
    (+ heads) trains, and the backward never materializes base grads."""
    if "lora" in params:
        from trlx_tpu.models.lora import merge_lora

        return merge_lora(
            jax.lax.stop_gradient(params["base"]), params["lora"],
            getattr(wrapper, "lora_scaling", 1.0),
        )
    if "prompt" in params or "prefix" in params:
        return jax.lax.stop_gradient(params["base"])
    return params["base"]


def _adapter_kwargs(params: Dict) -> Dict:
    """Prompt/prefix adapter kwargs for TransformerLM.__call__."""
    from trlx_tpu.models.peft import adapter_call_kwargs

    return adapter_call_kwargs(params)


class CausalLM:
    """Bare causal LM wrapper (SFT/RFT path — no auxiliary heads)."""

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.lm = TransformerLM(cfg)

    def init_params(self, rng: jax.Array, base_params: Optional[Dict] = None) -> Dict:
        if base_params is None:
            base_params = self.lm.init(rng)
        return {"base": base_params}

    def forward(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Optional[Array] = None,
        remat: bool = False,
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        return self.lm(
            _effective_base(self, params), input_ids, attention_mask,
            remat=remat, compute_logits=compute_logits,
            **_adapter_kwargs(params),
        )

    def logit_project_fn(self, params: Dict):
        """hidden -> logits closure for chunked-from-hidden losses
        (`ops.common.chunked_logprobs`); resolves any LoRA overlay so the
        projection matches the forward's effective weights."""
        return logit_projection(_effective_base(self, params))


class CausalLMWithValueHead:
    """Policy LM + scalar value head; optional hydra reference branch.

    `branch_at` (= n_layer - num_layers_unfrozen) picks where the frozen
    reference branch forks off. With `branch_at is None` (all layers
    unfrozen) PPO needs a full frozen copy of the params as reference —
    the trainer keeps that copy and calls `forward_ref_full`.
    """

    def __init__(
        self,
        cfg: TransformerConfig,
        branch_at: Optional[int] = None,
        value_branch_at: Optional[int] = None,
    ):
        self.cfg = cfg
        self.lm = TransformerLM(cfg)
        self.branch_at = branch_at
        # value branch: a separate TRAINABLE copy of the top layers feeding
        # the value head (reference make_value_branch /
        # num_value_layers_unfrozen, modeling_ppo.py:255-263)
        self.value_branch_at = value_branch_at

    # -- params ----------------------------------------------------------

    def init_params(self, rng: jax.Array, base_params: Optional[Dict] = None) -> Dict:
        r_base, r_head = jax.random.split(rng)
        if base_params is None:
            base_params = self.lm.init(r_base)
        params = {
            "base": base_params,
            "v_head": init_head(r_head, self.cfg.hidden_size, 1),
        }
        if self.value_branch_at is not None:
            # the same slice a reference branch takes (layers of the main
            # kind, above any leading dense ones), trainable
            branch = extract_branch_params(base_params, self.value_branch_at, self.cfg)
            params["v_branch"] = jax.tree_util.tree_map(
                jnp.copy, {k: v for k, v in branch.items() if k not in ("embed", "lm_head")}
            )
        return params

    @jax.named_scope("value_head")
    def _values(self, params: Dict, out: Dict) -> Array:
        """Value head input: final hidden, or the value branch re-run from
        its captured fork point."""
        if self.value_branch_at is None:
            return apply_head(params["v_head"], out["hidden_states"])[..., 0]
        h = out["v_branch_hidden"]
        ring = None
        if out["attn_bias"] is None:  # ring-attention trunk pass
            ring = self.lm._ring_mesh(h.shape[0], h.shape[1], None)
        h, _, _ = self.lm._run_layers(
            params["v_branch"], h, self.value_branch_at, self.cfg.n_layer,
            out["attn_bias"], out["positions"],
            local_bias=out.get("local_bias"),
            key_mask=out.get("key_mask"), ring_mesh=ring,
        )
        hidden = self.lm._final_hidden(params["v_branch"]["ln_f"], h)
        return apply_head(params["v_head"], hidden)[..., 0]

    def make_ref_params(self, params: Dict) -> Dict:
        """Frozen reference: the top branch only (hydra) or the full tree.

        Deep-copied: the trainer donates `params` buffers every step, so
        the reference must not alias them."""
        if self.branch_at is not None:
            branch = extract_branch_params(params["base"], self.branch_at, self.cfg)
        else:
            branch = jax.lax.stop_gradient(params["base"])
        return jax.tree_util.tree_map(jnp.copy, branch)

    # -- forwards --------------------------------------------------------

    def _capture_points(self):
        points = set()
        if self.branch_at is not None:
            points.add(self.branch_at)
        if self.value_branch_at is not None:
            points.add(self.value_branch_at)
        return tuple(sorted(points))

    def frozen_below(self) -> int:
        """Index of the first trunk layer that trains, as
        `make_freeze_mask` has it: the trunk under `branch_at` is frozen
        wherever the value branch forks (it trains its own copy,
        `params["v_branch"]`). 0 = the backward runs the whole trunk:
        all layers train, a peft adapter (`setup_model` leaves
        `branch_at` None), or an embedding LayerNorm, which trains
        under every layer. The layers under it run once an optimizer step
        in the per-step program and once a BLOCK in the fused one
        (`trunk_layers_held`)."""
        if self.branch_at is None or self.cfg.embed_layernorm:
            return 0
        return self.branch_at

    def trunk_layers_held(self) -> int:
        """Layers of the trunk whose output a fused block computes once
        and holds across its optimizer steps (`trunk_constants`): the
        frozen trunk up to the branch point, 0 where the step keeps the
        whole forward (`frozen_below()` 0, `pp > 1`, a ring mesh)."""
        return self.lm.resume_point(self._capture_points(), self.frozen_below())

    def trunk_constants(self, params, input_ids, attention_mask):
        """The frozen trunk's output for these rows, for `forward_train`'s
        `trunk` (`TransformerLM.trunk_constants`); None where
        `trunk_layers_held()` is 0."""
        return self.lm.trunk_constants(
            _effective_base(self, params), input_ids, attention_mask,
            self._capture_points(), self.frozen_below(),
        )

    def _multi_forward(self, params, input_ids, attention_mask, remat,
                       compute_logits=True, trunk=None):
        """Trunk pass capturing hydra and/or value-branch fork hiddens
        (resumed above the held captures where `trunk` gives them)."""
        base = _effective_base(self, params)
        points = self._capture_points()
        out = self.lm.forward_with_multi_capture(
            base, input_ids, attention_mask, points, remat=remat,
            compute_logits=compute_logits, frozen_below=self.frozen_below(),
            trunk=trunk,
        )
        named = dict(zip(points, out["captures"]))
        if self.branch_at is not None:
            out["branch_hidden"] = named[self.branch_at]
        if self.value_branch_at is not None:
            out["v_branch_hidden"] = named[self.value_branch_at]
        return out

    def forward(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Optional[Array] = None,
        remat: bool = False,
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        if self.value_branch_at is None:
            out = self.lm(
                _effective_base(self, params), input_ids, attention_mask,
                remat=remat, compute_logits=compute_logits,
                **_adapter_kwargs(params),
            )
        else:
            out = self._multi_forward(
                params, input_ids, attention_mask, remat, compute_logits
            )
        return dict(out, values=self._values(params, out))

    def logit_project_fn(self, params: Dict):
        """hidden -> logits closure for chunked-from-hidden losses
        (`ops.common.chunked_logprobs`); resolves any LoRA overlay so the
        projection matches the forward's effective weights."""
        base = _effective_base(self, params)
        if self.frozen_below():
            # a tied head reads the (frozen) embedding
            base = dict(base, embed=jax.lax.stop_gradient(base["embed"]))
        return logit_projection(base)

    def forward_train(
        self,
        params: Dict,
        ref_params: Dict,
        input_ids: Array,
        attention_mask: Optional[Array] = None,
        remat: bool = False,
        compute_logits: bool = True,
        trunk=None,
    ) -> Dict[str, Array]:
        """One pass producing policy logits, values AND reference logits.

        Hydra mode shares the trunk below `branch_at` between policy and
        reference (the whole point of the reference's hydra heads —
        modeling_ppo.py:410-453 — done here with an array slice instead of
        six per-arch branch classes). `trunk`: these rows'
        `trunk_constants`, where a fused block holds them; the pass then
        resumes at the branch point.

        `compute_logits=False` (train.logit_chunks) skips BOTH full-vocab
        projections; `ref_hidden` is always returned so chunked losses can
        project the reference's logprobs themselves.
        """
        if self.branch_at is None:
            out = self.forward(
                params, input_ids, attention_mask, remat=remat,
                compute_logits=compute_logits,
            )
            with jax.named_scope("ref_branch"):
                ref_out = self.lm(
                    ref_params, input_ids, attention_mask, remat=remat,
                    compute_logits=compute_logits,
                )
        else:
            out = self._multi_forward(
                params, input_ids, attention_mask, remat, compute_logits, trunk
            )
            out["values"] = self._values(params, out)
            with jax.named_scope("ref_branch"):
                ref_out = self.lm.forward_from_layer(
                    ref_params,
                    jax.lax.stop_gradient(out["branch_hidden"]),
                    out["attn_bias"],
                    out["positions"],
                    remat=remat,
                    local_bias=out.get("local_bias"),
                    key_mask=out.get("key_mask"),
                    compute_logits=compute_logits,
                )
        # a routed reference branch's counters stay apart from the policy's:
        # the scorer adds them, the train step reads neither them nor any
        # other output of the branch, which is then dead code there
        ref_stats = ref_out.get("moe_stats")
        return dict(
            out,
            ref_logits=(
                jax.lax.stop_gradient(ref_out["logits"])
                if compute_logits else None
            ),
            ref_hidden=jax.lax.stop_gradient(ref_out["hidden_states"]),
            **({"ref_moe_stats": ref_stats} if ref_stats else {}),
        )


class Seq2SeqLMWithValueHead:
    """Encoder-decoder policy + value head over decoder hidden states;
    optional frozen top-decoder reference branch.

    Parity: reference `AutoModelForSeq2SeqLMWith{Value,HydraValue}Head`
    (modeling_ppo.py:1242-1480) + the frozen `T5Branch` (:1483-1592).
    """

    def __init__(self, cfg, branch_at: Optional[int] = None):
        from trlx_tpu.models.seq2seq import T5LM

        self.cfg = cfg
        self.lm = T5LM(cfg)
        self.branch_at = branch_at

    def init_params(self, rng: jax.Array, base_params: Optional[Dict] = None) -> Dict:
        r_base, r_head = jax.random.split(rng)
        if base_params is None:
            base_params = self.lm.init(r_base)
        return {
            "base": base_params,
            "v_head": init_head(r_head, self.cfg.d_model, 1),
        }

    def frozen_below(self) -> int:
        """Index of the first decoder layer that trains, as
        `make_seq2seq_freeze_mask` has it (0 = the backward runs every
        layer, the encoder's too)."""
        return self.branch_at or 0

    def make_ref_params(self, params: Dict) -> Dict:
        from trlx_tpu.models.seq2seq import extract_t5_branch_params

        if self.branch_at is not None:
            return extract_t5_branch_params(params["base"], self.branch_at)
        return jax.tree_util.tree_map(
            jnp.copy, jax.lax.stop_gradient(params["base"])
        )

    def forward(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Array,
        decoder_input_ids: Array,
        decoder_attention_mask: Optional[Array] = None,
        remat: bool = False,
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        out = self.lm(
            _effective_base(self, params), input_ids, attention_mask,
            decoder_input_ids, decoder_attention_mask, remat=remat,
            compute_logits=compute_logits,
        )
        values = apply_head(params["v_head"], out["hidden_states"])[..., 0]
        return dict(out, values=values)

    def logit_project_fn(self, params: Dict):
        """hidden -> logits closure for chunked-from-hidden losses."""
        from trlx_tpu.models.seq2seq import t5_logit_projection

        base = _effective_base(self, params)
        if self.frozen_below():
            # a tied head reads the (frozen) shared embedding
            base = dict(base, shared=jax.lax.stop_gradient(base["shared"]))
        return t5_logit_projection(base, self.cfg)

    def forward_train(
        self,
        params: Dict,
        ref_params: Dict,
        input_ids: Array,
        attention_mask: Array,
        decoder_input_ids: Array,
        decoder_attention_mask: Optional[Array] = None,
        remat: bool = False,
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        if self.branch_at is None:
            out = self.forward(
                params, input_ids, attention_mask, decoder_input_ids,
                decoder_attention_mask, remat=remat,
                compute_logits=compute_logits,
            )
            with jax.named_scope("ref_branch"):
                ref_out = self.lm(
                    ref_params, input_ids, attention_mask, decoder_input_ids,
                    decoder_attention_mask, remat=remat,
                    compute_logits=compute_logits,
                )
        else:
            out = self.lm.forward_with_branch_capture(
                params["base"], input_ids, attention_mask, decoder_input_ids,
                decoder_attention_mask, self.branch_at, remat=remat,
                compute_logits=compute_logits,
                frozen_below=self.frozen_below(),
            )
            with jax.named_scope("value_head"):
                out["values"] = apply_head(params["v_head"], out["hidden_states"])[..., 0]
            with jax.named_scope("ref_branch"):
                ref_out = self.lm.forward_from_layer(
                    ref_params,
                    jax.lax.stop_gradient(out["branch_hidden"]),
                    out["self_bias"],
                    jax.lax.stop_gradient(out["encoder_hidden"]),
                    out["cross_bias"],
                    remat=remat,
                    compute_logits=compute_logits,
                    pos_bias=out.get("pos_bias"),
                    skey_mask=out.get("skey_mask"),
                    ckey_mask=out.get("ckey_mask"),
                )
        return dict(
            out,
            ref_logits=(
                jax.lax.stop_gradient(ref_out["logits"])
                if compute_logits else None
            ),
            ref_hidden=jax.lax.stop_gradient(ref_out["hidden_states"]),
        )


class Seq2SeqLMWithILQLHeads:
    """Encoder-decoder LM + ILQL head group over DECODER hidden states
    (parity: reference AutoModelForSeq2SeqLMWithILQLHeads,
    modeling_ilql.py:481-666)."""

    def __init__(self, cfg, two_qs: bool = True, alpha: float = 0.001):
        from trlx_tpu.models.seq2seq import T5LM

        self.cfg = cfg
        self.lm = T5LM(cfg)
        self.two_qs = two_qs
        self.alpha = alpha

    def init_params(self, rng: jax.Array, base_params: Optional[Dict] = None) -> Dict:
        r_base, r_heads = jax.random.split(rng)
        if base_params is None:
            base_params = self.lm.init(r_base)
        return {
            "base": base_params,
            "heads": init_ilql_heads(
                r_heads, self.cfg.d_model, self.cfg.vocab_size, self.two_qs
            ),
        }

    def forward(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Array,
        decoder_input_ids: Array,
        states_ixs: Array,
        actions_ixs: Array,
        remat: bool = False,
    ) -> Tuple[Array, Tuple]:
        from trlx_tpu.models.seq2seq import t5_logit_projection
        from trlx_tpu.ops.common import batched_index_select

        base = _effective_base(self, params)
        # the loss only needs logits AT the action positions: gather the
        # hidden rows first, then project — [B, A, V] instead of [B, T, V]
        # (identical math; the vocab matmul runs on A rows, not T)
        out = self.lm(
            base, input_ids, attention_mask,
            decoder_input_ids, remat=remat, compute_logits=False,
        )
        qs, target_qs, vs = apply_ilql_heads(
            params["heads"], out["hidden_states"], states_ixs, actions_ixs
        )
        h_at = batched_index_select(out["hidden_states"], actions_ixs, dim=1)
        logits_at_actions = t5_logit_projection(base, self.cfg)(h_at)
        return logits_at_actions, (qs, target_qs, vs)

    def sync_target(self, params: Dict, alpha: Optional[float] = None) -> Dict:
        return dict(
            params,
            heads=sync_target_q_heads(
                params["heads"], self.alpha if alpha is None else alpha
            ),
        )

    def make_logits_processor(self, params_heads: Dict, beta: float):
        from trlx_tpu.ops.ilql import ilql_shape_logits

        def processor(hidden_last: Array, logits_last: Array) -> Array:
            qs = [apply_head(h, hidden_last) for h in params_heads["target_q_heads"]]
            vs = apply_head(params_heads["v_head"], hidden_last)
            return ilql_shape_logits(logits_last, qs, vs, beta)

        return processor


class CausalLMWithILQLHeads:
    """Causal LM + ILQL head group (v, q, frozen target q).

    Parity: modeling_ilql.py:262-479; generation-time advantage shaping is
    a `logits_processor` for trlx_tpu.models.generation (built by
    `make_ilql_logits_processor`).
    """

    def __init__(self, cfg: TransformerConfig, two_qs: bool = True, alpha: float = 0.001):
        self.cfg = cfg
        self.lm = TransformerLM(cfg)
        self.two_qs = two_qs
        self.alpha = alpha

    def init_params(self, rng: jax.Array, base_params: Optional[Dict] = None) -> Dict:
        r_base, r_heads = jax.random.split(rng)
        if base_params is None:
            base_params = self.lm.init(r_base)
        return {
            "base": base_params,
            "heads": init_ilql_heads(
                r_heads, self.cfg.hidden_size, self.cfg.vocab_size, self.two_qs
            ),
        }

    def forward(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Optional[Array],
        states_ixs: Array,
        actions_ixs: Array,
        remat: bool = False,
    ) -> Tuple[Array, Tuple]:
        """Returns (logits_at_actions, (qs, target_qs, vs)) — the shape the
        ILQL loss consumes (trlx_tpu.ops.ilql.ilql_loss)."""
        from trlx_tpu.ops.common import batched_index_select

        base = _effective_base(self, params)
        # the loss only needs logits AT the action positions: gather the
        # hidden rows first, then project — [B, A, V] instead of [B, T, V]
        # (identical math; the vocab matmul runs on A rows, not T)
        out = self.lm(
            base, input_ids, attention_mask,
            remat=remat, compute_logits=False, **_adapter_kwargs(params),
        )
        qs, target_qs, vs = apply_ilql_heads(
            params["heads"], out["hidden_states"], states_ixs, actions_ixs
        )
        h_at = batched_index_select(out["hidden_states"], actions_ixs, dim=1)
        logits_at_actions = logit_projection(base)(h_at)
        return logits_at_actions, (qs, target_qs, vs)

    def sync_target(self, params: Dict, alpha: Optional[float] = None) -> Dict:
        return dict(
            params,
            heads=sync_target_q_heads(
                params["heads"], self.alpha if alpha is None else alpha
            ),
        )

    def make_logits_processor(self, params_heads: Dict, beta: float):
        """Advantage shaping `log pi_beta + beta * (minQ - V)` for the
        jitted decode loop (parity: modeling_ilql.py:365-374)."""
        from trlx_tpu.ops.ilql import ilql_shape_logits

        def processor(hidden_last: Array, logits_last: Array) -> Array:
            qs = [apply_head(h, hidden_last) for h in params_heads["target_q_heads"]]
            vs = apply_head(params_heads["v_head"], hidden_last)
            return ilql_shape_logits(logits_last, qs, vs, beta)

        return processor
