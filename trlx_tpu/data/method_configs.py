"""Method (algorithm) hyperparameter configs and their registry.

Parity: /root/reference/trlx/data/method_configs.py:9-56 (registry semantics),
/root/reference/trlx/models/modeling_ppo.py:73-238 (PPOConfig fields),
/root/reference/trlx/models/modeling_ilql.py:48-93 (ILQLConfig fields),
/root/reference/trlx/trainer/accelerate_sft_trainer.py:16-26 (SFTConfig),
/root/reference/trlx/trainer/accelerate_rft_trainer.py:18-44 (RFTConfig).

Unlike the reference, the loss functions themselves are pure jittable
functions in :mod:`trlx_tpu.ops`; the dataclasses here only carry
hyperparameters (and thin `.loss` delegates for API familiarity).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

_METHODS: Dict[str, type] = {}


def register_method(name_or_cls):
    """Register a method config class under a lowercase name (decorator).

    A duplicate name raises: two configs silently shadowing each other
    under one key is exactly the bug a registry exists to prevent.
    Re-registering the SAME class is a no-op (module reloads)."""

    def _register(cls, name: str):
        key = name.lower()
        existing = _METHODS.get(key)
        if existing is not None and (
            (existing.__module__, existing.__qualname__)
            != (cls.__module__, cls.__qualname__)
        ):
            raise ValueError(
                f"method config {name!r} is already registered to "
                f"{existing.__module__}.{existing.__qualname__}; refusing "
                "to overwrite it silently — pick a distinct name"
            )
        _METHODS[key] = cls
        return cls

    if isinstance(name_or_cls, str):
        return lambda cls: _register(cls, name_or_cls)
    return _register(name_or_cls, name_or_cls.__name__)


def get_method(name: str) -> type:
    try:
        return _METHODS[name.lower()]
    except KeyError:
        raise ValueError(
            f"Unknown method {name!r}; registered: {sorted(_METHODS)}"
        ) from None


def _fields_only(cls, config: Dict[str, Any]) -> Dict[str, Any]:
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = set(config) - known
    if unknown:
        raise ValueError(f"{cls.__name__}: unknown config keys {sorted(unknown)}")
    return {k: v for k, v in config.items() if k in known}


@dataclass
@register_method
class MethodConfig:
    """Base config for an RL method; `name` selects the registry entry."""

    name: str

    @classmethod
    def from_dict(cls, config: Dict[str, Any]):
        return cls(**_fields_only(cls, config))

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclass
@register_method
class PPOConfig(MethodConfig):
    """PPO hyperparameters (field parity with reference modeling_ppo.py:73-238)."""

    ppo_epochs: int = 4
    num_rollouts: int = 128
    chunk_size: int = 128
    init_kl_coef: float = 0.05
    target: Optional[float] = None
    horizon: int = 10000
    gamma: float = 1.0
    lam: float = 0.95
    cliprange: float = 0.2
    cliprange_value: float = 0.2
    vf_coef: float = 1.0
    scale_reward: Optional[str] = "ignored"
    ref_mean: Optional[float] = None
    ref_std: Optional[float] = None
    cliprange_reward: float = 10.0
    gen_kwargs: dict = field(default_factory=lambda: dict(max_new_tokens=40))
    gen_experience_kwargs: Optional[dict] = None
    num_value_layers_unfrozen: int = 0
    # Cycle-level rollout/optimization overlap: dispatch the first chunk
    # of cycle t+1's generation AHEAD of cycle t's fused optimization
    # block (device FIFO samples it first; the host decodes+scores it
    # while the block trains). The samples are one policy update stale,
    # which PPO's importance ratio absorbs — old_logprobs are recomputed
    # by the teacher-forced scorer with the params the optimization
    # epoch actually starts from, so the ratio stays self-consistent.
    # Preemption/resume cursors account for the in-flight chunk (it
    # rewinds if it never trains). Requires the scanned epoch path
    # (train.fused_inner_loop); off by default.
    overlap_rollouts: bool = False
    # Serving-grade rollout decode engine (models/gen_engine.py):
    # continuous batching over a paged int8 KV cache with optional
    # reference-drafted speculative decoding. Parsed by
    # gen_engine.GenEngineConfig (enabled/slots/page_size/paged/
    # pool_pages/refill_width/spec_decode/draft_k/kv_quant). Default {}
    # = disabled: rollouts keep the static whole-batch sampler. When
    # enabled, each generate() chunk runs through slot-based decode
    # (finished rows are refilled from the remaining prompts of the
    # chunk), and the engine's RNG is keyed per (prompt, position) —
    # sampled continuations differ from the static sampler's stream but
    # are invariant to slot assignment/batch composition (golden-checked
    # in tests/test_gen_engine.py). Composes with overlap_rollouts and
    # the preemption/rewind cursors unchanged: the engine sits behind
    # the same per-chunk generate() seam both already drive.
    gen_engine: dict = field(default_factory=dict)
    # Resilient experience transport (trlx_tpu/exp/): route rollout
    # chunks through a durable queue with at-least-once delivery —
    # lease-based production (an expired lease re-dispatches the chunk
    # to a live producer), consumer-side dedup, back-pressure past
    # exp.max_depth, a persisted consumer cursor (state.json, inside
    # the atomic checkpoint) and a staleness admission gate
    # (exp.staleness.mode: reject|clip, default reject at staleness>1;
    # clip threads IMPACT-style per-token importance weights into the
    # surrogate). Parsed by exp.queue.ExpConfig (enabled/max_depth/
    # lease_ttl_s/offer_timeout_s/wait_poll_s/staleness). Default {} =
    # disabled; enabled and fault-free it is golden-checked bit-equal
    # (losses + consumed prompt order) to the direct rollout path.
    # This is the substrate for the disaggregated actor-learner split
    # (ROADMAP item 1): remote producers plug in behind the same
    # transport the in-process loop chaos-proves.
    exp: dict = field(default_factory=dict)
    # Fault-tolerant rollout-worker fleet (trlx_tpu/fleet/): route
    # chunk PRODUCTION to cross-process workers behind the transport
    # seam — worker membership with heartbeat leases + membership
    # epochs (a restarted learner re-attaches surviving workers),
    # versioned weight broadcast with sha256 manifests (a corrupt
    # snapshot is rejected and the previous version kept; stale chunks
    # flow through exp.staleness), flap quarantine with doubling
    # backoff, and degraded-mode fallback to the in-process path (the
    # `fleet` guardrail signal) when live workers drop below
    # fleet.min_workers. Parsed by fleet.config.FleetConfig (enabled/
    # dir/min_workers/worker_ttl_s/flap_limit/...). Default {} =
    # disabled; requires ppo.exp.enabled; fault-free it is golden-
    # checked bit-equal to the in-process exp path.
    fleet: dict = field(default_factory=dict)

    def get_advantages_and_returns(self, values, rewards, response_length, use_whitening=True):
        from trlx_tpu.ops.ppo import gae_advantages_and_returns

        return gae_advantages_and_returns(
            values, rewards, gamma=self.gamma, lam=self.lam, use_whitening=use_whitening
        )

    def loss(self, logprobs, values, old_logprobs, old_values, advantages, returns, mask):
        from trlx_tpu.ops.ppo import ppo_loss

        return ppo_loss(
            logprobs, values, old_logprobs, old_values, advantages, returns, mask,
            cliprange=self.cliprange, cliprange_value=self.cliprange_value,
            vf_coef=self.vf_coef,
        )


@dataclass
@register_method
class GRPOConfig(MethodConfig):
    """GRPO hyperparameters (Group Relative Policy Optimization,
    arXiv:2402.03300): PPO's clipped surrogate with a critic-free
    group-relative advantage — ``group_size`` samples per prompt,
    advantage = per-group reward z-score (ops/grpo.py). No value head,
    no value loss, no critic optimizer state; the KL regularizer sits
    in the LOSS against the frozen reference (``kl_coef``) instead of
    riding the reward. The rollout engine — prompt stream, chunked
    generation, overlap prefetch, decode engine, experience transport,
    rollout fleet — is the shared online core (trainer.online.
    TPUOnlineTrainer): the ``overlap_rollouts`` / ``gen_engine`` /
    ``exp`` / ``fleet`` knobs below carry PPO's exact semantics
    (documented on PPOConfig)."""

    group_size: int = 8
    grpo_epochs: int = 4
    num_rollouts: int = 128
    # samples generated per chunk: chunk_size/group_size prompts are
    # pulled from the stream and each tiled group_size times, so every
    # group's members are consecutive rows of one chunk
    chunk_size: int = 128
    kl_coef: float = 0.001
    cliprange: float = 0.2
    scale_reward: Optional[str] = "ignored"
    ref_mean: Optional[float] = None
    ref_std: Optional[float] = None
    cliprange_reward: float = 10.0
    gen_kwargs: dict = field(default_factory=lambda: dict(max_new_tokens=40))
    gen_experience_kwargs: Optional[dict] = None
    overlap_rollouts: bool = False
    gen_engine: dict = field(default_factory=dict)
    exp: dict = field(default_factory=dict)
    fleet: dict = field(default_factory=dict)

    def __post_init__(self):
        if self.group_size < 2:
            raise ValueError(
                f"grpo.group_size must be >= 2 (got {self.group_size}): a "
                "group of one has no relative baseline"
            )
        if self.chunk_size % self.group_size:
            raise ValueError(
                f"grpo.chunk_size {self.chunk_size} must be divisible by "
                f"group_size {self.group_size} (whole groups per chunk)"
            )
        if self.num_rollouts % self.chunk_size:
            raise ValueError(
                f"grpo.num_rollouts {self.num_rollouts} must be divisible "
                f"by chunk_size {self.chunk_size}: a partial final chunk "
                "would split a group across cycles"
            )

    def loss(self, logprobs, old_logprobs, ref_logprobs, advantages, mask):
        from trlx_tpu.ops.grpo import grpo_loss

        return grpo_loss(
            logprobs, old_logprobs, ref_logprobs, advantages, mask,
            cliprange=self.cliprange, kl_coef=self.kl_coef,
        )


@dataclass
@register_method
class DPOConfig(MethodConfig):
    """DPO hyperparameters (Direct Preference Optimization,
    arXiv:2305.18290): offline sigmoid preference loss over
    policy-vs-frozen-reference logprob margins on (prompt, chosen,
    rejected) pairs. ``beta`` scales the implicit reward;
    ``label_smoothing`` is the conservative-DPO flip probability."""

    beta: float = 0.1
    label_smoothing: float = 0.0
    gen_kwargs: dict = field(default_factory=lambda: dict(max_new_tokens=40))

    def __post_init__(self):
        if self.beta <= 0:
            raise ValueError(f"dpo.beta must be > 0 (got {self.beta})")
        if not 0.0 <= self.label_smoothing < 0.5:
            raise ValueError(
                "dpo.label_smoothing must be in [0, 0.5) (got "
                f"{self.label_smoothing}): past 0.5 the labels invert"
            )

    def loss(self, policy_chosen, policy_rejected, ref_chosen, ref_rejected):
        from trlx_tpu.ops.dpo import dpo_loss

        return dpo_loss(
            policy_chosen, policy_rejected, ref_chosen, ref_rejected,
            beta=self.beta, label_smoothing=self.label_smoothing,
        )


@dataclass
@register_method
class ILQLConfig(MethodConfig):
    """ILQL hyperparameters (field parity with reference modeling_ilql.py:48-93)."""

    tau: float = 0.7
    gamma: float = 0.99
    cql_scale: float = 0.1
    awac_scale: float = 1.0
    alpha: float = 0.001
    beta: float = 0.0
    steps_for_target_q_sync: int = 5
    two_qs: bool = True
    gen_kwargs: dict = field(default_factory=lambda: dict(max_new_tokens=56, top_k=20, beta=1.0))

    def loss(self, outputs, labels):
        from trlx_tpu.ops.ilql import ilql_loss

        logits, (qs, target_qs, vs) = outputs
        return ilql_loss(
            logits, qs, target_qs, vs, labels,
            tau=self.tau, gamma=self.gamma, cql_scale=self.cql_scale,
            awac_scale=self.awac_scale, beta=self.beta, two_qs=self.two_qs,
        )


@dataclass
@register_method
class SFTConfig(MethodConfig):
    """SFT hyperparameters (parity: accelerate_sft_trainer.py:16-26)."""

    gen_kwargs: dict = field(default_factory=lambda: dict(max_new_tokens=40))


@dataclass
@register_method
class RFTConfig(MethodConfig):
    """Rejection-sampling fine-tuning (parity: accelerate_rft_trainer.py:18-44)."""

    gen_kwargs: dict = field(default_factory=lambda: dict(max_new_tokens=40))
    start_percentile: float = 0.7
    end_percentile: float = 0.95
    n_improve_steps: int = 4
    n_generations_per_prompt: int = 32
