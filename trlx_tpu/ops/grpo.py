"""GRPO numerics: group-relative advantages and the critic-free
clipped surrogate (DeepSeekMath, arXiv:2402.03300).

GRPO keeps PPO's clipped importance-ratio objective (ops/ppo.py) but
replaces the learned critic with a Monte-Carlo baseline computed from a
GROUP of N samples per prompt: each sample's advantage is the z-score
of its reward within its group. No value head, no value loss, no GAE —
the whole value column of PPO's train-phase state disappears. The KL
regularizer moves from the reward (PPO's per-token penalty) into the
LOSS, estimated per token against the frozen reference with the same
k3 estimator ops/ppo.py uses (http://joschu.net/blog/kl-approx.html).

Both functions are pure and jittable: `grpo_loss` runs inside the same
fused-block `lax.scan` train path as `ppo_loss` (train.fused_inner_loop
— the scanned epoch machinery is loss-agnostic), and
`group_relative_advantages` is shape-polymorphic so the trainer can
call it on host numpy or device arrays.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops.common import flatten_dict, get_tensor_stats

# degenerate-group guard: a group whose rewards are (numerically) all
# equal carries no preference signal — its advantages are defined as
# exactly zero rather than 0/eps noise (or NaN at eps=0)
GROUP_STD_FLOOR = 1e-6


def group_relative_advantages(
    rewards: jnp.ndarray, group_size: int
) -> jnp.ndarray:
    """Per-group reward z-scores: ``(r - mean_g) / (std_g + 1e-6)``.

    ``rewards``: [batch] scalar rewards where rows ``i*group_size ...
    (i+1)*group_size - 1`` are the N samples of prompt ``i`` (the GRPO
    trainer tiles each pulled prompt ``group_size`` times, so group
    members are consecutive). ``batch`` must be a multiple of
    ``group_size``. ``std_g`` is the population (1/N) standard
    deviation. A degenerate group (std <= 1e-6 — all members scored
    equal) gets advantage exactly 0 for every member, not NaN.
    """
    if rewards.shape[0] % group_size:
        raise ValueError(
            f"rewards batch {rewards.shape[0]} is not a multiple of "
            f"group_size {group_size}"
        )
    r = rewards.astype(jnp.float32).reshape(-1, group_size)
    centered = r - r.mean(axis=1, keepdims=True)
    std = jnp.sqrt((centered**2).mean(axis=1, keepdims=True))
    adv = jnp.where(
        std > GROUP_STD_FLOOR,
        centered / (std + GROUP_STD_FLOOR),
        jnp.zeros_like(centered),
    )
    return adv.reshape(rewards.shape)


@jax.named_scope("loss")
def grpo_loss(
    logprobs: jnp.ndarray,
    old_logprobs: jnp.ndarray,
    ref_logprobs: jnp.ndarray,
    advantages: jnp.ndarray,
    mask: jnp.ndarray,
    cliprange: float,
    kl_coef: float,
    is_weight: Optional[jnp.ndarray] = None,
    norm_n: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Clipped-ratio policy loss with a sequence-level advantage and an
    in-loss KL regularizer against the frozen reference.

    logprobs / old_logprobs / ref_logprobs / mask: [batch, resp_len];
    advantages: [batch] (one group-relative z-score per SAMPLE,
    broadcast over its response tokens). ``old_logprobs`` are the
    behavior logprobs stored at collection; ``ref_logprobs`` the frozen
    reference's, fixed for the life of the rollout batch.

    The KL term is the k3 estimator of KL(pi || pi_ref) per token,
    differentiated through ``logprobs`` (parity with the GRPO paper's
    unbiased low-variance form): ``exp(ref - lp) - 1 - (ref - lp)``.

    ``is_weight`` is the experience transport's staleness correction
    (``exp.staleness.mode: clip``) — identical contract to
    ops/ppo.py: a stop-gradiented per-token clipped importance weight
    multiplying only the policy surrogate; None = weight 1.

    ``norm_n`` overrides the mask-count normalizer (same contract as
    ops/ppo.py: the memory doctor's microbatch split passes
    full_total/num_mb so the accumulated mean equals the unsplit
    step's normalization exactly with ragged masks).
    """
    mask = mask.astype(jnp.float32)
    n = jnp.maximum(mask.sum() if norm_n is None else norm_n, 1e-8)
    adv = jax.lax.stop_gradient(advantages.astype(jnp.float32))[:, None]

    log_ratio = (logprobs - old_logprobs) * mask
    ratio = jnp.exp(log_ratio)
    approx_kl = jax.lax.stop_gradient(jnp.mean((ratio - 1) - log_ratio))

    w = 1.0 if is_weight is None else jax.lax.stop_gradient(
        is_weight.astype(jnp.float32)
    )
    pg_loss1 = -adv * ratio * w
    pg_loss2 = -adv * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange) * w
    pg_loss = (jnp.maximum(pg_loss1, pg_loss2) * mask).sum() / n
    pg_clipfrac = ((pg_loss2 > pg_loss1).astype(jnp.float32) * mask).sum() / n

    # k3 KL(pi||ref) >= 0 per token; masked token-mean
    ref_log_ratio = (ref_logprobs - logprobs) * mask
    kl = (jnp.exp(ref_log_ratio) - 1 - ref_log_ratio) * mask
    kl_loss = kl.sum() / n

    loss = pg_loss + kl_coef * kl_loss

    stats = dict(
        losses=dict(total_loss=loss, policy_loss=pg_loss, kl_loss=kl_loss),
        advantages=get_tensor_stats(
            jnp.broadcast_to(adv, mask.shape), mask, n
        ),
        policy=dict(approx_kl=approx_kl, clipfrac=pg_clipfrac, ref_kl=kl_loss),
        ratio=(ratio * mask).sum() / n,
        padding_percentage=1.0 - n / mask.size,
    )
    return loss, flatten_dict(stats)
