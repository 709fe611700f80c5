"""PPO numerics: GAE and the clipped surrogate objective.

Parity: /root/reference/trlx/models/modeling_ppo.py:136-238 — identical
math and stat keys; the reference's reversed Python loop over timesteps
becomes a `lax.scan` (single fused kernel, no per-step dispatch).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.ops.common import flatten_dict, get_tensor_stats, whiten


def gae_advantages_and_returns(
    values: jnp.ndarray,
    rewards: jnp.ndarray,
    gamma: float,
    lam: float,
    use_whitening: bool = True,
    axis_name: Optional[str] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Generalized advantage estimation over the response window.

    values, rewards: [batch, response_len] (rewards already include the
    per-token KL penalty). Returns (advantages, returns); advantages are
    whitened across the global batch and gradient-stopped.
    """
    resp_len = values.shape[1]
    next_values = jnp.concatenate(
        [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1
    )
    deltas = rewards + gamma * next_values - values  # [batch, T]

    def step(lastgaelam, delta_t):
        adv = delta_t + gamma * lam * lastgaelam
        return adv, adv

    # scan over time, reversed: carry is A_{t+1}
    _, advs = jax.lax.scan(
        step, jnp.zeros_like(deltas[:, 0]), deltas.T, reverse=True
    )
    advantages = advs.T  # [batch, T]
    returns = advantages + values
    if use_whitening:
        advantages = whiten(advantages, axis_name=axis_name)
    return jax.lax.stop_gradient(advantages), returns


@jax.named_scope("loss")
def ppo_loss(
    logprobs: jnp.ndarray,
    values: jnp.ndarray,
    old_logprobs: jnp.ndarray,
    old_values: jnp.ndarray,
    advantages: jnp.ndarray,
    returns: jnp.ndarray,
    mask: jnp.ndarray,
    cliprange: float,
    cliprange_value: float,
    vf_coef: float,
    is_weight: Optional[jnp.ndarray] = None,
    norm_n: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Clipped-ratio policy loss + clipped value loss, masked over real
    response tokens. All shapes [batch, response_len].

    ``is_weight`` is the experience transport's staleness correction
    (``exp.staleness.mode: clip``): a per-token CLIPPED importance
    weight rho = clip(pi_proximal/pi_behavior, 1±c) computed at chunk
    admission (IMPACT, arXiv:1912.00167 — ``old_logprobs`` are then the
    proximal recompute, and the behavior mismatch rides this factor).
    It multiplies only the policy surrogate; stop-gradiented, so it
    scales each token's objective without entering the ratio's
    gradient. None (the default and every fresh chunk) is exactly
    weight 1.

    ``norm_n`` overrides the mask-count normalizer (default: this
    call's own ``mask.sum()``). The memory doctor's microbatch split
    passes ``full_mask_total / num_mb`` so the mean over accumulated
    microbatches reproduces the unsplit step's ``sum/N_total`` EXACTLY
    even with ragged response masks — each microbatch normalizing by
    its own count would weight microbatches by 1/n_k instead."""
    mask = mask.astype(jnp.float32)
    n = jnp.maximum(mask.sum() if norm_n is None else norm_n, 1e-8)

    values_clipped = jnp.clip(
        values, old_values - cliprange_value, old_values + cliprange_value
    )
    vf_loss1 = (values - returns) ** 2
    vf_loss2 = (values_clipped - returns) ** 2
    vf_loss = 0.5 * (jnp.maximum(vf_loss1, vf_loss2) * mask).sum() / n
    vf_clipfrac = ((vf_loss2 > vf_loss1).astype(jnp.float32) * mask).sum() / n

    log_ratio = (logprobs - old_logprobs) * mask
    ratio = jnp.exp(log_ratio)
    # k3 estimator, http://joschu.net/blog/kl-approx.html
    approx_kl = jax.lax.stop_gradient(jnp.mean((ratio - 1) - log_ratio))

    w = 1.0 if is_weight is None else jax.lax.stop_gradient(
        is_weight.astype(jnp.float32)
    )
    pg_loss1 = -advantages * ratio * w
    pg_loss2 = -advantages * jnp.clip(ratio, 1.0 - cliprange, 1.0 + cliprange) * w
    pg_loss = (jnp.maximum(pg_loss1, pg_loss2) * mask).sum() / n
    pg_clipfrac = ((pg_loss2 > pg_loss1).astype(jnp.float32) * mask).sum() / n

    loss = pg_loss + vf_coef * vf_loss

    stats = dict(
        losses=dict(total_loss=loss, policy_loss=pg_loss, value_loss=vf_loss),
        values=dict(
            get_tensor_stats(values, mask, n),
            values_error=(((values - returns) * mask) ** 2).sum() / n,
            values_mape_error=(jnp.abs(values - returns) * mask
                               / jnp.abs(returns * mask + 1e-2)).sum() / n,
            clipfrac=vf_clipfrac,
        ),
        old_values=get_tensor_stats(old_values, mask, n),
        returns=get_tensor_stats(returns, mask, n),
        policy=dict(approx_kl=approx_kl, clipfrac=pg_clipfrac),
        ratio=(ratio * mask).sum() / n,
        padding_percentage=1.0 - n / mask.size,
    )
    return loss, flatten_dict(stats)
