"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

The reference has NO context parallelism — its long-context story is
Megatron sequence parallelism (activation sharding during norms) plus
activation checkpointing, capped at seq_length 2048
(SURVEY.md §2.7 row CP; configs/nemo_configs/megatron_20b.yaml:57). This
module is the TPU-native upgrade the survey calls for: each `sp` shard
holds one block of the sequence; K/V blocks rotate around the ring via
`ppermute` (ICI neighbor exchange) while every shard accumulates its
queries' attention with an online-softmax (flash-style m/l running
state). Peak memory per chip is O(T/sp · T/sp) instead of O(T²), and the
K/V transfer overlaps with the block matmuls.

`ring_attention` is the shard_map-aware primitive; `ring_attention_sharded`
wraps it for a [B, T, H, D] tensor sharded ('sp' on T) over a mesh.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attention(q, k, v, bias, m_prev, l_prev, o_prev):
    """One flash-attention accumulation step.

    q: [B, Tq, H, D], k/v: [B, Tk, H, D], bias: [B, 1, Tq, Tk] additive.
    Carries the running max (m), normalizer (l) and un-normalized output.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    s = s + bias
    # the softmax max-shift cancels analytically (d out / d m == 0), so the
    # running max is detached: without this, cotangents route through the
    # max/isfinite/exp chain and turn into NaN via inf*0 on fully-masked
    # (padding) rows
    m_cur = jax.lax.stop_gradient(jnp.max(s, axis=-1))  # [B, H, Tq]
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (all -inf): exp(-inf - -inf) -> keep finite
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])  # [B, H, Tq, Tk]
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_prev * alpha + p.sum(axis=-1)
    o_new = o_prev * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(p.dtype), preferred_element_type=jnp.float32
    )
    return m_new, l_new, o_new


def ring_attention(
    q: jnp.ndarray,  # [B, T_local, H, D] — this shard's queries
    k: jnp.ndarray,  # [B, T_local, H, D]
    v: jnp.ndarray,
    segment_mask: Optional[jnp.ndarray] = None,  # [B, T_local] 1 = real
    axis_name: str = "sp",
    causal: bool = True,
) -> jnp.ndarray:
    """Blockwise attention with K/V rotating around the `axis_name` ring.

    Must run inside shard_map/pmap with `axis_name` bound. Causality is
    enforced across blocks by comparing global positions (shard i holds
    positions [i*T_local, (i+1)*T_local))."""
    sp = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, T, H, D = q.shape
    q32 = q.astype(jnp.float32)

    q_pos = my * T + jnp.arange(T)  # global positions of local queries

    # derive the accumulators from q so they carry shard_map's
    # device-varying type (fresh constants would be typed as replicated
    # and fail the scan carry check); stop_gradient because they are
    # semantically constants — without it the backward pass routes
    # cotangents through `m0`'s -inf (inf * 0.0 = NaN in the q grads)
    qT = jax.lax.stop_gradient(q32.transpose(0, 2, 1, 3))  # [B, H, T, D]
    m0 = qT[..., 0] * 0.0 - jnp.inf
    l0 = qT[..., 0] * 0.0
    o0 = qT * 0.0
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(carry, step):
        k_blk, v_blk, mask_blk, m, l, o = carry
        src = (my - step) % sp  # which shard's block we now hold
        k_pos = src * T + jnp.arange(T)
        bias = jnp.zeros((B, 1, T, T), jnp.float32)
        if causal:
            bias = bias + jnp.where(
                q_pos[:, None] >= k_pos[None, :], 0.0, NEG_INF
            )[None, None]
        if mask_blk is not None:
            bias = bias + jnp.where(mask_blk[:, None, None, :] > 0, 0.0, NEG_INF)
        m, l, o = _block_attention(q32, k_blk, v_blk, bias, m, l, o)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        if mask_blk is not None:
            mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
        return (k_blk, v_blk, mask_blk, m, l, o), None

    carry = (k.astype(jnp.float32), v.astype(jnp.float32), segment_mask, m0, l0, o0)
    (k_f, v_f, _, m, l, o), _ = jax.lax.scan(body, carry, jnp.arange(sp))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, T, H, D]


def ring_attention_sharded(
    q: jnp.ndarray,  # [B, T, H, D] (global)
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    segment_mask: Optional[jnp.ndarray] = None,
    causal: bool = True,
) -> jnp.ndarray:
    """shard_map wrapper: sequence dim sharded over 'sp', batch over
    (dp, fsdp), heads over 'tp'."""
    spec_qkv = P(("dp", "fsdp"), "sp", "tp", None)
    spec_mask = P(("dp", "fsdp"), "sp")

    fn = partial(ring_attention, axis_name="sp", causal=causal)
    if segment_mask is None:
        sharded = jax.shard_map(
            lambda q_, k_, v_: fn(q_, k_, v_),
            mesh=mesh, in_specs=(spec_qkv,) * 3, out_specs=spec_qkv,
        )
        return sharded(q, k, v)
    sharded = jax.shard_map(
        lambda q_, k_, v_, m_: fn(q_, k_, v_, segment_mask=m_),
        mesh=mesh, in_specs=(spec_qkv,) * 3 + (spec_mask,), out_specs=spec_qkv,
    )
    return sharded(q, k, v, segment_mask)
