"""Pallas fused causal attention for TPU — forward AND backward.

The reference leans on flash/fused attention inside its native deps
(SURVEY.md §2.9 last row — NeMo/HF kernels). Here the fused kernel is
first-party Pallas: per (batch*head, q-block) grid cell, scores are
computed against key/value *chunks* with an online softmax, so VMEM
holds only [block_q, chunk] tiles — the [B, H, T, S] probability tensor
never exists anywhere, which is the HBM-bandwidth win on TPU (the MXU
runs the two matmuls back to back from VMEM).

Backward is fused too (flash-style): the forward emits per-row softmax
stats, and two pallas kernels recompute probabilities chunkwise from
(q, k, m, l) to produce dq and (dk, dv). This is what makes 8k+ token
*training* practical: an XLA recompute path spills a multi-GB score
tensor per layer.

The softmax stats are saved as (m, l) SEPARATELY, not lse = m + log l:
fully-masked rows (pure-padding queries) have m = NEG_INF and the fp32
sum would absorb log(l), breaking the backward's probability
reconstruction. With (m, l), p = exp(s - m) / l reproduces the
forward's uniform distribution on those rows exactly, and ds is zeroed
at masked entries so gradients match the XLA where()-mask reference.

Enable with `TransformerConfig(attention_impl="pallas")`; CPU tests run
the kernels in interpreter mode automatically.

VMEM budget: full-length K/V (or Q/dO) rows live in VMEM in bf16
(~1 MB per 8k tokens at D=64) while fp32 tiles are [block, chunk] —
bounded regardless of sequence length. Sequences beyond ~32k tokens
should shard the sequence instead (ring attention,
ops/ring_attention.py)."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
import numpy as np
from jax.experimental import pallas as pl

from trlx_tpu.ops.common import interpret_mode as _interpret
from trlx_tpu.ops.common import pick_block as _pick_block

NEG_INF = -1e30
# key/query chunk for the in-kernel loops: each fp32 score tile is
# [block, CHUNK]. 1024 runs the 8k fwd+bwd ~3x faster than 512 on v5e
# (better MXU occupancy per DMA) while keeping tiles ~1 MB in VMEM.
CHUNK = 1024


def _attention_reference(q, k, v, key_mask, causal: bool, sm_scale: float):
    """Plain XLA attention (numerics oracle for tests). Accepts GQA
    shapes (k/v with fewer heads) by repeating kv heads — the same thing
    transformer.py's XLA path does."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    T, S = s.shape[-2], s.shape[-1]
    if causal:
        qi = jnp.arange(T)[:, None] + (S - T)
        s = jnp.where(qi >= jnp.arange(S)[None, :], s, NEG_INF)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _tile_valid(bq, ck, row0, col0, causal):
    """validity of a [bq, ck] score tile whose global top-left is
    (row0, col0) in causal coordinates (rows already q_offset-shifted)."""
    if not causal:
        return jnp.ones((bq, ck), jnp.bool_)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, ck), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, ck), 1)
    return rows >= cols


def _flash_kernel(
    q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref,
    *, sm_scale, causal, q_offset, n_chunks, ck,
):
    bq = q_ref.shape[1]
    D = v_ref.shape[2]  # the output is as wide as the values (keys may differ)
    q = q_ref[0].astype(jnp.float32)  # [Bq, Dk]
    row0 = pl.program_id(1) * bq + q_offset

    def body(j, carry):
        o_acc, m_run, l_run = carry
        k_c = k_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)  # [ck, D]
        v_c = v_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)
        mk = mask_ref[0, 0, pl.ds(j * ck, ck)]  # [ck]
        s = jax.lax.dot_general(
            q, k_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [Bq, ck]
        valid = _tile_valid(bq, ck, row0, j * ck, causal) & (mk[None, :] > 0)
        s = jnp.where(valid, s, NEG_INF)

        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)  # [Bq, ck]
        l_new = l_run * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o_acc * corr + jax.lax.dot_general(
            p, v_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_chunks, body, (o0, m0, l0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    m_ref[0] = m
    l_ref[0] = l


def _kv_head_index(H: int, Hkv: int):
    """Grid-id -> kv row map for [B*Hkv, S, D] k/v arrays when the grid
    runs over B*H query heads: query head h reads kv head h // (H//Hkv)
    (grouped-query attention; identity when Hkv == H)."""
    rep = H // Hkv

    def ix(bh, qi):
        return ((bh // H) * Hkv + (bh % H) // rep, 0, 0)

    return ix


def _flash_forward(q, k, v, key_mask, causal, sm_scale, block_q,
                   with_stats=False, q_offset=None):
    B, H, T, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    if H % Hkv:
        raise ValueError(f"n_head={H} not a multiple of n_kv_head={Hkv}")
    if q_offset is None:
        q_offset = S - T  # right-aligned queries (teacher-forced default)
    if key_mask is None:
        key_mask = jnp.ones((B, S), jnp.int32)
    bq = _pick_block(T, block_q)
    ck = _pick_block(S, CHUNK)
    grid = (B * H, T // bq)

    qr = q.reshape(B * H, T, D)
    # GQA: k/v stay at Hkv heads — never materialized repeated; the
    # BlockSpec index map routes each q head's grid cells to its group's
    # kv rows, so HBM reads per kv head happen once per GROUP, which is
    # the bandwidth saving GQA exists for
    kr = k.reshape(B * Hkv, S, D)
    vr = v.reshape(B * Hkv, S, Dv)
    kv_ix = _kv_head_index(H, Hkv)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, q_offset=q_offset,
        n_chunks=S // ck, ck=ck,
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), kv_ix),
            pl.BlockSpec((1, S, Dv), kv_ix),
            # [B, 1, S] so the block's trailing two dims (1, S) equal the
            # array dims — Mosaic requires trailing block dims divisible
            # by (8, 128) OR equal to the array's (a bare (1, S) block
            # over [B, S] fails to lower on real TPU)
            pl.BlockSpec((1, 1, S), lambda bh, qi: (bh // H, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(qr, kr, vr, key_mask.astype(jnp.int32)[:, None, :])
    out = out.reshape(B, H, T, Dv)
    if with_stats:
        return out, m, l
    return out


def _dq_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, m_ref, l_ref, delta_ref, dq_ref,
    *, sm_scale, causal, q_offset, n_chunks, ck,
):
    bq = q_ref.shape[1]
    D = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32)  # [Bq, D]
    do = do_ref[0].astype(jnp.float32)  # [Bq, D]
    m = m_ref[0]  # [Bq, 1]
    l = jnp.maximum(l_ref[0], 1e-30)
    delta = delta_ref[0]  # [Bq, 1]
    row0 = pl.program_id(1) * bq + q_offset

    def body(j, dq_acc):
        k_c = k_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)  # [ck, D]
        v_c = v_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)
        mk = mask_ref[0, 0, pl.ds(j * ck, ck)]
        s = jax.lax.dot_general(
            q, k_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [Bq, ck]
        valid = _tile_valid(bq, ck, row0, j * ck, causal) & (mk[None, :] > 0)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - m) / l  # [Bq, ck]
        dp = jax.lax.dot_general(
            do, v_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Bq, ck]
        # masked entries carry no gradient into s (the reference's
        # where() routes their cotangent to the NEG_INF constant);
        # explicit zeroing matters on fully-masked rows where p is
        # uniform, not ~0
        ds = jnp.where(valid, p * (dp - delta) * sm_scale, 0.0)
        return dq_acc + jax.lax.dot_general(
            ds, k_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, n_chunks, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, mask_ref, do_ref, m_ref, l_ref, delta_ref, dk_ref, dv_ref,
    *, sm_scale, causal, q_offset, n_chunks, cq, q_chunks_per_head,
):
    """dk/dv for one key block. Works in TRANSPOSED orientation
    ([Bk, cq] score tiles) so the per-row stats stream in lane-major
    [1, T] layout — a [T, 1] operand would be lane-padded to [T, 128]
    in VMEM (4 MB per stat at 8k tokens), which blows the budget.

    GQA: the grid runs over B*Hkv and the q/do/stat refs carry the whole
    GROUP's rows ([rep*T] where rep = n_head // n_kv_head, heads
    contiguous), so each group member's contribution accumulates into
    the same (dk, dv) — the sum-over-group that jnp.repeat's transpose
    would otherwise do as a separate XLA pass. The chunk loop walks all
    rep*T rows; a row's causal position is its index within its own
    head, recovered per chunk as (j % q_chunks_per_head) * cq since cq
    divides T (chunks never straddle heads)."""
    bk = k_ref.shape[1]
    D = k_ref.shape[2]
    k = k_ref[0].astype(jnp.float32)  # [Bk, D]
    v = v_ref[0].astype(jnp.float32)
    col0 = pl.program_id(1) * bk
    mk = mask_ref[0, 0, pl.ds(col0, bk)]  # [Bk]

    def body(j, carry):
        dk_acc, dv_acc = carry
        q_c = q_ref[0, pl.ds(j * cq, cq), :].astype(jnp.float32)  # [cq, D]
        do_c = do_ref[0, pl.ds(j * cq, cq), :].astype(jnp.float32)
        m_c = m_ref[0, 0, pl.ds(j * cq, cq)]  # [cq] (lane vector)
        l_c = jnp.maximum(l_ref[0, 0, pl.ds(j * cq, cq)], 1e-30)
        delta_c = delta_ref[0, 0, pl.ds(j * cq, cq)]
        s_t = jax.lax.dot_general(
            k, q_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [Bk, cq]
        rows = col0 + jax.lax.broadcasted_iota(jnp.int32, (bk, cq), 0)  # key idx
        pos0 = (j % q_chunks_per_head) * cq  # q position within its head
        cols = pos0 + q_offset + jax.lax.broadcasted_iota(jnp.int32, (bk, cq), 1)
        valid = (cols >= rows) if causal else jnp.ones((bk, cq), jnp.bool_)
        valid = valid & (mk[:, None] > 0)
        s_t = jnp.where(valid, s_t, NEG_INF)
        p_t = jnp.exp(s_t - m_c[None, :]) / l_c[None, :]  # [Bk, cq]
        dv_new = dv_acc + jax.lax.dot_general(
            p_t, do_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Bk, D]
        dp_t = jax.lax.dot_general(
            v, do_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Bk, cq]
        ds_t = jnp.where(valid, p_t * (dp_t - delta_c[None, :]) * sm_scale, 0.0)
        dk_new = dk_acc + jax.lax.dot_general(
            ds_t, q_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Bk, D]
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        0, n_chunks, body,
        (jnp.zeros((bk, D), jnp.float32), jnp.zeros((bk, v_ref.shape[2]), jnp.float32)),
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, key_mask, o, m, l, g, causal, sm_scale, block_q,
                    q_offset=None):
    B, H, T, D = q.shape
    Hkv, S, Dv = k.shape[1], k.shape[2], v.shape[3]
    rep = H // Hkv
    if q_offset is None:
        q_offset = S - T
    if key_mask is None:
        key_mask = jnp.ones((B, S), jnp.int32)
    mask3 = key_mask.astype(jnp.int32)[:, None, :]

    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * Hkv, S, D)
    vr = v.reshape(B * Hkv, S, Dv)
    kv_ix = _kv_head_index(H, Hkv)
    dor = g.reshape(B * H, T, Dv)
    # delta_i = rowsum(dO_i * O_i): tiny elementwise pass, fine in XLA
    delta = jnp.sum(
        dor.astype(jnp.float32) * o.reshape(B * H, T, Dv).astype(jnp.float32),
        axis=-1, keepdims=True,
    )  # [BH, T, 1]

    bq = _pick_block(T, block_q)
    ck = _pick_block(S, CHUNK)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, q_offset=q_offset,
            n_chunks=S // ck, ck=ck,
        ),
        grid=(B * H, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), kv_ix),
            pl.BlockSpec((1, S, Dv), kv_ix),
            pl.BlockSpec((1, 1, S), lambda bh, qi: (bh // H, 0, 0)),
            pl.BlockSpec((1, bq, Dv), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(qr, kr, vr, mask3, dor, m, l, delta)

    bk = _pick_block(S, block_q)
    cq = _pick_block(T, CHUNK)
    # GQA: one dkv grid row per KV head; the group's q/do/stat rows are
    # flattened head-major ([B, Hkv, rep, T, ...] -> [B*Hkv, rep*T, ...])
    # so the kernel's chunk loop accumulates the whole group into its kv
    # head's (dk, dv) — no repeated kv materialization, no XLA reduce
    qg = q.reshape(B * Hkv, rep * T, D)
    dog = g.reshape(B * Hkv, rep * T, Dv)
    # lane-major stat views for the dkv kernel (see its docstring)
    m_t = m.reshape(B * Hkv, 1, rep * T)
    l_t = l.reshape(B * Hkv, 1, rep * T)
    delta_t = delta.reshape(B * Hkv, 1, rep * T)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, q_offset=q_offset,
            n_chunks=rep * T // cq, cq=cq, q_chunks_per_head=T // cq,
        ),
        grid=(B * Hkv, S // bk),
        in_specs=[
            pl.BlockSpec((1, rep * T, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, ki: (bh // Hkv, 0, 0)),
            pl.BlockSpec((1, rep * T, Dv), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, 1, rep * T), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, 1, rep * T), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, 1, rep * T), lambda bh, ki: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, S, Dv), v.dtype),
        ],
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(qg, kr, vr, mask3, dog, m_t, l_t, delta_t)

    return (
        dq.reshape(B, H, T, D),
        dk.reshape(B, Hkv, S, D),
        dv.reshape(B, Hkv, S, Dv),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention(q, k, v, key_mask, causal=True, sm_scale=None, block_q=256,
                    q_offset=None):
    """Fused attention. q: [B, H, T, D]; k: [B, Hkv, S, D]; v: [B, Hkv, S, Dv]
    (the values, and so the output, may be narrower or wider than the keys:
    latent attention has 192-wide keys and 128-wide values) with
    Hkv | H (grouped-query attention — pass kv heads UNREPEATED, the
    kernels route each q head to its group's kv rows and accumulate the
    group's dk/dv natively); key_mask: [B, S] (1=real).

    Causality compares PHYSICAL slots. `q_offset` (STATIC int) is the
    slot of query row 0; the default None means right-aligned queries
    (q_offset = S - T, the teacher-forced / hydra-branch layout). A
    KV-cache PREFILL passes its static write index instead: queries
    occupy slots [q_offset, q_offset + T) against the full cache length
    S, with unwritten future slots excluded via key_mask.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_forward(q, k, v, key_mask, causal, sm_scale, block_q,
                          q_offset=q_offset)


def _fwd(q, k, v, key_mask, causal, sm_scale, block_q, q_offset):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    out, m, l = _flash_forward(
        q, k, v, key_mask, causal, sm_scale, block_q, with_stats=True,
        q_offset=q_offset,
    )
    # named so a remat policy can pin the kernel's residuals: under
    # jax.checkpoint the custom-VJP primal re-executes to rebuild
    # residuals — i.e. the forward KERNEL runs again in the backward
    # pass. `save_attn` (ops/remat.py) saves exactly (out, m, l); q/k/v
    # rematerialize from their projection matmuls, which is cheap next
    # to a full online-softmax sweep.
    out = checkpoint_name(out, "flash_out")
    m = checkpoint_name(m, "flash_m")
    l = checkpoint_name(l, "flash_l")
    return out, (q, k, v, key_mask, out, m, l)


def _bwd(causal, sm_scale, block_q, q_offset, res, g):
    q, k, v, key_mask, o, m, l = res
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    dq, dk, dv = _flash_backward(
        q, k, v, key_mask, o, m, l, g, causal, sm_scale, block_q,
        q_offset=q_offset,
    )
    return dq, dk, dv, None


flash_attention.defvjp(_fwd, _bwd)


def flash_attention_on_mesh(mesh, q, k, v, key_mask, q_offset=None, sm_scale=None):
    """:func:`flash_attention` under a device mesh (None = one device).

    GSPMD cannot partition a Mosaic custom call — on a multi-device mesh
    JAX refuses to lower one outside a shard_map — so the call is a
    shard_map over the axes attention is embarrassingly parallel in:
    batch rows over (dp, fsdp), heads over tp. Each device runs the
    kernel on its own rows and heads; no collective is involved, and the
    specs are the layout the activations already have. Shapes the mesh
    does not divide raise: a replicated fallback would run every chip
    over the whole batch without a word. Under pp > 1 the block already
    runs inside the pipeline's shard_map and the call is left as it is.
    """
    if mesh is None or mesh.size == 1 or mesh.shape.get("pp", 1) > 1:
        return flash_attention(q, k, v, key_mask, sm_scale=sm_scale, q_offset=q_offset)
    from jax.sharding import PartitionSpec as P

    data, tp = mesh.shape["dp"] * mesh.shape["fsdp"], mesh.shape["tp"]
    if q.shape[0] % data or q.shape[1] % tp or k.shape[1] % tp:
        raise ValueError(
            f"attention_impl=pallas on mesh {dict(mesh.shape)}: batch "
            f"{q.shape[0]} must divide over dp*fsdp={data} and heads "
            f"{q.shape[1]}/{k.shape[1]} (q/kv) over tp={tp}"
        )
    heads = P(("dp", "fsdp"), "tp", None, None)
    return jax.shard_map(
        lambda q_, k_, v_, m_: flash_attention(
            q_, k_, v_, m_, sm_scale=sm_scale, q_offset=q_offset),
        mesh=mesh,
        in_specs=(heads, heads, heads, P(("dp", "fsdp"), None)),
        out_specs=heads,
        check_vma=False,
    )(q, k, v, key_mask)


# ---------------------------------------------------------------------------
# Bias-carrying variant (T5 relative position bias).
#
# T5 self-attention adds a LEARNED additive bias to the scores (and uses
# no 1/sqrt(d) scale). The bias is batch-invariant ([H, T, S]) and shared
# across the layer stack, so it is materialized ONCE per forward while
# the per-layer [B, H, T, S] score/probability tensors still never
# exist — the structural memory win stands. The backward returns dbias
# (= ds summed over batch, accumulated in-kernel across the grid's
# batch-innermost axis), so the rel_bias table trains exactly as on the
# XLA path. Scale note: the dense bias costs T*S fp32 once (2 GB/head-8
# at 32k) — beyond that, recomputing buckets in-kernel from the tiny
# [n_buckets, H] table (Toeplitz structure) is the planned follow-up.
# No GQA here (T5 has none): Hkv must equal H.
# ---------------------------------------------------------------------------


def _flash_bias_kernel(
    q_ref, k_ref, v_ref, mask_ref, bias_ref, o_ref, m_ref, l_ref,
    *, sm_scale, causal, n_chunks, ck,
):
    bq = q_ref.shape[1]
    D = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32)
    row0 = pl.program_id(1) * bq

    def body(j, carry):
        o_acc, m_run, l_run = carry
        k_c = k_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)
        v_c = v_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)
        mk = mask_ref[0, 0, pl.ds(j * ck, ck)]
        s = jax.lax.dot_general(
            q, k_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        s = s + bias_ref[0, :, pl.ds(j * ck, ck)]
        valid = _tile_valid(bq, ck, row0, j * ck, causal) & (mk[None, :] > 0)
        s = jnp.where(valid, s, NEG_INF)

        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o_acc * corr + jax.lax.dot_general(
            p, v_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((bq, D), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, n_chunks, body, (o0, m0, l0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    m_ref[0] = m
    l_ref[0] = l


def _bias_block_q(block_q: int, S: int) -> int:
    """Query block for the bias variants, shrunk with key length: each
    grid cell holds a [bq, S] fp32 bias strip (and the dq kernel a
    second [bq, S] dbias block) in VMEM, so bq must scale down as S
    grows — [128, 8192] alone is 4 MB and measured over the 16 MB
    scoped-vmem limit at 8k with the rest of the working set (double
    -buffered strips + the chunk loop's score tiles); 1 MB strips
    (bq=32 at 8k) fit with headroom."""
    return min(block_q, max(8, (1 << 20) // (4 * S)))


def _flash_bias_forward(q, k, v, key_mask, bias, causal, sm_scale, block_q,
                        with_stats=False):
    B, H, T, D = q.shape
    S = k.shape[2]
    if k.shape[1] != H:
        raise ValueError("flash_attention_bias does not support GQA")
    if key_mask is None:
        key_mask = jnp.ones((B, S), jnp.int32)
    bq = _pick_block(T, _bias_block_q(block_q, S))
    ck = _pick_block(S, CHUNK)
    grid = (B * H, T // bq)
    kernel = functools.partial(
        _flash_bias_kernel, sm_scale=sm_scale, causal=causal,
        n_chunks=S // ck, ck=ck,
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, qi: (bh // H, 0, 0)),
            # bias strip [bq, S] fp32 in VMEM — the reason the bias
            # variants default to block_q=128 (4 MB at 8k)
            pl.BlockSpec((1, bq, S), lambda bh, qi: (bh % H, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bias_fwd",
    )(
        q.reshape(B * H, T, D), k.reshape(B * H, S, D), v.reshape(B * H, S, D),
        key_mask.astype(jnp.int32)[:, None, :], bias.astype(jnp.float32),
    )
    out = out.reshape(B, H, T, D)
    if with_stats:
        return out, m, l
    return out


def _dq_dbias_kernel(
    q_ref, k_ref, v_ref, mask_ref, bias_ref, do_ref, m_ref, l_ref, delta_ref,
    dq_ref, dbias_ref, *, sm_scale, causal, n_chunks, ck,
):
    """dq for one (head, q-block, batch) cell + dbias accumulated across
    the batch-innermost grid axis (consecutive revisits of the same
    output block, so pallas keeps it resident and flushes once)."""
    bq = q_ref.shape[1]
    D = q_ref.shape[2]
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    m = m_ref[0]
    l = jnp.maximum(l_ref[0], 1e-30)
    delta = delta_ref[0]
    row0 = pl.program_id(1) * bq

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    def body(j, dq_acc):
        k_c = k_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)
        v_c = v_ref[0, pl.ds(j * ck, ck), :].astype(jnp.float32)
        mk = mask_ref[0, 0, pl.ds(j * ck, ck)]
        s = jax.lax.dot_general(
            q, k_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        s = s + bias_ref[0, :, pl.ds(j * ck, ck)]
        valid = _tile_valid(bq, ck, row0, j * ck, causal) & (mk[None, :] > 0)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - m) / l
        dp = jax.lax.dot_general(
            do, v_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = jnp.where(valid, p * (dp - delta), 0.0)  # d(score+bias)
        dbias_ref[0, :, pl.ds(j * ck, ck)] += ds
        return dq_acc + sm_scale * jax.lax.dot_general(
            ds, k_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(0, n_chunks, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_bias_kernel(
    q_ref, k_ref, v_ref, mask_ref, biasT_ref, do_ref, m_ref, l_ref, delta_ref,
    dk_ref, dv_ref, *, sm_scale, causal, cq,
):
    """dk/dv for one (head, key-block) pair, transposed orientation (see
    _dkv_kernel). Unlike the causal kernel, the q dimension is a GRID
    axis (innermost), not an in-kernel loop: the [bk, T] biasT strip the
    loop form needs in VMEM is 4 MB at 8k (measured over the scoped
    limit), while grid-blocked [bk, cq] bias tiles stay ~256 KB. dk/dv
    accumulate fp32 across the consecutive q-chunk revisits."""
    bk = k_ref.shape[1]
    j = pl.program_id(2)
    col0 = pl.program_id(1) * bk
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    mk = mask_ref[0, 0, pl.ds(col0, bk)]
    q_c = q_ref[0].astype(jnp.float32)  # [cq, D]
    do_c = do_ref[0].astype(jnp.float32)
    m_c = m_ref[0, 0]  # [cq]
    l_c = jnp.maximum(l_ref[0, 0], 1e-30)
    delta_c = delta_ref[0, 0]

    @pl.when(j == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    s_t = jax.lax.dot_general(
        k, q_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    s_t = s_t + biasT_ref[0]
    rows = col0 + jax.lax.broadcasted_iota(jnp.int32, (bk, cq), 0)
    cols = j * cq + jax.lax.broadcasted_iota(jnp.int32, (bk, cq), 1)
    valid = (cols >= rows) if causal else jnp.ones((bk, cq), jnp.bool_)
    valid = valid & (mk[:, None] > 0)
    s_t = jnp.where(valid, s_t, NEG_INF)
    p_t = jnp.exp(s_t - m_c[None, :]) / l_c[None, :]
    dv_ref[0] += jax.lax.dot_general(
        p_t, do_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dp_t = jax.lax.dot_general(
        v, do_c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds_t = jnp.where(valid, p_t * (dp_t - delta_c[None, :]), 0.0)
    dk_ref[0] += sm_scale * jax.lax.dot_general(
        ds_t, q_c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _flash_bias_backward(q, k, v, key_mask, bias, o, m, l, g, causal,
                         sm_scale, block_q):
    B, H, T, D = q.shape
    S = k.shape[2]
    if key_mask is None:
        key_mask = jnp.ones((B, S), jnp.int32)
    mask3 = key_mask.astype(jnp.int32)[:, None, :]
    bias32 = bias.astype(jnp.float32)

    qr = q.reshape(B * H, T, D)
    kr = k.reshape(B * H, S, D)
    vr = v.reshape(B * H, S, D)
    dor = g.reshape(B * H, T, D)
    delta = jnp.sum(
        dor.astype(jnp.float32) * o.reshape(B * H, T, D).astype(jnp.float32),
        axis=-1, keepdims=True,
    )

    bq = _pick_block(T, _bias_block_q(block_q, S))
    ck = _pick_block(S, CHUNK)
    # batch INNERMOST so the dbias output block (h, qi) is revisited on
    # consecutive grid steps, accumulating the sum over batch in VMEM
    dq, dbias = pl.pallas_call(
        functools.partial(
            _dq_dbias_kernel, sm_scale=sm_scale, causal=causal,
            n_chunks=S // ck, ck=ck,
        ),
        grid=(H, T // bq, B),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qi, b: (b * H + h, qi, 0)),
            pl.BlockSpec((1, S, D), lambda h, qi, b: (b * H + h, 0, 0)),
            pl.BlockSpec((1, S, D), lambda h, qi, b: (b * H + h, 0, 0)),
            pl.BlockSpec((1, 1, S), lambda h, qi, b: (b, 0, 0)),
            pl.BlockSpec((1, bq, S), lambda h, qi, b: (h, qi, 0)),
            pl.BlockSpec((1, bq, D), lambda h, qi, b: (b * H + h, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, qi, b: (b * H + h, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, qi, b: (b * H + h, qi, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, qi, b: (b * H + h, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qi, b: (b * H + h, qi, 0)),
            pl.BlockSpec((1, bq, S), lambda h, qi, b: (h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((H, T, S), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bias_bwd_dq",
    )(qr, kr, vr, mask3, bias32, dor, m, l, delta)

    # key blocks stay at 128: the kernel's mask slice pl.ds(ki*bk, bk)
    # must be statically provable as 128-aligned (Mosaic requirement on
    # dynamic lane-dim indices); q-chunks are an innermost GRID axis so
    # bias rides in [bk, cq] tiles (see _dkv_bias_kernel docstring)
    bk = _pick_block(S, 128)
    cq = _pick_block(T, CHUNK)
    biasT = bias32.transpose(0, 2, 1)  # [H, S, T] for lane-major tiles
    m_t = m.reshape(B * H, 1, T)
    l_t = l.reshape(B * H, 1, T)
    delta_t = delta.reshape(B * H, 1, T)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_bias_kernel, sm_scale=sm_scale, causal=causal, cq=cq,
        ),
        grid=(B * H, S // bk, T // cq),
        in_specs=[
            pl.BlockSpec((1, cq, D), lambda bh, ki, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki, j: (bh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki, j: (bh, ki, 0)),
            pl.BlockSpec((1, 1, S), lambda bh, ki, j: (bh // H, 0, 0)),
            pl.BlockSpec((1, bk, cq), lambda bh, ki, j: (bh % H, ki, j)),
            pl.BlockSpec((1, cq, D), lambda bh, ki, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, cq), lambda bh, ki, j: (bh, 0, j)),
            pl.BlockSpec((1, 1, cq), lambda bh, ki, j: (bh, 0, j)),
            pl.BlockSpec((1, 1, cq), lambda bh, ki, j: (bh, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, ki, j: (bh, ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, ki, j: (bh, ki, 0)),
        ],
        out_shape=[
            # fp32: dk/dv accumulate across q-chunk grid revisits
            jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
            jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
        ],
        interpret=_interpret(),
        name="flash_bias_bwd_dkv",
    )(qr, kr, vr, mask3, biasT, dor, m_t, l_t, delta_t)

    return (
        dq.reshape(B, H, T, D),
        dk.reshape(B, H, S, D).astype(k.dtype),
        dv.reshape(B, H, S, D).astype(v.dtype),
        dbias.astype(bias.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention_bias(q, k, v, key_mask, bias, causal=False,
                         sm_scale=1.0, block_q=128):
    """Fused attention with a learned additive bias (T5 relative
    position bias). q/k/v: [B, H, T|S, D] (no GQA); key_mask: [B, S];
    bias: [H, T, S], batch-invariant and DIFFERENTIABLE (the backward
    returns its gradient summed over batch). T5 semantics: sm_scale
    defaults to 1.0 (the scale is folded into T5's init), causality is
    optional (encoder False / decoder True), queries are assumed
    unpadded full-sequence (T == S layouts)."""
    return _flash_bias_forward(q, k, v, key_mask, bias, causal, sm_scale,
                               block_q)


def _bias_fwd(q, k, v, key_mask, bias, causal, sm_scale, block_q):
    out, m, l = _flash_bias_forward(
        q, k, v, key_mask, bias, causal, sm_scale, block_q, with_stats=True
    )
    out = checkpoint_name(out, "flash_out")
    m = checkpoint_name(m, "flash_m")
    l = checkpoint_name(l, "flash_l")
    return out, (q, k, v, key_mask, bias, out, m, l)


def _bias_bwd(causal, sm_scale, block_q, res, g):
    q, k, v, key_mask, bias, o, m, l = res
    dq, dk, dv, dbias = _flash_bias_backward(
        q, k, v, key_mask, bias, o, m, l, g, causal, sm_scale, block_q
    )
    return dq, dk, dv, None, dbias


flash_attention_bias.defvjp(_bias_fwd, _bias_bwd)
