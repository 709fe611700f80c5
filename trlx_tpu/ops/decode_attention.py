"""Pallas fused decode attention: contiguous int8 caches AND paged pools.

Two kernel families live here behind the two decode-cache layouts:

  * ``decode_attention_int8`` — the contiguous-layout kernel (stacked
    [L, B, Hkv, S, D] int8 caches): what ``kv_cache_quant="int8"`` runs
    at every decode step whose cache is whole 128-slot tiles
    (``transformer.decode_attn_unfused``); design notes below.
  * ``paged_attention_pallas`` (selected through
    ``paged_attention_step(impl="pallas")``) — the paged-pool kernel:
    the slot→page table becomes the block index map, so K/V pages load
    from the pool's HBM layout without the gathered S-width cache ever
    materializing, per-row int8 scales fold into the score/prob tiles
    in-kernel, GQA attends grouped, and the same kernel serves the T=1
    decode step and the T=draft_k speculative verify forward.


Decode at large batch×seq is bound on the cache read every step. Driven
through XLA ops that read costs the whole ALLOCATED cache whatever has
been written, at a rate the compiler's choice of window decides: the
folded-scale branch of transformer.Attention took 162 us a layer a step
over b8 x 16 heads x 1024 slots and 159 us over 2048 (ledger, PR 28:
33.5 and 67 MB of int8; its score fusion, a VPU multiply-reduce over a
cache XLA had re-laid as [L, S, Hkv, B, D], read at 147 and 510 GB/s).
This kernel does a layer's attention step in one pass over the WRITTEN
cache: a grid cell streams all heads of one row over a chunk of slots
into VMEM, computes fp32 scores on the MXU from int8 widened to the
compute dtype (exact) with the per-slot K scales folded in, runs an
online softmax across the row's chunks, and applies the per-channel V
scales to the tiny [H, D] output — nothing S-sized goes back to HBM,
and chunks past the write index are neither fetched nor computed. On the
chip (PR 30, same shapes): 52 us with all 1024 slots written, 20 us with
a quarter of them, 97-100 us at 2048: 650-690 GB/s of written bytes.
Both products push every int8 tile through the MXU as its stationary
operand, 128 x 128 a unit in 128 cycles: about 770 GB/s of int8 over the
four units, so MXU and HBM (819 GB/s) bound it alike.

Layer indexing: the decode loop scans over layers carrying the stacked
[L, B, Hkv, S, D] buffers; the layer index arrives as a SCALAR-PREFETCH
argument so the kernel reads its layer's blocks straight out of the
full carried buffer — slicing the layer out in XLA first would
materialize a 33 MB copy per layer per step, which is the exact
traffic the kernel exists to avoid.

Scale layout (chosen so both dequants commute out of the reductions):
  k_scale [L, B, Hkv, S] fp32 — multiplies scores per key slot
  v_scale [L, B, Hkv, 1, D] fp32 — multiplies the output per channel

The reference has no decode-attention kernel at all: its rollout
generation is HF `model.generate` over full-precision torch caches
(/root/reference/trlx/trainer/accelerate_ppo_trainer.py:285).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops.common import interpret_mode as _interpret

NEG_INF = -1e30
# int8 K (and V) bytes of one grid cell of the dense decode kernel. On the
# chip (PR 30, b8 x 16 heads x 128, S = 1024, us a call with 201, 576 and
# 1024 slots written): 256 slots a cell 20, 44, 52; 512 slots 32, 52, 52;
# 128 slots 26, 44, 64 (there the grid, not the memory, sets the pace)
CELL_BYTES = 1 << 19


def paged_attention_step(
    q,  # [B, T, H, D] queries (rope already applied), T >= 1
    k_new,  # [B, T, Hkv, D] this step's keys (pre-quantization)
    v_new,  # [B, T, Hkv, D] this step's values
    pools: Dict[str, jnp.ndarray],  # pk/pv [L, NP, PS, Hkv, D] (+ scales)
    layer_ix,  # scalar int32: which layer's pages to touch
    page_table,  # [B, MP] int32 slot -> page indirection
    slot_pos,  # [B] int32: logical slot of the FIRST incoming token
    attn_bias,  # [B, 1, T, S] additive fp32 (S = MP * PS)
    sm_scale: float,
    lane_valid: Optional[jnp.ndarray] = None,  # [B] bool; False -> trash write
    contiguous: bool = False,
    impl: str = "xla",
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One layer's attention over a paged KV cache: write the T incoming
    tokens' K/V into their pages, then attend every query against the
    slot's full logical sequence, with the per-row quant scales folded
    into the score / prob vectors so int8 K/V are never dequantized at
    S width (the dense int8 path's folded-scale recipe, generalized to
    per-row indirection and per-row positions).

    Serves both the single-token decode step (T=1) and the speculative
    verify forward (T=draft_k): causality among the T incoming tokens is
    carried by `attn_bias` (slot-index comparison), so the same code is
    exact for both. Returns (out [B, T, H, D], updated pools).

    ``impl`` selects the attend half (``gen_engine.paged_attention_impl``):

      xla     gather the slot's logical [B, S] view of the pool, then
              plain-XLA attention over it. GQA attends GROUPED (one
              einsum per kv-head group) — kv is never repeat-
              materialized at S width.
      pallas  :func:`paged_attention_pallas` — the page table becomes
              the kernel's block index map, so K/V pages stream from
              the pool's HBM layout into VMEM without the gathered
              S-width cache ever existing.

    The write half (a [B, T] scatter) is tiny and shared by both. The
    ``contiguous`` layout always takes the XLA path: its gather
    collapses to a slice+reshape that XLA fuses into the attention
    reads like a dense cache, which is the exact behavior the
    ``paged=false`` benches attribute against — a kernel there would
    change the baseline, not beat it.
    """
    from trlx_tpu.ops.paged_kv import (
        gather_layer,
        quantize_rows,
        scatter_layer,
        write_positions,
    )

    if impl not in ("xla", "pallas"):
        raise ValueError(f"paged attention impl must be xla/pallas, got {impl!r}")
    B, T, H, D = q.shape
    Hkv = k_new.shape[2]
    PS = pools["pk"].shape[2]
    quant = "pk_scale" in pools
    positions = slot_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    pids, offs = write_positions(page_table, positions, PS, lane_valid)

    new_pools = dict(pools)
    if quant:
        kq, ks = quantize_rows(k_new)  # [B, T, Hkv] scales
        vq, vs = quantize_rows(v_new)
        new_pools["pk"] = scatter_layer(pools["pk"], layer_ix, pids, offs, kq)
        new_pools["pv"] = scatter_layer(pools["pv"], layer_ix, pids, offs, vq)
        new_pools["pk_scale"] = scatter_layer(
            pools["pk_scale"], layer_ix, pids, offs, ks
        )
        new_pools["pv_scale"] = scatter_layer(
            pools["pv_scale"], layer_ix, pids, offs, vs
        )
    else:
        new_pools["pk"] = scatter_layer(pools["pk"], layer_ix, pids, offs, k_new)
        new_pools["pv"] = scatter_layer(pools["pv"], layer_ix, pids, offs, v_new)

    # read AFTER the write (update-carry-first, like the dense cache
    # branch): each query sees every token up to and including itself;
    # older/unwritten/stale slots are excluded by attn_bias
    if impl == "pallas" and not contiguous:
        out = paged_attention_pallas(
            q, new_pools, layer_ix, page_table, attn_bias, sm_scale
        )
        return out, new_pools

    k_all = gather_layer(new_pools["pk"], layer_ix, page_table, contiguous)
    v_all = gather_layer(new_pools["pv"], layer_ix, page_table, contiguous)
    ks_all = vs_all = None
    if quant:
        ks_all = gather_layer(
            new_pools["pk_scale"], layer_ix, page_table, contiguous
        )  # [B, S, Hkv]
        vs_all = gather_layer(
            new_pools["pv_scale"], layer_ix, page_table, contiguous
        )
    if H == Hkv:
        scores = jnp.einsum(
            "bthd,bshd->bhts", q, k_all.astype(q.dtype),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if quant:
            # per-row K scale rides the score tensor; per-row V scale
            # rides the prob tensor — both commute out of the reductions
            scores = scores * ks_all.transpose(0, 2, 1)[:, :, None, :]
            probs = jax.nn.softmax(scores + attn_bias, axis=-1)
            probs = (probs * vs_all.transpose(0, 2, 1)[:, :, None, :]).astype(
                q.dtype
            )
        else:
            probs = jax.nn.softmax(scores + attn_bias, axis=-1).astype(q.dtype)
        out = jnp.einsum("bhts,bshd->bthd", probs, v_all.astype(q.dtype))
        return out.astype(q.dtype), new_pools

    # GQA: attend GROUPED — the einsum batches over kv heads with the
    # rep query heads of each group as a free axis, so kv (and scales)
    # are read at Hkv width instead of being jnp.repeat-materialized to
    # H x S per step (the rep-fold memory the old fallback paid)
    rep = H // Hkv
    qg = q.reshape(B, T, Hkv, rep, D)
    scores = jnp.einsum(
        "btgrd,bsgd->bgrts", qg, k_all.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * sm_scale  # [B, Hkv, rep, T, S]
    bias_g = attn_bias[:, :, None]  # [B, 1, 1, T, S] broadcasts over (g, r)
    if quant:
        scores = scores * ks_all.transpose(0, 2, 1)[:, :, None, None, :]
        probs = jax.nn.softmax(scores + bias_g, axis=-1)
        probs = (
            probs * vs_all.transpose(0, 2, 1)[:, :, None, None, :]
        ).astype(q.dtype)
    else:
        probs = jax.nn.softmax(scores + bias_g, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrts,bsgd->btgrd", probs, v_all.astype(q.dtype))
    return out.reshape(B, T, H, D).astype(q.dtype), new_pools


def _paged_kernel(
    lx_ref,  # scalar prefetch: [1] layer index (consumed by index maps)
    pt_ref,  # scalar prefetch: [B*MP] flattened page table (index maps)
    q_ref,  # [1, Hkv, rep*T, D] — group-blocked queries, rows t*rep+r
    k_ref,  # [1, 1, PS, Hkv, D] — ONE page, routed here by pt_ref
    v_ref,  # [1, 1, PS, Hkv, D]
    *rest,  # (+ks_ref/vs_ref when quant) b_ref, o_ref, o/m/l scratch
    sm_scale,
    rep,
    quant,
):
    """One (batch row, page) grid cell: score the row's queries against
    this page's keys for every kv head, fold the page's per-row int8
    scales in, and fold the tile into the online-softmax accumulators.
    Pages are the INNERMOST grid axis, so the accumulators live in VMEM
    scratch across the row's page sweep and the output block flushes
    once at the last page."""
    if quant:
        ks_ref, vs_ref, b_ref, o_ref, o_scratch, m_scratch, l_scratch = rest
    else:
        b_ref, o_ref, o_scratch, m_scratch, l_scratch = rest
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    Hkv = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        o_scratch[...] = jnp.zeros_like(o_scratch)
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)

    # additive bias strip [T, PS] carries ALL masking (per-row lengths,
    # slot-index causality, null pages); rows are t*rep+r so the rep
    # group members of token t share bias[t]
    bias_rows = jnp.repeat(b_ref[0, 0], rep, axis=0)  # [rep*T, PS]
    for h in range(Hkv):  # static unroll: per-kv-head 2D dots
        qh = q_ref[0, h].astype(jnp.float32)  # [rep*T, D]
        kh = k_ref[0, 0, :, h, :].astype(jnp.float32)  # [PS, D]
        s = jax.lax.dot_general(
            qh, kh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [rep*T, PS]
        if quant:
            # per-slot K dequant folded into the score tile
            s = s * ks_ref[0, 0, :, h][None, :]
        s = s + bias_rows
        m_run = m_scratch[h]  # [rep*T, 1]
        l_run = l_scratch[h]
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_scratch[h] = l_run * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scratch[h] = m_new
        if quant:
            # per-slot V dequant rides the prob tile (commutes out of
            # the over-S dot, exactly like the gather path)
            p = p * vs_ref[0, 0, :, h][None, :]
        vh = v_ref[0, 0, :, h, :].astype(jnp.float32)
        o_scratch[h] = o_scratch[h] * corr + jax.lax.dot_general(
            p, vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _flush():
        o_ref[0] = (
            o_scratch[...] / jnp.maximum(l_scratch[...], 1e-30)
        ).astype(o_ref.dtype)


def paged_attention_pallas(
    q,  # [B, T, H, D]
    pools: Dict[str, jnp.ndarray],  # POST-write pools (pk/pv [+ scales])
    layer_ix,  # scalar int32
    page_table,  # [B, MP] int32
    attn_bias,  # [B, 1, T, S] additive fp32
    sm_scale: float,
):
    """Pallas paged-attention: the page table IS the block index map.

    Grid (B, MP) with pages innermost: cell (b, j) DMAs page
    ``page_table[b, j]`` of this layer straight out of the pool's
    [L, NP, PS, Hkv, D] HBM layout (both table and layer index arrive
    as scalar-prefetch arguments, so the routing happens before the
    kernel body runs) and folds it into per-(kv-head) online-softmax
    accumulators held in VMEM scratch across the row's page sweep. The
    gathered [B, S, Hkv, D] logical cache — the XLA path's three extra
    O(S·D) materializations per layer — never exists anywhere. GQA
    attends grouped: queries arrive group-blocked ([Hkv, rep*T, D] per
    row), so each page is read ONCE per row and shared by its group's
    rep query heads. Null pages (table entry 0) are loaded but fully
    masked by the bias strip, matching the gather path's null-page
    semantics slot for slot.

    One kernel serves the T=1 decode step and the T=draft_k speculative
    verify forward — causality among the T incoming tokens rides the
    same slot-index ``attn_bias`` the XLA path uses.
    """
    B, T, H, D = q.shape
    PS, Hkv = pools["pk"].shape[2], pools["pk"].shape[3]
    MP = page_table.shape[1]
    quant = "pk_scale" in pools
    if H % Hkv:
        raise ValueError(f"n_head={H} not a multiple of n_kv_head={Hkv}")
    rep = H // Hkv
    if not _interpret() and PS % 128:
        raise ValueError(
            f"gen_engine.paged_attention_impl=pallas needs page_size a "
            f"multiple of 128 on TPU (got {PS}): the per-page bias/score "
            "tiles are lane-blocked at 128 — use page_size=128 or "
            "paged_attention_impl=xla"
        )
    # group-blocked queries: row t*rep + r of group g is query head
    # g*rep + r at token t (consecutive rep heads share a kv head)
    qg = q.reshape(B, T, Hkv, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, Hkv, rep * T, D
    )

    def page_ix(b, j, lx, pt):
        return (lx[0], pt[b * MP + j], 0, 0, 0)

    def scale_ix(b, j, lx, pt):
        return (lx[0], pt[b * MP + j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hkv, rep * T, D), lambda b, j, lx, pt: (b, 0, 0, 0)),
        pl.BlockSpec((1, 1, PS, Hkv, D), page_ix),
        pl.BlockSpec((1, 1, PS, Hkv, D), page_ix),
    ]
    operands = [qg, pools["pk"], pools["pv"]]
    if quant:
        in_specs += [
            pl.BlockSpec((1, 1, PS, Hkv), scale_ix),
            pl.BlockSpec((1, 1, PS, Hkv), scale_ix),
        ]
        operands += [pools["pk_scale"], pools["pv_scale"]]
    in_specs.append(
        pl.BlockSpec((1, 1, T, PS), lambda b, j, lx, pt: (b, 0, 0, j))
    )
    operands.append(attn_bias.astype(jnp.float32))

    kernel = functools.partial(
        _paged_kernel, sm_scale=sm_scale, rep=rep, quant=quant
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, MP),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, Hkv, rep * T, D), lambda b, j, lx, pt: (b, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Hkv, rep * T, D), jnp.float32),
                pltpu.VMEM((Hkv, rep * T, 1), jnp.float32),
                pltpu.VMEM((Hkv, rep * T, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep * T, D), q.dtype),
        interpret=_interpret(),
        name="paged_decode_attn",
    )(
        jnp.reshape(layer_ix, (1,)).astype(jnp.int32),
        page_table.reshape(-1).astype(jnp.int32),
        *operands,
    )
    return out.reshape(B, Hkv, T, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, H, D
    )


def decode_chunk(S: int, Hkv: int, D: int) -> int:
    """Slots of one grid cell of :func:`decode_attention_int8`: the
    largest multiple of 128 that divides `S`, keeps a cell's int8 K
    block (all `Hkv` heads of a row) within CELL_BYTES and is at most
    512 (the step in which the bound at the write index moves); never
    under 128 (the lane width of the mask and scale blocks)."""
    n = S // 128
    cap = min(4, max(1, CELL_BYTES // (Hkv * D * 128)))
    return 128 * max(d for d in range(1, n + 1) if n % d == 0 and d <= cap)


def _decode_kernel(
    sp_ref,  # scalar prefetch [2]: layer index, leading steps to skip
    q_ref,  # [1, H, D]
    k_ref,  # [1, 1, Hkv, ck, D] int8
    v_ref,  # [1, 1, Hkv, ck, D] int8
    ks_ref,  # [1, 1, Hkv, ck] f32
    vs_ref,  # [1, Hkv, 1, D] f32 (per-layer slice; no layer axis)
    mask_ref,  # [1, 1, ck] int32
    o_ref,  # [1, H, D]
    o_scr,  # [H, D] f32: the row's running weighted sum
    m_scr,  # [H, 1] f32: running max
    l_scr,  # [H, 1] f32: running denominator
    *,
    sm_scale,
    rep,
):
    """One (batch row, S chunk) cell: all heads of the row against the
    chunk's keys and values, folded into the row's online softmax.

    A product runs all H query rows against ONE kv head's tile and the
    rows of that head's group are kept (`where` on the row index): the
    MXU's time is the tile it loads, not the rows that stream through
    it, so the other rows cost nothing, every product has H rows
    whatever `rep` is, and scores and probabilities stay whole
    [H, ck] tiles for the softmax."""
    j = pl.program_id(1)
    H, D = q_ref.shape[1], q_ref.shape[2]
    Hkv, ck = k_ref.shape[2], k_ref.shape[3]

    @pl.when(j == 0)
    def _init():
        o_scr[...] = jnp.zeros_like(o_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(j >= sp_ref[1])  # the row's leading steps: nothing to fetch or run
    def _chunk():
        q = q_ref[0]  # [H, D], compute dtype
        group = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // rep
        s = jnp.zeros((H, ck), jnp.float32)
        for h in range(Hkv):  # static unroll: one 2D product a kv head
            s_h = jax.lax.dot_general(
                q, k_ref[0, 0, h].astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, ck]
            # per-slot K dequant + softmax scale fold into the score tile
            s = jnp.where(group == h, s_h * (ks_ref[0, 0, h:h + 1] * sm_scale), s)
        s = jnp.where(mask_ref[0] > 0, s, NEG_INF)

        m_run = m_scr[...]
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        p = p.astype(q.dtype)
        pv = jnp.zeros((H, D), jnp.float32)
        for h in range(Hkv):
            pv_h = jax.lax.dot_general(
                p, v_ref[0, 0, h].astype(q.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [H, D]
            pv = jnp.where(group == h, pv_h, pv)
        o_scr[...] = o_scr[...] * corr + pv

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        group = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // rep
        vs = jnp.zeros((H, D), jnp.float32)
        for h in range(Hkv):
            vs = jnp.where(group == h, vs_ref[0, h], vs)
        # per-channel V dequant commutes out of the over-S dot: one
        # [H, D] multiply after normalization
        o = o_scr[...] / jnp.maximum(l_scr[...], 1e-30) * vs
        o_ref[0] = o.astype(o_ref.dtype)


def decode_attention_int8(
    q,  # [B, H, D] (rope already applied)
    ck,  # [L, B, Hkv, S, D] int8 — full stacked cache
    cv,  # [L, B, Hkv, S, D] int8
    k_scale,  # [L, B, Hkv, S] f32
    v_scale,  # [B, Hkv, 1, D] f32 — this layer's slice (frozen scales
    #           ride the layer scan's xs, so no layer axis here)
    key_mask,  # [B, S] int32 — 1 for attendable slots (incl. this token)
    layer_ix,  # scalar int32: which layer's blocks to read
    write_ix,  # scalar int32: the slot this step wrote; none later is read
    sm_scale: float,
):
    """One decode step's attention for ONE layer of the stacked cache.

    Grid (B, S // chunk), chunks innermost: a cell holds all Hkv heads
    of one row over `decode_chunk` slots, so the memory and not the
    grid sets the pace. The layer index and the number of chunks wholly
    past the write index are scalar-prefetched; a row's grid steps are
    that many idle steps FIRST, then its written chunks in order: step
    j reads chunk max(j - idle, 0). An idle step repeats the block of
    the step after it, which the pipeline fetched during the row
    before (no copy is issued for an unchanged block), and does
    nothing; idle steps at a row's END would leave the next row's first
    copy with nothing to hide behind (measured: 3 of 4 chunks then cost
    as much as 4). Inside the last chunk, and for left padding,
    `key_mask` decides.

    Returns [B, H, D] in q.dtype. Requires S % 128 == 0 (generate()
    rounds real rollout caches to 128 slots) — callers fall back to the
    XLA path otherwise (transformer.Attention gates on the same
    condition).
    """
    L, B, Hkv, S, D = ck.shape
    H = q.shape[1]
    if H % Hkv:
        raise ValueError(f"n_head={H} not a multiple of n_kv_head={Hkv}")
    if S % 128:
        raise ValueError(f"cache length {S} must be a multiple of 128")
    chunk = decode_chunk(S, Hkv, D)
    idle = S // chunk - 1 - jnp.clip(write_ix // chunk, 0, S // chunk - 1)

    def kv_ix(b, j, sp):
        return (sp[0], b, 0, jnp.maximum(j - sp[1], 0), 0)

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale, rep=H // Hkv)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, S // chunk),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, j, sp: (b, 0, 0)),
                pl.BlockSpec((1, 1, Hkv, chunk, D), kv_ix),
                pl.BlockSpec((1, 1, Hkv, chunk, D), kv_ix),
                pl.BlockSpec(
                    (1, 1, Hkv, chunk),
                    lambda b, j, sp: (sp[0], b, 0, jnp.maximum(j - sp[1], 0)),
                ),
                pl.BlockSpec((1, Hkv, 1, D), lambda b, j, sp: (b, 0, 0, 0)),
                pl.BlockSpec(
                    (1, 1, chunk), lambda b, j, sp: (b, 0, jnp.maximum(j - sp[1], 0))
                ),
            ],
            out_specs=pl.BlockSpec((1, H, D), lambda b, j, sp: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, D), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=_interpret(),
        name="decode_attn",
    )(
        jnp.stack([jnp.asarray(layer_ix, jnp.int32), idle.astype(jnp.int32)]),
        q,
        ck,
        cv,
        k_scale,
        v_scale,
        key_mask.astype(jnp.int32)[:, None, :],
    )


def decode_attention_on_mesh(
    mesh, q, ck, cv, k_scale, v_scale, key_mask, layer_ix, write_ix, sm_scale: float
):
    """:func:`decode_attention_int8` under a device mesh (None = one
    device). GSPMD cannot partition a Mosaic custom call, so the call is
    a shard_map over the axes a decode step is embarrassingly parallel
    in, like :func:`flash_attention_on_mesh`: batch rows over (dp, fsdp),
    heads over tp, the layout the cache already has. No collective is
    involved; `transformer.decode_attn_unfused` keeps shapes the mesh
    does not divide off this path."""
    kernel = functools.partial(decode_attention_int8, sm_scale=sm_scale)
    if mesh is None or mesh.size == 1:
        return kernel(q, ck, cv, k_scale, v_scale, key_mask, layer_ix, write_ix)
    from jax.sharding import PartitionSpec as P

    rows = ("dp", "fsdp")
    stacked = P(None, rows, "tp", None, None)
    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(
            P(rows, "tp", None), stacked, stacked, P(None, rows, "tp", None),
            P(rows, "tp", None, None), P(rows, None), P(), P(),
        ),
        out_specs=P(rows, "tp", None),
        check_vma=False,
    )(q, ck, cv, k_scale, v_scale, key_mask, layer_ix, write_ix)
