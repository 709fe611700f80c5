"""Blockwise 8-bit AdamW: the bitsandbytes replacement, as a first-party
optax transformation.

Parity: the reference offers `adamw_8bit_bnb` through bitsandbytes'
CUDA kernels (/root/reference/trlx/utils/__init__.py:104-123,
accelerate_base_trainer.py:183-191). The TPU-native shape is the same
math with the moment states held in int8 + per-block fp32 absmax scales
(block 256, bnb's default): m is symmetric int8, v (non-negative) uses
the positive half. Dequantize -> fused adam update -> requantize runs
inside the jitted train step; XLA fuses the (de)quantization into the
update elementwise pass, so the win is the 4x smaller optimizer state in
HBM (the dominant term beyond params for fsdp-sharded training), not
kernel time.
"""

from __future__ import annotations

from typing import NamedTuple

import flax
import jax
import jax.numpy as jnp
import optax

BLOCK = 256


@flax.struct.dataclass
class Q8:
    q: jnp.ndarray  # int8 payload, flattened + padded to BLOCK
    scale: jnp.ndarray  # f32 per-block absmax
    shape: tuple = flax.struct.field(pytree_node=False)  # original (static)


def _quant_blocks(blocks: jnp.ndarray):
    """[n, BLOCK] fp32 -> (int8 payload, fp32 per-block absmax)."""
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    norm = jnp.abs(blocks) / jnp.maximum(scale, 1e-30)
    q = jnp.round(jnp.sign(blocks) * jnp.sqrt(norm) * 127.0)
    return q.astype(jnp.int8), scale[:, 0]


def _deq_blocks(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    u = q.astype(jnp.float32) / 127.0
    return jnp.sign(u) * u * u * scale[:, None]


def _deq_second_moment(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Decode v with the zero code read as the TOP of its bin,
    (0.5/127)^2 * absmax, not as 0. The code has 127:1 of range in |g|
    inside a block; an entry below that loses its whole v history while
    its m (same code, linear in g) survives, and the step
    m / (sqrt(v) + eps) then rests on the current gradient alone — a
    gradient that happens to pass near zero divides a healthy m by ~eps.
    Measured on the chip: the 1.3B recipe's value head went 0.06 -> -54
    -> 4e5 in eight steps. Reading the bin conservatively bounds the
    step by Adam's own; entries far below the block's largest gradient
    move slower than exact Adam would move them, never faster."""
    u = jnp.maximum(q.astype(jnp.float32), 0.5) / 127.0
    return u * u * scale[:, None]


def _to_blocks(x: jnp.ndarray) -> jnp.ndarray:
    flat = x.reshape(-1)
    pad = (-flat.size) % BLOCK
    return jnp.pad(flat, (0, pad)).reshape(-1, BLOCK)


def _quantize(x: jnp.ndarray) -> Q8:
    """Blockwise companded int8: q = sign * 127 * sqrt(|x| / absmax).

    The sqrt companding matches bitsandbytes' non-linear dynamic map in
    spirit: Adam's second moment spans orders of magnitude within one
    block, and a LINEAR absmax code wipes out the small entries, which
    visibly corrupts the update direction (sqrt(vhat) sits in the
    denominator)."""
    q, scale = _quant_blocks(_to_blocks(x.astype(jnp.float32)))
    return Q8(q, scale, x.shape)


def _dequantize(s: Q8, deq=_deq_blocks) -> jnp.ndarray:
    flat = deq(s.q, s.scale).reshape(-1)
    n = 1
    for d in s.shape:
        n *= d
    return flat[:n].reshape(s.shape)


class Adam8bitState(NamedTuple):
    count: jnp.ndarray
    m: optax.Params  # tree of Q8
    v: optax.Params  # tree of Q8


def _init_adam8bit_state(params) -> Adam8bitState:
    # m and v must be INDEPENDENT buffers: sharing one quantized-zeros
    # tree between them makes a donated state donate each buffer twice
    # (Execute() rejects `f(donate(a), donate(a))`)
    def zeros(p):
        return _quantize(jnp.zeros(p.shape, jnp.float32))

    return Adam8bitState(
        count=jnp.zeros([], jnp.int32),
        m=jax.tree_util.tree_map(zeros, params),
        v=jax.tree_util.tree_map(zeros, params),
    )


def scale_by_adam_8bit(
    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
    step_dtype=None,
):
    """optax transformation holding both Adam moments in blockwise int8.

    `step_dtype`: dtype of the emitted updates tree. None (default)
    follows the gradient's dtype — bf16-grad callers get a bf16 updates
    tree (the memory-tight large-model behavior). Pass jnp.float32 to
    pin fp32 steps regardless of gradient precision."""

    init = _init_adam8bit_state

    def update(updates, state, params=None):
        count = state.count + 1

        def one(g, mq, vq):
            out_dtype = step_dtype if step_dtype is not None else g.dtype
            g = g.astype(jnp.float32)
            m = b1 * _dequantize(mq) + (1 - b1) * g
            v = b2 * _dequantize(vq, _deq_second_moment) + (1 - b2) * g * g
            mhat = m / (1 - b1 ** count.astype(jnp.float32))
            vhat = v / (1 - b2 ** count.astype(jnp.float32))
            step = mhat / (jnp.sqrt(vhat) + eps)
            # emit the step in the grad's dtype: moment math stays fp32,
            # but a bf16-grad caller (memory-tight large models) gets a
            # bf16 updates tree — the step is O(1)-scaled, so bf16's
            # ~0.4% relative error is noise next to int8 moment states,
            # and optax.apply_updates promotes back to fp32 params
            return step.astype(out_dtype), _quantize(m), _quantize(v)

        flat_u, tdef = jax.tree_util.tree_flatten(updates)
        flat_m = tdef.flatten_up_to(state.m)
        flat_v = tdef.flatten_up_to(state.v)
        out = [one(g, m, v) for g, m, v in zip(flat_u, flat_m, flat_v)]
        steps = tdef.unflatten([o[0] for o in out])
        new_m = tdef.unflatten([o[1] for o in out])
        new_v = tdef.unflatten([o[2] for o in out])
        return steps, Adam8bitState(count=count, m=new_m, v=new_v)

    return optax.GradientTransformation(init, update)


def adamw_8bit(
    learning_rate, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
    weight_decay: float = 0.0,
):
    """AdamW with int8 moment states (drop-in for optax.adamw)."""
    chain = [scale_by_adam_8bit(b1=b1, b2=b2, eps=eps)]
    if weight_decay:
        chain.append(optax.add_decayed_weights(weight_decay))
    chain.append(optax.scale_by_learning_rate(learning_rate))
    return optax.chain(*chain)


# elements of fp32 temporaries the fused apply allows live per leaf:
# 2^22 * 4 B = 16 MB per array, a handful of arrays in flight
_FUSED_CHUNK_ELEMS = 1 << 22


def fused_adamw_8bit_update(
    params,
    grads,
    state: Adam8bitState,
    learning_rate: float,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    mask=None,
):
    """One fused AdamW step over int8 moments: returns (new_params,
    new_state) directly, never materializing an fp32 moment OR updates
    tree — the dequantize -> moment update -> requantize -> parameter
    apply chain streams through a `lax.scan` over block chunks per leaf.

    This is what bitsandbytes' fused CUDA kernel does (the reference's
    `adamw_8bit_bnb` row, ref trlx/utils/__init__.py:104-123): the
    optax-style `scale_by_adam_8bit` keeps the standard updates-tree
    contract, but at billion-parameter scale the fp32 temporaries of
    that contract (moments + updates, ~3 full fp32 copies in flight)
    are exactly what doesn't fit next to fp32 master params on a 16 GB
    chip. Donate params+state into the jit that calls this and the whole
    optimizer phase runs in O(chunk) extra memory.

    `grads` may be lower precision (bf16): moment math runs fp32 per
    chunk regardless, and the apply writes fp32 master params.

    `mask` (optional {0,1} update-multiplier tree, broadcastable per
    leaf — the trainers' freeze masks): applied INSIDE the streaming
    chunk loop (`p - lr*mask*step`), so freezing costs O(chunk) extra
    memory. The previous design blended frozen values back AFTER the
    apply, which held old params + new params + blended params — three
    fp32 trees, 10.6 GB of transient HBM at 1.3B and the difference
    between the at-scale recipe fitting a 16 GB chip or OOMing by
    ~0.5 GB (measured). A whole-leaf zero mask skips the leaf entirely
    (no moment updates either — the reference's frozen params are
    excluded from optimizer param groups the same way).

    The trainers no longer hand this function the rows a mask freezes:
    `_step_update` cuts parameters, gradients and both `Q8` moments to the
    trainable view first (`ops/trainable_view.py`: of a stacked `[L, ...]`
    leaf whose mask is one run of rows, those rows and their range of
    blocks; nothing of a leaf whose mask is all zeros, scalar or by row)
    and writes the result back in place, so a frozen row costs no pass at
    all and keeps the zero moments `init` gave it. What still arrives here
    with a mask is a leaf the view could not cut: an elementwise mask,
    rows that are not one run, a row that is not whole blocks.
    """
    count = state.count + 1
    c = count.astype(jnp.float32)
    bc1 = 1 - b1 ** c
    bc2 = 1 - b2 ** c
    lr = jnp.asarray(learning_rate, jnp.float32)

    def one(p, g, mq, vq, m):
        if m is not None and jnp.ndim(m) == 0:
            if float(m) == 0.0:  # frozen leaf: untouched params AND moments
                return p, mq, vq
            m = None  # scalar 1: no masking needed
        shape, size, dtype = p.shape, p.size, p.dtype
        pb = _to_blocks(p)
        gb = _to_blocks(g)
        nb = pb.shape[0]
        mb = None  # [nb] per-block mask scalars, or [nb, BLOCK] elementwise
        if m is not None:
            import numpy as _np

            tail = int(_np.prod(shape[1:], dtype=_np.int64)) if len(shape) > 1 else 1
            if (
                all(d == 1 for d in _np.shape(m)[1:])
                and _np.shape(m)[0] == shape[0]
                and tail % BLOCK == 0
            ):
                # layer masks [L, 1, ...]: constant within every block
                # (per-layer tail divides the block size), so ONE scalar
                # per block suffices — 6 MB at 1.3B where a broadcast
                # elementwise mask would be a 1.6 GB fp32 transient per
                # large leaf (measured OOM)
                layer_ix = (jnp.arange(nb) * BLOCK) // tail
                mb = jnp.ravel(jnp.asarray(m, jnp.float32))[layer_ix]
            else:
                mb = _to_blocks(
                    jnp.broadcast_to(jnp.asarray(m, jnp.float32), shape)
                )
        # pad the block count up to a whole number of target-size chunks
        # (an exact-divisor search can collapse to huge chunks — e.g. a
        # prime block count would force ONE full-leaf fp32 chunk, which
        # defeats the O(chunk) memory bound this function exists for);
        # the pad rows quantize zeros and are sliced off below
        cb = max(1, _FUSED_CHUNK_ELEMS // BLOCK)
        n_chunks = -(-nb // cb)
        pad_rows = n_chunks * cb - nb

        def padb(x):
            if not pad_rows:
                return x
            widths = ((0, pad_rows),) + ((0, 0),) * (x.ndim - 1)
            return jnp.pad(x, widths)

        pb, gb = padb(pb), padb(gb)
        if mb is not None:
            mb = padb(mb)
        mq_q, mq_s = padb(mq.q), padb(mq.scale)
        vq_q, vq_s = padb(vq.q), padb(vq.scale)

        def body(_, xs):
            if mb is not None:
                p_c, g_c, mq_c, ms_c, vq_c, vs_c, m_c = xs
            else:
                p_c, g_c, mq_c, ms_c, vq_c, vs_c = xs
                m_c = None
            g32 = g_c.astype(jnp.float32)
            m = b1 * _deq_blocks(mq_c, ms_c) + (1 - b1) * g32
            v = b2 * _deq_second_moment(vq_c, vs_c) + (1 - b2) * g32 * g32
            step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            p32 = p_c.astype(jnp.float32)
            if weight_decay:
                step = step + weight_decay * p32
            if m_c is not None:
                step = step * (m_c[:, None] if m_c.ndim == 1 else m_c)
            new_p = (p32 - lr * step).astype(dtype)
            nmq, nms = _quant_blocks(m)
            nvq, nvs = _quant_blocks(v)
            return None, (new_p, nmq, nms, nvq, nvs)

        chunk = lambda x: x.reshape((n_chunks, cb) + x.shape[1:])
        xs = (
            chunk(pb), chunk(gb), chunk(mq_q), chunk(mq_s),
            chunk(vq_q), chunk(vq_s),
        )
        if mb is not None:
            xs = xs + (chunk(mb),)
        _, (new_p, nmq, nms, nvq, nvs) = jax.lax.scan(body, None, xs)
        new_p = new_p.reshape(-1)[:size].reshape(shape)
        # strip the chunk-pad rows so state shapes match init's exactly
        return (
            new_p,
            Q8(nmq.reshape(-1, BLOCK)[:nb], nms.reshape(-1)[:nb], shape),
            Q8(nvq.reshape(-1, BLOCK)[:nb], nvs.reshape(-1)[:nb], shape),
        )

    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state.m)
    flat_v = tdef.flatten_up_to(state.v)
    flat_mask = (
        tdef.flatten_up_to(mask) if mask is not None else [None] * len(flat_p)
    )
    out = [
        one(p, g, m, v, mk)
        for p, g, m, v, mk in zip(flat_p, flat_g, flat_m, flat_v, flat_mask)
    ]
    return (
        tdef.unflatten([o[0] for o in out]),
        Adam8bitState(
            count=count,
            m=tdef.unflatten([o[1] for o in out]),
            v=tdef.unflatten([o[2] for o in out]),
        ),
    )


class FusedAdamW8bit:
    """Registry-wirable fused variant: holds the AdamW hyperparameters
    and exposes `init` (optax-shaped, so `init_sharded_opt_state` and
    state checkpointing work unchanged) plus `fused_apply(params, grads,
    state) -> (new_params, new_state)`, which the trainers' step uses
    instead of the update/apply_updates pair whenever present.

    Select with `optimizer.name: adamw_8bit_fused` in a TRLConfig — the
    memory-tight large-model recipe (configs/mesh/single_chip_1p3b.yml)
    reachable from config, not just hand-rolled steps. `learning_rate`
    may be an optax schedule; it is evaluated at the pre-increment step
    count, matching `optax.scale_by_learning_rate`'s cadence.
    """

    def __init__(self, learning_rate, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, weight_decay: float = 0.0):
        self.learning_rate = learning_rate
        self.b1, self.b2, self.eps = b1, b2, eps
        self.weight_decay = weight_decay

    def init(self, params) -> Adam8bitState:
        return _init_adam8bit_state(params)

    def update(self, grads, state, params=None):
        """optax-contract fallback so generic consumers (optax.chain,
        clipping wrappers, anything that composes transformations) still
        work: runs the fused step and returns the parameter DELTA as the
        updates tree. This materializes one extra params-sized tree —
        callers that can, should use `fused_apply` (the trainers do)."""
        if params is None:
            raise ValueError(
                "FusedAdamW8bit.update needs `params` (AdamW applies "
                "weight decay and writes parameters directly); pass "
                "params or use fused_apply(params, grads, state)"
            )
        new_params, new_state = self.fused_apply(params, grads, state)
        updates = jax.tree_util.tree_map(
            lambda n, p: (n - p).astype(p.dtype), new_params, params
        )
        return updates, new_state

    def fused_apply(self, params, grads, state: Adam8bitState, mask=None):
        lr = (
            self.learning_rate(state.count)
            if callable(self.learning_rate)
            else self.learning_rate
        )
        return fused_adamw_8bit_update(
            params, grads, state, lr, b1=self.b1, b2=self.b2, eps=self.eps,
            weight_decay=self.weight_decay, mask=mask,
        )
