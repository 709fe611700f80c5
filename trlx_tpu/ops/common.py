"""Shared numeric primitives.

Parity: /root/reference/trlx/utils/modeling.py:185-314 (whiten,
logprobs_of_labels, get_tensor_stats, RunningMoments, flatten_dict) and
/root/reference/trlx/models/modeling_ilql.py:29-46 (topk_mask,
batched_index_select) — re-expressed as pure JAX.

Distribution note: these run inside `jit` over a `Mesh` with batch
sharded along `dp`. GSPMD makes `jnp.mean`/`jnp.sum` global across the
mesh automatically, so the reference's explicit all_reduce paths
(get_global_statistics) need no separate "distributed" branch. An
optional `axis_name` argument covers `shard_map`/`pmap` contexts where
reductions are per-shard.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, MutableMapping, Optional, Tuple, Union

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import PartitionSpec as P


def masked_mean(xs: jnp.ndarray, mask: Optional[jnp.ndarray], axis=None) -> jnp.ndarray:
    if mask is None:
        return jnp.mean(xs, axis=axis)
    mask = mask.astype(xs.dtype)
    return (xs * mask).sum(axis=axis) / jnp.maximum(mask.sum(axis=axis), 1e-8)


def _global_mean_var(
    xs: jnp.ndarray, axis_name: Optional[str] = None
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Element-count, mean and (biased) variance, reduced over `axis_name`
    if inside shard_map/pmap, else over the (logically global) array."""
    count = jnp.asarray(xs.size, jnp.float32)
    total = xs.sum()
    if axis_name is not None:
        count = jax.lax.psum(count, axis_name)
        total = jax.lax.psum(total, axis_name)
    mean = total / count
    sq = ((xs - mean) ** 2).sum()
    if axis_name is not None:
        sq = jax.lax.psum(sq, axis_name)
    return mean, sq / count, count


def whiten(
    xs: jnp.ndarray,
    shift_mean: bool = True,
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """Normalize to zero mean / unit variance (across the global batch).

    Uses the UNBIASED variance, matching the reference's single-process
    path (`torch.var_mean` default — utils/modeling.py:212), which is
    what its published curves were trained with. (The reference's
    distributed branch divides by N instead — an inconsistency we don't
    reproduce; golden tests pin the single-process numbers.)
    """
    mean, var, count = _global_mean_var(xs, axis_name)
    var = var * count / jnp.maximum(count - 1, 1.0)
    whitened = (xs - mean) * jax.lax.rsqrt(var + 1e-8)
    if not shift_mean:
        whitened = whitened + mean
    return whitened


def logprobs_of_labels(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """log p(label_t) from logits [..., seq, vocab] and labels [..., seq].

    Computed without materializing the full log-softmax gather in fp32 HBM:
    logsumexp is fused by XLA with the label gather.
    """
    labels = labels[..., None]
    picked = jnp.take_along_axis(logits, labels, axis=-1)[..., 0]
    return picked.astype(jnp.float32) - jax.nn.logsumexp(
        logits.astype(jnp.float32), axis=-1
    )


def _chunk_seq(n_chunks: int, *xs: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """Each [B, T, ...] -> [n_chunks, B, ceil(T/n_chunks), ...], the
    sequence zero-padded to whole chunks."""
    B, T = xs[0].shape[:2]
    ck = -(-T // n_chunks)
    pad = n_chunks * ck - T
    if pad:
        xs = [jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in xs]
    return tuple(jnp.swapaxes(x.reshape((B, n_chunks, ck) + x.shape[2:]), 0, 1) for x in xs)


def _unchunk_seq(xs: jnp.ndarray, T: int) -> jnp.ndarray:
    """`_chunk_seq`'s inverse: [n_chunks, B, ck, ...] -> [B, T, ...]."""
    n_chunks, B, ck = xs.shape[:3]
    return jnp.swapaxes(xs, 0, 1).reshape((B, n_chunks * ck) + xs.shape[3:])[:, :T]


def _project(h: jnp.ndarray, kernel: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum(
        "...e,ev->...v", h, kernel.astype(h.dtype),
        preferred_element_type=jnp.float32,
    )


@dataclasses.dataclass(frozen=True, eq=False)
class UntiedHead:
    """An untied head `kernel [E, V]` as a hidden -> fp32 logits
    projection (models.transformer.logit_projection), with the mesh its
    kernel is laid out on (None: no mesh). `chunked_logprobs` over it
    gathers the head once per call where `head_gathered_once` holds."""

    kernel: jnp.ndarray
    mesh: Any = None

    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        return _project(h, self.kernel)


def head_gathered_once(project_fn, rows: int) -> bool:
    """Whether `chunked_logprobs(project_fn, ...)` over `rows` rows runs
    as one shard_map over the rows (`_gathered_head_logprobs`): an
    untied head (`UntiedHead`) whose `E` the mesh's `fsdp` axis splits
    (parallel/sharding.py: `P("fsdp", "tp")`), no `tp`, `pp` or `sp`
    axis, and rows that divide over `dp * fsdp`. Elsewhere the chunk scan
    closes over the kernel and GSPMD lays it out: on `fsdp` that gathers
    the head and all-reduces its gradient once per chunk. Static, like
    `models.transformer.decode_weights_stationary`: `chunked_logprobs`
    asks at trace time, the trainer on the host for the gauge
    `model/head_gathered_once`."""
    if not isinstance(project_fn, UntiedHead) or project_fn.mesh is None:
        return False
    m = project_fn.mesh.shape
    return (
        m["fsdp"] > 1 and m["tp"] == m["pp"] == m["sp"] == 1
        and project_fn.kernel.shape[0] % m["fsdp"] == 0
        and rows % (m["dp"] * m["fsdp"]) == 0
    )


_ROWS = ("dp", "fsdp")


def _fsdp_ring(mesh) -> Optional[Tuple[int, ...]]:
    """The `fsdp` axis's indices in the order of a ring through its chips,
    as `jax.experimental.mesh_utils` finds one on a TPU torus, or None
    where that is the axis's own order (or where it finds none: CPU
    devices have no coordinates). XLA:TPU reduce-scatters a large array
    over a group listed out of ring order (a 2x2 host's devices 0-3 are
    not a ring: 0-1-3-2 is) as an all-reduce and a slice, which moves
    twice the bytes."""
    fsdp = mesh.shape["fsdp"]
    line = list(np.moveaxis(mesh.devices, mesh.axis_names.index("fsdp"), 0).reshape(fsdp, -1)[:, 0])
    try:
        ring = mesh_utils.create_device_mesh((fsdp,), devices=line)
    except (AssertionError, NotImplementedError, ValueError):  # not a whole box of chips
        return None
    order = tuple(line.index(d) for d in ring)
    return None if order == tuple(range(fsdp)) else order


def _in_ring_order(x: jnp.ndarray, order) -> jnp.ndarray:
    """`x`'s last axis as `len(order)` blocks, put in `order` (None: as it is)."""
    if order is None:
        return x
    parts = jnp.split(x, len(order), axis=-1)
    return jnp.concatenate([parts[i] for i in order], axis=-1)


def _gather_head(kernel: jnp.ndarray, dtype, order) -> jnp.ndarray:
    """A chip's `[E / fsdp, V]` shard of the head, cast, gathered whole
    with its row blocks in ring `order`."""
    return jax.lax.all_gather(
        kernel.astype(dtype), "fsdp", axis=0, tiled=True,
        axis_index_groups=None if order is None else [list(order)],
    )


def _logprobs_of_chunk(h, kernel, labels):
    return logprobs_of_labels(_project(h, kernel), labels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gathered_head_logprobs(kernel, hidden, labels, n_chunks: int, mesh):
    """`chunked_logprobs` where `head_gathered_once` holds: each chip
    gathers the head once and scans its own rows chunk by chunk against
    it. The backward (`_gathered_head_bwd`) adds the chunks' head
    gradients into a chip-local float32 sum and reduce-scatters it once,
    back to the kernel's layout. Head rows and hidden features both go
    in the ring's block order (`_fsdp_ring`): the product is the same."""
    order = _fsdp_ring(mesh)

    def local(k, h, lab):
        head = _gather_head(k, h.dtype, order)

        def body(carry, xt):
            return carry, _logprobs_of_chunk(xt[0], head, xt[1])

        _, lp = jax.lax.scan(
            body, jnp.zeros((), jnp.float32),
            _chunk_seq(n_chunks, _in_ring_order(h, order), lab),
        )
        return _unchunk_seq(lp, lab.shape[1])

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("fsdp", None), P(_ROWS, None, None), P(_ROWS, None)),
        out_specs=P(_ROWS, None), check_vma=False,
    )(kernel, hidden, labels)


def _gathered_head_fwd(kernel, hidden, labels, n_chunks, mesh):
    lp = _gathered_head_logprobs(kernel, hidden, labels, n_chunks, mesh)
    return lp, (kernel, hidden, labels)


def _gathered_head_bwd(n_chunks, mesh, res, g):
    kernel, hidden, labels = res
    order = _fsdp_ring(mesh)
    back = None if order is None else tuple(np.argsort(order))

    def local(k, h, lab, g):
        head = _gather_head(k, h.dtype, order)

        def body(acc, xt):
            hc, lc, gc = xt
            _, vjp = jax.vjp(lambda x, w: _logprobs_of_chunk(x, w, lc), hc, head)
            dh, dw = vjp(gc)
            return acc + dw.astype(jnp.float32), dh

        acc, dh = jax.lax.scan(
            body, jnp.zeros(head.shape, jnp.float32),
            _chunk_seq(n_chunks, _in_ring_order(h, order), lab, g),
        )
        # the transpose of the one gather: each chip keeps the sum over
        # every chip's rows of its own block of E, then over dp replicas
        dk = jax.lax.psum_scatter(
            acc, "fsdp", tiled=True,
            axis_index_groups=None if order is None else [list(order)],
        )
        if mesh.shape["dp"] > 1:
            dk = jax.lax.psum(dk, "dp")
        return dk.astype(k.dtype), _in_ring_order(_unchunk_seq(dh, lab.shape[1]), back)

    dk, dh = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("fsdp", None), P(_ROWS, None, None), P(_ROWS, None), P(_ROWS, None)),
        out_specs=(P("fsdp", None), P(_ROWS, None, None)), check_vma=False,
    )(kernel, hidden, labels, g)
    return dk, dh, None


_gathered_head_logprobs.defvjp(_gathered_head_fwd, _gathered_head_bwd)


@jax.named_scope("logprobs")
def chunked_logprobs(
    project_fn,
    hidden: jnp.ndarray,
    labels: jnp.ndarray,
    n_chunks: int,
) -> jnp.ndarray:
    """Per-token log p(label) from hidden states, never materializing the
    full [batch, seq, vocab] logits.

    `project_fn(hidden_chunk) -> logits_chunk` is the model's hidden->
    logits projection (models.transformer.logit_projection /
    models.seq2seq.t5_logit_projection — same einsum/dtype contract as
    the in-model `_logits`, so this path is numerically the full-logits
    path up to reduction order). The sequence axis is split into
    `n_chunks` pieces and scanned with `jax.checkpoint`: the backward
    recomputes each chunk's logits, so peak logit residency is
    [batch, ceil(seq/n_chunks), vocab] instead of [batch, seq, vocab] —
    at b8/seq2048/vocab50257 fp32 that's 0.4 GB instead of 3.3 GB, the
    difference between the 1.3B recipe fitting one 16 GB chip or not.

    An untied head split over `fsdp` (`head_gathered_once`) takes the
    same chunks inside one shard_map over the rows, gathering the head
    once per call instead of once per chunk.

    Returns fp32 logprobs with the shape of `labels`.
    """
    if head_gathered_once(project_fn, labels.shape[0]):
        return _gathered_head_logprobs(
            project_fn.kernel, hidden, labels, n_chunks, project_fn.mesh
        )

    def body(carry, xt):
        h, lab = xt
        return carry, logprobs_of_labels(project_fn(h), lab)

    body = jax.checkpoint(body, prevent_cse=False)
    _, lp = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        _chunk_seq(n_chunks, hidden, labels),
    )
    return _unchunk_seq(lp, labels.shape[1])


def topk_mask(xs: jnp.ndarray, k: int) -> jnp.ndarray:
    """Mask all but the top-k logits to -inf (k >= vocab is a no-op)."""
    if k <= 0 or k >= xs.shape[-1]:
        return xs
    kth = jax.lax.top_k(xs, k)[0][..., -1:]
    return jnp.where(xs < kth, -jnp.inf, xs)


def batched_index_select(x: jnp.ndarray, idxs: jnp.ndarray, dim: int = 1) -> jnp.ndarray:
    """Gather rows of x [batch, seq, hidden] at idxs [batch, n] along `dim`."""
    idxs = jnp.expand_dims(idxs, -1)
    if x.ndim == idxs.ndim:
        idxs = jnp.broadcast_to(idxs, idxs.shape[:-1] + (x.shape[-1],))
        return jnp.take_along_axis(x, idxs, axis=dim)
    return jnp.take_along_axis(x, idxs[..., 0], axis=dim)


def get_tensor_stats(xs: jnp.ndarray, mask: jnp.ndarray, n) -> Dict[str, jnp.ndarray]:
    """mean/min/max/std over masked entries (parity: utils/modeling.py:269-279)."""
    if xs.size == 0:
        zero = jnp.float32(0)
        return dict(mean=zero, min=zero, max=zero, std=zero)
    mask = mask.astype(xs.dtype)
    n = jnp.maximum(n, 1e-8)
    mean = (xs * mask).sum() / n
    return dict(
        mean=mean,
        min=jnp.where(mask > 0, xs, jnp.inf).min(),
        max=jnp.where(mask > 0, xs, -jnp.inf).max(),
        std=jnp.sqrt((((xs - mean) * mask) ** 2).sum() / n),
    )


def flatten_dict(d: Union[dict, MutableMapping], parent_key: str = "", sep: str = "/") -> dict:
    """{"a": {"b": 1}} -> {"a/b": 1} (metric-key parity with the reference)."""
    items = {}
    for k, v in d.items():
        key = f"{parent_key}{sep}{k}" if parent_key else str(k)
        if isinstance(v, MutableMapping):
            items.update(flatten_dict(v, key, sep=sep))
        else:
            items[key] = v
    return items


# ---------------------------------------------------------------------------
# Running moments — functional state (Chan et al. parallel variance), the
# pytree version of reference RunningMoments (utils/modeling.py:282-314).
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class RunningMoments:
    mean: jnp.ndarray
    var: jnp.ndarray
    std: jnp.ndarray
    count: jnp.ndarray


def running_moments_init() -> RunningMoments:
    return RunningMoments(
        mean=jnp.float32(0.0),
        var=jnp.float32(1.0),
        std=jnp.float32(1.0),
        count=jnp.float32(1e-24),
    )


def running_moments_update(
    state: RunningMoments, xs: jnp.ndarray, axis_name: Optional[str] = None
) -> Tuple[RunningMoments, jnp.ndarray, jnp.ndarray]:
    """Fold a batch into the running moments.

    Returns (new_state, batch_mean, batch_std) where batch_std is the
    unbiased standard deviation of `xs` itself.
    """
    xs_mean, xs_var, xs_count = _global_mean_var(xs, axis_name)
    delta = xs_mean - state.mean
    tot_count = state.count + xs_count

    new_sum = xs_var * xs_count
    old_sum = state.var * state.count + delta**2 * state.count * xs_count / tot_count
    tot_sum = old_sum + new_sum

    new_mean = state.mean + delta * xs_count / tot_count
    new_var = tot_sum / tot_count
    new_state = RunningMoments(
        mean=new_mean,
        var=new_var,
        std=jnp.sqrt(new_var * tot_count / jnp.maximum(tot_count - 1, 1e-8)),
        count=tot_count,
    )
    batch_std = jnp.sqrt(xs_var * xs_count / jnp.maximum(xs_count - 1, 1e-8))
    return new_state, xs_mean, batch_std


# ---------------------------------------------------------------------------
# Shared pallas plumbing — every kernel family (ops/flash_attention.py,
# ops/decode_attention.py, the paged decode kernel) makes the same two
# decisions the same way; private per-file copies of these had already
# drifted into three call sites before they were factored here.
# ---------------------------------------------------------------------------


def interpret_mode() -> bool:
    """True when pallas kernels should run interpreted (no Mosaic on
    this backend). CPU-only: TPU/GPU lower for real. Tier-1 runs every
    kernel through this path, which is what makes kernel==reference
    goldens runnable without device time."""
    return jax.default_backend() == "cpu"


@functools.lru_cache(maxsize=None)
def warn_pallas_fallback(where: str, why: str) -> None:
    """`attention_impl="pallas"` was asked for and `where` takes the XLA
    path instead: one warning per distinct (where, why) — the cache is
    the log-once — so a run can always tell which implementation ran."""
    from trlx_tpu.utils import logging

    logging.get_logger(__name__).warning(
        "attention_impl=pallas: %s runs on the XLA path (%s)", where, why
    )


def pick_block(n: int, block: int) -> int:
    """Largest power-of-two shrink of `block` that divides `n` (from
    min(block, n) downward). Callers gate `n` on their own alignment
    floors (e.g. 128-divisibility for lane-dim dynamic slices)."""
    b = min(block, n)
    while n % b:
        b //= 2
    return b
