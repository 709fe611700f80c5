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

import functools
from typing import Dict, MutableMapping, Optional, Tuple, Union

import flax.struct
import jax
import jax.numpy as jnp


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

    Returns fp32 logprobs with the shape of `labels`.
    """
    B, T = labels.shape
    ck = -(-T // n_chunks)
    pad = n_chunks * ck - T
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    hs = hidden.reshape(B, n_chunks, ck, hidden.shape[-1]).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n_chunks, ck).transpose(1, 0, 2)

    def body(carry, xt):
        h, lab = xt
        return carry, logprobs_of_labels(project_fn(h), lab)

    body = jax.checkpoint(body, prevent_cse=False)
    _, lp = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    lp = lp.transpose(1, 0, 2).reshape(B, n_chunks * ck)
    return lp[:, :T]


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
