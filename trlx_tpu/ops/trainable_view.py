"""A static view of what an optimizer step trains.

A freeze mask (`TPUBaseTrainer.make_freeze_mask`) is concrete when a train
step is traced, and block parameters are stacked `[L, ...]` leaves whose
trained rows are a suffix (a layer trains iff its index is at or above the
branch point). So what a step has to walk is known before it is built: of
each leaf the whole, a run of rows, or nothing. `trainable_view` reads that
off the mask and the shapes; `cut` takes the view of a tree (parameters,
gradients, the optimizer's state), the optimizer runs on it, and `paste`
writes the result back into the full (donated) leaves with
`lax.dynamic_update_slice` at static offsets, in place. The state's layout
does not change: frozen rows keep the zero moments `init` gave them, which
is what a walk over them with a zero gradient and a zero multiplier left
there too, bit for bit.

A mark is one of `WHOLE`, `NOTHING`, `MASKED` (the whole leaf walked with
its mask, the path every masked leaf took before there was a view: an
elementwise mask, rows that are not one run, a row's elements not whole
int8 blocks) or `Rows(lo, hi)`. A tree cut to the
view has None where nothing is walked, which JAX and optax read as an empty
subtree."""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
import optax

from trlx_tpu.ops.adam8bit import BLOCK, Q8

WHOLE, NOTHING, MASKED = "whole", "nothing", "masked"


@dataclasses.dataclass(frozen=True)
class Rows:
    """Rows `[lo, hi)` of a stacked leaf `[L, ...]`."""

    lo: int
    hi: int


def trainable_view(mask, params):
    """Tree of marks for a {0,1} update-multiplier tree (None for None)."""
    if mask is None:
        return None

    def mark(m, p):
        m, shape = np.asarray(m), tuple(p.shape)
        if m.ndim == 0:
            return WHOLE if m == 1 else NOTHING if m == 0 else MASKED
        if m.shape != shape[:1] + (1,) * (len(shape) - 1) or not np.isin(m, (0, 1)).all():
            return MASKED
        on = np.flatnonzero(m)
        if on.size in (0, shape[0]):
            return WHOLE if on.size else NOTHING
        lo, hi = int(on[0]), int(on[-1]) + 1
        # one run of rows, each a whole number of int8 moment blocks, so that
        # the rows' blocks of `Q8.q` / `Q8.scale` are a contiguous range
        if hi - lo == on.size and math.prod(shape[1:]) % BLOCK == 0:
            return Rows(lo, hi)
        return MASKED

    return jax.tree_util.tree_map(mark, mask, params)


def view_mask(mask, view):
    """The mask a step on the view still has to apply: a leaf's own where it
    fell back (`MASKED`), None wherever the cut left only trained elements;
    without a view, the mask as it is."""
    if view is None:
        return mask
    return jax.tree_util.tree_map(lambda mark, m: m if mark == MASKED else None, view, mask)


def state_view(tx, opt_state, view):
    """The view of an optimizer state: each tree in it that mirrors the
    parameters (optax finds them by initialising on a placeholder) gets the
    parameters' marks, everything else (step counts) is taken whole."""
    if view is None:
        return None
    return optax.tree_utils.tree_map_params(
        tx, lambda _, mark: mark, opt_state, view,
        transform_non_params=lambda _: WHOLE, is_leaf=lambda x: isinstance(x, Q8),
    )


def _blocks(mark: Rows, shape):
    per_row = math.prod(shape[1:]) // BLOCK
    return mark.lo * per_row, mark.hi * per_row


def cut(tree, view):
    """`tree` (arrays or `Q8` moments where `view` has marks) cut to the view."""
    if view is None:
        return tree

    def leaf(mark, x):
        if mark == NOTHING:
            return None
        if not isinstance(mark, Rows):
            return x
        if isinstance(x, Q8):
            b0, b1 = _blocks(mark, x.shape)
            return Q8(x.q[b0:b1], x.scale[b0:b1], (mark.hi - mark.lo,) + tuple(x.shape[1:]))
        return x[mark.lo:mark.hi]

    return jax.tree_util.tree_map(leaf, view, tree)


def paste(full, new, view):
    """`new` (a tree cut to the view, updated) written back into `full`."""
    if view is None:
        return new

    def update(x, rows, start):
        return jax.lax.dynamic_update_slice(x, rows, (start,) + (0,) * (x.ndim - 1))

    def leaf(mark, x, rows):
        if mark == NOTHING:
            return x
        if not isinstance(mark, Rows):
            return rows
        if isinstance(x, Q8):
            b0, _ = _blocks(mark, x.shape)
            return Q8(update(x.q, rows.q, b0), update(x.scale, rows.scale, b0), x.shape)
        return update(x, rows, mark.lo)

    return jax.tree_util.tree_map(leaf, view, full, new)


def counts(params, mask, view):
    """(elements a step on the view streams through the optimizer, elements
    whose mask is 1): the gauges `optim/params_walked`, `optim/params_trained`."""
    leaves = jax.tree_util.tree_leaves(params)
    sizes = [math.prod(p.shape) for p in leaves]
    if view is None:
        return sum(sizes), sum(sizes)
    walked = sum(
        0 if mark == NOTHING
        else (mark.hi - mark.lo) * (size // p.shape[0]) if isinstance(mark, Rows)
        else size
        for mark, size, p in zip(jax.tree_util.tree_leaves(view), sizes, leaves)
    )
    # a mask broadcasts over its leaf: each of its elements stands for size / m.size
    trained = sum(
        int(np.sum(np.asarray(m), dtype=np.float64)) * (size // max(np.size(m), 1))
        for m, size in zip(jax.tree_util.tree_leaves(mask), sizes)
    )
    return walked, trained
