"""Pallas decode step of a recurrent mixer: ONE pass over the carried state.

A delta-rule (KDA) or state-space (Mamba-2) layer keeps a float32 state
`[a, b]` a head in place of a cache (`[dk, dv]` = 128 x 128; `[P, N]` =
64 x 128), stacked `[layers, B, H, a, b]` in the sampler's carry. A decode
step decays every head's tile, adds a rank-one update and reads it out.
Through XLA ops that is three to four passes over the layer's rows (the
slice out of the carry, the decayed state times k, the update written
back, the read-out: 0.357 ms a layer a step over 67 MB for the delta rule,
0.59 ms over 134 MB for the state space; PERF.md section 5, PR 36), each
at 650-750 GB/s: no pass can be made much faster, so this kernel makes
one. A grid cell is one row and a block of heads (`head_block`: 2 MB, so
that the memory and not the grid sets the pace); it streams its tiles
out of the CARRIED array (the layer index is a scalar-prefetch argument,
as in ops/decode_attention.py: a slice taken in XLA would be a copy of
the layer), does the whole step on them in VMEM, float32 throughout and
on the VPU, and stores them back IN PLACE: the carry is aliased to the
kernel's output, the layer's blocks are the only ones written and every
other layer's bytes stay where they are. On the chip (PR 38, the cells'
shapes, 32 rows): 220 us a call for the delta rule (372 us the XLA branch),
423 us for the state space (605 us): 610 and 635 GB/s read and written.
That is the pipeline's pace, not the arithmetic's: with the products or
the read-out taken out the calls take the same time, and cells of 1 MB
or 4 MB no less.

One algorithm, two static kinds:

  delta   S_d = Diag(exp(g)) S;  S' = S_d + (beta k) (x) (v - S_d^T k);  o = S'^T q
          the decay a vector over `a`, a delta correction, the read-out
          reduces `a` (the tile's second-minor axis): a row of `b`
  ssm     h' = exp(dt a) h + (dt x) (x) B;  y = h' C
          the decay a scalar a head, no correction, the read-out reduces
          `b` (the minor axis): a column of `a`

What multiplies a tile along `a` (exp(g), k, beta k, q; the decay and
dt x) arrives `[B, H, a]`, `a` minor, as the mixer computes it, and
reaches a cell as the block of its row and heads; the kernel transposes
a cell's few vectors once (one small tile a cell, not a pass over the
state) and broadcasts a head's column along the lanes. What
multiplies along `b` (v; B and C of the head's group) is a row as it
comes. The state space's read-out sums along the lanes: two heads'
products (64 rows each) are transposed together and summed along the
sublanes, which leaves their 128 values side by side as `[H, P]` holds
them (a lane reduction a head and its columns put together cost a fifth
more than the whole call). The arithmetic is `kda_step` / `ssm_step`'s
of models/transformer.py, the new state in their order of operations (on
the chip bit for bit theirs); a masked row (g = 0, beta = 0; dt = 0)
leaves its state bit for bit.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from trlx_tpu.ops.common import interpret_mode as _interpret

# float32 state bytes of one grid cell. In, out and double-buffered a cell
# holds four times this in VMEM, under the 16 MiB of scoped VMEM; a head's
# tile alone (64 KB / 32 KB) would make the grid, not the memory, set the
# pace (a grid step costs about 0.35 us, the tile's bytes 0.1-0.2 us:
# ops/decode_attention.py found the same, the note at its CELL_BYTES)
CELL_BYTES = 2 << 20


def head_block(H: int, a: int, b: int, rep: int = 1, cell_bytes: int = CELL_BYTES) -> int:
    """Heads of one grid cell: the most whole groups of `rep` heads that
    divide `H` and keep the cell's float32 tiles within `cell_bytes`
    (never under one group)."""
    groups = H // rep
    fit = max(1, cell_bytes // (rep * a * b * 4))
    return rep * max(d for d in range(1, groups + 1) if groups % d == 0 and d <= fit)


def _readout_heads(Hb: int, a: int) -> int:
    """Heads whose read-out along `b` is taken together (ssm): the most that
    divide the cell's `Hb` and whose `a` values fill at most 128 lanes."""
    return max(d for d in range(1, Hb + 1) if Hb % d == 0 and d * a <= max(a, 128))


def _kernel(ix_ref, s_ref, *refs, kind: str, rep: int, n: int):
    """One (row, block of heads) cell. `s_ref`, `s_out` [1, 1, Hb, a, b]:
    the cell's tiles of the carried state, in and (the same bytes) out.
    Then the `n` vectors a head that multiply along `a`, each [1, 1, Hb,
    a], and what multiplies along `b`, each [1, 1, R, b] (delta: v a head;
    ssm: B and C of the block's groups). `o_ref`: the read-out, delta
    [1, 1, Hb, b], ssm [1, 1, Hb / m, m a] (m heads' values side by side,
    as they lie in [H, a])."""
    del ix_ref  # consumed by the index maps
    *vectors, s_out, o_ref = refs
    Hb = s_ref.shape[2]
    cols = jnp.concatenate([ref[0, 0] for ref in vectors[:n]], axis=0).T  # [a, n Hb]: a head's vectors as columns
    col = lambda i, h: cols[:, i * Hb + h : i * Hb + h + 1]  # [a, 1]
    row = lambda i, r: vectors[n + i][0, 0, r : r + 1, :]  # [1, b]
    together, read = Hb // o_ref.shape[2], []  # (ssm) heads whose read-out is one row of `o_ref`
    for h in range(Hb):  # static unroll: a head's tile is a few vector registers
        s = s_ref[0, 0, h] * col(0, h)
        if kind == "delta":
            seen = jnp.sum(s * col(1, h), axis=0, keepdims=True)  # S_d^T k
            s = s + col(2, h) * (row(0, h) - seen)
            o_ref[0, 0, h : h + 1, :] = jnp.sum(s * col(3, h), axis=0, keepdims=True)
        else:
            s = s + col(1, h) * row(0, h // rep)
            # h' C sums along the lanes: a few heads' products are turned
            # over together and summed along the sublanes instead, which
            # leaves their values side by side, as the output holds them
            read.append(s * row(1, h // rep))
            if len(read) == together:
                at = h // together
                o_ref[0, 0, at : at + 1, :] = jnp.sum(jnp.concatenate(read, axis=0).T, axis=0, keepdims=True)
                read = []
        s_out[0, 0, h] = s


def _state_step(kind: str, s, layer_ix, cols, rows, rep: int, cell_bytes: int):
    """The one `pallas_call`: `s` [layers, B, H, a, b] float32, the whole
    stacked carry; `cols` the vectors [B, H, a] and `rows` the vectors
    [B, H / rep, b], float32 -> (read-out [B, H, b or a], the carry with
    layer `layer_ix` stepped). A vector reaches a cell as the block of its
    rows and heads (groups): `[B, H, .]` seen as `[B, H / Hb, Hb, .]`."""
    _, B, H, a, b = s.shape
    Hb = head_block(H, a, b, rep, cell_bytes)
    nb = H // Hb
    vectors = [x.reshape(B, nb, -1, x.shape[-1]) for x in (*cols, *rows)]
    together = 1 if kind == "delta" else _readout_heads(Hb, a)
    out = (Hb, b) if kind == "delta" else (Hb // together, together * a)
    cell = lambda *tail: pl.BlockSpec((1, 1) + tail, lambda i, j, ix: (i, j) + (0,) * len(tail))
    state = pl.BlockSpec((1, 1, Hb, a, b), lambda i, j, ix: (ix[0], i, j, 0, 0))
    s, o = pl.pallas_call(
        functools.partial(_kernel, kind=kind, rep=rep, n=len(cols)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[state] + [cell(*x.shape[2:]) for x in vectors],
            out_specs=[state, cell(*out)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct((B, nb) + out, jnp.float32),
        ],
        # operand 1 (after the prefetched index) is the carry: output 0 is its bytes
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
        name="state_step",
    )(jnp.reshape(layer_ix, (1,)).astype(jnp.int32), s, *vectors)
    return o.reshape(B, H, -1), s


def delta_state_step(s, layer_ix, q, k, v, g, beta, cell_bytes: int = CELL_BYTES) -> Tuple[jax.Array, jax.Array]:
    """`kda_step` on layer `layer_ix` of the carried `s` [layers, B, H, dk,
    dv] float32: q, k, g [B, H, dk], v [B, H, dv], beta [B, H] ->
    (o [B, H, dv] float32, the carry with that layer's rows stepped in
    place)."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    return _state_step("delta", s, layer_ix, (jnp.exp(g), k, beta[..., None] * k, q), (v,), 1, cell_bytes)


def ssm_state_step(s, layer_ix, x, Bm, Cm, dt, a, cell_bytes: int = CELL_BYTES) -> Tuple[jax.Array, jax.Array]:
    """`ssm_step` on layer `layer_ix` of the carried `s` [layers, B, H, P,
    N] float32: x [B, H, P], Bm, Cm [B, G, N], dt [B, H] float32, a [H]
    -> (y [B, H, P] float32, the carry with that layer's rows stepped in
    place)."""
    f32 = jnp.float32
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    cols, rows = (decay, dt[..., None] * x.astype(f32)), (Bm.astype(f32), Cm.astype(f32))
    return _state_step("ssm", s, layer_ix, cols, rows, x.shape[1] // Bm.shape[1], cell_bytes)
