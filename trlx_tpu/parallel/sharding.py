"""Path-rule PartitionSpecs for the stacked-layer param tree.

Megatron TP parity (reference modeling_nemo_ppo.py:67-127 Column/Row
ParallelLinear, configs/nemo_configs/*.yaml `tensor_model_parallel_size`)
expressed as data layout, not module classes:

  q/k/v kernels  [L, E, H, D]  heads over `tp`, E over `fsdp`   (column-parallel)
  o kernel       [L, H, D, E]  heads over `tp`, E over `fsdp`   (row-parallel)
  mlp fc_in      [L, E, F]     F over `tp`                      (column-parallel)
  mlp fc_out     [L, F, E]     F over `tp`                      (row-parallel)
  embedding      [V, E]        vocab over `tp` (vocab-parallel embedding)
  lm_head        [E, V]        vocab over `tp` (vocab-parallel logits)

Everything also shards over `fsdp` on a non-tp dim: that is ZeRO-3
(DeepSpeed zero3.yaml parity). Batch rows shard over the same axis
(parallel/mesh.py `batch_pspec`), so wherever a forward carries many
tokens (a train step, scoring, prefill) XLA all-gathers params per
layer inside the scan and reduce-scatters grads, which is exactly the
ZeRO-3 schedule: a gather is paid once for thousands of tokens. A decode
step (T == 1) carries one token a row, 20,000 times fewer bytes than
its kernels, so there the kernels stay where they are and the step's
activations move instead (`DecodeLayouts`, below): same rules, same
single copy of every weight.

Rules match on the param path; unknown params fall back to replicated.
A spec axis is silently dropped when the dim size is not divisible by the
mesh axis (e.g. tiny test models on an 8-way mesh).
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trlx_tpu.parallel.mesh import batch_pspec

# (path regex, spec) — first match wins. Paths look like
# "base/blocks/attn/q/kernel", "base/embed/wte", "heads/q_heads/0/fc_in/kernel".
_RULES: List[Tuple[str, P]] = [
    (r"(^|/)embed/wte$", P("tp", "fsdp")),
    (r"(^|/)embed/wpe$", P(None, "fsdp")),
    # stacked blocks [L, ...]: the leading layer axis shards over `pp` —
    # each pipeline stage owns a contiguous slice (parallel/pipeline.py);
    # with pp=1 (the default) the entry is a no-op
    (r"(^|/)blocks/attn/[qkv]/kernel$", P("pp", "fsdp", "tp", None)),
    (r"(^|/)blocks/attn/[qkv]/bias$", P("pp", "tp", None)),
    (r"(^|/)blocks/attn/o/kernel$", P("pp", "tp", None, "fsdp")),
    (r"(^|/)blocks/attn/o/bias$", P("pp", None)),
    (r"(^|/)blocks/mlp/fc_(in|gate)/kernel$", P("pp", "fsdp", "tp")),
    (r"(^|/)blocks/mlp/fc_(in|gate)/bias$", P("pp", "tp")),
    (r"(^|/)blocks/mlp/fc_out/kernel$", P("pp", "tp", "fsdp")),
    (r"(^|/)blocks/mlp/fc_out/bias$", P("pp", None)),
    # any other per-layer param (layer norms): layer axis over pp only
    (r"(^|/)blocks/", P("pp")),
    (r"(^|/)lm_head/kernel$", P("fsdp", "tp")),
    # aux heads (value / Q): small — shard the wide input dim over fsdp only
    (r"(^|/)(v_head|q_heads(/\d+)?|target_q_heads(/\d+)?)/fc_in/kernel$", P("fsdp", None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_path(path_str: str) -> P:
    for pattern, spec in _RULES:
        if re.search(pattern, path_str):
            return spec
    return P()


def _fit_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Pad/trim a spec to the array rank and drop axes that don't divide
    the corresponding dim (tiny models on big meshes stay replicated)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    entries = entries[: len(shape)]
    fitted = []
    for dim, axis in zip(shape, entries):
        if axis is None:
            fitted.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        fitted.append(axis if dim % size == 0 else None)
    return P(*fitted)


def infer_param_pspecs(params: Dict, mesh: Optional[Mesh] = None) -> Dict:
    """PartitionSpec tree for a param tree (shape-fitted if mesh given)."""

    def leaf_spec(path, leaf):
        spec = spec_for_path(_path_str(path))
        if mesh is not None:
            spec = _fit_spec(spec, np.shape(leaf), mesh)
        return spec

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def param_shardings(mesh: Mesh, params: Dict) -> Dict:
    """NamedSharding tree for a param tree."""
    specs = infer_param_pspecs(params, mesh)
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def shard_params(mesh: Mesh, params: Dict) -> Dict:
    """device_put the tree with its inferred shardings (host numpy in,
    committed sharded device arrays out)."""
    shardings = param_shardings(mesh, params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), params, shardings
    )


class DecodeLayouts:
    """Where one decode step's activations `[B, 1, N, ...]` live on a mesh
    whose `fsdp` axis shards every kernel on its `E` dimension (the rules
    above), so that each chip multiplies with the shard it already holds:

      residual  [B, 1, E]       rows by chip, `E` whole: the cache's rows,
                                the norms, sampling
      split     [B, 1, E]       rows whole over `fsdp`, `E` over `fsdp`: what
                                q, k, v, fc_in, fc_gate and the head contract
                                (their partial products, float32, are then
                                reduced and scattered into `rows`) and what
                                o and fc_out produce
      rows      [B, 1, N, ...]  rows by chip, `N` (heads, the MLP's width,
                                the vocabulary) over `tp` as its kernel has it
      whole     [B, 1, N, ...]  rows gathered over `fsdp`: what o and fc_out
                                read

    Each method is a `with_sharding_constraint`; GSPMD then moves the
    131 KB of activations (all-to-all, reduce-scatter, all-gather) where
    it gathered 2.8 GB of kernels a step before (ledger, PR 31, fsdp4).
    `models/transformer.py` `decode_weights_stationary` says when."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.by_chip = batch_pspec()[0]  # the batch axes: rows by chip

    def _place(self, x, rows, feature) -> jax.Array:
        # `_fit_spec` leaves `N` whole over tp where its kernel is
        spec = _fit_spec(P(rows, None, feature), x.shape, self.mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def residual(self, x):
        return self._place(x, self.by_chip, None)

    def split(self, x):
        return self._place(x, "dp", "fsdp")

    def rows(self, x):
        return self._place(x, self.by_chip, "tp")

    def whole(self, x):
        return self._place(x, "dp", "tp")


def unshard_axis(params: Dict, mesh: Mesh, axis: str = "pp") -> Dict:
    """Re-lay out a param tree with `axis` dropped from every spec
    (all-gathering each leaf's shards over that mesh axis).

    Decode under pipeline parallelism is the use case: the sequential
    KV-cache scan reads every layer's weights each step, and with the
    stacked layer axis sharded over `pp` each step would gather the
    remote stages' slices — across DCN on a dcn_pp2-style mesh. Calling
    this once on the decode param copy (inside the sampler jit, before
    the while_loop) turns per-step cross-stage traffic into ONE gather
    per generate call; the loop then reads stage-local weights. Costs
    pp× block-param memory per device for the duration of the call —
    the decode copy is already materialized by `cast_params_for_decode`,
    so this re-shards that copy rather than duplicating params again.

    Implemented as `jax.lax.with_sharding_constraint` on every leaf:
    under jit this is a layout constraint the partitioner satisfies with
    an all-gather; called eagerly it relies on
    with_sharding_constraint's eager semantics (an immediate reshard).
    """

    def strip(spec_axis):
        if isinstance(spec_axis, tuple):
            rest = tuple(a for a in spec_axis if a != axis)
            return rest if len(rest) > 1 else (rest[0] if rest else None)
        return None if spec_axis == axis else spec_axis

    def constrain(path, x):
        spec = _fit_spec(spec_for_path(_path_str(path)), np.shape(x), mesh)
        stripped = P(*[strip(a) for a in spec])
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, stripped))

    return jax.tree_util.tree_map_with_path(constrain, params)


def unshard_for_decode(params: Dict, mesh: Optional[Mesh], axis: str = "pp") -> Dict:
    """The sampler-side gate for `unshard_axis`: no-op unless the mesh
    carries a real pp axis. Both samplers (models/generation.py and
    models/seq2seq.py:generate_seq2seq) share this so the decode-unshard
    condition can't drift between them.

    Only `pp` is gathered, once a call: a stage's slice of the LAYER axis
    is whole layers that the other stages' chips never hold, so there is
    no product a chip could form from its own shard. `fsdp` shards every
    layer's kernels on `E` instead, and a decode step multiplies with
    those shards in place (`DecodeLayouts`): nothing is gathered, once or
    at every step, and no second copy of the weights is held."""
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return params
    return unshard_axis(params, mesh, axis)


def init_sharded_opt_state(mesh: Mesh, tx, params: Dict):
    """Initialize optimizer state with mu/nu sharded like their params.

    This is the distributed-optimizer half of ZeRO-3 parity (reference
    megatron_20b.yaml `distributed_fused_adam`): optimizer moments follow
    the same path rules as the params they track (opt-state tree paths end
    with the param path, so the same regexes match). Without explicit
    out_shardings, `jax.jit(tx.init)` commits the whole state to one
    device — fully replicated optimizer memory and a retrace of the train
    step when GSPMD later re-lays it out.
    """
    abstract = jax.eval_shape(tx.init, params)

    def leaf_sharding(path, leaf):
        spec = _fit_spec(spec_for_path(_path_str(path)), leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    shardings = jax.tree_util.tree_map_with_path(leaf_sharding, abstract)
    return jax.jit(tx.init, out_shardings=shardings)(params)
