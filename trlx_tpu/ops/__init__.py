"""Pure jittable numerics: losses, advantages, sampling, statistics.

Everything in this package is a pure function of arrays + static
hyperparameters — the TPU-native answer to the reference's mixture of
loss methods on config objects and torch.distributed stat helpers
(/root/reference/trlx/utils/modeling.py:185-314).

The Pallas kernels live beside them, one module a family, imported where
they are called (never from here: importing the package builds nothing):
`flash_attention` (causal attention forward and backward, and the T5 bias
variant), `decode_attention` (a decode step over the int8 cache, and the
paged pool), `adam8bit` (the fused int8 AdamW) and `state_step`: a decode
step of a delta-rule or state-space layer as ONE pass over its recurrent
state. `state_step.delta_state_step(s, ix, q, k, v, g, beta)` and
`state_step.ssm_state_step(s, ix, x, B, C, dt, a)` take the whole stacked
carry `s` [layers, rows, heads, a, b] float32 (`kda_s*` [.., 128, 128],
`ssm_s` [.., 64, 128]), never sliced in XLA, and the layer index as a
scalar-prefetch argument; a grid cell is one row and a block of heads
(1-2 MB of tiles), stepped in VMEM and stored in place (the carry is
aliased to the output). `DeltaAttention` and `Mamba2Mixer` take it at
T == 1 with a cache where `transformer.state_step_unfused` returns None
(one device; compiled, a tile of whole (8, 128) float32 tiles), and
`kda_step` / `ssm_step` on a slice of the carry elsewhere.
"""

from trlx_tpu.ops.common import (
    RunningMoments,
    batched_index_select,
    flatten_dict,
    get_tensor_stats,
    logprobs_of_labels,
    masked_mean,
    running_moments_init,
    running_moments_update,
    topk_mask,
    whiten,
)
from trlx_tpu.ops.ppo import gae_advantages_and_returns, ppo_loss
from trlx_tpu.ops.ilql import ilql_loss

__all__ = [
    "RunningMoments",
    "batched_index_select",
    "flatten_dict",
    "gae_advantages_and_returns",
    "get_tensor_stats",
    "ilql_loss",
    "logprobs_of_labels",
    "masked_mean",
    "ppo_loss",
    "running_moments_init",
    "running_moments_update",
    "topk_mask",
    "whiten",
]
