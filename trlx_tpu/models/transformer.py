"""TPU-native causal transformer: one architecture-polymorphic decoder.

Replaces the reference's per-architecture `ModelBranch` family
(/root/reference/trlx/models/modeling_ppo.py:502-1637 — six hand-copied
decoder loops for GPT2/OPT/Bloom/Llama/BigCode/T5): here a single
functional decoder covers GPT-2 / GPT-J / GPT-NeoX / OPT / Llama through
config switches (position embedding, norm type, MLP gating, residual
layout), and "run the top-k layers from a hidden state" is an array slice
of the stacked layer parameters, not a reimplementation.

Design notes (TPU-first):
- Layer parameters are **stacked** along a leading `layer` axis
  (init via vmap) and the forward is a `lax.scan` over them: one traced
  block regardless of depth -> fast compile, and XLA keeps the loop on
  device. Hydra reference branches and layer freezing become slicing /
  masking of the leading axis.
- Sharding is by **path rules** (trlx_tpu/parallel/sharding.py), not
  boxed flax metadata: the param tree stays a plain pytree of arrays so
  the trainers can slice/mask/donate it freely.
- Compute dtype is configurable (bf16 on the MXU); attention scores,
  softmax, norms and logits accumulate in fp32.
- KV-cache decode reuses the same block code: attention takes
  preallocated static-shape cache buffers and a write index (no dynamic
  shapes anywhere).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.ops.common import interpret_mode as _interpret_mode
from trlx_tpu.ops.common import warn_pallas_fallback as _warn_pallas_fallback

Array = jnp.ndarray
NEG_INF = -1e9  # additive mask value (finite: avoids NaN rows for all-masked)


@dataclass(frozen=True)
class TransformerConfig:
    """Static architecture description (hashable: usable as a jit static)."""

    vocab_size: int
    hidden_size: int
    n_layer: int
    n_head: int
    n_positions: int = 1024
    intermediate_size: Optional[int] = None  # default 4*hidden
    n_kv_head: Optional[int] = None  # grouped-query attention; default n_head
    head_dim: Optional[int] = None  # default hidden // n_head

    # architecture switches
    pos_embed: str = "learned"  # "learned" | "rotary" | "alibi" | "none"
    pos_offset: int = 0  # OPT: learned table has 2 leading pad rows
    embed_layernorm: bool = False  # bloom: LayerNorm after word embeddings
    rotary_style: str = "neox"  # "neox" (half rotate) | "gptj" (interleaved)
    rotary_dim: Optional[int] = None  # default head_dim
    rope_theta: float = 10000.0
    # gpt-neo quirks: queries are NOT scaled by 1/sqrt(head_dim), and
    # every other layer attends only within a sliding window
    attn_scale: Optional[float] = None  # None -> 1/sqrt(head_dim)
    local_window: Optional[int] = None  # sliding-window size for "local" layers
    attn_layers: Optional[Tuple[str, ...]] = None  # per-layer "global"/"local"
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    layer_norm_epsilon: float = 1e-5
    activation: str = "gelu_new"  # "gelu_new" | "gelu" | "silu" | "relu" | "relu2" (relu squared)
    mlp_gated: bool = False  # llama-style SwiGLU
    parallel_residual: bool = False  # gptj/neox: attn and mlp share input
    use_attn_bias: bool = True
    # gpt-neo: q/k/v have no bias but out_proj does; None = use_attn_bias
    use_attn_out_bias: Optional[bool] = None
    use_mlp_bias: bool = True
    use_norm_bias: bool = True
    tie_word_embeddings: bool = True

    # numerics
    dtype: Any = jnp.bfloat16  # compute dtype inside blocks
    param_dtype: Any = jnp.float32
    # "xla" (let the compiler fuse) | "pallas" (first-party fused kernel
    # for full teacher-forced forwards; decode steps always use XLA) |
    # "ring" (sequence/context parallelism: teacher-forced forwards run
    # ops.ring_attention over the mesh's `sp` axis — requires
    # TransformerLM.mesh to be set and seq divisible by sp; decode steps
    # and non-plain-bias architectures fall back to XLA).
    # The pallas path is fused in BOTH directions (online-softmax forward
    # + chunked flash backward, ops/flash_attention.py): the [B,H,T,S]
    # score tensor never exists, so training at 8k+ tokens is where it
    # pays for itself.
    attention_impl: str = "xla"
    # None | "int8": generate() quantizes the KV cache after prefill so
    # the decode loop's full-cache read rides an int8 stream (half the
    # HBM traffic of bf16 — decode at large batch×seq is bound on
    # exactly that read). Prefill numerics are untouched; decode picks
    # up symmetric quantization noise (bounded in
    # tests/test_generation.py). A decode step over such a cache runs
    # the fused kernel (ops/decode_attention.py) wherever
    # `decode_attn_unfused` finds nothing against it, and the
    # folded-scale XLA branch of Attention elsewhere.
    kv_cache_quant: Optional[str] = None
    # None | "int8": generate() rewrites block kernels to int8 +
    # per-output-channel scales for the rollout (prefill AND decode run
    # the same quantized policy; the teacher-forced experience pass
    # keeps full precision). Halves the 2.4 GB/step block-weight read
    # that dominates decode after the int8 KV cache.
    decode_weights_quant: Optional[str] = None
    # pipeline parallelism: microbatches per pipelined forward when the
    # mesh has a pp axis > 1 (0 = one microbatch per pipeline stage).
    # The bubble fraction is (pp-1)/(M+pp-1); raise M to amortize it.
    pp_microbatches: int = 0
    # "gpipe" (differentiate the forward scan; stores M+pp-1 boundary
    # activations) | "1f1b" (custom-VJP backward interleaving recompute
    # with the cotangent pipeline; O(pp) boundary liveness per stage,
    # one extra forward — parallel/pipeline.py:_run_1f1b)
    pp_schedule: str = "gpipe"

    # latent attention (set when `kv_lora_rank` is): queries come through
    # a `q_lora_rank` latent, keys and values from one `kv_lora_rank`
    # latent per position plus `qk_rope_head_dim` rotary channels shared
    # by every head. Keys are qk_nope_head_dim + qk_rope_head_dim wide,
    # values v_head_dim. The cache holds the latent and the rotated
    # shared key, kv_lora_rank + qk_rope_head_dim numbers a position.
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN (set when `yarn_factor` is): per-channel blend of the rotary
    # frequencies and the frequencies over `yarn_factor`, and the score
    # scale m^2, m = 0.1 * yarn_mscale_all_dim * ln(factor) + 1
    yarn_factor: Optional[float] = None
    yarn_original_positions: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # routed feed-forward (set when `n_routed_experts` is): the router is
    # `n_routed_experts` wide as published; this chip holds experts
    # [first_expert_held, first_expert_held + n_experts_held) and computes
    # their part of the result. The bottom `first_k_dense` layers keep the
    # dense gated MLP of width `intermediate_size`.
    n_routed_experts: int = 0
    n_experts_held: Optional[int] = None  # default: all of them
    first_expert_held: int = 0
    n_experts_per_token: int = 0
    moe_intermediate_size: Optional[int] = None
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_k_dense: int = 0
    # a model initialised at random has a selection bias of zero, which no
    # pretraining has balanced: an online trainer then runs this many
    # steps of the balancing rule on its first prompts before the first
    # rollout (`balance_router_bias`). A loaded checkpoint keeps its own.
    router_balance_steps: int = 0
    # residual streams (manifold-constrained hyper-connections when > 1):
    # the state is [B, T, n, E], mixed around every sub-layer by matrices
    # computed from it, the stream-to-stream one made doubly stochastic by
    # `sinkhorn_iters` Sinkhorn steps
    residual_streams: int = 1
    sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: float = 30.0
    # the MIXER of each layer, one entry a layer: "softmax" | "latent" |
    # "delta" (gated delta-rule linear attention, `DeltaAttention`:
    # `delta_heads` heads of `delta_head_dim`, causal depthwise short
    # convolutions of `delta_conv` taps, a recurrent state in place of a
    # cache). None: every layer the attention the keys above describe
    mixer_layers: Optional[Tuple[str, ...]] = None
    delta_heads: int = 0
    delta_head_dim: int = 0
    delta_conv: int = 4
    # two more entries of `mixer_layers`: "ssm" (a Mamba-2 selective state
    # space, `Mamba2Mixer`: `ssm_heads` heads of `ssm_head_dim` with a
    # [head_dim, ssm_state] float32 state each, B and C shared by the heads
    # of one of `ssm_groups` groups, a causal depthwise convolution of
    # `ssm_conv` taps, the chunked form over `ssm_chunk` positions) and
    # "none": the layer is its feed-forward alone. `ssm_dt_*` are read at
    # initialisation only
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # the FEED-FORWARD of each layer, one entry a layer: "none" (the layer
    # is its mixer alone: ONE sub-layer a layer) or the kind the other
    # keys give it ("dense" below `first_k_dense` and in a model without
    # experts, "routed" elsewhere). None: every layer has one
    ffn_layers: Optional[Tuple[str, ...]] = None
    # routed experts that work in a latent space (set when
    # `moe_latent_size` is): x is projected down to it once a token, the
    # experts are that wide at both ends, and their weighted sum is
    # projected back up; the shared expert reads x itself.
    # `moe_gated` False: an expert is two products around the activation,
    # no gate matrix (the shared expert too). `moe_shared_intermediate_size`:
    # the shared expert's own width (None: moe_intermediate_size a shared expert)
    moe_latent_size: Optional[int] = None
    moe_gated: bool = True
    moe_shared_intermediate_size: Optional[int] = None

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def mixers(self) -> Tuple[str, ...]:
        """The mixer of each layer of the stack."""
        if self.mixer_layers is not None:
            return self.mixer_layers
        return ("latent" if self.latent else "softmax",) * self.n_layer

    @property
    def ffns(self) -> Tuple[str, ...]:
        """The feed-forward of each layer of the stack."""
        if self.ffn_layers is not None:
            return self.ffn_layers
        return tuple(self._default_ffn(i) for i in range(self.n_layer))

    def _default_ffn(self, layer: int) -> str:
        return "routed" if self.routed and layer >= self.first_k_dense else "dense"

    @property
    def hybrid(self) -> bool:
        """Some layer keeps a recurrent state where the others keep a cache."""
        return "delta" in self.mixers or "ssm" in self.mixers

    @property
    def routed(self) -> bool:
        return self.n_routed_experts > 0

    @property
    def beyond_dense(self) -> bool:
        """Latent attention, delta-rule or state-space layers, layers of one
        sub-layer, routed experts or several residual streams: the models
        that the paths below the dense decoder (pipeline, ring, paged
        engine, adapters, loaders) do not reach and raise for."""
        return (self.latent or self.hybrid or self.routed or self.residual_streams > 1
                or self.ffn_layers is not None or "none" in self.mixers)

    @property
    def cache_elems_per_position(self) -> int:
        """Numbers one cached position costs a row, in one layer THAT
        CACHES (`cache_layers` of them; a delta-rule or state-space layer
        caches nothing, its state is `state_elems_per_row` whatever the
        length)."""
        if self.latent:
            return self.kv_lora_rank + self.qk_rope_head_dim
        return 2 * self.n_kv_head * self.head_dim

    @property
    def cache_layers(self) -> int:
        """Layers whose cache grows with the sequence."""
        return sum(m in ("softmax", "latent") for m in self.mixers)

    @property
    def state_elems_per_row(self) -> int:
        """Numbers the layers with a recurrent state keep a row, whatever
        its length: in a delta-rule layer a [heads, d_k, d_v] state and the
        last `delta_conv - 1` inputs of the three convolutions; in a
        state-space layer a [heads, head_dim, ssm_state] state and the last
        `ssm_conv - 1` inputs of its one convolution (`ssm_conv_width`)."""
        width = self.delta_heads * self.delta_head_dim
        return (
            self.mixers.count("delta") * (width * self.delta_head_dim + (self.delta_conv - 1) * 3 * width)
            + self.mixers.count("ssm") * (self.ssm_heads * self.ssm_head_dim * self.ssm_state
                                          + (self.ssm_conv - 1) * self.ssm_conv_width))

    @property
    def ssm_conv_width(self) -> int:
        """Channels of a state-space layer's convolution: x', B and C."""
        return self.ssm_heads * self.ssm_head_dim + 2 * self.ssm_groups * self.ssm_state

    @property
    def attn_softmax_scale(self) -> float:
        """What the scores are multiplied by before the softmax."""
        if self.attn_scale is not None:
            return self.attn_scale
        if not self.latent:
            return 1.0 / math.sqrt(self.head_dim)
        m = 1.0
        if self.yarn_factor is not None and self.yarn_factor > 1:
            m = 0.1 * self.yarn_mscale_all_dim * math.log(self.yarn_factor) + 1.0
        return m * m / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)

    def __post_init__(self):
        if self.intermediate_size is None:
            object.__setattr__(self, "intermediate_size", 4 * self.hidden_size)
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size // self.n_head)
        if self.n_kv_head is None:
            object.__setattr__(self, "n_kv_head", self.n_head)
        if self.latent:
            object.__setattr__(self, "rotary_dim", self.qk_rope_head_dim)
        if self.rotary_dim is None and self.pos_embed == "rotary":
            object.__setattr__(self, "rotary_dim", self.head_dim)
        if self.routed and self.n_experts_held is None:
            object.__setattr__(self, "n_experts_held", self.n_routed_experts)
        for name in ("mixer_layers", "ffn_layers"):  # a list from a config file: hashable
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.kv_cache_quant not in (None, "int8"):
            raise ValueError(
                f"kv_cache_quant={self.kv_cache_quant!r}: the values are None and "
                '"int8" ("int8" runs the fused decode kernel wherever the cache\'s '
                "shape, the mask and the mesh allow it; no other value selects it)"
            )
        self._check_family()

    def _check_family(self) -> None:
        """What a latent, delta-rule, state-space, routed or multi-stream
        model does not reach raises here, at configuration time, not as a
        wrong answer later."""
        if self.mixer_layers is not None:
            base = "latent" if self.latent else "softmax"
            if len(self.mixer_layers) != self.n_layer or set(self.mixer_layers) - {base, "delta", "ssm", "none"}:
                raise ValueError(
                    f"mixer_layers names one of {base!r} (the attention the other keys "
                    f"describe), 'delta', 'ssm' or 'none' for each of the {self.n_layer} layers"
                )
        if not self.beyond_dense:
            return
        def no(what):
            raise NotImplementedError(
                f"{what} is not implemented for a model with latent attention, "
                "delta-rule (KDA) or state-space (Mamba-2) layers, layers of one sub-layer, "
                "routed experts or several residual streams"
            )
        if (self.latent or self.hybrid) and self.kv_cache_quant is not None:
            no(f"kv_cache_quant={self.kv_cache_quant!r} (an int8 latent cache, or a cache "
               "beside the float32 state of delta-rule or state-space layers)")
        if self.attention_impl == "ring":
            no("attention_impl='ring'")
        if self.latent and (self.pos_embed not in ("rotary", "none") or self.local_window is not None
                            or self.n_kv_head != self.n_head or self.attn_scale is not None):
            no("latent attention with learned or alibi positions, with windows, grouped "
               "heads or a set attn_scale")
        if "delta" in self.mixers:
            if not (self.delta_heads > 0 and self.delta_head_dim > 0 and self.delta_conv >= 2):
                raise ValueError("delta-rule layers need delta_heads, delta_head_dim and delta_conv >= 2")
            if "softmax" in self.mixers:
                no("delta-rule (KDA) layers beside softmax attention layers (their per-head "
                   "key and value cache)")
            if len(set(self.mixers[: self.first_k_dense])) > 1:
                no("leading dense layers of more than one mixer")
        if "ssm" in self.mixers:
            if not (self.ssm_heads > 0 and self.ssm_head_dim > 0 and self.ssm_state > 0
                    and self.ssm_conv >= 2 and self.ssm_chunk > 0
                    and self.ssm_groups > 0 and self.ssm_heads % self.ssm_groups == 0):
                raise ValueError("state-space layers need ssm_heads (whole groups of them), "
                                 "ssm_head_dim, ssm_state, ssm_chunk and ssm_conv >= 2")
            if "delta" in self.mixers or self.first_k_dense or self.local_window is not None:
                no("state-space (Mamba-2) layers beside delta-rule layers, leading dense "
                   "layers or windowed attention")
        self._check_sub_layers(no)
        if self.parallel_residual or self.embed_layernorm:
            no("parallel_residual / embed_layernorm")
        if self.routed:
            held_end = self.first_expert_held + self.n_experts_held
            if not (0 < self.n_experts_per_token <= self.n_routed_experts
                    and 0 <= self.first_expert_held and held_end <= self.n_routed_experts
                    and self.moe_intermediate_size and 0 <= self.first_k_dense < self.n_layer):
                raise ValueError(
                    "routed experts need 0 < n_experts_per_token <= n_routed_experts, the held "
                    "experts inside the router's range, moe_intermediate_size and "
                    "first_k_dense < n_layer"
                )
        elif self.first_k_dense:
            raise ValueError("first_k_dense without routed experts")

    def _check_sub_layers(self, no) -> None:
        """A layer names its mixer or none and its feed-forward or none."""
        if self.ffn_layers is None:
            if "ssm" in self.mixers or "none" in self.mixers:
                raise ValueError("a stack with 'ssm' or 'none' among its mixers states ffn_layers: "
                                 "a state-space layer is its mixer alone")
            return
        if len(self.ffn_layers) != self.n_layer:
            raise ValueError(f"ffn_layers names the feed-forward of each of the {self.n_layer} layers")
        for layer, (mixer, ffn) in enumerate(zip(self.mixers, self.ffn_layers)):
            if ffn not in ("none", self._default_ffn(layer)):
                raise ValueError(
                    f"ffn_layers[{layer}] is 'none' or {self._default_ffn(layer)!r}, the kind "
                    f"the other keys give layer {layer}, not {ffn!r}")
            if (mixer == "none" and ffn == "none") or (mixer == "ssm" and ffn != "none"):
                raise ValueError(
                    f"layer {layer}: mixer {mixer!r} with feed-forward {ffn!r} (a layer has a "
                    "mixer, a feed-forward or both; a state-space layer is its mixer alone)")
            if (mixer == "delta" and ffn == "none") or (layer < self.first_k_dense and "none" in (mixer, ffn)):
                no("a delta-rule layer without a feed-forward, or a leading dense layer of one sub-layer")
        if self.residual_streams > 1:
            no("layers of one sub-layer under several residual streams")

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)


# the stacks of a parameter tree, in the order a tree without delta-rule
# layers has always had them; a layer's kind (mixer, feed-forward) names
# its stack, so a stack holds layers equal in both. The last four hold
# layers of ONE sub-layer: a state-space mixer, attention, routed experts,
# a dense MLP
STACKS = ("dense_blocks", "blocks", "delta_blocks", "ssm_blocks", "attn_blocks", "moe_blocks", "mlp_blocks")


def _stack_of(cfg: TransformerConfig, layer: int) -> str:
    mixer, ffn = cfg.mixers[layer], cfg.ffns[layer]
    if layer < cfg.first_k_dense:
        return "dense_blocks"
    if ffn == "none":
        return "ssm_blocks" if mixer == "ssm" else "attn_blocks"
    if mixer == "none":
        return "moe_blocks" if ffn == "routed" else "mlp_blocks"
    return "delta_blocks" if mixer == "delta" else "blocks"


@functools.lru_cache(maxsize=None)
def layer_stacks(cfg: TransformerConfig) -> Tuple[Tuple[str, int], ...]:
    """(stack, row in it) of each layer of the whole stack: the
    `first_k_dense` leading layers under `dense_blocks`, above them the
    delta-rule layers under `delta_blocks`, layers of one sub-layer under
    the stack of their kind (`_stack_of`) and the rest under `blocks`.
    A SEGMENT is a run of consecutive layers of one stack (consecutive
    rows of it): one `lax.scan`. Everything that addresses a layer by its
    index in the whole stack (a branch point, the freeze mask, a cache
    row) goes through this."""
    out, rows = [], {}
    for i in range(cfg.n_layer):
        name = _stack_of(cfg, i)
        out.append((name, rows.get(name, 0)))
        rows[name] = rows.get(name, 0) + 1
    return tuple(out)


def stack_layers(cfg: TransformerConfig, name: str) -> Tuple[int, ...]:
    """Indices in the whole stack of the layers `name` holds, row by row."""
    return tuple(i for i, (stack, _) in enumerate(layer_stacks(cfg)) if stack == name)


def _activation(name: str) -> Callable[[Array], Array]:
    return {
        "gelu_new": partial(jax.nn.gelu, approximate=True),
        "gelu": partial(jax.nn.gelu, approximate=False),
        "silu": jax.nn.silu,
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(cfg: TransformerConfig, positions: Array) -> Tuple[Array, Array]:
    """cos/sin tables [batch, seq, rotary_dim//2] for given positions."""
    inv_freq = rope_inv_frequencies(cfg)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B, T, dim/2]
    if cfg.yarn_factor is not None and cfg.yarn_factor > 1:
        # YaRN scales cos/sin by mscale(factor, mscale) / mscale(factor,
        # mscale_all_dim); equal settings give 1 and the scale lives in
        # the softmax alone (TransformerConfig.attn_softmax_scale)
        ratio = _yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) / _yarn_mscale(
            cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        return jnp.cos(angles) * ratio, jnp.sin(angles) * ratio
    return jnp.cos(angles), jnp.sin(angles)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_frequencies(cfg: TransformerConfig) -> Array:
    """Rotary frequencies [rotary_dim // 2]. Under YaRN each channel blends
    1/theta_i (kept where the channel turns more than `beta_fast` times
    over the original context) and 1/(factor theta_i) (where it turns less
    than `beta_slow` times) along a linear ramp between the two correction
    dimensions."""
    dim = cfg.rotary_dim
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    inv_freq = 1.0 / (cfg.rope_theta ** exponent)
    if cfg.yarn_factor is None or cfg.yarn_factor <= 1:
        return inv_freq

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(cfg.yarn_original_positions / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction_dim(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # the published code's guard against a zero-width ramp
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return inv_freq / cfg.yarn_factor * ramp + inv_freq * (1.0 - ramp)


def apply_rope(x: Array, cos: Array, sin: Array, style: str) -> Array:
    """Rotate the first rotary_dim channels of x [B, T, H, D]."""
    rot_dim = cos.shape[-1] * 2
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    x_rot = x_rot.astype(jnp.float32)
    cos = cos[:, :, None, :]  # broadcast over heads
    sin = sin[:, :, None, :]
    if style == "gptj":
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        rotated = jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x_rot.shape)
    else:  # neox / llama: rotate halves
        half = rot_dim // 2
        x1, x2 = x_rot[..., :half], x_rot[..., half:]
        rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rotated.astype(x.dtype), x_pass], axis=-1)


def alibi_slopes(n_head: int) -> Array:
    """Per-head ALiBi slopes (bloom parity). The bias added to scores is
    `slope[h] * key_position`, equivalent to the canonical
    `-slope * (q_pos - k_pos)` because the per-query constant cancels in
    softmax — this is also how HF bloom builds its alibi tensor."""
    p = 2 ** math.floor(math.log2(n_head))
    base = 2.0 ** (-(2.0 ** -(math.log2(p) - 3)))
    slopes = [base ** i for i in range(1, p + 1)]
    if p < n_head:
        extra_base = 2.0 ** (-(2.0 ** -(math.log2(2 * p) - 3)))
        slopes += [extra_base ** i for i in range(1, 2 * (n_head - p) + 1, 2)]
    return jnp.asarray(slopes, jnp.float32)


# ---------------------------------------------------------------------------
# Modules (params are plain arrays; composition is functional below)
# ---------------------------------------------------------------------------


def decode_attn_unfused(cfg: "TransformerConfig", mesh, batch: int, slots: int) -> Optional[str]:
    """Why a decode step (T == 1) of `batch` rows over an int8 cache of
    `slots` slots takes Attention's folded-scale XLA branch, or None
    where it takes the fused kernel (ops/decode_attention.py). Static:
    Attention asks at trace time, the trainer on the host for the gauge
    `gen/decode_attn_fused`."""
    if cfg.attn_scale is not None or cfg.pos_embed == "alibi" or cfg.local_window is not None:
        return "attn_scale, alibi or a local window: the kernel has 1/sqrt(D) and a key mask alone"
    if slots % 128:
        return f"a cache of {slots} slots is not whole 128-slot tiles"
    if mesh is not None and mesh.size > 1:
        if mesh.shape["pp"] > 1:
            return "a pipelined mesh: decode runs outside the pipeline's shard_map"
        data, tp = mesh.shape["dp"] * mesh.shape["fsdp"], mesh.shape["tp"]
        if batch % data or cfg.n_head % tp or cfg.n_kv_head % tp:
            return (
                f"mesh {dict(mesh.shape)} does not divide {batch} rows over dp*fsdp={data} "
                f"and {cfg.n_head}/{cfg.n_kv_head} heads over tp={tp}"
            )
    return None


def decode_weights_stationary(cfg: "TransformerConfig", mesh, batch: int) -> bool:
    """Whether a decode step (T == 1) of `batch` rows leaves every kernel
    where `parallel/sharding.py` put it and moves its activations instead
    (`DecodeLayouts`): a mesh whose `fsdp` axis shards the kernels on `E`
    and, with `dp`, divides the rows, under a dense stack (latent
    attention, routed experts and several streams have no mesh to run on
    yet). Elsewhere GSPMD lays the step out as it does any forward (on
    `fsdp` that gathers each kernel whole at every step). Static, like
    `decode_attn_unfused`: the modules ask at
    trace time, the trainer on the host for the gauge
    `gen/decode_weights_stationary`."""
    if mesh is None or cfg.beyond_dense:
        return False
    fsdp, data = mesh.shape["fsdp"], mesh.shape["dp"] * mesh.shape["fsdp"]
    return (
        fsdp > 1 and mesh.shape["pp"] == 1
        and cfg.hidden_size % fsdp == 0 and batch % data == 0
    )


def _decode_layouts(cfg: "TransformerConfig", mesh, cache, x: Array):
    """The `DecodeLayouts` of this forward, or None where it is not a
    decode step over the sampler's cache with stationary weights (no
    constraint is emitted then: one chip's program is untouched)."""
    if cache is None or "pk" in cache or x.shape[1] != 1:
        return None
    if not decode_weights_stationary(cfg, mesh, x.shape[0]):
        return None
    from trlx_tpu.parallel.sharding import DecodeLayouts

    return DecodeLayouts(mesh)


@functools.lru_cache(maxsize=None)
def _warn_decode_unfused(why: str) -> None:
    """One warning per distinct reason (the cache is the log-once)."""
    from trlx_tpu.utils import logging

    logging.get_logger(__name__).warning(
        "kv_cache_quant=int8: decode attention runs the XLA branch, not the fused kernel (%s)", why
    )


def state_step_unfused(cfg: "TransformerConfig", mesh) -> Optional[str]:
    """Why a decode step (T == 1) of the delta-rule and state-space layers
    runs `kda_step` / `ssm_step` on a slice of the carried state, the XLA
    branch, or None where it takes the kernel that passes over the state
    once (ops/state_step.py). Static, like `decode_attn_unfused`: the
    mixers ask at trace time, the trainer on the host for the gauge
    `gen/state_step_fused`."""
    if mesh is not None and mesh.size > 1:
        return f"a mesh of {mesh.size} devices: the kernel is one chip's program"
    if _interpret_mode():
        return None
    tiles = {"delta": (cfg.delta_head_dim, cfg.delta_head_dim), "ssm": (cfg.ssm_head_dim, cfg.ssm_state)}
    for kind in sorted(set(cfg.mixers) & set(tiles)):
        a, b = tiles[kind]
        if a % 8 or b % 128:
            return f"a {kind} layer's state tile [{a}, {b}] is not whole (8, 128) float32 tiles"
    return None


@functools.lru_cache(maxsize=None)
def _warn_state_step_unfused(why: str) -> None:
    """One warning per distinct reason (the cache is the log-once)."""
    from trlx_tpu.utils import logging

    logging.get_logger(__name__).warning(
        "a decode step passes over the recurrent state in XLA ops, not in the fused kernel (%s)", why
    )


def _state_step_fused(cfg: "TransformerConfig", mesh) -> bool:
    """A mixer's question at a decode step: the kernel, or (warned once) the XLA branch."""
    why = state_step_unfused(cfg, mesh)
    if why:
        _warn_state_step_unfused(why)
    return not why


class Norm(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: Array) -> Array:
        cfg = self.cfg
        x32 = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (cfg.hidden_size,), cfg.param_dtype)
        if cfg.norm == "rmsnorm":
            var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
            y = x32 * jax.lax.rsqrt(var + cfg.layer_norm_epsilon) * scale
        else:
            mean = jnp.mean(x32, axis=-1, keepdims=True)
            var = jnp.var(x32, axis=-1, keepdims=True)
            y = (x32 - mean) * jax.lax.rsqrt(var + cfg.layer_norm_epsilon) * scale
            if cfg.use_norm_bias:
                y = y + self.param(
                    "bias", nn.initializers.zeros, (cfg.hidden_size,), cfg.param_dtype
                )
        return y.astype(x.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    # device mesh the fused pallas kernels are shard_mapped over
    # (TransformerLM.mesh); None = one device
    mesh: Any = None

    @nn.compact
    def __call__(
        self,
        x: Array,  # [B, T, E]
        attn_bias: Array,  # [B, 1, T, S] additive fp32
        positions: Array,  # [B, T] absolute positions (for rope)
        cache: Optional[Dict[str, Array]] = None,  # {"ck","cv"}: [L, B, S, Hkv, D], "ix", "index"
        key_mask: Optional[Array] = None,  # [B, T]; enables the pallas path
        ring_mesh=None,  # Mesh; non-None routes to ring attention over `sp`
    ) -> Tuple[Array, Optional[Dict[str, Array]]]:
        cfg = self.cfg
        B, T, E = x.shape
        H, Hkv, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        # a decode step on a sharded mesh (x arrives split over E, Block):
        # q, k, v are partial products reduced into the cache's rows, the
        # output projection reads all rows and gives its own columns
        lay = _decode_layouts(cfg, self.mesh, cache, x)

        dense = partial(
            QDense,
            axis=-1,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02),
            use_bias=cfg.use_attn_bias,
            reduce_into=lay and lay.rows,
        )
        q = dense(features=(H, D), name="q")(x)
        k = dense(features=(Hkv, D), name="k")(x)
        v = dense(features=(Hkv, D), name="v")(x)

        if cfg.pos_embed == "rotary":
            cos, sin = rope_frequencies(cfg, positions)
            q = apply_rope(q, cos, sin, cfg.rotary_style)
            k = apply_rope(k, cos, sin, cfg.rotary_style)

        new_kv = None
        kernel_out = None  # set by the fused int8 decode kernel path
        if cache is not None and "pk" in cache:
            # paged cache (slot→page indirection, models/gen_engine.py):
            # write the T incoming tokens into their pages, attend each
            # query against its slot's gathered logical sequence. All
            # masking (per-row lengths, causality among the T tokens,
            # refill staleness) rides the additive bias, so this branch
            # is generic over plain/alibi/local architectures. The
            # folded-scale int8 math and the gather/scatter live in
            # ops/decode_attention.paged_attention_step.
            from trlx_tpu.ops.decode_attention import paged_attention_step

            scale = (
                cfg.attn_scale if cfg.attn_scale is not None
                else 1.0 / math.sqrt(D)
            )
            pools = {
                name: cache[name]
                for name in ("pk", "pv", "pk_scale", "pv_scale")
                if name in cache
            }
            kernel_out, new_kv = paged_attention_step(
                q, k, v, pools, cache["ix"], cache["page_table"],
                cache["slot_pos"], attn_bias, scale,
                lane_valid=cache.get("lane_valid"),
                contiguous=bool(cache.get("contiguous", False)),
                impl=cache.get("attn_impl", "xla"),
            )
        elif cache is not None:
            # update-carry-FIRST: write this layer's new [B, T, Hkv, D]
            # column into the scan-carried stacked buffer, then attend
            # against a slice of the UPDATED buffer. The column write
            # aliases in place (the buffer is a scan carry) and the row
            # slice is a read, so the only cache traffic per step is one
            # full read + one column write. The previous design built a
            # per-layer `dynamic_update_slice(row, col)` copy BEFORE the
            # carry write — a second full-cache materialization costing
            # 3.2 GB of extra HBM writes per decoded token at 1.3B,
            # measured 13.6 vs 6.5 ms/step on the cache mechanics alone
            # (v5e, 24L x b8 x 2048 slots). Two earlier designs were
            # worse still: stacking full updated buffers as scan ys
            # (rewrites the whole cache every token), and attending
            # against the stale buffer + patching new-column scores
            # (defeats XLA's in-place aliasing entirely, 15x slower).
            idx = cache["index"]
            ix = cache["ix"]
            if "ck_scale" in cache:
                # int8 cache (decode only; generate() quantizes the
                # prefilled cache once — see quantize_kv_cache).
                # Buffer layout is [L, B, Hkv, S, D] (kv-head OUTSIDE
                # the slot axis) so the fused decode kernel's per-cell
                # blocks are plain trailing (S, D) tiles; scales are
                # K per (slot, kv-head) / V per (kv-head, channel) so
                # both dequants commute out of the attention reductions
                # (rationale + measured per-token-V cost in
                # ops/decode_attention.py).
                kq, ks = _quantize_kv(k)  # [B,T,Hkv,D] int8, [B,T,Hkv]
                layer_vs = cache["v_scale"]  # [B, Hkv, 1, D]
                vq = jnp.clip(
                    jnp.round(
                        v.astype(jnp.float32)
                        / jnp.maximum(layer_vs.transpose(0, 2, 1, 3), 1e-12)
                    ),
                    -127.0,
                    127.0,
                ).astype(jnp.int8)
                ck = jax.lax.dynamic_update_slice(
                    cache["ck"], kq.transpose(0, 2, 1, 3)[None],
                    (ix, 0, 0, idx, 0),
                )
                # V stores [.., S, D] like K. A [.., D, S] variant
                # (contracting axis minor for the AV dot) was measured
                # 2026-07-31: it re-fuses the AV convert but makes the
                # per-step column write strided across the minor axis —
                # net wash (849 vs 868 tok/s, inside run noise), so the
                # write-friendly layout stays
                cv = jax.lax.dynamic_update_slice(
                    cache["cv"], vq.transpose(0, 2, 1, 3)[None],
                    (ix, 0, 0, idx, 0),
                )
                cks = jax.lax.dynamic_update_slice(
                    cache["ck_scale"],
                    ks.transpose(0, 2, 1)[None].astype(cache["ck_scale"].dtype),
                    (ix, 0, 0, idx),
                )
                new_kv = {"ck": ck, "cv": cv, "ck_scale": cks}
                S = ck.shape[3]
                plain = (
                    cfg.attn_scale is None
                    and cfg.pos_embed != "alibi"
                    and cfg.local_window is None
                )
                fused = False
                if T == 1:
                    unfused = (
                        "no key mask" if key_mask is None
                        else decode_attn_unfused(cfg, self.mesh, B, S)
                    )
                    if unfused:
                        _warn_decode_unfused(unfused)
                    fused = not unfused
                if fused:
                    # ONE fused pass (ops/decode_attention.py): the
                    # layer's int8 K/V stream straight from the carried
                    # buffers (scalar-prefetched layer index), scales,
                    # mask, online softmax and weighted sum in VMEM, and
                    # chunks past the write index are neither fetched
                    # nor computed. The XLA branch below took 162 us a
                    # layer a step at S = 1024 and 159 us at S = 2048
                    # (b8, 16 heads of 128; ledger, PR 28: its score
                    # fusion read at 147 GB/s and 510 GB/s)
                    from trlx_tpu.ops.decode_attention import (
                        decode_attention_on_mesh,
                    )

                    with jax.named_scope("decode_attn"):
                        kernel_out = decode_attention_on_mesh(
                            self.mesh, q[:, 0], ck, cv, cks, layer_vs,
                            key_mask, ix, idx, sm_scale=1.0 / math.sqrt(D),
                        )[:, None]  # [B, 1, H, D]
                elif plain:
                    # folded-scale XLA path (what "int8" runs where the
                    # kernel's conditions do not hold, and the reference
                    # its tests compare with): keep K/V int8 end to end —
                    # the per-slot K scale rides the [B,H,T,S] scores (fuses into
                    # the softmax chain), the per-channel V scale rides
                    # the [B,T,H,D] output; nothing S-sized is ever
                    # dequantized to HBM
                    with jax.named_scope("decode_attn"):
                        k_i8 = jax.lax.dynamic_index_in_dim(
                            ck, ix, 0, keepdims=False
                        )  # [B, Hkv, S, D]
                        v_i8 = jax.lax.dynamic_index_in_dim(
                            cv, ix, 0, keepdims=False
                        )  # [B, Hkv, S, D]
                        ks_l = jax.lax.dynamic_index_in_dim(
                            cks, ix, 0, keepdims=False
                        )[:, :, None]  # [B, Hkv, 1, S]
                        if Hkv != H:
                            rep = H // Hkv
                            k_i8 = jnp.repeat(k_i8, rep, axis=1)
                            v_i8 = jnp.repeat(v_i8, rep, axis=1)
                            ks_l = jnp.repeat(ks_l, rep, axis=1)
                            layer_vs = jnp.repeat(layer_vs, rep, axis=1)
                        scores = jnp.einsum(
                            "bthd,bhsd->bhts",
                            q,
                            k_i8.astype(cfg.dtype),
                            preferred_element_type=jnp.float32,
                        ) * (1.0 / math.sqrt(D))
                        scores = scores * ks_l + attn_bias
                        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
                        kernel_out = jnp.einsum(
                            "bhts,bhsd->bthd", probs, v_i8.astype(cfg.dtype)
                        ) * layer_vs.transpose(0, 2, 1, 3).astype(cfg.dtype)
                else:
                    # non-plain-bias fallback: full dequant back to the
                    # [B, S, Hkv, D] orientation the generic XLA path
                    # expects — correctness, not a fast path
                    k = (
                        jax.lax.dynamic_index_in_dim(ck, ix, 0, keepdims=False)
                        .astype(jnp.float32)
                        * jax.lax.dynamic_index_in_dim(
                            cks, ix, 0, keepdims=False
                        )[..., None]
                    ).astype(cfg.dtype).transpose(0, 2, 1, 3)
                    v = (
                        jax.lax.dynamic_index_in_dim(cv, ix, 0, keepdims=False)
                        .astype(jnp.float32)
                        * layer_vs
                    ).astype(cfg.dtype).transpose(0, 2, 1, 3)
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["ck"], k[None].astype(cache["ck"].dtype), (ix, 0, idx, 0, 0)
                )
                cv = jax.lax.dynamic_update_slice(
                    cache["cv"], v[None].astype(cache["cv"].dtype), (ix, 0, idx, 0, 0)
                )
                new_kv = {"ck": ck, "cv": cv}
                k = jax.lax.dynamic_index_in_dim(ck, ix, 0, keepdims=False).astype(cfg.dtype)
                v = jax.lax.dynamic_index_in_dim(cv, ix, 0, keepdims=False).astype(cfg.dtype)

        # the pallas kernel bakes in 1/sqrt(D) scaling and a plain
        # causal+padding mask; architectures with nonstandard scaling or
        # extra additive biases (alibi, local windows) take the XLA path
        plain_bias = (
            cfg.attn_scale is None
            and cfg.pos_embed != "alibi"
            and cfg.local_window is None
        )
        # prefill (cache present, T>1) can use the pallas kernel when the
        # cache carries a STATIC write index (a Python int placed by
        # init_cache/generate; a cache crossing a jit boundary turns it
        # into a tracer and this cleanly falls back to XLA): queries sit
        # at slots [static_index, static_index+T) against the full cache
        # length. Decode steps (T=1) stay XLA — they're memory-bound.
        # Mosaic lowers the kernels' dynamic chunk loads only at aligned
        # offsets: cache length (lane dim of the mask load, chunked at
        # >=128 when 128 | S) and query length (sublane q blocks, 8-row
        # granularity). generate() rounds its cache to 128 slots so real
        # rollouts always qualify; unaligned callers fall back to XLA.
        prefill_offset = None
        if (
            cache is not None
            and T > 1
            and isinstance(cache.get("static_index"), int)
            and cache["ck"].shape[2] % 128 == 0
            and T % 8 == 0
        ):
            prefill_offset = cache["static_index"]
        # a teacher-forced forward is differentiated, and Mosaic lowers
        # the dk/dv kernel's key-mask lane slice only at a 128-aligned
        # key length (the forward alone takes any shape); interpret mode
        # has no such floor, so the CPU parity tests keep their sizes
        teacher_aligned = _interpret_mode() or (
            T % 8 == 0 and k.shape[1] % 128 == 0
        )
        wants_pallas = (
            cfg.attention_impl == "pallas"
            and ring_mesh is None
            and kernel_out is None
        )
        use_pallas = (
            wants_pallas
            and key_mask is not None
            and plain_bias
            and (teacher_aligned if cache is None else prefill_offset is not None)
        )
        if (
            wants_pallas
            and not use_pallas
            and T > 1
            and (cache is None or isinstance(cache.get("static_index"), int))
        ):
            # a teacher-forced forward or a prefill asked for the fused
            # kernel and takes XLA instead: say so, once per shape
            # (decode steps staying on XLA is the design, not a fallback)
            _warn_pallas_fallback(
                "teacher-forced forward" if cache is None else "prefill",
                f"T={T} S={k.shape[1]} plain_bias={plain_bias} "
                f"key_mask={key_mask is not None}: the kernels need "
                "T % 8 == 0, S % 128 == 0, 1/sqrt(D) scaling and a "
                "causal+padding mask",
            )
        if Hkv != H and ring_mesh is not None and kernel_out is None:
            # grouped-query on the ring path: repeat kv heads (the pallas
            # kernel handles GQA natively and must NOT see repeated kv —
            # that would forfeit its grouped HBM reads; the XLA path below
            # groups the queries instead)
            rep = H // Hkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        if kernel_out is not None:
            out = kernel_out
        elif ring_mesh is not None:
            # sequence-parallel path: K/V rotate around the `sp` ring via
            # ppermute while each shard accumulates its queries' attention
            # (TransformerLM._ring_mesh gates on plain-bias archs, full
            # teacher-forced forwards and mesh-divisible shapes)
            from trlx_tpu.ops.ring_attention import ring_attention_sharded

            out = ring_attention_sharded(
                q, k, v, ring_mesh, segment_mask=key_mask, causal=True
            )
        elif use_pallas:
            from trlx_tpu.ops.flash_attention import flash_attention_on_mesh

            out = flash_attention_on_mesh(
                self.mesh,
                q.transpose(0, 2, 1, 3),
                k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3),
                key_mask,
                q_offset=prefill_offset,
            ).transpose(0, 2, 1, 3)
        else:
            scale = cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(D)
            if Hkv != H:
                # grouped-query: the queries of a key-value head side by
                # side against its keys, [B, Hkv, rep, T, S]. Repeating the
                # heads instead wrote the whole cache out rep times at every
                # decode step (two heads to 32: 1.86 s of a 14.9 s cycle,
                # PERF.md section 6, PR 35)
                rep = H // Hkv
                scores = jnp.einsum(
                    "btkrd,bskd->bkrts", q.reshape(B, T, Hkv, rep, D), k, preferred_element_type=jnp.float32
                ) * scale
                bias = attn_bias[:, :, None] if attn_bias.shape[1] == 1 else attn_bias.reshape(
                    (B, Hkv, rep) + attn_bias.shape[2:])
                probs = jax.nn.softmax(scores + bias, axis=-1).astype(cfg.dtype)
                out = jnp.einsum("bkrts,bskd->btkrd", probs, v).reshape(B, T, H, D)
            else:
                # [B, H, T, S]; accumulate scores in fp32 for stability
                scores = jnp.einsum(
                    "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
                ) * scale
                scores = scores + attn_bias
                probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
                out = jnp.einsum("bhts,bshd->bthd", probs, v)

        out_bias = (
            cfg.use_attn_out_bias
            if cfg.use_attn_out_bias is not None
            else cfg.use_attn_bias
        )
        proj = QDense(
            features=E,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
            use_bias=out_bias,
            name="o",
        )
        if lay:
            return lay.split(proj(lay.whole(out))), new_kv
        return proj(out), new_kv


class QDense(nn.Module):
    """DenseGeneral-compatible linear that additionally accepts an int8
    kernel with a per-output-channel dequant scale.

    Same param names/shapes/init as `nn.DenseGeneral` (kernel =
    (input_dims..., features...), zero bias), so checkpoints and HF
    interop are unchanged. At decode time `quantize_decode_weights`
    rewrites the param tree: kernel → int8, plus a `kernel_scale` leaf
    this module detects via `has_variable`. The int8→compute-dtype
    convert fuses into the dot's operand load, so the HBM weight stream
    halves (the dominant decode cost at 1.3B: 2.4 GB of block weights
    per step); the scale multiplies the tiny output because per-output-
    channel scaling commutes out of the contraction. Training paths
    never see a scale and run the exact DenseGeneral math.
    """

    features: Any  # int or tuple
    axis: Any = -1  # int or tuple of input axes to contract
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.normal(0.02)
    use_bias: bool = True
    # a decode step whose input and kernel are both split over the
    # contracted dimension (parallel/sharding.py `DecodeLayouts`): the
    # constraint that the float32 partial products are reduced into
    reduce_into: Optional[Callable[[Array], Array]] = None

    @nn.compact
    def __call__(self, x: Array) -> Array:
        feats = (
            self.features if isinstance(self.features, tuple)
            else (self.features,)
        )
        axes = self.axis if isinstance(self.axis, tuple) else (self.axis,)
        axes = tuple(a % x.ndim for a in axes)
        in_shape = tuple(x.shape[a] for a in axes)
        kernel = self.param(
            "kernel", self.kernel_init, in_shape + feats, self.param_dtype
        )
        y = jax.lax.dot_general(
            x.astype(self.dtype),
            kernel.astype(self.dtype),
            ((axes, tuple(range(len(axes)))), ((), ())),
            preferred_element_type=None if self.reduce_into is None else jnp.float32,
        )
        if self.reduce_into is not None:
            # y is this chip's product over its slice of the contraction:
            # summed across the chips in float32 and only then rounded,
            # no less precise than the single product; scale and bias
            # once, after the sum
            y = self.reduce_into(y).astype(self.dtype)
        if self.has_variable("params", "kernel_scale"):
            y = y * self.get_variable("params", "kernel_scale").astype(
                self.dtype
            )
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros, feats, self.param_dtype
            )
            y = y + bias.astype(self.dtype)
        return y


def quantize_decode_weights(params: Dict) -> Dict:
    """Rewrite every stacked block kernel to int8 + per-output-channel
    scale (consumed by QDense) for the decode loop.

    Decode reads every weight once per token: at 1.3B the 2.4 GB of
    block kernels dominate the per-step HBM budget even after the int8
    KV cache. Per-output-channel symmetric scales keep the error at the
    per-matmul level (~0.4% relative); sampling runs the SAME quantized
    policy for prefill and every decode step, so trajectories are
    self-consistent — the teacher-forced experience pass then scores
    them with the full-precision weights, which is the usual
    behavior-policy/scoring split (same contract as the int8 KV cache,
    quantize_kv_cache above). Embeddings and the logit projection stay
    in compute dtype (the tied wte must serve lookups).

    Only kernels under the layer stacks' (`STACKS`) dense modules and the
    stacked expert kernels are rewritten; scan xs-slicing delivers
    per-layer int8 kernels + scales to QDense and to `RoutedMLP`
    automatically.
    """
    # feature rank by dense-module name (kernel = (L, inputs..., feats...));
    # of a latent attention the four projections that are used as written
    # (the up-projection of the cached latent, `kv_b`, is used transposed
    # in the decode form and stays as it is, as does the float32 router);
    # of a delta-rule layer q, k, v and o (95% of its elements: the two
    # low-rank pairs and beta's projection feed float32 gates and stay);
    # of a state-space layer both projections (its taps, A_log, D, dt_bias
    # and norm stay); of latent-space experts both latent projections
    n_feats = {"q": 2, "k": 2, "v": 2, "o": 1,
               "fc_in": 1, "fc_gate": 1, "fc_out": 1,
               "q_a": 1, "q_b": 2, "kv_a": 1,
               "in_proj": 1, "out_proj": 1, "latent_in": 1, "latent_out": 1}
    # stacked expert kernels [L, held, in, out]: one scale per expert and
    # output channel
    experts = ("experts_fc_in", "experts_fc_gate", "experts_fc_out")

    def walk(tree, name=None):
        out = {}
        for child_name, leaf in tree.items():
            if isinstance(leaf, dict):
                out[child_name] = walk(leaf, child_name)
            else:
                out[child_name] = leaf
        if (name in n_feats or name in experts) and "kernel" in tree:
            w = tree["kernel"].astype(jnp.float32)
            red = (2,) if name in experts else tuple(range(1, w.ndim - n_feats[name]))  # input dims
            s = jnp.max(jnp.abs(w), axis=red) / 127.0  # [L, feats...] or [L, held, out]
            out["kernel"] = jnp.round(
                w / jnp.maximum(jnp.expand_dims(s, red), 1e-12)
            ).astype(jnp.int8)
            out["kernel_scale"] = s.astype(jnp.float32)
        return out

    # (in the order the rewrite has always walked them: the lowered sampler
    # of a model without delta-rule layers stays what it was)
    stacks = {k: walk(params[k]) for k in ("blocks", "dense_blocks") + STACKS[2:] if k in params}
    return dict(params, **stacks)


def _quantize_kv(x: Array) -> Tuple[Array, Array]:
    """Symmetric per-(…, head) int8 quantization over the trailing D
    axis: returns (int8 values, per-row fp32 scales shaped x.shape[:-1]).
    Rows of zeros (unwritten cache slots) get scale 0 and dequantize
    back to exact zeros."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    s = amax / 127.0
    q = jnp.round(
        x.astype(jnp.float32) / jnp.maximum(s, 1e-12)[..., None]
    ).astype(jnp.int8)
    return q, s


def quantize_kv_cache(cache: Dict) -> Dict:
    """One-shot int8 quantization of a prefilled KV cache.

    Decode at large batch×seq is HBM-bandwidth-bound on the full-cache
    read every step (3.22 GB at 1.3B b8 seq2048 in bf16); int8 halves
    that stream. Quantizing AFTER prefill keeps the pallas prefill path
    byte-identical — only the decode loop sees int8, and Attention's
    scaled-score path (see the cache branch in Attention.__call__)
    never materializes a dequantized copy. The reference has no KV
    quantization at all (HF `generate` caches follow model dtype); this
    is a TPU-roofline design choice, opt-in via
    TransformerConfig.kv_cache_quant="int8".

    Layout change: the bf16 cache is [L, B, S, Hkv, D]; the quantized
    cache is [L, B, Hkv, S, D] — kv-head OUTSIDE the slot axis, so the
    fused decode kernel's grid cells (a row's heads over a chunk of
    slots) read plain trailing (S, D) tiles (ops/decode_attention.py).
    Scales: K per (layer, batch, kv-head, slot) over D, stored
    [L, B, Hkv, S] (whole (Hkv, S) tiles: with a unit axis before S a
    decode step's 128 new scales landed in 128 tiles, 12.7 us a layer
    on the chip, PR 30); V
    per (layer, batch, kv-head, channel) over the slot axis, stored
    [L, B, Hkv, 1, D] and FROZEN here — decode writes saturate against
    it. The 1.25x headroom covers new tokens whose |v| drifts past the
    prefix max on a channel (post-norm value magnitudes are
    near-stationary across decode); saturation error is bounded either
    way, and the headroom costs ~0.3 bits of prefix precision.
    """
    k = cache["k"].astype(jnp.float32).transpose(0, 1, 3, 2, 4)
    v = cache["v"].astype(jnp.float32).transpose(0, 1, 3, 2, 4)
    kq, ks = _quantize_kv(k)  # per (L, B, Hkv, S) over D — the same
    # formula Attention's decode write path applies to new columns
    vs = jnp.max(jnp.abs(v), axis=3) * (1.25 / 127.0)  # [L, B, Hkv, D]
    vq = jnp.clip(
        jnp.round(v / jnp.maximum(vs, 1e-12)[:, :, :, None]), -127.0, 127.0
    ).astype(jnp.int8)
    out = dict(
        cache, k=kq, v=vq,
        k_scale=ks.astype(jnp.float32),
        v_scale=vs[:, :, :, None].astype(jnp.float32),
    )
    out.pop("static_index", None)  # decode loops carry arrays only
    return out


class MLP(nn.Module):
    cfg: TransformerConfig
    # a shared expert is this MLP at its own width, gated and without bias
    width: Optional[int] = None  # None: cfg.intermediate_size
    gated: Optional[bool] = None  # None: cfg.mlp_gated
    bias: Optional[bool] = None  # None: cfg.use_mlp_bias

    @nn.compact
    def __call__(self, x: Array, lay=None) -> Array:
        """`lay`: the `DecodeLayouts` of a decode step on a sharded mesh
        (x split over E), as in `Attention`; None everywhere else."""
        cfg = self.cfg
        act = _activation(cfg.activation)
        use_bias = cfg.use_mlp_bias if self.bias is None else self.bias
        up = partial(
            QDense,
            features=cfg.intermediate_size if self.width is None else self.width,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02),
            use_bias=use_bias,
            # summed into ALL rows on every chip: fc_out reads them all,
            # and the activation over 16 rows twice costs less than a
            # second collective
            reduce_into=lay and lay.whole,
        )
        h = act(up(name="fc_in")(x))
        if cfg.mlp_gated if self.gated is None else self.gated:
            h = h * up(name="fc_gate")(x)
        down = QDense(
            features=cfg.hidden_size,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
            use_bias=use_bias,
            name="fc_out",
        )
        return lay.split(down(h)) if lay else down(h)


class _Kernel(nn.Module):
    """A bare `kernel` param under its own name, for a weight that is used
    in two orientations (the latent up-projection: expanded in the
    teacher-forced form, absorbed into query and output in the decode
    form). Named `kernel` so that decode casts it to the compute dtype
    with the rest; `quantize_decode_weights` leaves it as it is."""

    shape: Tuple[int, ...]
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self) -> Array:
        return self.param("kernel", nn.initializers.normal(0.02), self.shape, self.param_dtype)


def _rms_norm(x: Array, scale: Optional[Array], eps: float) -> Array:
    """RMSNorm over the last axis in float32; `scale` None = no learned weight."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y if scale is None else y * scale


class LatentAttention(nn.Module):
    """Multi-head latent attention with a latent cache.

        c_q = RMSNorm(x W_dq);  [q_n | q_r] = c_q W_uq  per head;  q_r rotated
        [c_kv | k_r] = x W_dkv;  c_kv = RMSNorm(c_kv);  k_r rotated, one for all heads
        [k_n | v] = c_kv W_ukv  per head
        score = (q_n . k_n + q_r . k_r) * scale, causal;  y = (softmax(score) v) W_o

    Two forms, chosen by the shape of the call and by nothing else:
    - teacher-forced and prefill (T > 1), scope `latent_attn`: the latent
      is EXPANDED to per-head keys (nope + shared rotary part) and values,
      and attention runs over them (the flash kernels under `pallas`).
      A prefill writes (c_kv, k_r) of its tokens into the cache and, the
      cache being empty before it, attends among them alone.
    - a decode step (T == 1 with a cache), scope `latent_decode_attn`: the
      ABSORBED form. q_n W_uk^T (kv_lora_rank wide) scores against the
      cached c_kv directly, q_r against the cached k_r; W_uv is applied
      after the weighted sum of c_kv. Per-head keys and values of the
      whole cache are never rebuilt.
    """

    cfg: TransformerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, attn_bias, positions, cache=None, key_mask=None, ring_mesh=None):
        cfg = self.cfg
        B, T, E = x.shape
        H, dn, dr, dv, rank = (cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                               cfg.v_head_dim, cfg.kv_lora_rank)
        dense = partial(QDense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02), use_bias=False)
        if cfg.q_lora_rank:
            q_scale = self.param("q_a_norm", nn.initializers.ones, (cfg.q_lora_rank,), cfg.param_dtype)
            c_q = _rms_norm(dense(features=cfg.q_lora_rank, name="q_a")(x), q_scale,
                            cfg.layer_norm_epsilon).astype(cfg.dtype)
        else:
            c_q = x
        q = dense(features=(H, dn + dr), name="q_b")(c_q)  # [B, T, H, dn + dr]
        kv = dense(features=rank + dr, name="kv_a")(x)  # [B, T, rank + dr]
        kv_scale = self.param("kv_a_norm", nn.initializers.ones, (rank,), cfg.param_dtype)
        c_kv = _rms_norm(kv[..., :rank], kv_scale, cfg.layer_norm_epsilon).astype(cfg.dtype)
        w_ukv = _Kernel((rank, H, dn + dv), cfg.param_dtype, name="kv_b")().astype(cfg.dtype)

        if cfg.pos_embed == "rotary":
            cos, sin = rope_frequencies(cfg, positions)
            q_n = q[..., :dn]
            q_r = apply_rope(q[..., dn:], cos, sin, cfg.rotary_style)
            k_r = apply_rope(kv[..., None, rank:], cos, sin, cfg.rotary_style)[:, :, 0]  # [B, T, dr]
        else:  # "none": the same channels, unrotated
            q_n, q_r, k_r = q[..., :dn], q[..., dn:], kv[..., rank:]
        scale = cfg.attn_softmax_scale

        new_kv = None
        if cache is not None:
            entry = jnp.concatenate([c_kv, k_r], axis=-1)[None].astype(cache["c"].dtype)
            c_all = jax.lax.dynamic_update_slice(cache["c"], entry, (cache["ix"], 0, cache["index"], 0))
            new_kv = {"c": c_all}

        if cache is not None and T == 1:
            with jax.named_scope("latent_decode_attn"):
                row = jax.lax.dynamic_index_in_dim(c_all, cache["ix"], 0, keepdims=False)  # [B, S, rank + dr]
                c_s, kr_s = row[..., :rank].astype(cfg.dtype), row[..., rank:].astype(cfg.dtype)
                q_lat = jnp.einsum("bthd,chd->bthc", q_n, w_ukv[..., :dn])  # [B, 1, H, rank]
                scores = (
                    jnp.einsum("bthc,bsc->bhts", q_lat, c_s, preferred_element_type=jnp.float32)
                    + jnp.einsum("bthr,bsr->bhts", q_r, kr_s, preferred_element_type=jnp.float32)
                ) * scale + attn_bias
                probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
                o_lat = jnp.einsum("bhts,bsc->bthc", probs, c_s)  # [B, 1, H, rank]
                out = jnp.einsum("bthc,chd->bthd", o_lat, w_ukv[..., dn:])
        else:
            if cache is not None:
                if cache.get("static_index") != 0:
                    raise NotImplementedError(
                        "latent attention prefills an empty cache in one call (cache index 0, "
                        "known at trace time); a prefill in pieces, or after a soft prompt or "
                        "a key/value prefix, is not implemented"
                    )
                # slots [0, T) are the only ones written: attend among them
                if key_mask is not None:
                    key_mask = key_mask[:, :T]
                attn_bias = attn_bias[..., :T]
            with jax.named_scope("latent_attn"):
                kv_up = jnp.einsum("btc,chd->bthd", c_kv, w_ukv)  # [B, T, H, dn + dv]
                k = jnp.concatenate(
                    [kv_up[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, dr))], axis=-1)
                v = kv_up[..., dn:]
                q_full = jnp.concatenate([q_n, q_r], axis=-1)
                aligned = _interpret_mode() or (T % 8 == 0 and T % 128 == 0)
                wants_pallas = cfg.attention_impl == "pallas"
                if wants_pallas and key_mask is not None and aligned:
                    from trlx_tpu.ops.flash_attention import flash_attention_on_mesh

                    out = flash_attention_on_mesh(
                        self.mesh, q_full.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), key_mask, sm_scale=scale,
                    ).transpose(0, 2, 1, 3)
                else:
                    if wants_pallas:
                        _warn_pallas_fallback(
                            "latent attention", f"T={T} key_mask={key_mask is not None}: the "
                            "kernels need whole 128-slot tiles and a causal+padding mask")
                    scores = jnp.einsum(
                        "bthd,bshd->bhts", q_full, k, preferred_element_type=jnp.float32
                    ) * scale + attn_bias
                    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
                    out = jnp.einsum("bhts,bshd->bthd", probs, v)

        proj = QDense(
            features=E, axis=(-2, -1), dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
            use_bias=False, name="o",
        )
        return proj(out), new_kv


# chunk of the delta rule's chunked form, and the sub-chunk inside which
# differences of cumulative log-gates are exponentiated one by one. Read on
# the chip at a train step's shape (8 x 1024, 32 heads of 128, forward and
# backward of one layer; PERF.md section 6, PR 33): 64 / 16 53.9 ms, 32 / 16
# 41.9, 32 / 8 34.4, 16 / 16 31.4, 16 / 8 28.4: in plain XLA the in-chunk
# products and their intermediates cost more than the scan's steps. 32 / 8
# and not 16 / 8: a backward pass keeps one [B, H, d, d] state a chunk, and
# at 16 the train step plans 1.0 GiB more (compiled for a described v5e)
# for 1% of the cycle
KDA_CHUNK, KDA_SUB = 32, 8


def _small_product(a: Array, b: Array) -> Array:
    """a [..., i, j] @ b [..., j, k] for blocks of a few rows, as one multiply
    and reduce on the vector unit, exact in float32: the matrix unit would pad
    each to its 128 x 128 tile and, at the highest precision, pass over it
    six times."""
    return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)


def _unit_lower_inverse(a: Array, base: int) -> Array:
    """(I + a)^-1 for strictly lower-triangular `a` [..., C, C], C = base x
    a power of two: the diagonal blocks of `base` rows by forward
    substitution, a row at a time (on the chip faster than the product of
    the blocks' doubled powers, on the matrix unit or off it: 53.9 against
    60.5 and 68.0 ms at chunks of 64), then pairs of blocks merged,
    [[T11, 0], [-T22 a21 T11, T22]], until one is left. Exact in float32."""
    C = a.shape[-1]

    def diag_blocks(m, size):  # [..., C, C] -> [..., C / size, size, size]
        n = C // size
        m = m.reshape(m.shape[:-2] + (n, size, n, size))
        return jnp.stack([m[..., i, :, i, :] for i in range(n)], axis=-3)

    blocks = diag_blocks(a, base)
    eye = jnp.eye(base, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], blocks.shape[:-2] + (base,))]
    for r in range(1, base):
        solved = jnp.stack(rows, axis=-2)  # [..., r, base]
        rows.append(eye[r] - jnp.sum(blocks[..., r, :r, None] * solved, axis=-2))
    t = jnp.stack(rows, axis=-2)  # [..., C / base, base, base]
    size = base
    while size < C:
        a21 = diag_blocks(a, 2 * size)[..., size:, :size]
        t11, t22 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        t21 = -_small_product(_small_product(t22, a21), t11)
        t = jnp.concatenate([jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
                             jnp.concatenate([t21, t22], axis=-1)], axis=-2)
        size *= 2
    return t[..., 0, :, :]


def _kda_chunk(state: Array, xs, sub: int):
    """One chunk of `kda_chunked` for every row and head: `state`
    [B, H, dk, dv], q, k, g [B, H, C, dk], v [B, H, C, dv], beta [B, H, C]
    -> (state after the chunk, o [B, H, C, dv]). With G the cumulative
    log-gate inside the chunk and Gamma = exp(G):

        A[r, i] = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])     i < r
        P[r, i] =        sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])     i <= r
        U = (I + A)^-1 (beta v  -  (beta k Gamma) S)                  the WY / UT transform
        o = (q Gamma) S + P U
        S' = Diag(Gamma_C) S + (k Gamma_C / Gamma)^T U

    Every exponent is a difference formed first and never positive: inside
    a sub-chunk of `sub` positions the differences G_r - G_i are taken one
    by one; across sub-chunks both factors decay towards the reference
    point R_a = G at the end of the sub-chunk before r's, which lies
    between G_i and G_r."""
    q, k, v, g, beta = xs
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    B, H, C, D = k.shape
    n = C // sub
    G = jnp.cumsum(g, axis=2)
    Gs = G.reshape(B, H, n, sub, D)
    R = jnp.concatenate([jnp.zeros_like(Gs[:, :, :1, 0]), Gs[:, :, :-1, -1]], axis=2)  # [B, H, n, D]
    to_ref = jnp.exp(Gs - R[:, :, :, None])  # from the reference point down to r
    ks, qs = k.reshape(B, H, n, sub, D), q.reshape(B, H, n, sub, D)
    # keys decayed from i down to sub-chunk a's reference point (i in an earlier sub-chunk)
    k_ref = k[:, :, None] * jnp.exp(jnp.minimum(R[:, :, :, None] - G[:, :, None], 0.0))  # [B, H, n, C, D]
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    within = jnp.exp(jnp.where(tri[..., None], Gs[:, :, :, :, None] - Gs[:, :, :, None], -jnp.inf))
    eye = jnp.eye(n, dtype=k.dtype)
    earlier = (jnp.arange(C)[:, None] // sub > jnp.arange(C)[None, :] // sub)

    def pairs(rows):  # rows [B, H, n, sub, D]: q or k at r -> [B, H, C, C] over (r, i), i <= r
        across = jnp.einsum("bhard,bhaid->bhari", rows * to_ref, k_ref).reshape(B, H, C, C)
        inside = jnp.sum(rows[:, :, :, :, None] * ks[:, :, :, None] * within, axis=-1)  # [B, H, n, sub, sub]
        inside = jnp.einsum("bhari,ae->bharei", inside, eye).reshape(B, H, C, C)
        return jnp.where(earlier, across, 0.0) + inside

    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    t = _unit_lower_inverse(jnp.where(strict, beta[..., None] * pairs(ks), 0.0), sub)
    gamma = jnp.exp(G)
    w_v = jnp.einsum("bhri,bhid->bhrd", t, beta[..., None] * v)
    w_k = jnp.einsum("bhri,bhid->bhrd", t, beta[..., None] * k * gamma)
    u = w_v - jnp.einsum("bhrk,bhkd->bhrd", w_k, state)
    o = jnp.einsum("bhrk,bhkd->bhrd", q * gamma, state) + jnp.einsum("bhri,bhid->bhrd", pairs(qs), u)
    to_end = jnp.exp(G[:, :, -1:] - G)
    state = gamma[:, :, -1, :, None] * state + jnp.einsum("bhik,bhid->bhkd", k * to_end, u)
    return state, o


def kda_chunked(q: Array, k: Array, v: Array, g: Array, beta: Array,
                state: Optional[Array] = None, chunk: int = KDA_CHUNK,
                sub: int = KDA_SUB) -> Tuple[Array, Array]:
    """The gated delta rule over T positions in chunks:

        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t

    q, k [B, T, H, dk], v [B, T, H, dv] (any float dtype: they enter the
    products rounded to the compute dtype either way, and are laid out in
    chunks as they come), g [B, T, H, dk] and beta [B, T, H] float32,
    `state` [B, H, dk, dv] (None: zeros) -> (o [B, T, H, dv] float32, the
    state after position T). One state is handed from chunk to chunk by a scan
    over ceil(T / chunk) steps; inside a chunk `_kda_chunk`. T is padded
    to whole chunks with positions that change nothing (beta 0, g 0). The
    scan's body is checkpointed: a backward pass keeps one state a chunk
    and recomputes the chunk's inside."""
    B, T, H, D = k.shape
    if state is None:
        state = jnp.zeros((B, H, D, v.shape[-1]), jnp.float32)
    pad = (-T) % chunk

    def chunks(x):  # [B, T, H, ...] -> [T / chunk, B, H, chunk, ...]
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, (T + pad) // chunk, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    body = jax.checkpoint(functools.partial(_kda_chunk, sub=sub))
    state, o = jax.lax.scan(body, state, tuple(chunks(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)  # [B, T / chunk, chunk, H, dv]
    return o.reshape((B, T + pad) + o.shape[3:])[:, :T], state


def kda_step(q: Array, k: Array, v: Array, g: Array, beta: Array, state: Array) -> Tuple[Array, Array]:
    """One position of the same rule on a carried state: q, k, g [B, H, dk],
    v [B, H, dv], beta [B, H], `state` [B, H, dk, dv] float32 -> (o [B, H, dv],
    the new state). Elementwise and exact in float32: the state is read and
    written, nothing of it multiplied in a lower precision."""
    state = state * jnp.exp(g)[..., None]
    seen = jnp.sum(state * k[..., None], axis=-2)  # S^T k
    state = state + (beta[..., None] * k)[..., None] * (v - seen)[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _conv_tap_init(key, shape, dtype=jnp.float32):
    """U(-1 / sqrt(taps), 1 / sqrt(taps)): a depthwise convolution's usual start."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32, lo=0.001, hi=0.1, floor=0.0):
    """Inverse softplus of dt = max(exp(U(log lo, log hi)), floor)."""
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, math.log(lo), math.log(hi))), floor)
    return dt + jnp.log(-jnp.expm1(-dt))


class DeltaAttention(nn.Module):
    """Gated delta-rule linear attention with a per-channel decay (KDA): H
    heads of d, a recurrent state S [d, d] a head in place of a cache.

        q~, k~, v = SiLU(Conv(x W_q)), SiLU(Conv(x W_k)), SiLU(Conv(x W_v))
            Conv: causal depthwise, y_t = sum_i w[i] u_{t - (taps - 1) + i}, zeros before the start
        q = q~ / |q~| * d^-0.5;  k = k~ / |k~|                          per head
        g = -exp(A_log) softplus((x W_fa) W_fb + dt_bias)               log-decay per channel, float32
        beta = sigmoid(x W_b)                                           per head, float32
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T;   o_t = S_t^T q_t
        y = (RMSNorm_head(o) * sigmoid((x W_ga) W_gb)) W_o

    A position whose mask is 0 changes nothing: its convolution input is
    zero, its beta 0 and its g 0, so a left-padded row reaches its first
    token with S = 0 and an empty window.

    Two forms, chosen by the shape of the call and by nothing else:
    - teacher-forced and prefill (T > 1), scope `kda_chunk`: the chunked
      form (`kda_chunked`). A prefill starts from the state and the window
      the cache holds (zeros) and leaves the state after its last position
      and its last `taps - 1` convolution inputs there.
    - a decode step (T == 1 with a cache), scope `kda_step`: one step of
      the rule on the carried state, as one pass over it in place
      (ops/state_step.py) or, where `state_step_unfused` gives a reason,
      `kda_step` on the layer's slice.
    The cache of a segment of such layers (`TransformerLM.init_cache`):
    `s` [layers, B, H, d, d] float32 and `u` [layers, B, taps - 1, 3 H d];
    a layer reads and writes its own row `ix`.
    """

    cfg: TransformerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, attn_bias, positions, cache=None, key_mask=None, ring_mesh=None):
        cfg = self.cfg
        B, T, E = x.shape
        H, D, taps = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_conv
        W = H * D
        dense = partial(QDense, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02), use_bias=False)
        if key_mask is None:
            live = jnp.ones((B, T), jnp.float32)
        elif cache is None:
            live = key_mask.astype(jnp.float32)
        else:  # the mask of the slots this call writes
            live = jax.lax.dynamic_slice_in_dim(key_mask, cache["index"], T, axis=1).astype(jnp.float32)

        u = jnp.concatenate(
            [dense(features=(H, D), name=name)(x).reshape(B, T, W) for name in ("q", "k", "v")], axis=-1)
        with jax.named_scope("kda_conv"):
            u = u * live[..., None].astype(u.dtype)
            tap = lambda name: self.param(name, _conv_tap_init, (taps, W), jnp.float32)
            w = jnp.concatenate([tap("conv_q"), tap("conv_k"), tap("conv_v")], axis=-1)  # [taps, 3 W]
            if cache is None:
                before = jnp.zeros((B, taps - 1, 3 * W), u.dtype)
            else:
                before = jax.lax.dynamic_index_in_dim(cache["u"], cache["ix"], 0, keepdims=False).astype(u.dtype)
            window = jnp.concatenate([before, u], axis=1)  # [B, taps - 1 + T, 3 W]
            y = sum(w[i] * window[:, i : i + T].astype(jnp.float32) for i in range(taps))
            q, k, v = (a.reshape(B, T, H, D) for a in jnp.split(jax.nn.silu(y), 3, axis=-1))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * D ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        with jax.named_scope("kda_gate"):
            a_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (H, D), jnp.float32)
            f = dense(features=(H, D), name="f_b")(dense(features=D, name="f_a")(x))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(f.astype(jnp.float32) + dt_bias)
            g = g * live[..., None, None]  # [B, T, H, D]
            beta = jax.nn.sigmoid(dense(features=H, name="b")(x).astype(jnp.float32)) * live[..., None]

        # with a cache, this layer's state comes out of the carried array and
        # goes back into it under the scope of the form that runs, so that the
        # scope's seconds hold every pass over the state
        step = cache is not None and T == 1
        with jax.named_scope("kda_step" if step else "kda_chunk"):
            if step and _state_step_fused(cfg, self.mesh):
                # ONE pass (ops/state_step.py): the layer's tiles stream out of
                # the carried array, are stepped in VMEM and stored in place
                from trlx_tpu.ops.state_step import delta_state_step

                o, carried = delta_state_step(cache["s"], cache["ix"], q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o = o[:, None]
            else:
                state = None
                if cache is not None:
                    # taken out before anything else touches it: read through two
                    # fusions and written back in a third, the compiler copied the
                    # whole array twice a layer a decode step (the barrier keeps
                    # the slice a value of its own)
                    state = jax.lax.optimization_barrier(
                        jax.lax.dynamic_index_in_dim(cache["s"], cache["ix"], 0, keepdims=False))
                if step:
                    o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
                    o = o[:, None]
                else:
                    o, state = kda_chunked(*(x.astype(cfg.dtype) for x in (q, k, v)), g, beta, state)
                if cache is not None:
                    carried = jax.lax.dynamic_update_slice(cache["s"], state[None], (cache["ix"], 0, 0, 0, 0))
            new_kv = None
            if cache is not None:
                new_kv = {
                    "s": carried,
                    "u": jax.lax.dynamic_update_slice(
                        cache["u"], window[None, :, T:].astype(cache["u"].dtype), (cache["ix"], 0, 0, 0)),
                }

        with jax.named_scope("kda_gate"):
            o_scale = self.param("o_norm", nn.initializers.ones, (D,), cfg.param_dtype)
            gate = dense(features=(H, D), name="g_b")(dense(features=D, name="g_a")(x))
            out = (_rms_norm(o, o_scale, cfg.layer_norm_epsilon)
                   * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
        proj = QDense(
            features=E, axis=(-2, -1), dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
            use_bias=False, name="o",
        )
        return proj(out), new_kv


def _ssm_chunk(state: Array, xs, a: Array, dtype):
    """One chunk of `ssm_chunked` for every row and head, heads laid out by
    group: `state` [B, G, Hg, P, N] float32, and of the chunk's C positions x
    [B, C, G Hg P], Bm, Cm [B, C, G N], dt [B, C, G Hg] -> (state after the
    chunk, y [B, C, G Hg P]): flat, as they lie in memory outside the scan (a
    minor dimension of P = 64 would be half a lane tile, and every reshape
    around it a copy of the whole sequence; a chunk's are small). With
    l = dt * a the log-decay of a position (never positive) and S its
    cumulative sum inside the chunk:

        y_r = exp(S_r) C_r h  +  sum_{i <= r} exp(S_r - S_i) (C_r . B_i) dt_i x_i
        h'  = exp(S_C) h  +  sum_i exp(S_C - S_i) dt_i x_i (x) B_i

    Every exponent is a difference formed first and never positive. Products
    take operands in `dtype` and accumulate in float32; decays are float32."""
    x, Bm, Cm, dt = xs
    f32 = jnp.float32
    B, c = x.shape[:2]
    G, Hg, P, N = state.shape[1:]
    x = x.reshape(B, c, G, Hg, P)
    Bm, Cm = Bm.reshape(B, c, G, N).astype(dtype), Cm.reshape(B, c, G, N).astype(dtype)
    dt = dt.reshape(B, c, G, Hg)
    cs = jnp.cumsum(dt * a, axis=1)  # [B, C, G, Hg]
    by_head = jnp.moveaxis(cs, 1, -1)  # [B, G, Hg, C]
    # (r, i): S_r - S_i below the diagonal, 0 on it as a constant (the two
    # terms of S_r - S_r would each carry a gradient of order 1 that cancels
    # only up to rounding, which drowns a small gradient of `a`), nothing above
    below = jnp.tril(jnp.ones((c, c), bool), -1)
    decay = jnp.exp(jnp.where(below, by_head[..., :, None] - by_head[..., None, :],
                              jnp.where(jnp.eye(c, dtype=bool), 0.0, -jnp.inf)))
    scores = jnp.einsum("brgn,bign->bgri", Cm, Bm, preferred_element_type=f32)
    xdt = x * dt[..., None]
    y = jnp.einsum("bghri,bighp->brghp", (decay * scores[:, :, None]).astype(dtype), xdt.astype(dtype),
                   preferred_element_type=f32)
    y = y + jnp.exp(cs)[..., None] * jnp.einsum(
        "brgn,bghpn->brghp", Cm, state.astype(dtype), preferred_element_type=f32)
    to_end = jnp.exp(cs[:, -1:] - cs)
    state = jnp.exp(cs[:, -1])[..., None, None] * state + jnp.einsum(
        "bighp,bign->bghpn", (xdt * to_end[..., None]).astype(dtype), Bm, preferred_element_type=f32)
    return state, y.reshape(B, c, G * Hg * P).astype(dtype)


def ssm_chunked(x: Array, Bm: Array, Cm: Array, dt: Array, a: Array, state: Optional[Array] = None,
                chunk: int = 128, dtype: Any = jnp.float32) -> Tuple[Array, Array]:
    """The selective state space (Mamba-2: a scalar decay a head) over T
    positions in chunks:

        h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t;   y_t = h_t C_t

    x [B, T, H, P], Bm, Cm [B, T, G, N] (a group serves H / G heads), dt
    [B, T, H] float32 (0 where a position changes nothing), a [H] (negative),
    `state` [B, H, P, N] float32 (None: zeros) -> (y [B, T, H, P] in `dtype`,
    the state after position T). One state is handed from chunk to chunk by a
    scan over ceil(T / chunk) steps; inside a chunk `_ssm_chunk`. T is
    padded to whole chunks with positions that change nothing (dt 0). The
    scan's body is checkpointed: a backward pass keeps one state a chunk
    and recomputes the chunk's inside. The skip `D x` is the caller's."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    if state is None:
        state = jnp.zeros((B, H, P, N), jnp.float32)
    pad = (-T) % chunk

    def chunks(v):  # [B, T, ...] -> [T / chunk, B, chunk, all the rest flat]
        v = jnp.pad(v.reshape(B, T, -1), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(v.reshape(B, (T + pad) // chunk, chunk, -1), 1, 0)

    body = jax.checkpoint(functools.partial(_ssm_chunk, a=a.reshape(G, H // G), dtype=dtype))
    state, y = jax.lax.scan(
        body, state.reshape(B, G, H // G, P, N), (chunks(x), chunks(Bm), chunks(Cm), chunks(dt)))
    # (the barrier: what comes out is this array in `dtype`, not a float32 copy
    # of it that the consumer's cast was fused into, twice its size)
    y = jax.lax.optimization_barrier(jnp.moveaxis(y, 0, 1).reshape(B, T + pad, H * P))
    return y.reshape(B, T + pad, H, P)[:, :T], state.reshape(B, H, P, N)


def ssm_step(x: Array, Bm: Array, Cm: Array, dt: Array, a: Array, state: Array) -> Tuple[Array, Array]:
    """One position of the same recurrence on a carried state: x [B, H, P],
    Bm, Cm [B, G, N], dt [B, H], a [H], `state` [B, H, P, N] float32 ->
    (y [B, H, P], the new state). Elementwise and exact in float32."""
    rep = x.shape[1] // Bm.shape[1]
    b, c = (jnp.repeat(v.astype(jnp.float32), rep, axis=1) for v in (Bm, Cm))  # [B, H, N]
    decay, xdt = jnp.exp(dt * a), dt[..., None] * x  # [B, H], [B, H, P]
    # the read-out from the state as it came, h' C = decay (h C) + dt x (B . C): the
    # update and the read-out then pass over the same array, once between them
    y = decay[..., None] * jnp.sum(state * c[:, :, None, :], axis=-1) + xdt * jnp.sum(b * c, axis=-1)[..., None]
    return y, state * decay[..., None, None] + xdt[..., None] * b[:, :, None, :]


class Mamba2Mixer(nn.Module):
    """Mamba-2 selective state space: H heads of P with a float32 state
    [P, N] a head in place of a cache, B and C shared by the heads of a group.

        [z | xBC | dt] = x W_in                                  (H P | H P + 2 G N | H)
        xBC = SiLU(Conv(xBC) + b_conv)     causal depthwise, zeros before the start
        [x' | B | C] = xBC                 x' H heads of P; B, C G groups of N
        dt = softplus(dt + dt_bias);  a = -exp(A_log)            one a head, float32
        h_t = exp(dt_t a) h_{t-1} + dt_t x'_t (x) B_t;   y_t = h_t C_t + D x'_t
        y = w * RMSNorm_group(y * SiLU(z))                       over a group's H P / G channels
        out = y W_out

    A position whose mask is 0 changes nothing: its convolution input is
    zero, what the convolution gives there is zeroed and its dt is 0, so a
    left-padded row reaches its first token with h = 0 and an empty window.

    Two forms of one recurrence, chosen by the shape of the call and by
    nothing else:
    - teacher-forced and prefill (T > 1), scope `ssm_chunk`: the chunked
      form (`ssm_chunked`). A prefill starts from the state and the window
      the cache holds (zeros) and leaves the state after its last position
      and its last `taps - 1` convolution inputs there.
    - a decode step (T == 1 with a cache), scope `ssm_step`: one step on
      the carried state, as one pass over it in place (ops/state_step.py)
      or, where `state_step_unfused` gives a reason, `ssm_step` on the
      layer's slice.
    The cache of the stack of such layers (`TransformerLM.init_cache`):
    `s` [layers, B, H, P, N] float32 and `u` [layers, B, taps - 1, H P + 2 G N];
    a layer reads and writes its own row `ix`.
    """

    cfg: TransformerConfig
    mesh: Any = None

    @nn.compact
    def __call__(self, x, attn_bias, positions, cache=None, key_mask=None, ring_mesh=None):
        cfg = self.cfg
        B, T, E = x.shape
        H, P, N, G, taps = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups, cfg.ssm_conv
        W, C = H * P, cfg.ssm_conv_width
        if key_mask is None:
            live = jnp.ones((B, T), jnp.float32)
        elif cache is None:
            live = key_mask.astype(jnp.float32)
        else:  # the mask of the slots this call writes
            live = jax.lax.dynamic_slice_in_dim(key_mask, cache["index"], T, axis=1).astype(jnp.float32)

        zxbcdt = QDense(features=W + C + H, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        kernel_init=nn.initializers.normal(0.02), use_bias=False, name="in_proj")(x)
        z, u, dt = zxbcdt[..., :W], zxbcdt[..., W : W + C], zxbcdt[..., W + C :]
        with jax.named_scope("ssm_conv"):
            u = u * live[..., None].astype(u.dtype)
            w = self.param("conv_w", _conv_tap_init, (taps, C), jnp.float32)
            # (the bias starts as a tap does: U(-1 / sqrt(taps), 1 / sqrt(taps)))
            b = self.param("conv_b", lambda key, shape: _conv_tap_init(key, (taps,) + shape)[0], (C,))
            if cache is None:
                before = jnp.zeros((B, taps - 1, C), u.dtype)
            else:
                before = jax.lax.dynamic_index_in_dim(cache["u"], cache["ix"], 0, keepdims=False).astype(u.dtype)
            window = jnp.concatenate([before, u], axis=1)  # [B, taps - 1 + T, C]
            y = sum(w[i] * window[:, i : i + T].astype(jnp.float32) for i in range(taps)) + b
            # (rounded here: the recurrence's products take operands in the
            # compute dtype either way, and at 32 x 1024 positions the 10,240
            # channels are 1.25 GiB in float32)
            y = (jax.nn.silu(y) * live[..., None]).astype(cfg.dtype)
            x_flat = y[..., :W]
            xs = x_flat.reshape(B, T, H, P)
            Bm, Cm = (y[..., W + i * G * N : W + (i + 1) * G * N].reshape(B, T, G, N) for i in (0, 1))
        with jax.named_scope("ssm_gate"):
            a = -jnp.exp(self.param("A_log", _a_log_init, (H,), jnp.float32))
            dt_bias = self.param(
                "dt_bias", functools.partial(_dt_bias_init, lo=cfg.ssm_dt_min, hi=cfg.ssm_dt_max,
                                             floor=cfg.ssm_dt_floor), (H,), jnp.float32)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias) * live[..., None]  # [B, T, H]
            skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)

        # with a cache, this layer's state comes out of the carried array and
        # goes back into it under the scope of the form that runs
        step = cache is not None and T == 1
        with jax.named_scope("ssm_step" if step else "ssm_chunk"):
            if step and _state_step_fused(cfg, self.mesh):
                # ONE pass (ops/state_step.py), as in `DeltaAttention`
                from trlx_tpu.ops.state_step import ssm_state_step

                o, carried = ssm_state_step(cache["s"], cache["ix"], xs[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], a)
                o = o[:, None]
            else:
                state = None
                if cache is not None:
                    # no barrier here: `ssm_step` reads the state as it came in both
                    # its uses, so the compiler updates the carried array in place and
                    # reads the row straight out of it, three passes over a row where
                    # the slice taken out behind a barrier made five (PERF.md section
                    # 6, PR 35)
                    state = jax.lax.dynamic_index_in_dim(cache["s"], cache["ix"], 0, keepdims=False)
                if step:
                    o, state = ssm_step(xs[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], a, state)
                    o = o[:, None]
                else:
                    o, state = ssm_chunked(xs, Bm, Cm, dt, a, state, cfg.ssm_chunk, cfg.dtype)
                if cache is not None:
                    carried = jax.lax.dynamic_update_slice(cache["s"], state[None], (cache["ix"], 0, 0, 0, 0))
            new_kv = None
            if cache is not None:
                new_kv = {
                    "s": carried,
                    "u": jax.lax.dynamic_update_slice(
                        cache["u"], window[None, :, T:].astype(cache["u"].dtype), (cache["ix"], 0, 0, 0)),
                }

        with jax.named_scope("ssm_gate"):
            scale = self.param("norm", nn.initializers.ones, (W,), cfg.param_dtype)
            # y = h C + D x', the gate and the grouped norm, all on [B, T, W] as it
            # lies in memory: a head's 64 channels are no lane tile of their own and
            # a group's [.., G, W / G] another tiling of the same numbers, so each
            # reshape of the float32 sequence was a copy of 1 GiB at 32 x 1024
            # positions (compiled for a described v5e). A group's mean square is a
            # product with the groups' indicator [W, G], and comes back through it
            gated = (o.reshape(B, T, W) + jnp.repeat(skip, P) * x_flat) * jax.nn.silu(z.astype(jnp.float32))
            member = (jnp.arange(W)[:, None] // (W // G) == jnp.arange(G)).astype(jnp.float32)
            exact = jax.lax.Precision.HIGHEST
            square = jnp.einsum("btw,wg->btg", gated * gated, member, precision=exact) / (W // G)
            inverse = jnp.einsum("btg,wg->btw", jax.lax.rsqrt(square + cfg.layer_norm_epsilon), member,
                                 precision=exact)
            out = (gated * inverse * scale).astype(cfg.dtype)
        proj = QDense(
            features=E, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
            use_bias=False, name="out_proj",
        )
        return proj(out), new_kv


@jax.custom_vjp
def _rows_by_assignment(x, order, inverse):
    """x [N, E] -> [N * k, E]: row r is the token of assignment `order[r]`
    (assignment a = token * k + choice). Its transpose, written as the
    gather it is: token n's gradient is the sum of its k rows, found
    through `inverse` (assignment -> row). XLA would transpose the gather
    into a scatter-add of N * k rows: with this and `_permute_rows` left to
    XLA the train step is 5.5% slower on the chip (PERF.md section 6, PR 28)."""
    k = order.shape[0] // x.shape[0]
    return jnp.take(x, order // k, axis=0)


def _rows_fwd(x, order, inverse):
    return _rows_by_assignment(x, order, inverse), (inverse, x.shape[0])


def _rows_bwd(res, g):
    inverse, n = res
    return jnp.take(g, inverse, axis=0).reshape(n, -1, g.shape[-1]).sum(axis=1).astype(g.dtype), None, None


_rows_by_assignment.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _permute_rows(x, index, inverse):
    """x[index] for a permutation `index` whose inverse is `inverse`: the
    transpose is the gather by the inverse, not a scatter."""
    return jnp.take(x, index, axis=0)


def _permute_fwd(x, index, inverse):
    return jnp.take(x, index, axis=0), inverse


def _permute_bwd(inverse, g):
    return jnp.take(g, inverse, axis=0), None, None


_permute_rows.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _rows_of_tokens(x, token, rows_of, held_of):
    """x [N, E] -> [M, E]: row r is token `token[r]`. Its transpose as the
    gather it is: `rows_of` [N, Kh] lists for each token the rows that may
    be its own, `held_of` which of them are (a token has at most Kh)."""
    return jnp.take(x, token, axis=0)


def _rows_of_tokens_fwd(x, token, rows_of, held_of):
    return jnp.take(x, token, axis=0), (rows_of, held_of)


def _rows_of_tokens_bwd(res, g):
    rows_of, held_of = res
    own = jnp.where(held_of[..., None], jnp.take(g, rows_of, axis=0), 0)  # [N, Kh, E]
    return own.sum(axis=1).astype(g.dtype), None, None, None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def _rows_to_slots(out, rows_of, slot_of):
    """out [M, E] -> [N, Kh, E]: slot (n, p) reads row `rows_of[n, p]`. Its
    transpose as the gather it is: row r is read by slot `slot_of[r]` (and by
    slots that weigh it with zero, whose gradient is zero)."""
    return jnp.take(out, rows_of, axis=0)


def _rows_to_slots_fwd(out, rows_of, slot_of):
    return jnp.take(out, rows_of, axis=0), slot_of


def _rows_to_slots_bwd(slot_of, g):
    return jnp.take(g.reshape((-1, g.shape[-1])), slot_of, axis=0), None, None


_rows_to_slots.defvjp(_rows_to_slots_fwd, _rows_to_slots_bwd)


class _ExpertKernel(nn.Module):
    """One stacked expert weight [held, in, out] under `<name>/kernel`, with
    the per-expert, per-output-channel scale `quantize_decode_weights` puts
    beside an int8 kernel for the rollout."""

    shape: Tuple[int, ...]
    std: float
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self) -> Tuple[Array, Optional[Array]]:
        kernel = self.param("kernel", nn.initializers.normal(self.std), self.shape, self.param_dtype)
        scale = (self.get_variable("params", "kernel_scale")
                 if self.has_variable("params", "kernel_scale") else None)
        return kernel, scale


class RoutedMLP(nn.Module):
    """Routed experts as THIS chip's share of them, plus the shared expert.

        s = sigmoid(x W_g) (float32);  chosen = top-k of (s + b);  w = c * s[chosen] / sum(s[chosen])
        y = Shared(x) + sum over chosen experts HELD HERE of w_e Expert_e(x)

    The router is as wide as published (`n_routed_experts`); the chip holds
    experts [first_expert_held, first_expert_held + n_experts_held) and
    computes their part for the tokens routed to them. No capacity, no
    dropped token; an assignment to an expert held elsewhere costs nothing
    here and adds nothing (the chips that hold it would add it). Nothing
    stands in for those chips or their exchange.

    Two forms by the shape of the call: a decode step (T == 1, a handful of
    rows) runs every held expert over every row as one batched product and
    weights by the routing (weights are read once, which is all a step at
    this size can do); everything else sorts assignments by expert and runs
    grouped products (`jax.lax.ragged_dot`) over the rows routed here. Both
    were read on the chip at 32 rows a step (PERF.md section 6, PR 28): the
    sorted form there makes the sampler 24% slower (a sort, three gathers
    and three grouped products of 16 rows a layer a step, launches all).
    With `moe_latent_size` (scope `moe_latent`) the experts work in a latent
    space: u = x W_down once a token, the experts are that wide at both
    ends, and r W_up brings their weighted sum back (linear: the shares'
    parts add up); the shared expert reads x itself, at its own width.
    `moe_gated` False: an expert is W2 act(W1 u), no gate matrix.
    Returns (y, stats): `load`, the rows each held expert computed,
    `assignments`, the token-expert pairs the router made (`moe_counters`),
    and `choices`, how often each of the router's experts was chosen, held
    here or not (`balance_router_bias`).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: Array, decode: bool = False) -> Tuple[Array, Dict[str, Array]]:
        cfg = self.cfg
        B, T, E = x.shape
        K, held, first, F = (cfg.n_experts_per_token, cfg.n_experts_held, cfg.first_expert_held,
                             cfg.moe_intermediate_size)
        act = _activation(cfg.activation)
        xf = x.reshape(B * T, E)
        N = B * T
        Ein = cfg.moe_latent_size or E  # what an expert reads and writes

        with jax.named_scope("moe_router"):
            # float32 here and in every program, so that the sampler and
            # the scorer choose from the same function of their inputs
            gate = self.param("router_gate", nn.initializers.normal(0.02),
                              (E, cfg.n_routed_experts), jnp.float32)
            bias = self.param("router_bias", nn.initializers.zeros, (cfg.n_routed_experts,), jnp.float32)
            s = jax.nn.sigmoid(jnp.dot(xf.astype(jnp.float32), gate.astype(jnp.float32),
                                       precision=jax.lax.Precision.HIGHEST))
            _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), K)  # [N, K]
            picked = jnp.take_along_axis(s, chosen, axis=-1)
            weight = cfg.routed_scaling_factor * picked / jnp.sum(picked, axis=-1, keepdims=True)
            local = chosen - first
            here = (local >= 0) & (local < held)
            weight = jnp.where(here, weight, 0.0)
            choices = jnp.sum(chosen[..., None] == jnp.arange(cfg.n_routed_experts), axis=(0, 1))

        std_out = 0.02 / math.sqrt(2 * cfg.n_layer)
        w_in, s_in = _ExpertKernel((held, Ein, F), 0.02, cfg.param_dtype, name="experts_fc_in")()
        w_gate = s_gate = None
        if cfg.moe_gated:
            w_gate, s_gate = _ExpertKernel((held, Ein, F), 0.02, cfg.param_dtype, name="experts_fc_gate")()
        w_out, s_out = _ExpertKernel((held, F, Ein), std_out, cfg.param_dtype, name="experts_fc_out")()

        latent = partial(QDense, dtype=cfg.dtype, param_dtype=cfg.param_dtype, use_bias=False)
        if cfg.moe_latent_size:
            with jax.named_scope("moe_latent"):
                xf = latent(features=Ein, kernel_init=nn.initializers.normal(0.02), name="latent_in")(xf)

        with jax.named_scope("moe_experts"):
            load = jnp.sum(
                (local[..., None] == jnp.arange(held)) & here[..., None], axis=(0, 1)
            ).astype(jnp.int32)  # rows of each held expert

            def hidden(product, a):  # an expert up to its second matrix
                h = act(product(a, w_in, s_in))
                return h * product(a, w_gate, s_gate) if cfg.moe_gated else h

            if decode:
                xb = jnp.broadcast_to(xf.astype(cfg.dtype)[None], (held, N, Ein))

                def product(a, w, scale):
                    y = jnp.einsum("enk,ekf->enf", a, w.astype(cfg.dtype))
                    return y if scale is None else y * scale[:, None, :].astype(cfg.dtype)

                out = product(hidden(product, xb), w_out, s_out)  # [held, N, Ein]
                per_expert = jnp.sum(
                    jnp.where(local[..., None] == jnp.arange(held), weight[..., None], 0.0), axis=1
                )  # [N, held]
                routed = jnp.einsum("ne,end->nd", per_expert.astype(cfg.dtype), out)
            else:
                key = jnp.where(here, local, held).reshape(N * K)  # held = elsewhere, sorts last
                order = jnp.argsort(key, stable=True).astype(jnp.int32)  # row -> assignment
                inverse = jnp.argsort(order).astype(jnp.int32)  # assignment -> row
                # a token meets at most `held` of the experts held here: where it
                # is sent to more than that (22 of 512, 8 held), the first
                # N * held rows hold every assignment computed here, and the rest
                # of the N * K are never built
                Kh = min(K, held)
                M = N * Kh
                valid = (jnp.arange(M) < jnp.sum(load))[:, None]
                if Kh == K:
                    rows = jnp.where(valid, _rows_by_assignment(xf.astype(cfg.dtype), order, inverse), 0)
                else:
                    # of a token's K rows the Kh first (its held ones among them), slot by slot
                    by_token = inverse.reshape(N, K)
                    nth = jnp.argsort(by_token, axis=1)[:, :Kh].astype(jnp.int32)  # [N, Kh] choices
                    rows_of = jnp.take_along_axis(by_token, nth, axis=1)  # their rows
                    held_of = rows_of < jnp.sum(load)
                    rows_of = jnp.minimum(rows_of, M - 1)
                    order = order[:M]
                    token = order // K
                    # row r is slot (token, p): p, how many of the token's rows come before it
                    before = jnp.sum(jnp.take(rows_of, token, axis=0) < jnp.arange(M)[:, None], axis=1)
                    slot_of = token * Kh + jnp.minimum(before, Kh - 1).astype(jnp.int32)
                    rows = jnp.where(valid, _rows_of_tokens(xf.astype(cfg.dtype), token, rows_of, held_of), 0)
                row_expert = jnp.take(key, order)

                def product(a, w, scale):
                    y = jax.lax.ragged_dot(a, w.astype(cfg.dtype), load)
                    if scale is not None:
                        y = y * jnp.take(scale, jnp.minimum(row_expert, held - 1), axis=0).astype(cfg.dtype)
                    return y

                out = jnp.where(valid, product(hidden(product, rows), w_out, s_out), 0)  # [M, Ein]
                if Kh == K:
                    back = _permute_rows(out, inverse, order).reshape(N, K, Ein)
                else:  # (a slot that is not the token's own weighs what it reads with zero)
                    back = _rows_to_slots(out, rows_of, slot_of)
                    weight = jnp.take_along_axis(weight, nth, axis=1)
                routed = jnp.einsum("nk,nkd->nd", weight.astype(cfg.dtype), back)

        if cfg.moe_latent_size:
            with jax.named_scope("moe_latent"):
                routed = latent(features=E, kernel_init=nn.initializers.normal(std_out), name="latent_out")(routed)

        with jax.named_scope("moe_shared"):
            y = routed.reshape(B, T, E)
            if cfg.n_shared_experts:
                width = cfg.moe_shared_intermediate_size or F * cfg.n_shared_experts
                y = y + MLP(cfg, width=width, gated=cfg.moe_gated, bias=False, name="shared")(x)
        return y, {"load": load.astype(jnp.float32), "assignments": jnp.float32(N * K),
                   "choices": choices.astype(jnp.float32)}


def sinkhorn(m: Array, iters: int, eps: float) -> Array:
    """`iters` times: rows over their sums, then columns over theirs. m [..., n, n] > 0.
    Unrolled: a step is a handful of elementwise operations on n x n numbers a
    token, which the compiler fuses; as a loop each would be a launch of its own,
    fourteen loops a decode step."""
    def step(m, _):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps), None

    return jax.lax.scan(step, m, None, length=iters, unroll=True)[0]


class StreamMix(nn.Module):
    """The mixing matrices of one sub-layer of a multi-stream residual path,
    computed from the state X [B, T, n, E]:

        x~     = RMSNorm(vec(X))                                (n E wide, no learned weight)
        H_pre  = sigmoid(a_pre * (x~ Phi_pre) + b_pre)          [B, T, n]
        H_post = 2 sigmoid(a_post * (x~ Phi_post) + b_post)     [B, T, n]
        H_res  = Sinkhorn(exp(clamp(a_res * mat(x~ Phi_res) + b_res)))   [B, T, n, n]

    in float32 (the projection itself takes compute-dtype operands: it is
    n E deep and n^2 + 2n wide). The sub-layer then reads H_pre X and the
    state becomes H_res X + H_post^T F(H_pre X)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, X: Array) -> Tuple[Array, Array, Array]:
        cfg = self.cfg
        n, E = cfg.residual_streams, cfg.hidden_size
        B, T = X.shape[:2]
        phi = self.param("phi", nn.initializers.normal(0.02), (n * E, n * n + 2 * n), cfg.param_dtype)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,), jnp.float32)
        b_pre = self.param("b_pre", nn.initializers.zeros, (n,), jnp.float32)
        b_post = self.param("b_post", nn.initializers.zeros, (n,), jnp.float32)
        # 2 I: H_res starts at 0.71 on the diagonal, which the Sinkhorn steps
        # balance to 1e-8; nearer the identity a step shrinks the error by
        # the square of the second singular value only (4 I: 4e-3 left of 20)
        b_res = self.param("b_res", lambda *_: 2.0 * jnp.eye(n, dtype=jnp.float32))
        flat = _rms_norm(X.reshape(B, T, n * E), None, cfg.layer_norm_epsilon)
        z = jnp.dot(flat.astype(cfg.dtype), phi.astype(cfg.dtype), preferred_element_type=jnp.float32)
        h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + b_pre)
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n : 2 * n] + b_post)
        logits = alpha[2] * z[..., 2 * n :].reshape(B, T, n, n) + b_res
        h_res = sinkhorn(jnp.exp(jnp.clip(logits, -cfg.hc_clamp, cfg.hc_clamp)),
                         cfg.sinkhorn_iters, cfg.hc_eps)
        return h_pre, h_post, h_res


class Block(nn.Module):
    """Pre-norm decoder block; sequential (gpt2/llama) or parallel
    (gptj/neox) residual layout. `kind` says what follows the mixer: the
    dense MLP, routed experts (`RoutedMLP`) or nothing; `mixer` "none" is
    a layer that is its feed-forward alone (ONE sub-layer a layer: `ln_1`
    belongs to the mixer, `ln_2` to the feed-forward, and a layer has the
    norm of what it has); with several residual streams each sub-layer
    reads and writes the state through its own mixing matrices
    (`StreamMix`). Returns (x, new_kv, stats), stats the routed layer's
    counters or None."""

    cfg: TransformerConfig
    mesh: Any = None  # forwarded to Attention
    kind: str = "dense"  # "dense" | "routed" | "none"
    mixer: str = ""  # "softmax" | "latent" | "delta" | "ssm" | "none"; "": the attention cfg describes

    @nn.compact
    def __call__(
        self,
        x: Array,
        attn_bias: Array,
        positions: Array,
        cache: Optional[Dict[str, Array]] = None,
        key_mask: Optional[Array] = None,
        ring_mesh=None,
    ) -> Tuple[Array, Optional[Dict[str, Array]], Optional[Dict[str, Array]]]:
        cfg = self.cfg
        mixer = self.mixer or ("latent" if cfg.latent else "softmax")
        if mixer == "ssm":
            attention = Mamba2Mixer(cfg, self.mesh, name="ssm")
        elif mixer != "none":
            attention = {"softmax": Attention, "latent": LatentAttention,
                         "delta": DeltaAttention}[mixer](cfg, self.mesh, name="attn")

        # a decode step on a sharded mesh: the residual stays rows by
        # chip; what the sub-layers read is split over E once, and what
        # they write comes back to the rows once (both branches of a
        # parallel residual together)
        lay = _decode_layouts(cfg, self.mesh, cache, x)
        into = back = lambda a: a
        if lay:
            # the norm itself by rows: constrained only after it, the
            # split would reach back into the norm and send its
            # statistics across the chips too
            into, back = (lambda a: lay.split(lay.residual(a))), lay.residual

        def feed_forward(h):
            if self.kind == "routed":
                return RoutedMLP(cfg, name="moe")(h, decode=cache is not None and h.shape[1] == 1)
            return MLP(cfg, name="mlp")(h, lay), None

        if cfg.residual_streams > 1:
            def sub_layer(name, X, fn):
                with jax.named_scope("hc_mix"):
                    h_pre, h_post, h_res = StreamMix(cfg, name=name)(X)
                    u = jnp.einsum("btn,btne->bte", h_pre.astype(X.dtype), X)
                out = fn(u)
                y, rest = out[0], out[1:]
                with jax.named_scope("hc_mix"):
                    X = (jnp.einsum("btij,btje->btie", h_res.astype(X.dtype), X)
                         + h_post.astype(X.dtype)[..., None] * y[:, :, None, :])
                return X, rest

            x, (new_kv,) = sub_layer("hc_attn", x, lambda u: attention(
                Norm(cfg, name="ln_1")(u), attn_bias, positions, cache, key_mask, ring_mesh))
            x, (stats,) = sub_layer("hc_mlp", x, lambda u: feed_forward(Norm(cfg, name="ln_2")(u)))
            return x, new_kv, stats

        if mixer == "none":  # the feed-forward alone
            mlp_out, stats = feed_forward(Norm(cfg, name="ln_2")(x))
            return x + mlp_out, None, stats
        h = into(Norm(cfg, name="ln_1")(x))
        attn_out, new_kv = attention(h, attn_bias, positions, cache, key_mask, ring_mesh)
        if self.kind == "none":  # the mixer alone
            return x + back(attn_out), new_kv, None
        if lay and cfg.parallel_residual:
            mlp_out, stats = feed_forward(h)
            x = back(x) + back(attn_out + mlp_out)
        elif cfg.parallel_residual:
            x = x + attn_out
            mlp_out, stats = feed_forward(h)
            x = x + mlp_out
        else:
            x = x + back(attn_out)
            mlp_out, stats = feed_forward(into(Norm(cfg, name="ln_2")(x)))
            x = x + back(mlp_out)
        return x, new_kv, stats


class Embedding(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, input_ids: Array, positions: Array) -> Array:
        cfg = self.cfg
        wte = self.param(
            "wte", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype,
        )
        h = jnp.take(wte, input_ids, axis=0)
        if cfg.pos_embed == "learned":
            # pos_offset: OPT's table carries 2 leading pad rows; real
            # position i lives at table row i + offset
            wpe = self.param(
                "wpe", nn.initializers.normal(0.01),
                (cfg.n_positions + cfg.pos_offset, cfg.hidden_size), cfg.param_dtype,
            )
            h = h + jnp.take(
                wpe,
                jnp.clip(positions, 0, cfg.n_positions - 1) + cfg.pos_offset,
                axis=0,
            )
        return h.astype(cfg.dtype)

    def attend(self, hidden: Array) -> Array:
        """Tied-embedding logits: hidden @ wte.T (fp32 accumulation)."""
        wte = self.get_variable("params", "wte")
        return jnp.einsum(
            "bte,ve->btv", hidden, wte.astype(hidden.dtype),
            preferred_element_type=jnp.float32,
        )


class LMHead(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, hidden: Array) -> Array:
        kernel = self.param(
            "kernel", nn.initializers.normal(0.02),
            (self.cfg.hidden_size, self.cfg.vocab_size), self.cfg.param_dtype,
        )
        return jnp.einsum(
            "bte,ev->btv", hidden, kernel.astype(hidden.dtype),
            preferred_element_type=jnp.float32,
        )


# ---------------------------------------------------------------------------
# Functional composition: explicit param tree, scan over stacked layers
# ---------------------------------------------------------------------------


def logit_projection(params: Dict):
    """hidden -> fp32 logits closure over a TransformerLM param tree
    (tied wte or untied lm_head), matching `TransformerLM._logits`
    numerics exactly (compute-dtype matmul, fp32 accumulation). Feeds
    `ops.common.chunked_logprobs` so losses can avoid materializing
    full [B, T, V] logits."""
    if "lm_head" in params:
        kernel = params["lm_head"]["kernel"]

        def proj(h: Array) -> Array:
            return jnp.einsum(
                "...e,ev->...v", h, kernel.astype(h.dtype),
                preferred_element_type=jnp.float32,
            )

        return proj
    wte = params["embed"]["wte"]

    def proj(h: Array) -> Array:
        return jnp.einsum(
            "...e,ve->...v", h, wte.astype(h.dtype),
            preferred_element_type=jnp.float32,
        )

    return proj


def make_attention_bias(
    key_mask: Array,  # [B, S] 1 = attendable key slot
    q_slots: Array,  # [T] or [B, T] slot index of each query token
    k_slots: Array,  # [S] slot index of each key slot
) -> Array:
    """Additive causal+padding bias [B, 1, T, S] in fp32.

    Causality compares SLOT indices (physical storage order), which stays
    correct under left padding; rope/wpe positions are a separate notion
    (real position = cumsum of the mask) handled by the caller.
    """
    if q_slots.ndim == 1:
        q_slots = q_slots[None, :]
    causal = q_slots[:, :, None] >= k_slots[None, None, :]
    visible = causal & (key_mask[:, None, :] > 0)
    return jnp.where(visible, 0.0, NEG_INF)[:, None, :, :].astype(jnp.float32)


def _fold_layer_stats(stats: Optional[Dict[str, Array]]) -> Optional[Dict[str, Array]]:
    """A scan's stacked per-layer counters: `load` [layers, held] and
    `choices` [layers, published] stay by layer, the assignments made are summed."""
    if not stats:
        return None
    return {"load": stats["load"], "assignments": jnp.sum(stats["assignments"]),
            "choices": stats["choices"]}


def _join_stats(a: Optional[Dict[str, Array]], b: Optional[Dict[str, Array]]):
    """Counters of two stretches of layers of one pass: loads side by side."""
    if not a or not b:
        return a or b
    return {"load": jnp.concatenate([a["load"], b["load"]], axis=0),
            "assignments": a["assignments"] + b["assignments"],
            "choices": jnp.concatenate([a["choices"], b["choices"]], axis=0)}


def _add_stats(a: Optional[Dict[str, Array]], b: Optional[Dict[str, Array]]):
    """Counters of the same layers over two calls (a further decode step)."""
    if not a or not b:
        return a or b
    return jax.tree_util.tree_map(jnp.add, a, b)


def moe_counters(stats: Optional[Dict[str, Array]], program: str) -> Dict[str, Array]:
    """What a jitted program hands out for the flight stream, by program
    (`sampler`, `scorer`, `train`): `moe/assignments_here` (token-expert
    pairs computed on this chip), `moe/assignments` (pairs the routers made:
    tokens x experts per token x routed layers run) and
    `moe/load_max_over_mean` (the fullest held expert's rows over the mean
    of the held experts, in the worst layer). Empty for a model without experts."""
    if not stats:
        return {}
    load = jax.lax.stop_gradient(stats["load"])
    ratio = jnp.max(load, axis=-1) / jnp.maximum(jnp.mean(load, axis=-1), 1e-9)
    return {
        f"moe/assignments_here.{program}": jnp.sum(load),
        f"moe/assignments.{program}": jax.lax.stop_gradient(stats["assignments"]),
        f"moe/load_max_over_mean.{program}": jnp.max(ratio),
    }


def router_bias(tree: Dict) -> Dict[str, Array]:
    """The routers' selection bias by stack, {stack: [rows, published experts]}."""
    return {name: tree[name]["moe"]["router_bias"] for name in STACKS
            if name in tree and "moe" in tree[name]}


def with_router_bias(tree: Dict, bias: Dict[str, Array]) -> Dict:
    """`tree` with each routed stack's selection bias replaced by `bias[stack]`."""
    return dict(tree, **{
        name: dict(tree[name], moe=dict(tree[name]["moe"], router_bias=new))
        for name, new in bias.items()})


def _balance_step(lm: "TransformerLM", tree: Dict, bias: Dict[str, Array], input_ids: Array,
                  attention_mask: Array, rate: Array) -> Tuple[Dict[str, Array], Array]:
    """One step of `balance_router_bias`: (the new bias, the fullest expert's
    choices over the mean under the old one, by layer)."""
    out = lm(with_router_bias(tree, bias), input_ids, attention_mask, compute_logits=False)
    choices = out["moe_stats"]["choices"]  # [routed layers, published experts], in layer order
    mean = jnp.mean(choices, axis=-1, keepdims=True)
    step = rate * jnp.sign(mean - choices)
    routed = [layer for layer, ffn in enumerate(lm.cfg.ffns) if ffn == "routed"]
    new = {}
    for name, old in bias.items():
        rows = [routed.index(layer) for layer in stack_layers(lm.cfg, name)]  # this stack's layers among the routed ones
        # (one routed stack holds every routed layer, in order: no gather)
        new[name] = old + (step if len(bias) == 1 else step[jnp.array(rows)])
    return new, jnp.max(choices, axis=-1) / mean[:, 0]


def balance_router_bias(lm: "TransformerLM", params: Dict, input_ids: Array,
                        attention_mask: Array, steps: int,
                        rates: Tuple[float, float] = (0.03, 0.003)) -> Tuple[Dict, Array]:
    """`steps` steps of auxiliary-loss-free load balancing on one batch, the
    weights held: after each forward of `input_ids`, in every routed layer

        b_e += rate * sign(mean over experts of choices - choices_e)

    the rule a selection bias that is "added for the choice only" is
    trained by (`choices_e`: how often the layer's router chose expert e),
    with the rate falling geometrically from rates[0] to rates[1]. A
    published checkpoint's bias has been through it for its whole
    pretraining; under random weights a bias of zero sends a third of all
    assignments to four experts, the same four at most positions, and how
    many of them this chip holds is the draw of the seed. Returns the
    language model's tree with the new `router_bias` and, by layer, the
    fullest expert's choices over the mean before the first and after the
    last step, [2, routed layers]."""
    step = jax.jit(functools.partial(_balance_step, lm))
    bias = router_bias(params)
    ratios = []
    for i in range(steps):
        rate = rates[0] * (rates[1] / rates[0]) ** (i / max(steps - 1, 1))
        bias, ratio = step(params, bias, input_ids, attention_mask, jnp.float32(rate))
        ratios.append(ratio)
    # one more forward, at rate 0: what the last step left
    ratios.append(step(params, bias, input_ids, attention_mask, jnp.float32(0.0))[1])
    return with_router_bias(params, bias), jnp.stack([ratios[0], ratios[-1]])


class TransformerLM:
    """Functional causal LM: explicit params, scan-over-layers forward.

    params pytree:
      embed:  {wte, [wpe]}
      blocks: every Block param stacked with leading axis n_layer
      ln_f:   final norm
      [lm_head]: untied output projection

    Not an nn.Module by design — explicit params let the PPO hydra branch
    (`forward_from_layer` over a sliced param stack) and per-layer freeze
    masks operate on the tree directly (SURVEY.md §2.5 ModelBranch
    collapse).
    """

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.embed = Embedding(cfg)
        self._mesh = None
        self._build_blocks()
        self.ln_f = Norm(cfg)  # stateless: also applied with ln_embed params
        self.lm_head = None if cfg.tie_word_embeddings else LMHead(cfg)

    def _build_blocks(self) -> None:
        """The stack is segments of a kind (`layer_stacks`): a run of
        layers equal in mixer AND feed-forward. `first_k_dense` leading
        dense layers (`params["dense_blocks"]`), then the rest, routed
        where the model has experts, dense otherwise: the delta-rule
        layers under `params["delta_blocks"]`, layers of one sub-layer
        under the stack of their kind, the others under
        `params["blocks"]`. One module a stack (`blocks[name]`), one scan
        a segment."""
        cfg = self.cfg
        self.blocks: Dict[str, Block] = {}
        for (name, _), mixer, ffn in zip(layer_stacks(cfg), cfg.mixers, cfg.ffns):
            if name not in self.blocks:
                self.blocks[name] = Block(cfg, self._mesh, kind=ffn, mixer=mixer)
        self.block = self.blocks.get("blocks")  # the one stack of a dense decoder (pipelining)

    @property
    def mesh(self):
        """The device mesh, set by the trainer whenever it has more than
        one device, for what the model itself must know of it: ring
        attention (`sp` carries the sequence shards), pipelining (`pp`
        carries the layer stages), the pallas kernels (GSPMD cannot
        partition a Mosaic call, so Attention shard_maps it over this
        mesh), and a decode step's layouts on `fsdp`
        (`decode_weights_stationary`)."""
        return self._mesh

    @mesh.setter
    def mesh(self, mesh) -> None:
        self._mesh = mesh
        self._build_blocks()

    def _ring_mesh(self, batch: int, seq: int, cache) -> Optional[Any]:
        """The mesh to run ring attention over, or None for the XLA/pallas
        paths. Static (trace-time) decision: ring needs a full
        teacher-forced forward, a plain causal+padding bias, and shapes
        divisible by the mesh axes shard_map will split them over."""
        cfg = self.cfg
        if cfg.attention_impl != "ring" or self.mesh is None or cache is not None:
            return None
        if (
            cfg.attn_scale is not None
            or cfg.pos_embed == "alibi"
            or cfg.local_window is not None
        ):
            return None
        m = self.mesh.shape
        if m.get("sp", 1) <= 1:
            return None
        if (
            seq % m["sp"]
            or batch % (m["dp"] * m["fsdp"])
            or cfg.n_head % m["tp"]
        ):
            # sp>1 was requested but this call can't ring-shard — falling
            # back to full attention materializes the O(T^2) bias the user
            # configured sp to avoid, so say so (warnings dedupe per site)
            import warnings

            warnings.warn(
                f"ring attention requested (sp={m['sp']}) but shapes "
                f"batch={batch}, seq={seq}, n_head={cfg.n_head} don't divide "
                f"mesh axes {dict(m)}; falling back to full XLA attention",
                stacklevel=3,
            )
            return None
        return self.mesh

    def _pp_microbatches(self, batch: int, cache) -> int:
        """Microbatch count for a pipelined forward, or 0 for the
        sequential scan. Static (trace-time) decision. Pipelining needs a
        teacher-forced forward (decode steps thread a KV cache through
        every layer sequentially anyway) and divisible shapes; ring
        attention (sp) composes with dp/fsdp/tp but not with pp —
        eligibility rules live in parallel.pipeline.pp_microbatch_count,
        shared with the seq2seq stacks."""
        from trlx_tpu.parallel.pipeline import pp_microbatch_count

        cfg = self.cfg
        if cfg.beyond_dense and (
            self.mesh is not None and self.mesh.shape.get("pp", 1) > 1
        ):
            raise NotImplementedError(
                "pipeline parallelism (pp > 1) is not implemented for a model with latent "
                "attention, delta-rule (KDA) or state-space (Mamba-2) layers, routed experts or "
                "several residual streams"
            )
        if cache is not None:
            return 0
        return pp_microbatch_count(
            self.mesh, self.cfg.n_layer, batch, self.cfg.pp_microbatches
        )

    def _pipeline_blocks(
        self,
        block_params: Dict,
        h: Array,
        attn_bias: Array,
        positions: Array,
        *,
        n_microbatch: int,
        remat: bool = False,
        key_mask: Optional[Array] = None,
        local_bias: Optional[Array] = None,
        capture_points: Tuple[int, ...] = (),
    ) -> Tuple[Array, Tuple[Array, ...]]:
        """The pipelined counterpart of `_scan_blocks` over the FULL layer
        stack: stages = contiguous slices of the stacked params on the
        mesh's `pp` axis, GPipe microbatch schedule, captures returned for
        the hydra/value branches (parallel/pipeline.py has the schedule)."""
        from trlx_tpu.parallel.pipeline import pipelined_layers

        cfg = self.cfg
        flags = self._layer_flags(cfg.n_layer, 0)
        xs: Dict[str, Any] = {"p": block_params}
        if flags is not None:
            xs["flag"] = flags
        ctx = {
            "bias": attn_bias,
            "pos": positions,
            "km": key_mask,
            "lb": local_bias,
        }

        def layer_apply(layer, h, ctx_mb):
            bias = ctx_mb["bias"]
            if "flag" in layer:
                bias = bias + layer["flag"] * ctx_mb["lb"]
            out, _, _ = self.block.apply(
                {"params": layer["p"]}, h, bias, ctx_mb["pos"], None,
                ctx_mb["km"], None,
            )
            return out

        return pipelined_layers(
            self.mesh,
            layer_apply,
            xs,
            h,
            ctx,
            n_microbatch=n_microbatch,
            capture_points=capture_points,
            remat=remat,
            schedule=cfg.pp_schedule,
        )

    # -- bias / embedding helpers ---------------------------------------

    def _build_bias(
        self, key_mask: Array, q_slots: Array, k_slots: Array
    ) -> Tuple[Array, Optional[Array]]:
        """(attn_bias, local_bias): the base causal+padding bias, with the
        per-key ALiBi term folded in for bloom-style models, plus the extra
        sliding-window bias applied only on "local" layers (gpt-neo)."""
        cfg = self.cfg
        bias = make_attention_bias(key_mask, q_slots, k_slots)
        if cfg.pos_embed == "alibi":
            key_pos = jnp.maximum(jnp.cumsum(key_mask, axis=1) - 1, 0)
            alibi = (
                alibi_slopes(cfg.n_head)[None, :, None, None]
                * key_pos.astype(jnp.float32)[:, None, None, :]
            )
            bias = bias + alibi * (key_mask[:, None, None, :] > 0)
        local_bias = None
        if cfg.local_window is not None:
            qs = q_slots if q_slots.ndim == 2 else q_slots[None, :]
            dist = qs[:, :, None] - k_slots[None, None, :]  # [1|B, T, S]
            local_bias = jnp.where(dist >= cfg.local_window, NEG_INF, 0.0)[
                :, None, :, :
            ].astype(jnp.float32)
        return bias, local_bias

    def _embed_h(self, params: Dict, input_ids: Array, positions: Array) -> Array:
        h = self.embed.apply({"params": params["embed"]}, input_ids, positions)
        if self.cfg.embed_layernorm:
            h = self.ln_f.apply({"params": params["ln_embed"]}, h)
        return h

    def _to_streams(self, h: Array) -> Array:
        """[B, T, E] -> the residual state: the embedding copied to each
        of the streams, [B, T, n, E] (as it is for one stream)."""
        n = self.cfg.residual_streams
        return h if n == 1 else jnp.broadcast_to(h[:, :, None, :], h.shape[:2] + (n, h.shape[-1]))

    def _final_hidden(self, ln_f_params: Dict, h: Array) -> Array:
        """The streams summed, then the final norm."""
        if self.cfg.residual_streams > 1:
            h = h.sum(axis=2)
        return self.ln_f.apply({"params": ln_f_params}, h)

    def _layer_flags(self, n: int, layer_offset: int) -> Optional[Array]:
        """1.0 for layers using the local sliding window, else 0.0 — for
        the n layers starting at layer_offset in the full stack."""
        cfg = self.cfg
        if cfg.attn_layers is None or cfg.local_window is None:
            return None
        kinds = cfg.attn_layers[layer_offset : layer_offset + n]
        return jnp.asarray(
            [1.0 if k == "local" else 0.0 for k in kinds], jnp.float32
        )

    # -- init ------------------------------------------------------------

    def init(self, rng: jax.Array) -> Dict:
        cfg = self.cfg
        B, T = 1, 8
        ids = jnp.zeros((B, T), jnp.int32)
        pos = jnp.arange(T)[None, :]
        bias = make_attention_bias(jnp.ones((B, T), jnp.int32), pos, jnp.arange(T))

        r_embed, r_block, r_head, r_lm = jax.random.split(rng, 4)
        embed_params = self.embed.init(r_embed, ids, pos)["params"]
        h = jnp.zeros((B, T, cfg.hidden_size), cfg.dtype)
        state = self._to_streams(h)

        def stacked(block, keys):
            init = lambda key: block.init(key, state, bias, pos)["params"]
            # a routed block's grouped products (`jax.lax.ragged_dot`) take
            # no batch dimension on the chip: its layers are initialised
            # one after another and stacked
            return jax.lax.map(init, keys) if block.kind == "routed" else jax.vmap(init)(keys)

        layer_keys = jax.random.split(r_block, cfg.n_layer)
        params = {"embed": embed_params, "ln_f": self.ln_f.init(r_head, h)["params"]}
        for name, block in self.blocks.items():  # a layer's key is its index's, in whatever stack it lies
            params[name] = stacked(block, layer_keys[jnp.array(stack_layers(cfg, name))])
        if cfg.embed_layernorm:
            params["ln_embed"] = self.ln_f.init(r_head, h)["params"]
        if self.lm_head is not None:
            params["lm_head"] = self.lm_head.init(r_lm, h)["params"]
        return params

    # -- forward ---------------------------------------------------------

    def _scan_segment(
        self,
        block_params: Dict,
        h: Array,
        attn_bias: Array,
        positions: Array,
        cache: Optional[Dict[str, Array]] = None,
        remat: bool = False,
        key_mask: Optional[Array] = None,
        local_bias: Optional[Array] = None,
        layer_offset: int = 0,
        ring_mesh=None,
        stack: str = "blocks",
        rows: Optional[Tuple[int, int]] = None,
    ) -> Tuple[Array, Optional[Dict[str, Array]], Optional[Dict[str, Array]]]:
        """lax.scan over the stacked layer params (and cache layers) of
        ONE segment: layers of a kind, rows of `stack` (default: the main
        kind). `layer_offset` locates this slice within the full stack so
        per-layer attention kinds (gpt-neo global/local) and the layers'
        rows of a latent cache or a recurrent state line up. Returns
        (h, new_cache, stats): stats the routed layers' counters folded
        over the segment, or None. `rows` (first row, count): the segment is
        those rows of `block_params`, indexed in place a layer at a time
        (with a cache: a slice of the stack would be copied, every weight
        of it, at every decode step).

        Cache path: the [L, B, S, Hkv, D] buffers are CARRIED through
        the scan; each layer's attention writes only its new
        [B, T, Hkv, D] column in place and attends against a slice of
        the updated buffer (update-carry-first — the full design
        history and measured costs are in Attention.__call__)."""
        n = rows[1] if rows else jax.tree_util.tree_leaves(block_params)[0].shape[0]
        flags = self._layer_flags(n, layer_offset)
        blk = self.blocks[stack]
        from trlx_tpu.ops.remat import wrap_remat

        def layers_of(xs):  # the scan's xs, and how its body finds a layer's parameters
            if rows is None:
                return dict(xs, p=block_params), lambda layer: layer["p"]
            at = lambda layer: jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(x, layer["row"], 0, keepdims=False), block_params)
            return dict(xs, row=rows[0] + jnp.arange(n)), at

        if cache is not None and "pk" in cache and self.cfg.beyond_dense:
            raise NotImplementedError(
                "the paged decode engine (models/gen_engine.py) has no latent page pool, "
                "keeps no recurrent state for delta-rule (KDA) or state-space (Mamba-2) "
                "layers and runs no routed or multi-stream layer"
            )
        lead = "_lead" if stack == "dense_blocks" else ""
        if cache is not None and blk.mixer == "none" and "pk" not in cache:
            # layers that are a feed-forward alone keep nothing: the cache
            # passes by, and says only that a single token is a decode step
            xs, params_of = layers_of({})

            def bare_body(hidden, layer):
                out, _, stats = blk.apply(
                    {"params": params_of(layer)}, hidden, attn_bias, positions,
                    {"index": cache["index"]}, key_mask, ring_mesh,
                )
                return out, stats

            h, stats = jax.lax.scan(wrap_remat(bare_body, remat), h, xs)
            new_cache = {k: v for k, v in cache.items() if k != "static_index"}
            new_cache["index"] = cache["index"] + positions.shape[1]
            return h, new_cache, _fold_layer_stats(stats)

        if cache is not None and blk.mixer in ("delta", "ssm"):
            # recurrent state and convolution inputs, this stack's rows
            # (`kda_s` [layers, B, H, d, d] float32, `kda_u` [layers, B,
            # taps - 1, 3 H d]; of state-space layers `ssm_s` [layers, B,
            # H, P, N] float32, `ssm_u` [layers, B, taps - 1, H P + 2 G N]):
            # carried whole through the scan, as the latent rows below
            # are; a layer reads and writes its own row
            prefix = "kda" if blk.mixer == "delta" else "ssm"
            s_key, u_key = f"{prefix}_s{lead}", f"{prefix}_u{lead}"

            xs, params_of = layers_of({"ix": layer_stacks(self.cfg)[layer_offset][1] + jnp.arange(n)})

            def delta_body(carry, layer):
                hidden, s, u = carry
                layer_cache = {"s": s, "u": u, "ix": layer["ix"], "index": cache["index"]}
                out, new_kv, stats = blk.apply(
                    {"params": params_of(layer)}, hidden, attn_bias, positions, layer_cache,
                    key_mask, ring_mesh,
                )
                return (out, new_kv["s"], new_kv["u"]), stats

            (h, s, u), stats = jax.lax.scan(
                wrap_remat(delta_body, remat), (h, cache[s_key], cache[u_key]), xs)
            new_cache = {k: v for k, v in cache.items() if k != "static_index"}
            new_cache.update({s_key: s, u_key: u, "index": cache["index"] + positions.shape[1]})
            return h, new_cache, _fold_layer_stats(stats)

        if cache is not None and "c" in cache:
            # latent cache, this segment's rows [layers, B, S, rank + rope]
            # (the leading dense layers keep theirs apart, `c_lead`: one
            # array for both segments made the compiler lay it out anew
            # between them, twice a decode step): carried like the dense
            # one; each layer writes its positions' row in place and reads
            # its own [B, S, rank + rope] slice
            c_key = "c" + lead
            row0 = layer_stacks(self.cfg)[layer_offset][1]

            xs, params_of = layers_of({"ix": row0 + jnp.arange(n)})

            def latent_body(carry, layer):
                hidden, c = carry
                layer_cache = {"c": c, "ix": layer["ix"], "index": cache["index"]}
                if "static_index" in cache:
                    layer_cache["static_index"] = cache["static_index"]
                out, new_kv, stats = blk.apply(
                    {"params": params_of(layer)}, hidden, attn_bias, positions, layer_cache,
                    key_mask, ring_mesh,
                )
                return (out, new_kv["c"]), stats

            (h, c), stats = jax.lax.scan(
                wrap_remat(latent_body, remat), (h, cache[c_key]), xs)
            new_cache = {k: v for k, v in cache.items() if k != "static_index"}
            new_cache.update({c_key: c, "index": cache["index"] + positions.shape[1]})
            return h, new_cache, _fold_layer_stats(stats)

        if cache is not None and "pk" in cache:
            # paged cache: the scan carries the page POOLS; the page
            # table / slot positions / validity masks are per-forward
            # constants (the engine advances them between forwards), so
            # they ride the closure, not the carry
            pool_keys = tuple(
                name for name in ("pk", "pv", "pk_scale", "pv_scale")
                if name in cache
            )
            meta = {
                name: cache[name]
                for name in (
                    "page_table", "slot_pos", "lane_valid", "contiguous",
                    "attn_impl",
                )
                if name in cache
            }

            def paged_body(carry, layer):
                hidden = carry[0]
                layer_cache = dict(zip(pool_keys, carry[1:]), ix=layer["ix"], **meta)
                lp = layer["p"]
                bias = attn_bias
                if flags is not None:
                    bias = bias + layer["flag"] * local_bias
                out, new_kv, _ = blk.apply(
                    {"params": lp}, hidden, bias, positions, layer_cache,
                    key_mask, ring_mesh,
                )
                return (out,) + tuple(new_kv[k] for k in pool_keys), None

            paged_body = wrap_remat(paged_body, remat)
            # "layer_ixs" remaps this forward's layers onto pool layer
            # slots (gen_engine's spec-decode trunk sharing: the hydra
            # DRAFT's trunk layers index the policy pool's trunk — their
            # KV is identical by construction — while its branch layers
            # index the extension slots past the policy stack)
            layer_ixs = cache.get("layer_ixs")
            if layer_ixs is None:
                layer_ixs = jnp.arange(n)
            xs: Dict[str, Any] = {"p": block_params, "ix": layer_ixs}
            if flags is not None:
                xs["flag"] = flags
            carry, _ = jax.lax.scan(
                paged_body, (h,) + tuple(cache[k] for k in pool_keys), xs
            )
            new_cache = dict(cache, **dict(zip(pool_keys, carry[1:])))
            return carry[0], new_cache, None

        quant = cache is not None and "k_scale" in cache

        def body(carry, layer):
            if cache is not None:
                # hand the attention the FULL carried buffers + this
                # layer's row index: it writes its new column in place
                # and attends against a slice of the updated buffer (the
                # update-carry-first design; rationale in Attention)
                if quant:
                    hidden, ck, cv, cks = carry
                    layer_cache = {
                        "ck": ck, "cv": cv,
                        "ck_scale": cks,
                        # frozen per-layer V scales ride the scan's xs
                        # (sliced to this layer's [B, Hkv, D] row), not
                        # the carry: decode never updates them
                        "v_scale": layer["vs"],
                        "ix": layer["ix"], "index": cache["index"],
                    }
                else:
                    hidden, ck, cv = carry
                    layer_cache = {
                        "ck": ck,
                        "cv": cv,
                        "ix": layer["ix"],
                        "index": cache["index"],
                    }
                if "static_index" in cache:  # pallas prefill offset
                    layer_cache["static_index"] = cache["static_index"]
            else:
                hidden = carry
                layer_cache = None
            lp = params_of(layer)
            bias = attn_bias
            if flags is not None:
                bias = bias + layer["flag"] * local_bias
            out, new_kv, stats = blk.apply(
                {"params": lp}, hidden, bias, positions, layer_cache, key_mask,
                ring_mesh,
            )
            if quant:
                return (out, new_kv["ck"], new_kv["cv"], new_kv["ck_scale"]), stats
            if cache is not None:
                return (out, new_kv["ck"], new_kv["cv"]), stats
            return out, stats

        body = wrap_remat(body, remat)

        xs: Dict[str, Any] = {}
        if cache is not None:
            # beside a recurrent state the k/v rows are the attention
            # layers' alone: a layer's row is its row in its own stack
            row0 = layer_stacks(self.cfg)[layer_offset][1] if self.cfg.hybrid else 0
            xs["ix"] = row0 + jnp.arange(n) if row0 else jnp.arange(n)
        if flags is not None:
            xs["flag"] = flags
        xs, params_of = layers_of(xs)
        if quant:
            xs["vs"] = cache["v_scale"]
            (h, ck, cv, cks), stats = jax.lax.scan(
                body,
                (h, cache["k"], cache["v"], cache["k_scale"]),
                xs,
            )
            new_cache = dict(
                k=ck, v=cv, k_scale=cks, v_scale=cache["v_scale"],
                index=cache["index"] + positions.shape[1],
                key_mask=cache["key_mask"],
            )
        elif cache is not None:
            (h, ck, cv), stats = jax.lax.scan(body, (h, cache["k"], cache["v"]), xs)
            # (what else the cache holds, a recurrent state beside it, passes by)
            new_cache = dict(
                {k: v for k, v in cache.items() if k != "static_index"},
                k=ck, v=cv, index=cache["index"] + positions.shape[1],
            )
        else:
            h, stats = jax.lax.scan(body, h, xs)
            new_cache = None
        return h, new_cache, _fold_layer_stats(stats)

    def _run_layers(
        self,
        params: Dict,
        h: Array,
        lo: int,
        hi: int,
        attn_bias: Array,
        positions: Array,
        cache: Optional[Dict[str, Array]] = None,
        **kw,
    ) -> Tuple[Array, Optional[Dict[str, Array]], Optional[Dict[str, Array]]]:
        """Layers [lo, hi) of the whole stack, segment by segment
        (`layer_stacks`); a cache advances its index once. `params` is a
        whole tree or a BRANCH (`extract_branch_params`): of each stack
        the rows of the layers at or above a branch point, so a layer's
        row there is its row in the whole stack less what the branch
        left behind."""
        plan = layer_stacks(self.cfg)
        stats = None
        index = None if cache is None else (cache.get("index"), cache.get("static_index"))
        start = lo
        while start < hi:
            name, row = plan[start]
            end = start + 1
            while end < hi and plan[end][0] == name:
                end += 1
            stack = params[name]
            held = jax.tree_util.tree_leaves(stack)[0].shape[0]
            row -= len(stack_layers(self.cfg, name)) - held
            rows = None
            if (row, row + end - start) != (0, held):
                if cache is not None and "pk" not in cache:
                    rows = (row, end - start)  # a decode step copies no weight: the rows in place
                else:
                    stack = jax.tree_util.tree_map(lambda x: x[row : row + end - start], stack)
            h, new_cache, seg_stats = self._scan_segment(
                stack, h, attn_bias, positions, cache, layer_offset=start, stack=name, rows=rows, **kw)
            stats = _join_stats(stats, seg_stats)
            if cache is not None:
                cache = new_cache
                if end < hi and index[0] is not None:  # a further segment writes the same positions
                    cache = dict(cache, index=index[0])
                    if index[1] is not None:
                        cache["static_index"] = index[1]
            start = end
        return h, cache, stats

    def __call__(
        self,
        params: Dict,
        input_ids: Array,  # [B, T]
        attention_mask: Optional[Array] = None,  # [B, T]
        positions: Optional[Array] = None,
        cache: Optional[Dict[str, Array]] = None,
        remat: bool = False,
        prefix_embeds: Optional[Array] = None,  # [n, E] prompt tuning
        kv_prefix: Optional[Dict[str, Array]] = None,  # {k,v}: [L, n, Hkv, D]
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        """Full forward. Without `cache`: plain teacher-forced pass over a
        (possibly left-padded) sequence. With `cache`: the input occupies
        cache slots [index, index+T) and attends over the cache prefix —
        the same entry point serves prefill (T=prompt_len) and decode
        (T=1).

        Adapters (teacher-forced paths; generation warms the KV cache
        instead — see models/generation.py):
        - `prefix_embeds` (PROMPT tuning): n trainable soft tokens run as
          real leading sequence positions; outputs keep [B, T] shapes
          (the virtual rows are sliced off after the blocks).
        - `kv_prefix` (PREFIX tuning): trainable per-layer key/values,
          realized as a pre-warmed pseudo-cache so the attention path is
          untouched. Real-token positions shift by n in both cases
          (HF peft past-length semantics)."""
        B, T = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, T), jnp.int32)
        n_virtual = 0  # rows to slice off the outputs (prompt tuning)
        if prefix_embeds is not None and cache is None:
            # teacher-forced prompt tuning: soft tokens become real
            # leading positions; callers keep [B, T] output shapes
            n_virtual = prefix_embeds.shape[0]
            input_ids = jnp.concatenate(
                [jnp.zeros((B, n_virtual), input_ids.dtype), input_ids], axis=1
            )
            attention_mask = jnp.concatenate(
                [jnp.ones((B, n_virtual), jnp.int32), attention_mask], axis=1
            )
            positions = None  # recomputed over the extended mask below
            T = T + n_virtual
        if kv_prefix is not None and cache is None:
            # prefix tuning: trainable per-layer k/v realized as a
            # pre-warmed pseudo-cache occupying slots [0, n); the input
            # occupies [n, n+T) so the attention path is untouched
            n = kv_prefix["k"].shape[1]
            S = n + T
            shape = (self.cfg.n_layer, B, S) + kv_prefix["k"].shape[2:]

            def tiled(x):
                return jnp.broadcast_to(
                    x[:, None], (self.cfg.n_layer, B) + x.shape[1:]
                ).astype(self.cfg.dtype)

            cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros(shape, self.cfg.dtype), tiled(kv_prefix["k"]), 0, axis=2
                ),
                "v": jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros(shape, self.cfg.dtype), tiled(kv_prefix["v"]), 0, axis=2
                ),
                "index": jnp.int32(n),
                "static_index": n,
                "key_mask": jnp.concatenate(
                    [jnp.ones((B, n), jnp.int32), attention_mask], axis=1
                ),
            }
            # pad-aware positions shifted past the prefix (HF past-length
            # semantics)
            positions = n + jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
        ring = None
        if cache is not None and "pk" in cache:
            # paged cache (gen_engine): per-ROW slot positions — each
            # decode lane sits at its own depth, unlike the dense cache's
            # single scalar write index. The engine precomputes key_mask
            # to cover exactly the valid logical slots INCLUDING the T
            # incoming tokens; causality among those tokens falls out of
            # the slot-index comparison in make_attention_bias.
            S = cache["page_table"].shape[1] * cache["pk"].shape[2]
            q_slots = cache["slot_pos"][:, None] + jnp.arange(T)[None, :]
            if positions is None:
                positions = q_slots
            key_mask = cache["key_mask"].astype(jnp.int32)
            bias, local_bias = self._build_bias(key_mask, q_slots, jnp.arange(S))
            layer_cache = cache
        elif cache is not None:
            # bf16 cache: [L, B, S, Hkv, D]; int8 (quantized) cache:
            # [L, B, Hkv, S, D] (layout rationale: quantize_kv_cache)
            S = cache["key_mask"].shape[1]  # one slot a key, whatever the layers keep of it
            q_slots = cache["index"] + jnp.arange(T)
            if positions is None:
                positions = q_slots[None, :] * jnp.ones((B, 1), jnp.int32)
            within = jnp.arange(S)[None, :] < cache["index"] + T  # [1, S]
            key_mask = (within & (cache["key_mask"] > 0)).astype(jnp.int32)
            bias, local_bias = self._build_bias(key_mask, q_slots, jnp.arange(S))
            layer_cache = cache
        else:
            if positions is None:
                positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
            ring = self._ring_mesh(B, T, cache)
            if ring is not None:
                # the ring path masks via per-shard segment masks and global
                # position comparison — never materialize the [B,1,T,T] bias
                bias, local_bias = None, None
            else:
                bias, local_bias = self._build_bias(
                    attention_mask, jnp.arange(T), jnp.arange(T)
                )
            layer_cache = None

        if self.cfg.beyond_dense and (prefix_embeds is not None or kv_prefix is not None):
            raise NotImplementedError(
                "prompt and prefix adapters are not implemented for a model with latent "
                "attention, delta-rule (KDA) or state-space (Mamba-2) layers, routed experts or "
                "several residual streams"
            )
        h = self._embed_h(params, input_ids, positions)
        if prefix_embeds is not None:
            # the virtual slots were embedded as token 0 (+wpe): swap the
            # wte row for the trainable soft embedding, keeping wpe
            n_rows = n_virtual if n_virtual else h.shape[1]
            wte0 = params["embed"]["wte"][0].astype(h.dtype)
            soft = prefix_embeds[None, :n_rows].astype(h.dtype)
            h = jax.lax.dynamic_update_slice_in_dim(
                h, h[:, :n_rows] - wte0 + soft, 0, axis=1
            )
        n_mb = 0 if ring is not None else self._pp_microbatches(B, layer_cache)
        if n_mb:
            h, _ = self._pipeline_blocks(
                params["blocks"], h, bias, positions, n_microbatch=n_mb,
                remat=remat, key_mask=attention_mask, local_bias=local_bias,
            )
            new_cache, stats = None, None
        else:
            h, new_cache, stats = self._run_layers(
                params, self._to_streams(h), 0, self.cfg.n_layer, bias, positions,
                layer_cache, remat=remat,
                key_mask=key_mask if cache is not None else attention_mask,
                local_bias=local_bias,
                ring_mesh=None if cache is not None else ring,
            )
        hidden = self._final_hidden(params["ln_f"], h)
        # compute_logits=False: callers using chunked-from-hidden losses
        # (train.logit_chunks) skip the full [B, T, V] projection here
        logits = None
        lay = compute_logits and _decode_layouts(self.cfg, self.mesh, cache, input_ids)
        if lay:
            # the head is split over E like q, k, v: float32 partial
            # logits (both heads accumulate so), reduced into the rows
            logits = lay.rows(self._logits(params, lay.split(lay.residual(hidden))))
        elif compute_logits:
            logits = self._logits(params, hidden)
        if n_virtual:
            hidden = hidden[:, n_virtual:]
            logits = logits[:, n_virtual:] if logits is not None else None
            positions = positions[:, n_virtual:]
        out = {
            "logits": logits,
            "hidden_states": hidden,
            "cache": new_cache,
            "positions": positions,
        }
        if stats:
            out["moe_stats"] = stats
        return out

    def _logits(self, params: Dict, hidden: Array) -> Array:
        if self.lm_head is not None:
            return self.lm_head.apply({"params": params["lm_head"]}, hidden)
        return self.embed.apply(
            {"params": params["embed"]}, hidden, method=Embedding.attend
        )

    # -- hydra support ---------------------------------------------------

    def forward_with_branch_capture(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Optional[Array],
        branch_at: int,
        remat: bool = False,
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        """Forward that also returns the hidden state entering layer
        `branch_at`: the scan is split into [0, branch_at) + [branch_at,
        L), same total compute. The captured hidden feeds the frozen
        reference branch (`forward_from_layer`)."""
        B, T = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, T), jnp.int32)
        positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
        ring = self._ring_mesh(B, T, None)
        if ring is not None:
            bias, local_bias = None, None
        else:
            bias, local_bias = self._build_bias(
                attention_mask, jnp.arange(T), jnp.arange(T)
            )
        h = self._embed_h(params, input_ids, positions)

        n_mb = 0 if ring is not None else self._pp_microbatches(B, None)
        if n_mb:
            h_top, (h_branch,) = self._pipeline_blocks(
                params["blocks"], h, bias, positions, n_microbatch=n_mb,
                remat=remat, key_mask=attention_mask, local_bias=local_bias,
                capture_points=(branch_at,),
            )
        else:
            h_branch, _, _ = self._run_layers(
                params, self._to_streams(h), 0, branch_at, bias, positions,
                remat=remat, key_mask=attention_mask,
                local_bias=local_bias, ring_mesh=ring,
            )
            h_top, _, _ = self._run_layers(
                params, h_branch, branch_at, self.cfg.n_layer, bias, positions,
                remat=remat, key_mask=attention_mask,
                local_bias=local_bias, ring_mesh=ring,
            )
        hidden = self._final_hidden(params["ln_f"], h_top)
        logits = self._logits(params, hidden) if compute_logits else None
        return {
            "logits": logits,
            "hidden_states": hidden,
            "branch_hidden": h_branch,
            "positions": positions,
            "attn_bias": bias,
            "local_bias": local_bias,
            "key_mask": attention_mask,
        }

    def _capture_context(self, input_ids: Array, attention_mask: Optional[Array]):
        """(what `_run_layers` takes beside the layers of a teacher-forced
        capture forward: `attn_bias`, `positions`, `key_mask`, `local_bias`,
        `ring_mesh`; the pipeline's microbatch count): cheap functions of
        the tokens' mask, rebuilt wherever the forward starts or resumes."""
        B, T = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, T), jnp.int32)
        positions = jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
        ring = self._ring_mesh(B, T, None)
        if ring is not None:
            bias, local_bias = None, None
        else:
            bias, local_bias = self._build_bias(
                attention_mask, jnp.arange(T), jnp.arange(T)
            )
        n_mb = 0 if ring is not None else self._pp_microbatches(B, None)
        return dict(attn_bias=bias, positions=positions, key_mask=attention_mask,
                    local_bias=local_bias, ring_mesh=ring), n_mb

    def resume_point(self, points: Tuple[int, ...], frozen_below: int) -> int:
        """The layer a capture forward resumes at when it is handed its
        constants (`trunk_constants`): the highest capture point at or
        below `frozen_below`. 0 where nothing is held: no such point, or
        a mesh on which the forward is pipelined (`pp > 1`) or runs ring
        attention (`sp > 1`), decided by the mesh alone so that the rows a
        constant was computed in never matter."""
        m = {} if self.mesh is None else dict(self.mesh.shape)
        if m.get("pp", 1) > 1 or (self.cfg.attention_impl == "ring" and m.get("sp", 1) > 1):
            return 0
        return max((p for p in points if 0 < p <= frozen_below), default=0)

    def _constant_segments(self, frozen: Dict, h: Array, points: Tuple[int, ...], upto: int, ctx: Dict):
        """The segments between capture points that end at or below
        `upto`, on gradient-stopped params, without remat (nothing is
        transposed, so nothing is saved or recomputed), each output
        gradient-stopped: (h, captures, stats, the layer reached)."""
        captures, stats, prev = [], None, 0
        for point in points:
            if point > upto:
                break
            if point > prev:
                h, _, seg_stats = self._run_layers(frozen, h, prev, point, remat=False, **ctx)
                stats = _join_stats(stats, seg_stats)
                h = jax.lax.stop_gradient(h)
            if point < self.cfg.n_layer:
                captures.append(h)
            prev = point
        return h, captures, stats, prev

    def trunk_constants(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Optional[Array],
        points: Tuple[int, ...],
        frozen_below: int,
    ) -> Optional[Tuple[Tuple[Array, ...], Optional[Dict[str, Array]]]]:
        """What `forward_with_multi_capture` computes of these rows that no
        optimizer step changes: the embedding and every segment that ends
        at or below `frozen_below`, on gradient-stopped params in the
        compute dtype, without remat. Returns (captures, moe_stats): the
        hidden state entering each capture point up to `resume_point`, in
        stream form ([B, T, E]; [B, T, n, E] for n streams), the last of
        them the residual state entering the first layer the resumed
        forward runs; and the routed layers' counters over these rows (None
        without experts). None where `resume_point` is 0. A fused block
        computes them once over all its rows (`trainer/base.py`
        `make_fused_train_steps`) and hands each step its rows'."""
        upto = self.resume_point(points, frozen_below)
        if not upto:
            return None
        ctx, _ = self._capture_context(input_ids, attention_mask)
        frozen = jax.lax.stop_gradient(params)
        h = self._to_streams(self._embed_h(frozen, input_ids, ctx["positions"]))
        _, captures, stats, _ = self._constant_segments(frozen, h, tuple(points), upto, ctx)
        return tuple(captures), jax.lax.stop_gradient(stats)

    def forward_with_multi_capture(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Optional[Array],
        points: Tuple[int, ...],
        remat: bool = False,
        compute_logits: bool = True,
        frozen_below: int = 0,
        trunk: Optional[Tuple[Tuple[Array, ...], Optional[Dict[str, Array]]]] = None,
    ) -> Dict[str, Array]:
        """Forward capturing the hidden state entering each layer index in
        `points` (sorted ascending). Generalizes branch capture so the
        hydra reference branch and the trainable value branch
        (reference make_value_branch, modeling_ppo.py:255-263) can fork at
        different depths in ONE trunk pass.

        `frozen_below` (static) is the index of the first trainable
        layer. Everything under it is a constant of a differentiated
        forward: the embedding and the segments that end at or below it
        run on gradient-stopped params without remat (nothing is
        transposed, so nothing is saved or recomputed), and their output
        is gradient-stopped too, so the backward ends where the freeze
        mask (`make_freeze_mask`) says training does. A segment that
        straddles it, and the pipeline-parallel path (`pp > 1`), keep
        the full backward.

        `trunk` is what `trunk_constants` returned for these rows (with
        its counters as the caller wants them counted in this call): the
        constant part is then not run here. The forward rebuilds
        positions and bias, starts from the last held capture at
        `resume_point` and returns what it returns without `trunk`. A
        fused block runs the trunk once that way, not once an optimizer
        step; every other caller passes none and runs it here."""
        ctx, n_mb = self._capture_context(input_ids, attention_mask)
        attention_mask, positions = ctx["key_mask"], ctx["positions"]
        bias, local_bias = ctx["attn_bias"], ctx["local_bias"]
        if n_mb:
            frozen_below = 0
        frozen = jax.lax.stop_gradient(params) if frozen_below else params
        points = tuple(points)

        if n_mb:
            h = self._embed_h(frozen, input_ids, positions)
            stats = None
            # match the sequential path: points >= n_layer are omitted
            # (never captured), not returned as zeros
            in_range = tuple(p for p in points if p < self.cfg.n_layer)
            h, caps = self._pipeline_blocks(
                params["blocks"], h, bias, positions, n_microbatch=n_mb,
                remat=remat, key_mask=attention_mask, local_bias=local_bias,
                capture_points=in_range,
            )
            captures = list(caps)
        else:
            if trunk is None:
                h = self._to_streams(self._embed_h(frozen, input_ids, positions))
                h, captures, stats, prev = self._constant_segments(frozen, h, points, frozen_below, ctx)
            else:
                captures, stats = list(trunk[0]), trunk[1]
                h, prev = captures[-1], self.resume_point(points, frozen_below)
            for point in points[sum(p <= prev for p in points):] + (self.cfg.n_layer,):
                if point > prev:
                    h, _, seg_stats = self._run_layers(params, h, prev, point, remat=remat, **ctx)
                    stats = _join_stats(stats, seg_stats)
                if point < self.cfg.n_layer:
                    captures.append(h)
                prev = point
        hidden = self._final_hidden(params["ln_f"], h)
        # a tied head reads the (frozen) embedding
        head = dict(params, embed=frozen["embed"])
        logits = self._logits(head, hidden) if compute_logits else None
        out = {
            "logits": logits,
            "hidden_states": hidden,
            "captures": captures,
            "positions": positions,
            "attn_bias": bias,
            "local_bias": local_bias,
            "key_mask": attention_mask,
        }
        if stats:
            out["moe_stats"] = jax.lax.stop_gradient(stats)
        return out

    def forward_from_layer(
        self,
        branch_params: Dict,
        branch_hidden: Array,
        attn_bias: Array,
        positions: Array,
        remat: bool = False,
        local_bias: Optional[Array] = None,
        key_mask: Optional[Array] = None,
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        """Run only a top-k branch from a captured hidden state.

        `branch_params` holds {"blocks": stacked top-k params (with
        delta-rule layers among them, those under "delta_blocks"), "ln_f",
        "embed", ["lm_head"]} — the frozen in-process reference model
        (parity: hydra `forward_hydra`, reference modeling_ppo.py:410-453).
        The branch is always the TOP k layers, so per-layer attention
        kinds are aligned from the end of the stack. With `attn_bias=None`
        (ring-attention capture) the padding mask rides in `key_mask`.
        """
        k = sum(jax.tree_util.tree_leaves(branch_params[name])[0].shape[0]
                for name in STACKS if name in branch_params)
        ring = None
        if attn_bias is None and key_mask is not None:
            B, T = branch_hidden.shape[:2]
            ring = self._ring_mesh(B, T, None)
        h, _, stats = self._run_layers(
            branch_params, branch_hidden, self.cfg.n_layer - k, self.cfg.n_layer,
            attn_bias, positions, remat=remat, local_bias=local_bias,
            key_mask=key_mask, ring_mesh=ring,
        )
        hidden = self._final_hidden(branch_params["ln_f"], h)
        logits = self._logits(branch_params, hidden) if compute_logits else None
        out = {"logits": logits, "hidden_states": hidden}
        if stats:
            out["moe_stats"] = jax.lax.stop_gradient(stats)
        return out

    # -- cache -----------------------------------------------------------

    def init_cache(self, batch: int, max_len: int, key_mask: Optional[Array] = None) -> Dict:
        """Preallocate a static-shape KV cache [L, B, S, Hkv, D].

        `static_index` mirrors `index` as a PYTHON int while the cache
        stays inside one trace: it lets the first forward (prefill, T>1)
        take the pallas kernel at a static slot offset. Forwards drop it
        from the cache they return (decode loops carry arrays only), and
        a cache that crosses a jit boundary loses its int-ness — both
        cases just fall back to the XLA path."""
        cfg = self.cfg
        mask = key_mask if key_mask is not None else jnp.ones((batch, max_len), jnp.int32)
        if cfg.hybrid:
            # two kinds of state in one carry, each stack's in its own
            # arrays: rows that grow with the sequence (latent `c`,
            # `c_lead`, as below, or per-head `k` and `v` of the attention
            # layers alone) and, for delta-rule and state-space layers, a
            # float32 state and the convolutions' last inputs, which do
            # not (`kda_s`, `kda_u`, `_lead` for the leading dense layers;
            # `ssm_s`, `ssm_u`). A layer without a mixer keeps nothing
            cache = {"index": jnp.int32(0), "static_index": 0, "key_mask": mask}
            H, D, width = cfg.delta_heads, cfg.delta_head_dim, 3 * cfg.delta_heads * cfg.delta_head_dim
            for name, block in self.blocks.items():
                layers = len(stack_layers(cfg, name))
                lead = "_lead" if name == "dense_blocks" else ""
                if block.mixer == "delta":
                    cache["kda_s" + lead] = jnp.zeros((layers, batch, H, D, D), jnp.float32)
                    cache["kda_u" + lead] = jnp.zeros((layers, batch, cfg.delta_conv - 1, width), cfg.dtype)
                elif block.mixer == "ssm":
                    cache["ssm_s"] = jnp.zeros(
                        (layers, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
                    cache["ssm_u"] = jnp.zeros((layers, batch, cfg.ssm_conv - 1, cfg.ssm_conv_width), cfg.dtype)
                elif block.mixer == "softmax":
                    for key in ("k", "v"):
                        cache[key] = jnp.zeros((layers, batch, max_len, cfg.n_kv_head, cfg.head_dim), cfg.dtype)
                elif block.mixer == "latent":
                    cache["c" + lead] = jnp.zeros(
                        (layers, batch, max_len, cfg.cache_elems_per_position), cfg.dtype)
            return cache
        if cfg.latent:
            # [layers, B, S, rank + rope]: the normed latent and the rotated
            # shared key of each position, not per-head keys and values; a
            # segment of layers of a kind keeps its rows in its own array
            # (`c_lead`: the leading dense layers, `c`: the rest)
            def rows(layers):
                return jnp.zeros((layers, batch, max_len, cfg.cache_elems_per_position), cfg.dtype)

            cache = {"c": rows(cfg.n_layer - cfg.first_k_dense), "index": jnp.int32(0),
                     "static_index": 0, "key_mask": mask}
            if cfg.first_k_dense:
                cache["c_lead"] = rows(cfg.first_k_dense)
            return cache
        shape = (cfg.n_layer, batch, max_len, cfg.n_kv_head, cfg.head_dim)
        return {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "index": jnp.int32(0),
            "static_index": 0,
            "key_mask": key_mask if key_mask is not None
            else jnp.ones((batch, max_len), jnp.int32),
        }


def extract_branch_params(params: Dict, branch_at: int, cfg: Optional[TransformerConfig] = None) -> Dict:
    """Copy the top-(L-branch_at) layers + final norm + logit head as a
    frozen reference branch. Parity: the hydra 'frozen_head' build
    (reference modeling_ppo.py:475-499) without per-arch classes. A branch
    forks at or above the leading dense layers (`params["dense_blocks"]`),
    which it leaves behind; of every other stack it takes the rows of the
    layers at or above `branch_at` (`cfg` says which those are where the
    tree has delta-rule layers; without them they are the top rows of
    `blocks`)."""
    lead = jax.tree_util.tree_leaves(params["dense_blocks"])[0].shape[0] if "dense_blocks" in params else 0
    if branch_at < lead:
        raise NotImplementedError(
            f"a branch at layer {branch_at} would reach into the {lead} leading dense layers"
        )
    if cfg is None and any(name in params for name in STACKS[2:]):
        raise ValueError("a tree with delta-rule layers or layers of one sub-layer needs its config "
                         "to place a branch point")
    branch = {"ln_f": params["ln_f"], "embed": params["embed"]}
    for name in STACKS[1:]:
        if name in params:
            below = branch_at - lead if cfg is None else sum(i < branch_at for i in stack_layers(cfg, name))
            branch[name] = jax.tree_util.tree_map(lambda x: x[below:], params[name])
    if "lm_head" in params:
        branch["lm_head"] = params["lm_head"]
    return jax.lax.stop_gradient(branch)
