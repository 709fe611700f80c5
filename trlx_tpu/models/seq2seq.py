"""TPU-native encoder-decoder (T5-family) model.

Parity: the reference's seq2seq support — value-head wrappers
(/root/reference/trlx/models/modeling_ppo.py:1242-1480), the frozen `T5Branch`
(modeling_ppo.py:1483-1592) and ILQL seq2seq (modeling_ilql.py:481-666) all
wrap HF T5. Here the model itself is first-party: one functional
encoder/decoder with scan-stacked layers, mirroring
trlx_tpu.models.transformer's design (static shapes, explicit param
trees, KV-cache decode, branch capture for the hydra reference).

T5 specifics honored: RMS layer norm without bias, no attention scaling
(folded into init), relative position bias shared across layers (a
single [n_buckets, n_head] table per stack), optional gated-GELU MLP
(v1.1), logits scaled by d_model^-0.5 when embeddings are tied.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from trlx_tpu.models.transformer import NEG_INF, QDense

Array = jnp.ndarray


@dataclass(frozen=True)
class Seq2SeqConfig:
    vocab_size: int
    d_model: int
    n_layer: int  # encoder layers
    n_decoder_layer: Optional[int] = None  # default n_layer
    n_head: int = 8
    d_kv: int = 64
    d_ff: int = 2048
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    layer_norm_epsilon: float = 1e-6
    activation: str = "relu"  # "relu" | "gated-gelu"
    tie_word_embeddings: bool = True
    decoder_start_token_id: int = 0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # "xla" | "pallas": fused flash kernels for teacher-forced encoder
    # and decoder self-attention (ops/flash_attention.flash_attention_bias
    # — carries the learned relative position bias with a proper dbias
    # backward) and for cross-attention (padding-mask-only kernel).
    # Decode steps (KV cache) and shapes not divisible by 128 fall back
    # to XLA. The per-layer [B, H, T, S] score tensor never materializes
    # on this path — long-context summarization training's memory win.
    attention_impl: str = "xla"
    # None | "int8": generate_seq2seq rewrites the DECODER block kernels
    # to int8 + per-output-channel scales (QDense) for the decode loop —
    # the decoder weights are the stream every step re-reads, while the
    # encoder runs once per sample at full precision. Same contract as
    # TransformerConfig.decode_weights_quant.
    decode_weights_quant: Optional[str] = None
    # pipeline parallelism: microbatches per pipelined stack when the
    # mesh has a pp axis > 1 (0 = one per stage); raise to shrink the
    # (pp-1)/(M+pp-1) bubble — mirrors TransformerConfig.pp_microbatches
    pp_microbatches: int = 0
    pp_schedule: str = "gpipe"  # mirrors TransformerConfig.pp_schedule

    def __post_init__(self):
        if self.n_decoder_layer is None:
            object.__setattr__(self, "n_decoder_layer", self.n_layer)

    def replace(self, **kw) -> "Seq2SeqConfig":
        return dataclasses.replace(self, **kw)


def relative_position_bucket(
    relative_position: Array, bidirectional: bool, num_buckets: int, max_distance: int
) -> Array:
    """T5's log-binned relative position bucketing."""
    ret = jnp.zeros_like(relative_position)
    n = -relative_position
    if bidirectional:
        num_buckets //= 2
        ret = ret + (n < 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = jnp.maximum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_large = max_exact + (
        jnp.log(jnp.maximum(n, 1).astype(jnp.float32) / max_exact)
        / jnp.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    val_large = jnp.minimum(val_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_large)


def compute_position_bias(
    rel_bias_table: Array,  # [n_buckets, n_head]
    q_pos: Array,  # [T]
    k_pos: Array,  # [S]
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> Array:
    """[1, n_head, T, S] additive attention bias.

    The gather is head-major ([H, T, S] directly, NOT [T, S, H] then
    transpose): a [T*S, H] intermediate has an H-wide minor dim that the
    TPU lane layout pads to 128 — 16x inflation, a 34 GB allocation at
    8k/8-head where the real tensor is 2 GB."""
    rel = k_pos[None, :] - q_pos[:, None]  # [T, S]
    buckets = relative_position_bucket(rel, bidirectional, num_buckets, max_distance)
    bias = jnp.take(rel_bias_table.transpose(1, 0), buckets, axis=1)  # [H, T, S]
    return bias[None].astype(jnp.float32)


def compute_position_bias_dense(
    rel_bias_table: Array,  # [n_buckets, n_head]
    T: int,
    S: int,
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> Array:
    """[1, n_head, T, S] bias for CONSECUTIVE positions (arange(T) vs
    arange(S)) — every teacher-forced stack call.

    Exploits the Toeplitz structure (the bias depends only on s - t):
    bucket and gather a tiny [H, T+S-1] relative vector, then expand to
    [H, T, S] with vmapped dynamic slices. A direct [T, S]-indexed
    gather (and its scatter-add transpose for the trainable table's
    gradient) lowers to a [T*S, H]-shaped buffer whose 8-wide minor dim
    the TPU lane layout pads 16x — a 34 GB allocation at 8k tokens
    (measured); this construction never builds a lane-padded buffer in
    either direction."""
    R = T + S - 1
    rel_vec = jnp.arange(R) - (T - 1)  # s - t for each diagonal
    buckets = relative_position_bucket(
        rel_vec, bidirectional, num_buckets, max_distance
    )
    bias_rel = jnp.take(
        rel_bias_table.transpose(1, 0), buckets, axis=1
    )  # [H, R]

    def row(t):
        return jax.lax.dynamic_slice_in_dim(bias_rel, (T - 1) - t, S, axis=1)

    bias = jax.vmap(row)(jnp.arange(T)).transpose(1, 0, 2)  # [H, T, S]
    return bias[None].astype(jnp.float32)


class T5Norm(nn.Module):
    cfg: Seq2SeqConfig

    @nn.compact
    def __call__(self, x: Array) -> Array:
        x32 = x.astype(jnp.float32)
        scale = self.param(
            "scale", nn.initializers.ones, (self.cfg.d_model,), self.cfg.param_dtype
        )
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.cfg.layer_norm_epsilon) * scale).astype(
            x.dtype
        )


class T5Attention(nn.Module):
    cfg: Seq2SeqConfig

    @nn.compact
    def __call__(
        self,
        x: Array,  # [B, T, D] queries
        kv: Array,  # [B, S, D] keys/values source
        bias: Optional[Array],  # [B or 1, H, T, S] additive — None takes
        # the fused pallas path (the caller gated shapes) with the
        # structured pieces below instead
        cache: Optional[Dict[str, Array]] = None,
        pos_bias: Optional[Array] = None,  # [1, H, T, S] learned rel bias
        # (rank-4 with a leading broadcast dim so pipeline-parallel ctx
        # splitting never mistakes the head axis for a batch axis)
        key_mask: Optional[Array] = None,  # [B, S] 1 = attendable
        causal: bool = False,
    ) -> Tuple[Array, Optional[Dict[str, Array]]]:
        cfg = self.cfg
        H, Dk = cfg.n_head, cfg.d_kv
        dense = partial(
            QDense,
            axis=-1,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=nn.initializers.normal(cfg.d_model**-0.5),
        )
        q = dense(features=(H, Dk), name="q")(x)
        k = dense(features=(H, Dk), name="k")(kv)
        v = dense(features=(H, Dk), name="v")(kv)

        new_kv = None
        if cache is not None:
            # update-carry-first, same as the causal stack (rationale and
            # measured design history in TransformerLM Attention): write
            # this layer's new column into the scan-carried stacked
            # buffer, then attend against a slice of the UPDATED buffer —
            # one full cache read + one column write per step, no
            # per-layer updated-row copy
            idx = cache["index"]
            ix = cache["ix"]
            ck = jax.lax.dynamic_update_slice(
                cache["ck"], k[None].astype(cache["ck"].dtype), (ix, 0, idx, 0, 0)
            )
            cv = jax.lax.dynamic_update_slice(
                cache["cv"], v[None].astype(cache["cv"].dtype), (ix, 0, idx, 0, 0)
            )
            new_kv = {"ck": ck, "cv": cv}
            k = jax.lax.dynamic_index_in_dim(ck, ix, 0, keepdims=False).astype(cfg.dtype)
            v = jax.lax.dynamic_index_in_dim(cv, ix, 0, keepdims=False).astype(cfg.dtype)

        if bias is None:
            # fused path (NOTE: T5 has no 1/sqrt(d) — sm_scale=1.0):
            # self-attention carries the learned rel bias through
            # flash_attention_bias (dbias flows back to the table);
            # cross-attention has padding masking only, so the plain
            # kernel serves it
            from trlx_tpu.ops.flash_attention import (
                flash_attention,
                flash_attention_bias,
            )

            qT = q.transpose(0, 2, 1, 3)
            kT = k.transpose(0, 2, 1, 3)
            vT = v.transpose(0, 2, 1, 3)
            if pos_bias is not None:
                out = flash_attention_bias(
                    qT, kT, vT, key_mask, pos_bias[0], causal=causal,
                    sm_scale=1.0,
                )
            else:
                out = flash_attention(
                    qT, kT, vT, key_mask, causal=False, sm_scale=1.0
                )
            out = out.transpose(0, 2, 1, 3).astype(cfg.dtype)
        else:
            scores = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32)
            scores = scores + bias
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bhts,bshd->bthd", probs, v)
        proj = QDense(
            features=cfg.d_model,
            axis=(-2, -1),
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=nn.initializers.normal((H * Dk) ** -0.5),
            name="o",
        )
        return proj(out), new_kv


class T5MLP(nn.Module):
    cfg: Seq2SeqConfig

    @nn.compact
    def __call__(self, x: Array) -> Array:
        cfg = self.cfg
        dense = partial(
            QDense,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            use_bias=False,
            kernel_init=nn.initializers.normal(cfg.d_model**-0.5),
        )
        if cfg.activation == "gated-gelu":
            h = jax.nn.gelu(dense(features=cfg.d_ff, name="fc_in")(x), approximate=True)
            h = h * dense(features=cfg.d_ff, name="fc_gate")(x)
        else:
            h = jax.nn.relu(dense(features=cfg.d_ff, name="fc_in")(x))
        return dense(features=cfg.d_model, name="fc_out",
                     kernel_init=nn.initializers.normal(cfg.d_ff**-0.5))(h)


class T5Block(nn.Module):
    cfg: Seq2SeqConfig
    is_decoder: bool

    @nn.compact
    def __call__(
        self,
        x: Array,
        self_bias: Optional[Array],
        enc_out: Optional[Array] = None,
        cross_bias: Optional[Array] = None,
        pos_bias: Optional[Array] = None,  # pallas path (self_bias None)
        skey_mask: Optional[Array] = None,
        ckey_mask: Optional[Array] = None,
        cache: Optional[Dict[str, Array]] = None,
    ) -> Tuple[Array, Optional[Dict[str, Array]]]:
        cfg = self.cfg
        h = T5Norm(cfg, name="ln_1")(x)
        attn_out, new_kv = T5Attention(cfg, name="self_attn")(
            h, h, self_bias, cache, pos_bias=pos_bias, key_mask=skey_mask,
            causal=self.is_decoder,
        )
        x = x + attn_out
        if self.is_decoder and enc_out is not None:
            h = T5Norm(cfg, name="ln_cross")(x)
            cross_out, _ = T5Attention(cfg, name="cross_attn")(
                h, enc_out, cross_bias, key_mask=ckey_mask
            )
            x = x + cross_out
        x = x + T5MLP(cfg, name="mlp")(T5Norm(cfg, name="ln_2")(x))
        return x, new_kv


class T5LM:
    """Functional encoder-decoder LM with stacked-layer scan stacks.

    params:
      shared:  {wte [V, D]}
      encoder: {blocks (stacked), ln_f, rel_bias [n_buckets, H]}
      decoder: {blocks (stacked), ln_f, rel_bias [n_buckets, H]}
      [lm_head: {kernel [D, V]}]
    """

    def __init__(self, cfg: Seq2SeqConfig):
        self.cfg = cfg
        self.enc_block = T5Block(cfg, is_decoder=False)
        self.dec_block = T5Block(cfg, is_decoder=True)
        self.norm = T5Norm(cfg)
        # set by the trainer when the mesh has a pp axis > 1: encoder and
        # decoder stacks pipeline over it (parallel/pipeline.py); decode
        # steps (cache path) stay sequential
        self.mesh = None

    # -- init ------------------------------------------------------------

    def init(self, rng: jax.Array) -> Dict:
        cfg = self.cfg
        B, T = 1, 4
        x = jnp.zeros((B, T, cfg.d_model), cfg.dtype)
        bias = jnp.zeros((1, cfg.n_head, T, T), jnp.float32)
        keys = jax.random.split(rng, 6)

        enc_blocks = jax.vmap(lambda k: self.enc_block.init(k, x, bias)["params"])(
            jax.random.split(keys[0], cfg.n_layer)
        )
        dec_blocks = jax.vmap(
            lambda k: self.dec_block.init(k, x, bias, x, bias)["params"]
        )(jax.random.split(keys[1], cfg.n_decoder_layer))

        n_b = cfg.relative_attention_num_buckets
        params = {
            "shared": {
                "wte": jax.random.normal(keys[2], (cfg.vocab_size, cfg.d_model), cfg.param_dtype)
                * 1.0
            },
            "encoder": {
                "blocks": enc_blocks,
                "ln_f": self.norm.init(keys[3], x)["params"],
                "rel_bias": jax.random.normal(keys[4], (n_b, cfg.n_head), cfg.param_dtype) * 0.1,
            },
            "decoder": {
                "blocks": dec_blocks,
                "ln_f": self.norm.init(keys[3], x)["params"],
                "rel_bias": jax.random.normal(keys[5], (n_b, cfg.n_head), cfg.param_dtype) * 0.1,
            },
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = {
                "kernel": jax.random.normal(keys[4], (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
                * cfg.d_model**-0.5
            }
        return params

    # -- helpers ---------------------------------------------------------

    def _embed(self, params: Dict, ids: Array) -> Array:
        return jnp.take(params["shared"]["wte"], ids, axis=0).astype(self.cfg.dtype)

    def _scan(self, block: nn.Module, stacked: Dict, h: Array, *args, cache=None,
              remat=False):
        """Cache path mirrors TransformerLM._scan_blocks: the [L, ...]
        cache buffers are CARRIED and each layer's attention writes its
        new column in place then attends against a slice of the updated
        buffer (update-carry-first; design history in TransformerLM
        Attention)."""
        def body(carry, layer):
            if cache is not None:
                hidden, ck, cv = carry
                lp, ix = layer
                layer_cache = {
                    "ck": ck,
                    "cv": cv,
                    "ix": ix,
                    "index": cache["index"],
                }
            else:
                hidden, lp, layer_cache = carry, layer, None
            out, new_kv = block.apply({"params": lp}, hidden, *args, cache=layer_cache)
            if cache is not None:
                return (out, new_kv["ck"], new_kv["cv"]), None
            return out, None

        if cache is None:
            from trlx_tpu.ops.remat import wrap_remat

            body = wrap_remat(body, remat)
            h, _ = jax.lax.scan(body, h, stacked)
            return h, None
        n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
        (h, ck, cv), _ = jax.lax.scan(
            body, (h, cache["k"], cache["v"]), (stacked, jnp.arange(n))
        )
        return h, dict(k=ck, v=cv, index=cache["index"] + 1)

    def _pp_microbatches(self, n_layer: int, batch: int) -> int:
        """Microbatch count for a pipelined stack, or 0 for the
        sequential scan — same shared gate as TransformerLM
        (parallel.pipeline.pp_microbatch_count)."""
        from trlx_tpu.parallel.pipeline import pp_microbatch_count

        return pp_microbatch_count(
            self.mesh, n_layer, batch, self.cfg.pp_microbatches
        )

    def _pp_scan(
        self,
        block: nn.Module,
        stacked: Dict,
        h: Array,
        args: tuple,
        n_microbatch: int,
        capture_points: tuple = (),
        remat=False,
    ):
        """Pipelined counterpart of `_scan` for teacher-forced stacks:
        `args` (biases / encoder hidden) ride as per-microbatch ctx."""
        from trlx_tpu.parallel.pipeline import pipelined_layers

        def layer_apply(layer, h, ctx_mb):
            out, _ = block.apply({"params": layer["p"]}, h, *ctx_mb, cache=None)
            return out

        return pipelined_layers(
            self.mesh,
            layer_apply,
            {"p": stacked},
            h,
            tuple(args),
            n_microbatch=n_microbatch,
            capture_points=capture_points,
            remat=remat,
            schedule=self.cfg.pp_schedule,
        )

    def _logits(self, params: Dict, hidden: Array) -> Array:
        if "lm_head" in params:
            kernel = params["lm_head"]["kernel"]
        else:
            kernel = params["shared"]["wte"].T
            hidden = hidden * (self.cfg.d_model**-0.5)  # tied-embedding scale
        return jnp.einsum(
            "btd,dv->btv", hidden, kernel.astype(hidden.dtype),
            preferred_element_type=jnp.float32,
        )

    # -- forward ---------------------------------------------------------

    def _pallas_ok(self, *seq_dims) -> bool:
        """Static gate for the fused-attention path: teacher-forced
        shapes with 128-divisible sequence dims (Mosaic lane/DMA
        alignment); decode steps (cache) never come through here."""
        if self.cfg.attention_impl != "pallas":
            return False
        ok = all(d % 128 == 0 for d in seq_dims)
        if not ok:
            from trlx_tpu.ops.common import warn_pallas_fallback

            warn_pallas_fallback(
                "seq2seq teacher-forced forward",
                f"sequence dims {seq_dims} are not all multiples of 128",
            )
        return ok

    def _self_attn_args(self, params, stack: str, T: int, key_mask, causal,
                        use_pallas: bool):
        """(self_bias, pos_bias, skey_mask) for a self-attention stack:
        the combined additive [.., T, T] bias on the XLA path, or the
        structured (learned bias, padding mask) pieces on the pallas one
        — where the combined tensor is exactly what must NOT be built."""
        cfg = self.cfg
        pos = jnp.arange(T)
        pb = compute_position_bias_dense(
            params[stack]["rel_bias"], T, T, not causal,
            cfg.relative_attention_num_buckets,
            cfg.relative_attention_max_distance,
        )  # [1, H, T, T]
        if use_pallas:
            return None, pb, key_mask
        bias = pb
        if causal:
            causal_ok = pos[:, None] >= pos[None, :]
            bias = bias + jnp.where(causal_ok[None, None], 0.0, NEG_INF)
        if key_mask is not None:
            bias = bias + jnp.where(key_mask[:, None, None, :] > 0, 0.0, NEG_INF)
        return bias, None, None

    def _decoder_args(self, params, B, T, S_enc, decoder_attention_mask,
                      attention_mask, encoder_hidden):
        """The decoder stacks' shared 6-tuple of block args (combined
        biases on the XLA path; structured pos-bias/key-mask pieces on
        the pallas one) — one place, so the teacher-forced and
        hydra-capture paths cannot diverge."""
        use_pallas = self._pallas_ok(T, S_enc)
        self_bias, pos_bias, skey_mask = self._self_attn_args(
            params, "decoder", T, decoder_attention_mask, causal=True,
            use_pallas=use_pallas,
        )
        if use_pallas and skey_mask is None:
            skey_mask = jnp.ones((B, T), jnp.int32)
        if use_pallas:
            cross_bias, ckey_mask = None, attention_mask
        else:
            cross_bias = jnp.where(
                attention_mask[:, None, None, :] > 0, 0.0, NEG_INF
            )
            ckey_mask = None
        return (self_bias, encoder_hidden, cross_bias, pos_bias, skey_mask,
                ckey_mask)

    def encode(self, params: Dict, input_ids: Array, attention_mask: Array,
               remat=False) -> Array:
        cfg = self.cfg
        T = input_ids.shape[1]
        use_pallas = self._pallas_ok(T)
        self_bias, pos_bias, skey_mask = self._self_attn_args(
            params, "encoder", T, attention_mask, causal=False,
            use_pallas=use_pallas,
        )
        args = (self_bias, None, None, pos_bias, skey_mask, None)
        h = self._embed(params, input_ids)
        n_mb = self._pp_microbatches(cfg.n_layer, h.shape[0])
        if n_mb:
            h, _ = self._pp_scan(
                self.enc_block, params["encoder"]["blocks"], h, args, n_mb,
                remat=remat,
            )
        else:
            h, _ = self._scan(self.enc_block, params["encoder"]["blocks"], h,
                              *args, remat=remat)
        return self.norm.apply({"params": params["encoder"]["ln_f"]}, h)

    def __call__(
        self,
        params: Dict,
        input_ids: Array,  # [B, S_enc]
        attention_mask: Array,  # [B, S_enc]
        decoder_input_ids: Array,  # [B, T]
        decoder_attention_mask: Optional[Array] = None,
        encoder_hidden: Optional[Array] = None,
        remat: bool = False,
        compute_logits: bool = True,
    ) -> Dict[str, Array]:
        """Teacher-forced forward. `encoder_hidden` may be reused across
        calls (e.g. computed once during rollout generation)."""
        cfg = self.cfg
        if encoder_hidden is None:
            encoder_hidden = self.encode(params, input_ids, attention_mask,
                                         remat=remat)
        B, T = decoder_input_ids.shape
        args = self._decoder_args(
            params, B, T, encoder_hidden.shape[1], decoder_attention_mask,
            attention_mask, encoder_hidden,
        )

        h = self._embed(params, decoder_input_ids)
        n_mb = self._pp_microbatches(cfg.n_decoder_layer, B)
        if n_mb:
            h, _ = self._pp_scan(
                self.dec_block, params["decoder"]["blocks"], h,
                args, n_mb, remat=remat,
            )
        else:
            h, _ = self._scan(
                self.dec_block, params["decoder"]["blocks"], h, *args,
                remat=remat,
            )
        hidden = self.norm.apply({"params": params["decoder"]["ln_f"]}, h)
        return {
            "logits": self._logits(params, hidden) if compute_logits else None,
            "hidden_states": hidden,
            "encoder_hidden": encoder_hidden,
        }

    # -- hydra support ---------------------------------------------------

    def forward_with_branch_capture(
        self,
        params: Dict,
        input_ids: Array,
        attention_mask: Array,
        decoder_input_ids: Array,
        decoder_attention_mask: Optional[Array],
        branch_at: int,
        remat=False,
        compute_logits: bool = True,
        frozen_below: int = 0,
    ) -> Dict[str, Array]:
        """Teacher-forced forward that also returns the decoder hidden
        state entering layer `branch_at` plus the biases needed to re-run
        the top branch (parity: the reference's frozen `T5Branch`,
        modeling_ppo.py:1483-1592, which re-runs top decoder blocks).

        `frozen_below` (static) is the index of the first trainable
        decoder layer. With `branch_at <= frozen_below` everything
        `make_seq2seq_freeze_mask` freezes is a constant of a
        differentiated forward: the encoder, the shared embedding, the
        relative biases and the bottom decoder layers run on
        gradient-stopped params without remat, so the backward ends at
        `branch_hidden` (same contract as
        `TransformerLM.forward_with_multi_capture`). The
        pipeline-parallel path (`pp > 1`) keeps the full backward."""
        cfg = self.cfg
        B, T = decoder_input_ids.shape
        n_mb = self._pp_microbatches(cfg.n_decoder_layer, B)
        const = 0 < branch_at <= frozen_below and not n_mb
        frozen = jax.lax.stop_gradient(params) if const else params
        encoder_hidden = self.encode(frozen, input_ids, attention_mask,
                                     remat=False if const else remat)
        args = self._decoder_args(
            frozen, B, T, encoder_hidden.shape[1], decoder_attention_mask,
            attention_mask, encoder_hidden,
        )
        (self_bias, _, cross_bias, pos_bias, skey_mask, ckey_mask) = args

        h = self._embed(frozen, decoder_input_ids)
        if n_mb:
            h_top, (h_branch,) = self._pp_scan(
                self.dec_block, params["decoder"]["blocks"], h,
                args, n_mb, capture_points=(branch_at,), remat=remat,
            )
        else:
            bottom = jax.tree_util.tree_map(
                lambda x: x[:branch_at], frozen["decoder"]["blocks"]
            )
            top = jax.tree_util.tree_map(
                lambda x: x[branch_at:], params["decoder"]["blocks"]
            )
            h_branch, _ = self._scan(
                self.dec_block, bottom, h, *args, remat=False if const else remat,
            )
            if const:
                h_branch = jax.lax.stop_gradient(h_branch)
            h_top, _ = self._scan(
                self.dec_block, top, h_branch, *args, remat=remat,
            )
        hidden = self.norm.apply({"params": params["decoder"]["ln_f"]}, h_top)
        # a tied head reads the (frozen) shared embedding
        head = dict(params, shared=frozen["shared"])
        return {
            "logits": self._logits(head, hidden) if compute_logits else None,
            "hidden_states": hidden,
            "branch_hidden": h_branch,
            "self_bias": self_bias,
            "cross_bias": cross_bias,
            "pos_bias": pos_bias,
            "skey_mask": skey_mask,
            "ckey_mask": ckey_mask,
            "encoder_hidden": encoder_hidden,
        }

    def forward_from_layer(
        self,
        branch_params: Dict,
        branch_hidden: Array,
        self_bias: Optional[Array],
        encoder_hidden: Array,
        cross_bias: Optional[Array],
        remat=False,
        compute_logits: bool = True,
        pos_bias: Optional[Array] = None,
        skey_mask: Optional[Array] = None,
        ckey_mask: Optional[Array] = None,
    ) -> Dict[str, Array]:
        """Run a frozen top-k decoder branch from a captured hidden state.
        Under the pallas path the combined biases are None and the
        structured (pos_bias, key-mask) pieces ride instead."""
        h, _ = self._scan(
            self.dec_block, branch_params["blocks"], branch_hidden, self_bias,
            encoder_hidden, cross_bias, pos_bias, skey_mask, ckey_mask,
            remat=remat,
        )
        hidden = self.norm.apply({"params": branch_params["ln_f"]}, h)
        return {
            "logits": self._logits(branch_params, hidden) if compute_logits else None,
            "hidden_states": hidden,
        }


    # -- decoding --------------------------------------------------------

    def init_cache(self, batch: int, max_len: int) -> Dict:
        cfg = self.cfg
        shape = (cfg.n_decoder_layer, batch, max_len, cfg.n_head, cfg.d_kv)
        return {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "index": jnp.int32(0),
        }

    def decode_step(
        self,
        params: Dict,
        token: Array,  # [B, 1]
        encoder_hidden: Array,
        attention_mask: Array,  # [B, S_enc]
        cache: Dict,
    ) -> Tuple[Dict[str, Array], Dict]:
        """One decoder step at cache position `cache['index']`."""
        cfg = self.cfg
        S = cache["k"].shape[2]
        t = cache["index"]
        k_pos = jnp.arange(S)
        self_bias = compute_position_bias(
            params["decoder"]["rel_bias"], t[None], k_pos, False,
            cfg.relative_attention_num_buckets, cfg.relative_attention_max_distance,
        )
        visible = k_pos[None, None, None, :] <= t
        self_bias = jnp.where(visible, self_bias, NEG_INF)
        cross_bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, NEG_INF)

        h = self._embed(params, token)
        h, new_cache = self._scan(
            self.dec_block, params["decoder"]["blocks"], h, self_bias,
            encoder_hidden, cross_bias, cache=cache,
        )
        hidden = self.norm.apply({"params": params["decoder"]["ln_f"]}, h)
        return {"logits": self._logits(params, hidden), "hidden_states": hidden}, new_cache


def t5_logit_projection(params: Dict, cfg):
    """hidden -> fp32 logits closure over a T5LM param tree, matching
    `T5LM._logits` numerics exactly (tied-embedding d_model^-0.5 scale,
    compute-dtype matmul, fp32 accumulation). Feeds
    `ops.common.chunked_logprobs` so losses can avoid materializing
    full [B, T, V] logits."""
    if "lm_head" in params:
        kernel = params["lm_head"]["kernel"]

        def proj(h: Array) -> Array:
            return jnp.einsum(
                "...d,dv->...v", h, kernel.astype(h.dtype),
                preferred_element_type=jnp.float32,
            )

        return proj
    wte = params["shared"]["wte"]
    scale = cfg.d_model ** -0.5

    def proj(h: Array) -> Array:
        return jnp.einsum(
            "...d,vd->...v", h * scale, wte.astype(h.dtype),
            preferred_element_type=jnp.float32,
        )

    return proj


def extract_t5_branch_params(params: Dict, branch_at: int) -> Dict:
    """Frozen top decoder branch + final norm + logit head (deep-copied:
    trainers donate the policy buffers)."""
    branch = {
        "blocks": jax.tree_util.tree_map(
            lambda x: x[branch_at:], params["decoder"]["blocks"]
        ),
        "ln_f": params["decoder"]["ln_f"],
        "shared": params["shared"],
    }
    if "lm_head" in params:
        branch["lm_head"] = params["lm_head"]
    return jax.tree_util.tree_map(jnp.copy, jax.lax.stop_gradient(branch))


def generate_seq2seq(
    model: T5LM,
    params: Dict,
    input_ids: Array,
    attention_mask: Array,
    rng: jax.Array,
    settings,
    logits_processor=None,
) -> Dict[str, Array]:
    """Sample decoder continuations (analog of models.generation.generate
    for the encoder-decoder path). Output starts with
    `decoder_start_token_id` (the <pad> HF T5 convention)."""
    from trlx_tpu.models.generation import cast_params_for_decode, sample_token

    cfg = model.cfg
    B = input_ids.shape[0]
    N = settings.max_new_tokens
    params = cast_params_for_decode(params, cfg.dtype)
    # same pp-decode weight-gather hoist as models.generation.generate,
    # restricted to the decoder stack: the encoder runs ONCE (pipelined
    # when pp>1) and its pp-sharded blocks are never read by the decode
    # loop, so gathering them would spend cross-stage (possibly DCN)
    # bandwidth and pp× encoder-param memory for nothing
    from trlx_tpu.parallel.sharding import unshard_for_decode

    mesh = getattr(model, "mesh", None)
    params = dict(params, decoder=unshard_for_decode(params["decoder"], mesh))
    if cfg.decode_weights_quant == "int8":
        # decoder-only weight quantization: the decode loop re-reads the
        # decoder stack every step (the encoder ran once, full precision)
        from trlx_tpu.models.transformer import quantize_decode_weights

        params = dict(params, decoder=quantize_decode_weights(params["decoder"]))
    enc = model.encode(params, input_ids, attention_mask)
    cache = model.init_cache(B, N + 1)
    start = jnp.full((B, 1), cfg.decoder_start_token_id, jnp.int32)

    def pick(rng_t, hidden_last, logits_last, finished):
        if logits_processor is not None:
            logits_last = logits_processor(hidden_last, logits_last)
        tok = sample_token(rng_t, logits_last, settings)
        tok = jnp.where(finished, jnp.int32(settings.pad_token_id), tok)
        return tok, finished | (tok == settings.eos_token_id)

    out, cache = model.decode_step(params, start, enc, attention_mask, cache)
    rng, sub = jax.random.split(rng)
    tok0, fin0 = pick(sub, out["hidden_states"][:, -1], out["logits"][:, -1],
                      jnp.zeros((B,), bool))

    def step(carry, rng_t):
        cache, tok, finished, was_real = carry
        step_out, cache = model.decode_step(
            params, tok[:, None], enc, attention_mask, cache
        )
        nxt, now_fin = pick(
            rng_t, step_out["hidden_states"][:, -1], step_out["logits"][:, -1], finished
        )
        return (cache, nxt, now_fin, ~finished), (tok, was_real)

    if N > 1:
        carry0 = (cache, tok0, fin0, jnp.ones((B,), bool))
        (cache, tok_last, fin, last_real), (toks, reals) = jax.lax.scan(
            step, carry0, jax.random.split(rng, N - 1)
        )
        response_ids = jnp.concatenate([toks.T, tok_last[:, None]], axis=1)
        response_mask = jnp.concatenate([reals.T, last_real[:, None]], axis=1)
    else:
        response_ids = tok0[:, None]
        response_mask = jnp.ones((B, 1), bool)

    decoder_ids = jnp.concatenate([start, response_ids], axis=1)  # with start token
    return {
        "sequences": decoder_ids,
        "response_ids": response_ids,
        "response_mask": response_mask.astype(jnp.int32),
        "encoder_hidden": enc,
    }
