"""Jitted autoregressive generation: static-shape prefill + decode scan.

Parity: the reference delegates sampling to HF `model.generate`
(/root/reference/trlx/trainer/accelerate_base_trainer.py:256-288) and to a
custom token-by-token loop for ILQL
(/root/reference/trlx/models/modeling_ilql.py:325-412). Here generation is
one jitted function: a KV-cache prefill over the (left-padded) prompt and
a `lax.scan` over `max_new_tokens` single-token steps.

TPU design notes:
- Static shapes everywhere: the cache is preallocated to
  prompt_len + max_new_tokens; finished sequences keep stepping but emit
  `pad_token_id` (the reference needed `synced_gpus` / no-early-break
  hacks for ZeRO-3 — SPMD makes "all devices run the full loop" the
  default, and the mask bookkeeping makes it correct).
- Sampling is `jax.random.categorical` over processed logits
  (temperature / top-k / top-p) — fp32 on the VPU, fused by XLA.
- An optional `logits_processor(hidden, logits) -> logits` hook runs
  inside the loop; ILQL's `pi_beta + beta*(minQ - V)` shaping plugs in
  here without a separate decode implementation.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from trlx_tpu.models.transformer import (
    TransformerLM,
    _add_stats,
    logit_projection,
    moe_counters,
)
from trlx_tpu.ops.common import topk_mask

Array = jnp.ndarray

# HF `generate` kwargs this sampler deliberately does not implement.
# Reference configs pass HF gen_kwargs verbatim (ref
# trlx/data/default_configs.py gen_kwargs), so these degrade with a
# warning — at config load (from_gen_kwargs) and per-call
# (BaseTrainer.generate consults the same set) — instead of loading
# fine then crashing evaluate() mid-sweep. Names outside this set are
# either sampler/processor-owned (validated by the trainer, which knows
# the processor's signature) or unknown.
HF_GEN_KWARGS_UNIMPLEMENTED = frozenset({
    "num_beams", "num_beam_groups", "penalty_alpha", "use_cache",
    "typical_p", "epsilon_cutoff", "eta_cutoff", "diversity_penalty",
    "repetition_penalty", "encoder_repetition_penalty", "length_penalty",
    "no_repeat_ngram_size", "bad_words_ids", "force_words_ids",
    "renormalize_logits", "constraints", "forced_bos_token_id",
    "forced_eos_token_id", "remove_invalid_values", "early_stopping",
    "exponential_decay_length_penalty", "suppress_tokens",
    "begin_suppress_tokens", "forced_decoder_ids", "num_return_sequences",
    "output_attentions", "output_hidden_states", "output_scores",
    "return_dict_in_generate", "min_length", "min_new_tokens",
    "max_length", "max_time",
})


@dataclass(frozen=True)
class SamplerSettings:
    """Static sampling hyperparameters (hashable: usable as jit statics).

    Mirrors the reference's HF `gen_kwargs` surface
    (default_configs.py:36: max_new_tokens / top_k / top_p / do_sample /
    temperature, plus eos/pad ids resolved by the trainer).
    """

    max_new_tokens: int
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    do_sample: bool = True
    eos_token_id: int = -1  # -1: never stops early
    pad_token_id: int = 0

    @classmethod
    def from_gen_kwargs(cls, gen_kwargs: Dict, eos_token_id=None, pad_token_id=None):
        kw = dict(gen_kwargs)
        eos = kw.pop("eos_token_id", eos_token_id)
        pad = kw.pop("pad_token_id", pad_token_id)
        known = {f.name for f in dataclasses.fields(cls)}
        # HF gen_kwargs this sampler doesn't implement are ignored
        # rather than fatal, so reference configs run unmodified — with
        # a warning for recognized-HF names (the same set the trainer's
        # generate() warns on per-call). Other unknown names (e.g. beta,
        # ILQL's shaping strength consumed by the logits processor) pass
        # silently here: only the trainer knows its processor signature.
        dropped_hf = set(kw) & HF_GEN_KWARGS_UNIMPLEMENTED
        if dropped_hf:
            from trlx_tpu.utils import logging

            logging.get_logger(__name__).warning(
                "SamplerSettings: ignoring HF gen_kwargs this sampler "
                f"does not implement: {sorted(dropped_hf)}"
            )
        kw = {k: v for k, v in kw.items() if k in known}
        return cls(
            **kw,
            eos_token_id=-1 if eos is None else int(eos),
            pad_token_id=0 if pad is None else int(pad),
        )


def top_p_mask(logits: Array, p: float) -> Array:
    """Nucleus filtering: mask logits outside the smallest set with
    cumulative probability >= p (always keeps the argmax)."""
    sorted_desc = -jnp.sort(-logits, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # a sorted position is kept while the mass *before* it is < p
    keep = cum - probs < p
    cutoff = jnp.where(keep, sorted_desc, jnp.inf).min(axis=-1, keepdims=True)
    return jnp.where(logits < cutoff, -jnp.inf, logits)


def process_logits(logits: Array, settings: SamplerSettings) -> Array:
    """Temperature / top-k / top-p pipeline in fp32."""
    logits = logits.astype(jnp.float32)
    if settings.temperature != 1.0:
        logits = logits / max(settings.temperature, 1e-6)
    if settings.top_k:
        logits = topk_mask(logits, settings.top_k)
    if settings.top_p < 1.0:
        logits = top_p_mask(logits, settings.top_p)
    return logits


def sample_token(rng: jax.Array, logits: Array, settings: SamplerSettings) -> Array:
    """Draw next tokens [B] from last-position logits [B, V]."""
    logits = process_logits(logits, settings)
    if not settings.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def lane_keys(base: jax.Array, lane_ids: Array) -> jax.Array:
    """Per-lane PRNG keys: fold a vector of ids into one base key.

    The decode engine (models/gen_engine.py) keys every sampling event
    on (prompt index, token position, event kind) folded into the call's
    base key, so a prompt's sampled continuation is INDEPENDENT of which
    slot served it, how the batch was composed, and whether speculative
    decoding was on — the property the golden-equivalence tests pin."""
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(
        lane_ids.astype(jnp.uint32)
    )


def sample_token_lanes(
    keys: jax.Array,  # [B] per-lane keys (lane_keys)
    logits: Array,  # [B, V]
    settings: SamplerSettings,
) -> Array:
    """Per-lane sampling: like `sample_token` but each row draws from
    its own key (gumbel-max == categorical, one lane at a time)."""
    logits = process_logits(logits, settings)
    if not settings.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    g = jax.vmap(lambda k: jax.random.gumbel(k, (logits.shape[-1],)))(keys)
    return jnp.argmax(logits + g, axis=-1).astype(jnp.int32)


def categorical_lanes(keys: jax.Array, probs: Array) -> Array:
    """Per-lane categorical draw from probability rows [B, V] (used by
    the speculative residual re-draw; probs need not be normalized)."""
    logp = jnp.log(jnp.maximum(probs, 1e-30))
    g = jax.vmap(lambda k: jax.random.gumbel(k, (probs.shape[-1],)))(keys)
    return jnp.argmax(logp + g, axis=-1).astype(jnp.int32)



def cast_params_for_decode(params: Dict, compute_dtype) -> Dict:
    """Hoist the per-matmul param casts out of a decode loop: every step
    re-reads every weight, so pre-casting MATMUL leaves to the compute
    dtype halves decode weight traffic when params are stored fp32
    (training precision). Only rank>=2 kernels/embeddings are cast — the
    model casts exactly those at each use (flax dtype=cfg.dtype) — and
    1-D norm scales/biases and the T5 rel_bias table stay fp32 BY DESIGN
    (their math runs in fp32).

    Numerics: bit-identical to the uncast forward for rotary/alibi/none
    position embeddings. For `pos_embed="learned"` the uncast forward
    adds take(wte)+take(wpe) in fp32 *before* rounding to the compute
    dtype, while the pre-cast version adds two pre-rounded operands — an
    ulp-level divergence in the sampled policy only. PPO correctness is
    unaffected: old/new logprob ratios both come from the teacher-forced
    scorer (which never sees pre-cast params), so the ratio is computed
    consistently either way; we keep the cast because the tied wte is
    the largest single matrix read per decode step (e.g. 39% of GPT-2's
    weights). Shared by the causal and seq2seq samplers."""

    # whitelist exactly the weights the forward casts per use (flax
    # DenseGeneral kernels + embedding tables); norm scales (stacked
    # [L, E] under blocks), biases and rel_bias tables keep fp32
    matmul_keys = ("kernel", "wte", "wpe")

    def needs_cast(path, x):
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.dtype == compute_dtype:
            return False
        last = getattr(path[-1], "key", None) if path else None
        return last in matmul_keys

    # already-compute-dtype params (bf16 deployment checkpoints, or a
    # caller that pre-cast): return the SAME tree — at 1.3B the cast
    # copy is +2.6 GB of HBM that would sit next to the KV cache for
    # the whole rollout, for zero bandwidth benefit
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    if not any(needs_cast(path, x) for path, x in flat):
        return params

    return jax.tree_util.tree_map_with_path(
        lambda path, x: x.astype(compute_dtype) if needs_cast(path, x) else x,
        params,
    )


def cache_slots(cfg, n_virt: int, P: int, N: int) -> int:
    """Slots generate() allocates a row: virtual prefix, prompt and new
    tokens, and under `attention_impl="pallas"` rounded up to 128 —
    Mosaic needs a 128-aligned cache length to lower the prefill's
    chunked loads (the pad slots stay masked and decode never reaches
    them). Gated on the prefill actually qualifying for the kernel
    (Attention also needs 8-row-aligned queries, P % 8 == 0): when the
    prefill will fall back to XLA anyway, the pad would just inflate
    cache memory and every decode step's masked score width for
    nothing — same reason the plain XLA path skips it."""
    total = n_virt + P + N
    if cfg.attention_impl == "pallas" and P % 8 == 0:
        total += (-total) % 128
    return total


def fused_decode_cells(
    model: TransformerLM, rows: int, n_virt: int, P: int, N: int
) -> Optional[Dict[str, int]]:
    """The grid the fused decode kernel walks for a sampler of this
    shape, or None where its decode steps run something else (no int8
    cache, a single new token, or `decode_attn_unfused`). Host
    arithmetic: what Attention will see, without tracing it."""
    from trlx_tpu.models.transformer import decode_attn_unfused
    from trlx_tpu.ops.decode_attention import decode_chunk

    cfg = model.cfg
    slots = cache_slots(cfg, n_virt, P, N)
    if cfg.kv_cache_quant != "int8" or N < 2 or decode_attn_unfused(cfg, model.mesh, rows, slots):
        return None
    return {
        "cells": cfg.n_layer * rows,  # (layer, row) pairs a decode step
        "chunk": decode_chunk(slots, cfg.n_kv_head, cfg.head_dim),
        "slots": slots,
        "first_write": n_virt + P,  # the slot the first decode step writes
    }


def chunks_streamed(steps: int, cells: int, chunk: int, slots: int, first_write: int) -> Dict[str, int]:
    """Chunks of the int8 cache that `steps` decode steps streamed
    (`cache_chunks_read`: step i writes slot first_write + i and reads
    the chunks up to it) and the chunks allocated to them
    (`cache_chunks_held`): their ratio is the share of the cache that
    the bounded pass read."""
    steps = max(steps, 0)
    read = sum((first_write + i) // chunk + 1 for i in range(steps))
    return {
        "cache_chunks_read": cells * read,
        "cache_chunks_held": cells * steps * (slots // chunk),
    }


def state_bytes_per_step(cfg, rows: int) -> int:
    """Bytes of recurrent state one decode step carries for `rows` rows:
    what the delta-rule and state-space layers keep
    (`memdoctor.recurrent_state_bytes`: a float32 state and the inputs their
    convolutions hold), read once and written once, whatever the length of
    the rows. 0 for a model without such layers. Host arithmetic, as
    `chunks_streamed` is: times the steps the loop ran it is the
    `tokens_wait` span's `state_bytes_carried`."""
    from trlx_tpu.utils.memdoctor import recurrent_state_bytes

    return 2 * recurrent_state_bytes(cfg, rows, jnp.dtype(getattr(cfg, "dtype", jnp.bfloat16)).itemsize)


def generate(
    model: TransformerLM,
    params: Dict,
    input_ids: Array,  # [B, P] int32, LEFT-padded
    attention_mask: Array,  # [B, P] int32
    rng: jax.Array,
    settings: SamplerSettings,
    logits_processor: Optional[Callable[[Array, Array], Array]] = None,
    soft_prompt: Optional[Array] = None,  # [n, E] prompt-tuning tokens
    kv_prefix: Optional[Dict[str, Array]] = None,  # prefix-tuning k/v
    row_budget: Optional[Array] = None,  # [B] per-row max_new cap (<= N)
) -> Dict[str, Array]:
    """Sample up to `settings.max_new_tokens` continuations.

    Returns:
      sequences:      [B, P+N] prompt ++ response (response right-padded)
      response_ids:   [B, N]
      response_mask:  [B, N] 1 for real response tokens (incl. the EOS)

    `logits_processor(hidden_last, logits) -> logits` (both [B, ...]) runs
    before temperature/top-k/top-p — the ILQL advantage-shaping hook.

    Adapters warm the KV cache: soft-prompt tokens run one extra prefill
    segment over slots [0, n); kv prefixes are written into the cache
    directly. Either way the prompt then occupies slots [n, n+P) and
    sampled tokens follow — the decode loop is adapter-oblivious.
    """
    B, P = input_ids.shape
    N = settings.max_new_tokens
    if N < 1:
        raise ValueError("max_new_tokens must be >= 1")
    params = cast_params_for_decode(params, model.cfg.dtype)
    # decode runs the sequential layer scan even when training is
    # pipelined; gather each stage's layer slice ONCE here instead of
    # on every decode step (parallel/sharding.py:unshard_axis). What
    # `fsdp` shards is not gathered at all: a decode step multiplies
    # with each chip's kernel shards in place and moves its activations
    # (transformer.decode_weights_stationary); prefill keeps the
    # per-layer gathers, paid once for the whole prompt
    from trlx_tpu.parallel.sharding import unshard_for_decode

    params = unshard_for_decode(params, getattr(model, "mesh", None))
    if getattr(model.cfg, "decode_weights_quant", None) == "int8":
        # rollout-policy weight quantization: block kernels go int8 +
        # per-channel scale (QDense picks the scale up via
        # has_variable). One-time cost per generate call (a read+write
        # of the block weights), amortized over prefill + every decode
        # step; see transformer.quantize_decode_weights for numerics.
        from trlx_tpu.models.transformer import quantize_decode_weights

        params = quantize_decode_weights(params)
    n_virt = 0
    if soft_prompt is not None:
        n_virt = soft_prompt.shape[0]
    elif kv_prefix is not None:
        n_virt = kv_prefix["k"].shape[1]
    total = cache_slots(model.cfg, n_virt, P, N)
    pad_slots = total - (n_virt + P + N)

    # response slots count as attendable keys once written
    key_mask = jnp.concatenate(
        [
            jnp.ones((B, n_virt), jnp.int32),
            attention_mask.astype(jnp.int32),
            jnp.ones((B, N), jnp.int32),
            jnp.zeros((B, pad_slots), jnp.int32),
        ],
        axis=1,
    )
    cache = model.init_cache(B, total, key_mask)
    if kv_prefix is not None:
        L = cache["k"].shape[0]

        def tiled(x):
            return jnp.broadcast_to(
                x[:, None], (L, B) + x.shape[1:]
            ).astype(cache["k"].dtype)

        cache = dict(
            cache,
            k=jax.lax.dynamic_update_slice_in_dim(
                cache["k"], tiled(kv_prefix["k"]), 0, axis=2
            ),
            v=jax.lax.dynamic_update_slice_in_dim(
                cache["v"], tiled(kv_prefix["v"]), 0, axis=2
            ),
            index=jnp.int32(n_virt),
            static_index=n_virt,
        )
    elif soft_prompt is not None:
        warm = model(
            params,
            jnp.zeros((B, n_virt), input_ids.dtype),
            cache=cache,
            prefix_embeds=soft_prompt,
            compute_logits=False,  # cache warm only; nothing samples here
        )
        # forwards drop the static index from the cache they return;
        # re-attach it so the main prefill keeps the pallas path
        cache = dict(warm["cache"], static_index=n_virt)

    # real positions (rope/wpe) run over non-pad tokens only, offset past
    # any virtual prefix (HF past-length semantics)
    positions = n_virt + jnp.maximum(jnp.cumsum(attention_mask, axis=1) - 1, 0)
    # compute_logits=False: only the LAST position samples, so the full
    # [B, P, V] prefill logits (3.3 GB fp32 at b8/seq2048/vocab50257 —
    # and ~7% of prefill FLOPs) are never materialized; the one needed
    # row is projected from the final hidden below
    with jax.named_scope("prefill"):
        out = model(
            params, input_ids, attention_mask, positions=positions,
            cache=cache, compute_logits=False,
        )
    prompt_len = n_virt + attention_mask.sum(axis=1)  # [B] next real position

    def pick_next(rng, hidden_last, logits_last, finished):
        with jax.named_scope("sample"):
            if logits_processor is not None:
                logits_last = logits_processor(hidden_last, logits_last)
            tok = sample_token(rng, logits_last, settings)
            tok = jnp.where(finished, jnp.int32(settings.pad_token_id), tok)
            now_finished = finished | (tok == settings.eos_token_id)
        return tok, now_finished

    rng, sub = jax.random.split(rng)
    finished0 = jnp.zeros((B,), bool)
    h_last = out["hidden_states"][:, -1]
    logits_last = logit_projection(params)(h_last)
    tok0, finished0 = pick_next(sub, h_last, logits_last, finished0)
    if row_budget is not None:
        # per-row response budgets (serving-style per-request
        # max_tokens; also how the bench builds honestly-ragged decode
        # workloads): a row that hits its budget finishes like an EOS
        budget = jnp.asarray(row_budget, jnp.int32)
        finished0 = finished0 | (budget <= 1)

    # a routed model's counters (each held expert's rows by layer, the
    # assignments made): the prefill's, then every decode step's, carried
    # out of the loop with the tokens (transformer.moe_counters)
    moe_stats = out.get("moe_stats") or {}
    decode_cache = out["cache"]
    if model.cfg.kv_cache_quant == "int8":
        # quantize ONCE after prefill (prefill numerics/pallas path stay
        # untouched); every decode step then reads an int8 cache stream
        # — half the HBM traffic of bf16, which is what bounds decode at
        # large batch×seq (models/transformer.py:quantize_kv_cache)
        from trlx_tpu.models.transformer import quantize_kv_cache

        decode_cache = quantize_kv_cache(decode_cache)

    if N > 1:
        pos0 = prompt_len  # next token's real position
        ids_buf = jnp.full((B, N), jnp.int32(settings.pad_token_id))
        mask_buf = jnp.zeros((B, N), bool)
        ids_buf = ids_buf.at[:, 0].set(tok0)
        mask_buf = mask_buf.at[:, 0].set(True)

        # lax.while_loop instead of a fixed-trip scan: once every row has
        # emitted its EOS the loop exits early — real tasks' responses
        # average well under max_new_tokens, and SPMD makes the early
        # exit safe (every host runs the same global condition; the
        # reference needed synced_gpus/no-early-break workarounds —
        # SURVEY §7 hard parts)
        def cond(state):
            _, _, _, finished, t, _, _, _, _ = state
            return (t < N) & ~jnp.all(finished)

        def body(state):
            cache, tok, pos, finished, t, rng, ids_buf, mask_buf, moe_stats = state
            with jax.named_scope("decode_step"):
                step_out = model(
                    params, tok[:, None], positions=pos[:, None], cache=cache
                )
            rng, sub = jax.random.split(rng)
            next_tok, now_finished = pick_next(
                sub, step_out["hidden_states"][:, -1], step_out["logits"][:, -1],
                finished,
            )
            if row_budget is not None:
                now_finished = now_finished | (budget <= t + 1)
            real = ~finished  # next_tok is real iff not finished before it
            ids_buf = jax.lax.dynamic_update_slice_in_dim(
                ids_buf, next_tok[:, None], t, axis=1
            )
            mask_buf = jax.lax.dynamic_update_slice_in_dim(
                mask_buf, real[:, None], t, axis=1
            )
            return (
                step_out["cache"], next_tok, pos + 1, now_finished, t + 1,
                rng, ids_buf, mask_buf,
                _add_stats(moe_stats, step_out.get("moe_stats")) or {},
            )

        state = (decode_cache, tok0, pos0, finished0, jnp.int32(1), rng,
                 ids_buf, mask_buf, moe_stats)
        (_, _, _, _, _, _, response_ids, response_mask, moe_stats) = jax.lax.while_loop(
            cond, body, state
        )
    else:
        response_ids = tok0[:, None]
        response_mask = jnp.ones((B, 1), bool)

    sequences = jnp.concatenate([input_ids, response_ids], axis=1)
    result = {
        "sequences": sequences,
        "response_ids": response_ids,
        "response_mask": response_mask.astype(jnp.int32),
    }
    if moe_stats:
        result["moe_stats"] = moe_counters(moe_stats, "sampler")
    return result


def make_generate_fn(
    model: TransformerLM,
    settings: SamplerSettings,
    logits_processor: Optional[Callable] = None,
):
    """Build a jitted `(params, input_ids, attention_mask, rng) -> dict`
    sampler. Shapes are static per (B, P); XLA caches one executable per
    distinct prompt padding length (trainers pad prompts to a fixed
    max_prompt_length so there is exactly one)."""

    def fn(params, input_ids, attention_mask, rng):
        return generate(
            model, params, input_ids, attention_mask, rng, settings,
            logits_processor=logits_processor,
        )

    fn.__name__ = "generate"  # the XLA module is jit_generate
    return jax.jit(fn)
