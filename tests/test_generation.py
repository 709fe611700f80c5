"""Generation tests: greedy/teacher-forced consistency, EOS masking,
sampling processors (reference analog: HF generate is assumed correct;
here the decode loop is first-party so it gets direct coverage)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models.generation import (
    SamplerSettings,
    generate,
    process_logits,
    top_p_mask,
)
import trlx_tpu.models.transformer as tr
from trlx_tpu.models.transformer import TransformerConfig, TransformerLM


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = TransformerConfig(
        vocab_size=64, hidden_size=16, n_layer=2, n_head=2, n_positions=64,
        dtype=jnp.float32,
    )
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    return lm, params


@pytest.mark.slow
def test_greedy_matches_teacher_forced(tiny_lm):
    lm, params = tiny_lm
    B, P, N = 2, 6, 5
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, 64)
    mask = jnp.ones((B, P), jnp.int32).at[0, :2].set(0)  # left-pad row 0
    settings = SamplerSettings(max_new_tokens=N, do_sample=False)
    out = generate(lm, params, ids, mask, jax.random.PRNGKey(2), settings)

    full_mask = jnp.concatenate([mask, jnp.ones((B, N), jnp.int32)], 1)
    logits = lm(params, out["sequences"], full_mask)["logits"]
    for b in range(B):
        for t in range(N):
            pred = int(jnp.argmax(logits[b, P + t - 1]))
            assert pred == int(out["sequences"][b, P + t])


def test_eos_stops_and_pads(tiny_lm):
    lm, params = tiny_lm
    B, P, N = 2, 4, 6
    EOS, PAD = 7, 9
    ids = jnp.ones((B, P), jnp.int32)
    mask = jnp.ones((B, P), jnp.int32)

    calls = {"n": 0}

    def force_eos_at_2(hidden, logits):
        # step counter trick won't trace; instead force EOS always for
        # row 0 and never for row 1 via a huge logit bump
        bump = jnp.zeros_like(logits).at[0, EOS].set(1e9)
        anti = jnp.zeros_like(logits).at[1, EOS].set(-1e9)
        return logits + bump + anti

    settings = SamplerSettings(
        max_new_tokens=N, do_sample=False, eos_token_id=EOS, pad_token_id=PAD
    )
    out = generate(
        lm, params, ids, mask, jax.random.PRNGKey(0), settings,
        logits_processor=force_eos_at_2,
    )
    resp = np.asarray(out["response_ids"])
    rmask = np.asarray(out["response_mask"])
    # row 0 emits EOS immediately; EOS itself is real, everything after pad
    assert resp[0, 0] == EOS
    assert rmask[0].tolist() == [1, 0, 0, 0, 0, 0]
    assert (resp[0, 1:] == PAD).all()
    # row 1 never finishes
    assert rmask[1].tolist() == [1] * N
    assert not (resp[1] == EOS).any()


def test_top_p_mask_keeps_nucleus():
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    masked = top_p_mask(logits, 0.7)
    finite = np.isfinite(np.asarray(masked))[0]
    assert finite.tolist() == [True, True, False, False]
    # always keeps argmax even for tiny p
    masked = top_p_mask(logits, 1e-9)
    assert np.isfinite(np.asarray(masked))[0].tolist() == [True, False, False, False]


def test_process_logits_temperature_topk():
    logits = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    s = SamplerSettings(max_new_tokens=1, temperature=0.5, top_k=2)
    out = np.asarray(process_logits(logits, s))[0]
    assert np.isinf(out[0]) and np.isinf(out[1]) and out[0] < 0
    np.testing.assert_allclose(out[2:], [6.0, 8.0])


def test_from_gen_kwargs_ignores_foreign_keys():
    s = SamplerSettings.from_gen_kwargs(
        dict(max_new_tokens=4, top_k=5, max_length=99, num_beams=2, beta=1.0),
        eos_token_id=3, pad_token_id=0,
    )
    assert s.max_new_tokens == 4 and s.top_k == 5 and s.eos_token_id == 3


def test_early_exit_pads_after_all_eos(tiny_lm):
    # once every row emits EOS the while_loop exits; remaining columns
    # must be pad with mask 0, identical to running the full trip count
    lm, params = tiny_lm
    EOS, PAD, N = 7, 0, 10

    def force_eos_at_1(hidden, logits):
        # first sampled token free, everything after forced to EOS
        return jnp.full_like(logits, -1e9).at[:, EOS].set(0.0)

    settings = SamplerSettings(
        max_new_tokens=N, do_sample=False, eos_token_id=EOS, pad_token_id=PAD
    )
    B, P = 2, 4
    ids = jnp.ones((B, P), jnp.int32)
    mask = jnp.ones((B, P), jnp.int32)
    out = generate(
        lm, params, ids, mask, jax.random.PRNGKey(0), settings,
        logits_processor=force_eos_at_1,
    )
    resp = np.asarray(out["response_ids"])
    rmask = np.asarray(out["response_mask"])
    # col 0: EOS (real), cols 1..: pad, not real
    assert (resp[:, 0] == EOS).all()
    assert (resp[:, 1:] == PAD).all()
    assert rmask[:, 0].all() and not rmask[:, 1:].any()


def test_int8_kv_cache_decode_matches_bf16(tiny_lm):
    """kv_cache_quant="int8": greedy decode through the quantized cache
    must track the full-precision decode closely — same tokens on a
    tiny model (logit gaps are wide), and small relative logit error.
    Also: the int8 cache buffers really are int8 (the HBM win is the
    point), and the quantized prefix dequantizes to ~the bf16 prefix."""
    import dataclasses

    from trlx_tpu.models.transformer import quantize_kv_cache

    lm, params = tiny_lm
    qlm = TransformerLM(dataclasses.replace(lm.cfg, kv_cache_quant="int8"))
    B, P, N = 2, 6, 8
    ids = jnp.ones((B, P), jnp.int32) * 3
    mask = jnp.ones((B, P), jnp.int32)
    settings = SamplerSettings(max_new_tokens=N, do_sample=False)

    out_fp = generate(lm, params, ids, mask, jax.random.PRNGKey(0), settings)
    out_q = generate(qlm, params, ids, mask, jax.random.PRNGKey(0), settings)
    assert (np.asarray(out_fp["response_ids"]) == np.asarray(out_q["response_ids"])).all()
    assert (np.asarray(out_fp["response_mask"]) == np.asarray(out_q["response_mask"])).all()

    # quantize_kv_cache round-trip on a prefilled cache
    key_mask = jnp.ones((B, P + N), jnp.int32)
    cache = lm.init_cache(B, P + N, key_mask)
    warm = lm(params, ids, mask, cache=cache, compute_logits=False)
    qcache = quantize_kv_cache(warm["cache"])
    assert qcache["k"].dtype == jnp.int8 and qcache["v"].dtype == jnp.int8
    # int8 layout is [L, B, Hkv, S, D] with k_scale [L, B, Hkv, S]
    deq = np.asarray(qcache["k"], np.float32) * np.asarray(
        qcache["k_scale"], np.float32
    )[..., None]
    ref = np.asarray(warm["cache"]["k"], np.float32).transpose(0, 1, 3, 2, 4)
    # written slots within 1% of full precision; unwritten slots exact 0
    assert np.abs(deq[:, :, :, :P] - ref[:, :, :, :P]).max() <= 0.01 * (
        np.abs(ref[:, :, :, :P]).max() + 1e-6
    )
    assert (deq[:, :, :, P:] == 0).all()


def test_int8_decode_kernel_matches_fallback():
    """The fused pallas decode kernel (cache length % 128 == 0 engages
    it; interpret mode on CPU) must match the XLA full-dequant fallback
    and the bf16 decode: same greedy tokens, left-padded prompts
    included (padding slots masked inside the kernel)."""
    import dataclasses

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=16, n_layer=2, n_head=2, n_positions=128,
        dtype=jnp.float32,
    )
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(1))
    qlm = TransformerLM(dataclasses.replace(cfg, kv_cache_quant="int8"))
    B, P, N = 2, 64, 64  # P + N = 128: kernel path engages
    ids = jnp.asarray(np.tile(np.arange(3, 3 + P), (B, 1)), jnp.int32)
    mask = np.ones((B, P), np.int32)
    mask[0, :5] = 0  # left padding on row 0
    mask = jnp.asarray(mask)
    settings = SamplerSettings(max_new_tokens=N, do_sample=False)

    out_fp = generate(lm, params, ids, mask, jax.random.PRNGKey(0), settings)
    out_q = generate(qlm, params, ids, mask, jax.random.PRNGKey(0), settings)
    agree = (
        np.asarray(out_fp["response_ids"]) == np.asarray(out_q["response_ids"])
    ).mean()
    # int8 noise may flip a near-tie on a long greedy rollout; demand
    # near-total agreement rather than bitwise equality
    assert agree >= 0.95, f"only {agree:.2%} of greedy tokens agree"


def _int8_decode_step(rep, S, write_ix, fused, mesh=None, garbage=False, dtype=jnp.float32):
    """One decode step of `Attention` over a seeded int8 cache written
    up to `write_ix - 1`, rows 0 and 1 left-padded by 0 and 3 slots:
    the fused kernel where `fused`, else the folded-scale XLA branch
    (the predicate patched to refuse). `garbage` fills every slot past
    the write index with 127s and NaN scales first."""
    from unittest import mock

    from trlx_tpu.models import transformer as tr

    B, Hkv, D, L = 4, 2, 16, 2
    cfg = TransformerConfig(
        vocab_size=32, hidden_size=Hkv * rep * D, n_layer=L, n_head=Hkv * rep,
        n_kv_head=Hkv, head_dim=D, n_positions=S, pos_embed="rotary",
        dtype=dtype, kv_cache_quant="int8",
    )
    rng = np.random.default_rng(S + rep)
    kf = rng.standard_normal((L, B, Hkv, S, D)).astype(np.float32)
    vf = rng.standard_normal((L, B, Hkv, S, D)).astype(np.float32)
    ks = np.abs(kf).max(-1) / 127.0  # [L, B, Hkv, S]
    vs = np.abs(vf).max(3) * 1.25 / 127.0  # [L, B, Hkv, D]
    ck = np.round(kf / ks[..., None]).astype(np.int8)
    cv = np.round(vf / vs[:, :, :, None]).astype(np.int8)
    if garbage:
        ck[:, :, :, write_ix + 1:] = 127
        cv[:, :, :, write_ix + 1:] = -127
        ks[:, :, :, write_ix + 1:] = np.nan
    slots = np.arange(S)
    key_mask = ((slots[None] <= write_ix) & (slots[None] >= 3 * (np.arange(B) == 1)[:, None])).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((B, 1, cfg.hidden_size)), dtype)
    attn = tr.Attention(cfg, mesh)
    positions = jnp.full((B, 1), write_ix, jnp.int32)
    bias = tr.make_attention_bias(jnp.asarray(key_mask), jnp.asarray([write_ix]), jnp.asarray(slots))
    params = attn.init(jax.random.PRNGKey(0), x, bias, positions)

    def step(write_ix, ck, cv, ks):
        cache = {"ck": ck, "cv": cv, "ck_scale": ks, "v_scale": jnp.asarray(vs[1])[:, :, None],
                 "ix": jnp.int32(1), "index": write_ix}
        return attn.apply(params, x, bias, positions, cache, jnp.asarray(key_mask))[0]

    refuse = (lambda *a: None) if fused else (lambda *a: "the test asks for the XLA branch")
    with mock.patch.object(tr, "decode_attn_unfused", refuse):
        out = jax.jit(step)(jnp.int32(write_ix), jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(ks))
    return np.asarray(out, np.float32)


@pytest.mark.parametrize(
    "rep,S,write_ix",
    [
        (1, 640, 300),  # inside the third of five chunks
        (1, 640, 255),  # a chunk's last slot
        (1, 640, 256),  # the next chunk's first slot
        (1, 640, 5),  # in the first chunk: four idle steps a row
        (4, 640, 300),  # grouped heads: four query rows a kv head
        (1, 128, 100),  # one chunk
        (4, 128, 127),
    ],
)
def test_fused_int8_decode_matches_the_xla_branch(rep, S, write_ix, monkeypatch):
    """The fused decode kernel against the folded-scale XLA branch on
    the same int8 cache (interpret mode), chunks of 128 slots: the same
    output wherever the write index lies, with left padding and grouped
    heads, and nothing past the write index is read (127s and NaN
    scales there change nothing)."""
    from trlx_tpu.ops import decode_attention

    monkeypatch.setattr(decode_attention, "CELL_BYTES", 1)  # one 128-slot chunk a cell
    assert decode_attention.decode_chunk(S, 2, 16) == 128
    want = _int8_decode_step(rep, S, write_ix, fused=False)
    got = _int8_decode_step(rep, S, write_ix, fused=True, garbage=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_fused_int8_decode_on_a_four_device_mesh():
    """GSPMD cannot partition a Mosaic call: on a mesh the kernel runs
    under shard_map (rows over dp x fsdp, heads over tp), one row a
    device here, and gives what the XLA branch gives unmeshed; rows the
    mesh does not divide keep the XLA branch, with the reason."""
    from trlx_tpu.models.transformer import decode_attn_unfused
    from trlx_tpu.parallel import make_mesh

    mesh = make_mesh({"dp": 1, "fsdp": 4}, devices=jax.devices()[:4])
    want = _int8_decode_step(1, 256, 200, fused=False)
    got = _int8_decode_step(1, 256, 200, fused=True, mesh=mesh, garbage=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    cfg = TransformerConfig(vocab_size=32, hidden_size=32, n_layer=1, n_head=2, kv_cache_quant="int8")
    assert decode_attn_unfused(cfg, mesh, 8, 256) is None
    assert "does not divide 6 rows" in decode_attn_unfused(cfg, mesh, 6, 256)
    assert "128-slot" in decode_attn_unfused(cfg, None, 8, 200)
    assert "alibi" in decode_attn_unfused(cfg.replace(pos_embed="alibi"), None, 8, 256)


def test_int8_kernel_is_no_value_of_kv_cache_quant():
    with pytest.raises(ValueError, match='"int8"'):
        TransformerConfig(vocab_size=8, hidden_size=8, n_layer=1, n_head=1, kv_cache_quant="int8_kernel")


def test_chunks_streamed_is_the_bound_at_the_write_index():
    """The `tokens_wait` span's chunk counts, at the longgen cell's
    shapes: 895 steps from slot 128 of 1024 in chunks of 256 read 68%
    of the cache they hold; a cache that is full from the start reads
    all of it; a sampler that is not fused has no grid."""
    from trlx_tpu.models.generation import chunks_streamed, fused_decode_cells

    got = chunks_streamed(steps=895, cells=22 * 8, chunk=256, slots=1024, first_write=128)
    assert got == {"cache_chunks_read": 176 * 2428, "cache_chunks_held": 176 * 895 * 4}
    full = chunks_streamed(steps=127, cells=1, chunk=256, slots=2048, first_write=1920)
    assert full["cache_chunks_read"] == full["cache_chunks_held"] == 127 * 8
    cfg = TransformerConfig(vocab_size=32, hidden_size=32, n_layer=3, n_head=2,
                            attention_impl="pallas", kv_cache_quant="int8")
    assert fused_decode_cells(TransformerLM(cfg), 8, 0, 120, 8) == {
        "cells": 24, "chunk": 128, "slots": 128, "first_write": 120}
    assert fused_decode_cells(TransformerLM(cfg), 8, 0, 120, 1) is None  # no decode step
    assert fused_decode_cells(TransformerLM(cfg.replace(attention_impl="xla")), 8, 0, 100, 8) is None
    assert fused_decode_cells(TransformerLM(cfg.replace(kv_cache_quant=None)), 8, 0, 120, 8) is None


def test_generate_program_and_its_stages_are_named(tiny_lm):
    """The sampler's XLA module is `jit_generate`, and the stages a
    trace must tell apart carry their scope: prefill, decode_step (with
    decode_attn over the int8 cache inside it) and sample."""
    import dataclasses

    from trlx_tpu.models.generation import make_generate_fn

    lm, params = tiny_lm
    qlm = TransformerLM(dataclasses.replace(lm.cfg, kv_cache_quant="int8"))
    fn = make_generate_fn(qlm, SamplerSettings(max_new_tokens=4))
    ids = jnp.ones((2, 6), jnp.int32)
    text = fn.lower(params, ids, jnp.ones_like(ids), jax.random.PRNGKey(0)).as_text(
        debug_info=True
    )
    assert "module @jit_generate" in text
    for scope in ("prefill/", "decode_step/", "/decode_attn/", "sample/"):
        assert scope in text, scope


def test_int8_decode_weights_track_full_precision(tiny_lm):
    """decode_weights_quant="int8": the whole rollout (prefill +
    decode) runs the quantized policy; greedy tokens must track the
    full-precision rollout on a tiny model, and the transformed tree
    must actually carry int8 kernels + scales."""
    import dataclasses

    from trlx_tpu.models.transformer import quantize_decode_weights

    lm, params = tiny_lm
    qlm = TransformerLM(
        dataclasses.replace(lm.cfg, decode_weights_quant="int8")
    )
    B, P, N = 2, 6, 8
    ids = jnp.ones((B, P), jnp.int32) * 5
    mask = jnp.ones((B, P), jnp.int32)
    settings = SamplerSettings(max_new_tokens=N, do_sample=False)
    out_fp = generate(lm, params, ids, mask, jax.random.PRNGKey(0), settings)
    out_q = generate(qlm, params, ids, mask, jax.random.PRNGKey(0), settings)
    agree = (
        np.asarray(out_fp["response_ids"]) == np.asarray(out_q["response_ids"])
    ).mean()
    assert agree >= 0.9, f"only {agree:.2%} of greedy tokens agree"

    qp = quantize_decode_weights(params)
    qkern = qp["blocks"]["attn"]["q"]["kernel"]
    assert qkern.dtype == jnp.int8
    scale = qp["blocks"]["attn"]["q"]["kernel_scale"]
    # dequant within int8 rounding of the original
    w = np.asarray(params["blocks"]["attn"]["q"]["kernel"], np.float32)
    deq = np.asarray(qkern, np.float32) * np.asarray(scale)[:, None]
    assert np.abs(deq - w).max() <= np.abs(w).max() / 127.0 + 1e-6


# --- a decode step on a sharded mesh: weights stay, activations move ---

_TOY_ROWS, _TOY_PROMPT, _TOY_NEW = 4, 120, 8


@pytest.fixture(scope="module")
def neox_toy():
    """A GPT-NeoX block at toy widths (rotary on part of the head,
    parallel residual, untied head) over an int8 cache of one whole
    128-slot tile (the fused kernel under `shard_map` pins the rows, as
    in the four-chip cell), its parameters, and the unsharded sampler's
    tokens by (decode weights, sampled). Float32 compute: a random toy's
    logits are near ties, which bf16 breaks by the order of the partial
    sums; in float32 equal tokens say the layouts compute one function."""
    cfg = TransformerConfig(
        vocab_size=256, hidden_size=64, n_layer=2, n_head=4, n_positions=128,
        intermediate_size=256, pos_embed="rotary", rotary_dim=4, activation="gelu",
        parallel_residual=True, tie_word_embeddings=False, kv_cache_quant="int8",
        dtype=jnp.float32,
    )
    params = TransformerLM(cfg).init(jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (_TOY_ROWS, _TOY_PROMPT), 0, 256)
    mask = jnp.ones_like(ids).at[0, :3].set(0)  # one left-padded row
    want = {
        (quant, sampled): np.asarray(_toy_sampler(cfg, quant, sampled, None)(
            params, ids, mask, jax.random.PRNGKey(2))["response_ids"])
        for quant in (None, "int8") for sampled in (False, True)
    }
    return cfg, params, ids, mask, want


def _toy_sampler(cfg, quant, sampled, mesh):
    import dataclasses

    from trlx_tpu.models.generation import make_generate_fn

    lm = TransformerLM(dataclasses.replace(cfg, decode_weights_quant=quant))
    lm.mesh = mesh
    return make_generate_fn(lm, SamplerSettings(max_new_tokens=_TOY_NEW, do_sample=sampled))


def _decode_loop_collectives(hlo: str):
    """(kind, result dims) of every all-gather, all-to-all and
    collective-permute that a decode step runs: the instructions whose
    `op_name` lies under the scope `decode_step` (the partitioner names
    a collective after the op it made it for), in the optimised text."""
    import re

    found = []
    for line in hlo.splitlines():
        m = re.search(r"= (.*?) (all-gather|all-to-all|collective-permute)(-start)?\(", line)
        if m and "decode_step" in line:
            shapes = re.findall(r"[a-z]+\d+\[([\d,]*)\]", m.group(1))
            found += [(m.group(2), tuple(int(d) for d in s.split(",") if d)) for s in shapes]
    return found


@pytest.mark.parametrize("quant", [None, "int8"], ids=["unquantized", "int8"])
@pytest.mark.parametrize("axes", [{"fsdp": 4}, {"dp": 2, "fsdp": 2}, {"fsdp": 2, "tp": 2}],
                         ids=["fsdp4", "dp2xfsdp2", "fsdp2xtp2"])
def test_decode_on_a_sharded_mesh_keeps_tokens_and_gathers_no_kernel(neox_toy, axes, quant):
    """On a mesh whose fsdp axis shards the kernels a decode step
    multiplies with the shards in place: greedy and sampled tokens are
    the unsharded sampler's, and no collective of the decode loop has a
    block kernel's or the head's shape (whole, or a tp shard of it)."""
    from trlx_tpu.parallel import data_sharding, make_mesh, shard_params

    cfg, params, ids, mask, want = neox_toy
    mesh = make_mesh({"dp": 1, **axes}, devices=jax.devices()[:4])
    assert tr.decode_weights_stationary(cfg, mesh, _TOY_ROWS)
    sharded = shard_params(mesh, params)
    ids, mask = (jax.device_put(a, data_sharding(mesh)) for a in (ids, mask))
    args = (sharded, ids, mask, jax.random.PRNGKey(2))
    for sampled in (False, True):
        compiled = _toy_sampler(cfg, quant, sampled, mesh).lower(*args).compile()
        got = np.asarray(compiled(*args)["response_ids"])
        np.testing.assert_array_equal(got, want[quant, sampled])
    tp = mesh.shape["tp"]
    E, H, D, F, V = cfg.hidden_size, cfg.n_head, cfg.head_dim, cfg.intermediate_size, cfg.vocab_size
    kernels = {(E, H // t, D) for t in (1, tp)} | {(H // t, D, E) for t in (1, tp)}
    kernels |= {(E, F // t) for t in (1, tp)} | {(F // t, E) for t in (1, tp)}
    kernels |= {(E, V // t) for t in (1, tp)}
    moved = _decode_loop_collectives(compiled.as_text())
    assert moved, "the decode loop's collectives were not found in the optimised text"
    for kind, dims in moved:
        bare = tuple(d for d in dims if d != 1)
        assert bare not in kernels, (kind, dims)
        # and nothing larger than a step's widest activation moves at all
        assert int(np.prod(dims)) <= _TOY_ROWS * max(F, V), (kind, dims)


@pytest.mark.parametrize("axes", [None, {"dp": 4}], ids=["no_mesh", "fsdp1"])
def test_decode_on_one_chip_or_without_fsdp_emits_no_constraint(neox_toy, axes, monkeypatch):
    """The one-chip cells' guard: with no mesh, and on a mesh whose fsdp
    axis is 1, the sampler lowers to the text it lowers to with the
    layouts taken out of the program; on fsdp the texts differ."""
    from trlx_tpu.parallel import data_sharding, make_mesh, shard_params

    cfg, params, ids, mask, _ = neox_toy

    def lowered(axes):
        mesh = axes and make_mesh({"dp": 1, **axes}, devices=jax.devices()[:4])
        p, a, m = params, ids, mask
        if mesh:
            p = shard_params(mesh, params)
            a, m = (jax.device_put(x, data_sharding(mesh)) for x in (ids, mask))
        return _toy_sampler(cfg, "int8", True, mesh).lower(p, a, m, jax.random.PRNGKey(2)).as_text()

    assert not tr.decode_weights_stationary(cfg, axes and make_mesh(axes, devices=jax.devices()[:4]), _TOY_ROWS)
    with_layouts, on_fsdp = lowered(axes), lowered({"fsdp": 4})
    monkeypatch.setattr(tr, "_decode_layouts", lambda *a: None)
    assert lowered(axes) == with_layouts
    assert lowered({"fsdp": 4}) != on_fsdp
