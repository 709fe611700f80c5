"""chip_smoke.py — the quickest proof that the PPO main path still starts
on the chip.

    python chip_smoke.py                # legs C, A, B; needs a TPU

One process (a chip belongs to one process at a time), no network,
prompts and weights from a seed, full model width:

  leg C  every pallas entry point compiled by Mosaic (never interpreted)
         at the 1.3B recipe's head geometry, against the XLA path the
         repo has for it
  leg A  GPT2-small PPO through `trlx_tpu.train()`: three learn()
         cycles with the overlapped rollout prefetch (the donation
         hazard), a checkpoint and `best_checkpoint`
  leg B  the 1.3B single-chip recipe (configs/mesh/single_chip_1p3b.yml,
         pallas attention fwd+bwd+prefill, int8 KV + decode weights,
         chunked logprobs, fused int8 AdamW, hydra reference): two
         learn() cycles and the final eval, no checkpoint (leg A proves
         that path; a 1.3B one is 8 GB in 2 GiB files, more than the
         machine that checks this script lets a process write)

Every leg asserts on what it produced; any failed assertion makes the
exit code non-zero. Off the chip the script refuses before it builds
anything: JAX with no accelerator carries on on the CPU, and that is the
fallback this file exists to refuse. The mesh comes from the chips
found (1 -> the single-chip recipe; 4 -> fsdp=4 with plain adamw), or
from `--mesh` for a builder's extra layouts.

`--rehearse` runs the same code at toy sizes on whatever backend JAX
has (kernels interpreted on a CPU) to debug the script itself; it never
prints the result line and never exits 0.

Writes only under ./chiprun_out/chip_smoke/ (and the compile cache, see
trlx_tpu/utils/compile_cache.py). The last line of a passing run is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
Walls printed along the way are smoke walls, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import functools
import gc
import glob
import json
import math
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
VOCAB = 50257  # GPT-2 vocabulary, the width both model legs run at
SEED = 1000


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CompileMeter:
    """Per-leg compile seconds and persistent-cache traffic, from JAX's
    own monitoring events (trace + lower + backend compile, the last of
    which is a cache read on a hit)."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def peak_bytes():
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def seeded_prompts(n: int, n_bytes: int, seed: int):
    """`n` lowercase pseudo-text prompts of exactly `n_bytes` bytes (the
    byte tokenizer then fills the whole prompt window)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    alphabet = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz     ", dtype=np.uint8)
    return [
        bytes(rng.choice(alphabet, size=n_bytes)).decode("ascii")
        for _ in range(n)
    ]


def reward_fn(samples, prompts, outputs, **kw):
    return [float(o.count("a")) - 0.1 * len(o) for o in outputs]


# ---------------------------------------------------------------------
# leg C: kernels
# ---------------------------------------------------------------------


def _rel_err(got, want) -> float:
    """Max error normalized by the reference's largest magnitude."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


def _mosaic_calls(fn, *args) -> int:
    import jax

    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


# Tolerances (normalized max error, see _rel_err). Inputs and outputs are
# bf16 (8 mantissa bits: one rounding is 2^-9 ~ 0.2%); the kernels
# accumulate in f32 but the MXU multiplies in bf16 passes, while the
# reference runs under default_matmul_precision("highest"). A forward
# stacks two such products and the output rounding; a backward stacks
# four and sums over up to 8192 keys. A wrong mask, scale, head routing
# or page index is an O(1) error, two orders above either bound.
TOL_FWD = 2e-2
TOL_BWD = 4e-2


def leg_c(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.ops import paged_kv
    from trlx_tpu.ops.common import interpret_mode
    from trlx_tpu.ops.decode_attention import (
        decode_attention_int8,
        paged_attention_step,
    )
    from trlx_tpu.ops.flash_attention import (
        NEG_INF,
        _attention_reference,
        flash_attention,
        flash_attention_bias,
    )

    tiny = ctx["rehearse"]
    if not tiny and interpret_mode():
        raise AssertionError("kernels would run interpreted on this backend")
    bf = jnp.bfloat16
    failures = []

    def check(name, got, want, tol, n_calls):
        errs = [
            _rel_err(g, w)
            for g, w in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want))
        ]
        finite = all(
            bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
            for g in jax.tree_util.tree_leaves(got)
        )
        ok = finite and max(errs) <= tol and (tiny or n_calls > 0)
        log(
            f"  {'ok  ' if ok else 'FAIL'} {name}: err "
            f"{' '.join(f'{e:.2e}' for e in errs)} (tol {tol:.0e}) "
            f"mosaic_calls={n_calls}"
        )
        if not ok:
            failures.append(name)

    def rand(key, shape, dtype=bf):
        return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)

    def f32(*xs):
        return tuple(x.astype(jnp.float32) for x in xs)

    # -- flash_attention fwd + bwd, MHA and GQA, teacher-forced + prefill
    B, H, D = (2, 16, 128) if not tiny else (2, 4, 16)
    S = 2048 if not tiny else 256
    for tag, Hkv, T, q_offset in (
        ("mha", H, S, None),
        ("gqa", H // 4, S, None),
        ("prefill", H, S - S // 16, 0),
    ):
        q, k, v = rand(1, (B, H, T, D)), rand(2, (B, Hkv, S, D)), rand(3, (B, Hkv, S, D))
        ct = rand(4, (B, H, T, D))
        # left padding on row 0; a prefill's unwritten tail is masked too
        mask = jnp.ones((B, S), jnp.int32).at[0, : S // 8].set(0)
        if q_offset is not None:
            mask = mask.at[:, T:].set(0)
        sm = 1.0 / math.sqrt(D)

        def kern(q, k, v, q_offset=q_offset, mask=mask):
            return flash_attention(q, k, v, mask, True, None, 256, q_offset)

        def ref(q, k, v, q_offset=q_offset, mask=mask, T=T):
            if q_offset is not None:  # prefill: queries at slots [0, T)
                k, v, mask = k[:, :, :T], v[:, :, :T], mask[:, :T]
            return _attention_reference(q, k, v, mask, True, sm)

        def real_rows(o, mask=mask, T=T, q_offset=q_offset):
            # fully-masked (padding) query rows are don't-care
            qm = mask[:, :T] if q_offset is not None else mask[:, S - T:]
            return o * qm[:, None, :, None].astype(o.dtype)

        n = _mosaic_calls(kern, q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*f32(q, k, v))
            gwant = jax.jit(jax.grad(
                lambda a: (real_rows(ref(*a)) * ct.astype(jnp.float32)).sum()
            ))(f32(q, k, v))
        got = jax.jit(kern)(q, k, v)
        check(f"flash_fwd/{tag} B{B} H{H} Hkv{Hkv} T{T} S{S} D{D}",
              real_rows(got), real_rows(want), TOL_FWD, n)
        gfn = jax.grad(
            lambda a: (real_rows(kern(*a)).astype(jnp.float32)
                       * ct.astype(jnp.float32)).sum()
        )
        ng = _mosaic_calls(gfn, (q, k, v))
        check(f"flash_bwd/{tag} (dq dk dv)", jax.jit(gfn)((q, k, v)), gwant,
              TOL_BWD, ng)

    # -- flash_attention_bias (T5): encoder + causal decoder, two lengths
    # (fewer heads at 8k: the reference's [H, S, S] f32 tensors are 1 GB
    # a head there, and its backward holds several)
    Db = 64 if not tiny else 16
    for S_b, Bb, Hb in ((2048, 2, 8), (8192, 1, 4)) if not tiny else ((256, 2, 2),):
        for causal in (False, True):
            q, k, v = (rand(i, (Bb, Hb, S_b, Db)) for i in (5, 6, 7))
            ct = rand(8, (Bb, Hb, S_b, Db))
            bias = rand(9, (Hb, S_b, S_b), jnp.float32)
            mask = jnp.ones((Bb, S_b), jnp.int32).at[0, -S_b // 8:].set(0)

            def kern(q, k, v, bias, mask=mask, causal=causal):
                return flash_attention_bias(q, k, v, mask, bias, causal, 1.0, 128)

            def ref(q, k, v, bias, mask=mask, causal=causal, S_b=S_b):
                # models/seq2seq.py T5Attention's XLA branch: additive
                # bias (learned + causal + padding), no 1/sqrt(d)
                s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                               preferred_element_type=jnp.float32) + bias[None]
                if causal:
                    pos = jnp.arange(S_b)
                    s = jnp.where(pos[:, None] >= pos[None, :], s, NEG_INF)
                s = jnp.where(mask[:, None, None, :] > 0, s, NEG_INF)
                return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

            n = _mosaic_calls(kern, q, k, v, bias)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(ref)(*f32(q, k, v), bias)
                gwant = jax.jit(jax.grad(
                    lambda a: (ref(*a) * ct.astype(jnp.float32)).sum()
                ))((*f32(q, k, v), bias))
            got = jax.jit(kern)(q, k, v, bias)
            tag = f"{'dec' if causal else 'enc'} B{Bb} H{Hb} S{S_b} D{Db}"
            check(f"flash_bias_fwd/{tag}", got, want, TOL_FWD, n)
            gfn = jax.grad(
                lambda a: (kern(*a).astype(jnp.float32)
                           * ct.astype(jnp.float32)).sum()
            )
            ng = _mosaic_calls(gfn, (q, k, v, bias))
            check(f"flash_bias_bwd/{tag} (dq dk dv dbias)",
                  jax.jit(gfn)((q, k, v, bias)), gwant, TOL_BWD, ng)
            del want, gwant, got

    # -- decode_attention_int8: one step over a stacked int8 cache
    L, Bd = (4, 8) if not tiny else (2, 2)
    for Hkv in (H, H // 4):
        kf = jax.random.normal(jax.random.PRNGKey(10), (L, Bd, Hkv, S, D))
        vf = jax.random.normal(jax.random.PRNGKey(11), (L, Bd, Hkv, S, D))
        ks = jnp.max(jnp.abs(kf), -1) / 127.0  # [L, B, Hkv, S]
        ck = jnp.round(kf / ks[..., None]).astype(jnp.int8)
        vs = jnp.max(jnp.abs(vf), 3) / 127.0  # [L, B, Hkv, D]
        cv = jnp.round(vf / vs[:, :, :, None]).astype(jnp.int8)
        k_scale = ks  # [L, B, Hkv, S]
        v_scale = vs[:, :, :, None, :]  # [L, B, Hkv, 1, D]
        q = rand(12, (Bd, H, D))
        key_mask = (
            jnp.arange(S)[None, :] < (S - 7 * jnp.arange(Bd))[:, None]
        ).astype(jnp.int32)
        lx = jnp.int32(L - 1)
        sm = 1.0 / math.sqrt(D)

        def kern(q, ck, cv, k_scale, v_scale):
            return decode_attention_int8(
                q, ck, cv, k_scale, v_scale[L - 1], key_mask, lx,
                jnp.int32(S - 1), sm,
            )

        def ref(q, ck, cv, k_scale, v_scale):
            # models/transformer.py's folded-scale XLA decode branch,
            # grouped over kv heads
            rep = H // Hkv
            qg = q.astype(jnp.float32).reshape(Bd, Hkv, rep, D)
            s = jnp.einsum("bgrd,bgsd->bgrs", qg, ck[L - 1].astype(jnp.float32)) * sm
            s = s * k_scale[L - 1][:, :, None]
            s = jnp.where(key_mask[:, None, None, :] > 0, s, NEG_INF)
            o = jnp.einsum("bgrs,bgsd->bgrd", jax.nn.softmax(s, -1),
                           cv[L - 1].astype(jnp.float32))
            return (o * v_scale[L - 1]).reshape(Bd, H, D)

        n = _mosaic_calls(kern, q, ck, cv, k_scale, v_scale)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(q, ck, cv, k_scale, v_scale)
        check(f"decode_int8 B{Bd} H{H} Hkv{Hkv} S{S} D{D} L{L}",
              jax.jit(kern)(q, ck, cv, k_scale, v_scale), want, TOL_FWD, n)
        del kf, vf, ck, cv

    # -- paged_attention_pallas: T=1 decode and T=draft_k verify
    PS = 128 if not tiny else 8
    MP, Lp, Bp = S // PS, 2, 4
    NP = 1 + Bp * MP
    for quant in (None, "int8"):
        for Hkv in (H, H // 4):
            for T in (1, 4):
                pools = paged_kv.init_pool(Lp, NP, PS, Hkv, D, quant, bf)
                # scattered (non-contiguous) page table, ragged depths
                perm = np.random.default_rng(0).permutation(NP - 1) + 1
                table = jnp.asarray(perm.reshape(Bp, MP), jnp.int32)
                depth = jnp.asarray(
                    [S - T, S // 2, PS + 3, 5][:Bp], jnp.int32
                )  # slot of the first incoming token per lane
                ctx_len = S - T
                ctx_kv = rand(13, (Bp, ctx_len, Hkv, D))
                step = jax.jit(
                    paged_attention_step,
                    static_argnames=("sm_scale", "contiguous", "impl"),
                )
                zero_bias = jnp.zeros((Bp, 1, ctx_len, S), jnp.float32)
                _, pools = step(  # pre-context through the op's own write path
                    jnp.zeros((Bp, ctx_len, H, D), bf), ctx_kv, ctx_kv, pools,
                    jnp.int32(1), table, jnp.zeros((Bp,), jnp.int32),
                    zero_bias, sm_scale=1.0,
                )
                q = rand(14, (Bp, T, H, D))
                kn, vn = rand(15, (Bp, T, Hkv, D)), rand(16, (Bp, T, Hkv, D))
                q_slots = depth[:, None] + jnp.arange(T)[None, :]
                ok = (q_slots[:, :, None] >= jnp.arange(S)[None, None, :])
                bias = jnp.where(ok, 0.0, -1e30)[:, None].astype(jnp.float32)
                sm = 1.0 / math.sqrt(D)
                args = (q, kn, vn, pools, jnp.int32(1), table, depth, bias)
                n = jax.jit(
                    lambda *a: paged_attention_step(*a, sm, impl="pallas")[0]
                ).lower(*args).as_text().count("tpu_custom_call")
                with jax.default_matmul_precision("highest"):
                    want, _ = step(*args, sm_scale=sm, impl="xla")
                got, _ = step(*args, sm_scale=sm, impl="pallas")
                check(
                    f"paged/{quant or 'bf16'} T{T} B{Bp} H{H} Hkv{Hkv} "
                    f"PS{PS} MP{MP} D{D}", got, want, TOL_FWD, n,
                )
                del pools
    if failures:
        raise AssertionError(f"leg C: {len(failures)} kernel checks failed: {failures}")


# ---------------------------------------------------------------------
# legs A and B: trlx_tpu.train()
# ---------------------------------------------------------------------


def resolve_mesh(n_devices: int, override):
    """(mesh axis sizes, sharded?) from the chips found."""
    if override:
        mesh = dict(override)
    elif n_devices == 1:
        mesh = {"dp": 1}
    elif n_devices == 4:
        mesh = {"fsdp": 4}
    else:
        raise SystemExit(
            f"chip_smoke: no mesh rule for {n_devices} devices; pass --mesh"
        )
    return mesh, mesh.get("fsdp", 1) * mesh.get("tp", 1) > 1


def build_config(ctx, leg: str):
    from trlx_tpu.data.default_configs import default_ppo_config

    tiny = ctx["rehearse"]
    mesh, sharded = resolve_mesh(ctx["n_devices"], ctx["mesh"])
    ckpt_dir = os.path.join(OUT, f"leg{leg}")
    base = default_ppo_config()
    if leg == "A":
        geom = dict(hidden_size=768, n_layer=12, n_head=12, n_positions=1024)
        prompt_len, new_tokens, rollouts, batch, cycles = 32, 32, 64, 32, 3
        if tiny:
            geom = dict(hidden_size=64, n_layer=2, n_head=2, n_positions=64)
            prompt_len, new_tokens, rollouts, batch = 16, 8, 16, 8
        cfg = base.evolve(
            train=dict(compute_dtype="bfloat16", mesh=mesh),
            model=dict(num_layers_unfrozen=-1),
            method=dict(overlap_rollouts=True),
        )
    else:
        import yaml

        geom = dict(hidden_size=2048, n_layer=24, n_head=16,
                    attention_impl="pallas")
        prompt_len, new_tokens, rollouts, batch, cycles = 1920, 128, 8, 8, 2
        if tiny:
            geom = dict(hidden_size=64, n_layer=4, n_head=2,
                        attention_impl="pallas")
            prompt_len, new_tokens = 120, 8
        with open(os.path.join(HERE, "configs", "mesh", "single_chip_1p3b.yml")) as f:
            cfg = base.evolve(**yaml.safe_load(f)).evolve(train=dict(mesh=mesh))
        if sharded:
            # the sharded-moment layout docs/multihost.md prescribes:
            # adamw_8bit_fused all-gathers under GSPMD
            cfg = cfg.evolve(optimizer=dict(name="adamw"))
        geom["n_positions"] = prompt_len + new_tokens
    steps_per_cycle = 4 * (rollouts // batch)  # ppo_epochs x minibatches
    # leg A: one mid-run save (+ the final one) and evals that commit
    # best_checkpoint; leg B: the final eval only
    every = (2 if leg == "A" else cycles) * steps_per_cycle
    cfg = cfg.evolve(
        train=dict(
            batch_size=batch, seq_length=prompt_len + new_tokens,
            total_steps=cycles * steps_per_cycle, epochs=10_000,
            checkpoint_interval=every if leg == "A" else 0,
            save_best=leg == "A", eval_interval=every,
            checkpoint_dir=ckpt_dir, tracker="jsonl", seed=SEED,
        ),
        model=dict(
            model_path="random",
            model_extra_configs={"transformer": dict(vocab_size=VOCAB, **geom)},
        ),
        tokenizer=dict(
            tokenizer_path="byte",
            tokenizer_extra_configs=dict(vocab_size=VOCAB),
        ),
        method=dict(
            num_rollouts=rollouts, chunk_size=rollouts, ppo_epochs=4,
            gen_kwargs=dict(max_new_tokens=new_tokens, top_k=0, top_p=1.0,
                            do_sample=True),
        ),
    )
    prompts = seeded_prompts(rollouts, prompt_len, SEED + ord(leg))
    return cfg, prompts, cycles, sharded


def _lowered_texts(trainer):
    """StableHLO of the fused train step and of every compiled sampler,
    re-lowered from abstract arguments (tracing only, no compile)."""
    import jax
    import jax.numpy as jnp

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            tree,
        )

    full, n = trainer._fused_epoch_batch()
    device_full = trainer.place_batch(full)
    perms = trainer._epoch_perms(n)
    rng = jax.ShapeDtypeStruct(trainer.rng.shape, trainer.rng.dtype)
    with trainer.mesh:
        train_txt = trainer._fused_train_step.lower(
            abstract(trainer.params), abstract(trainer.opt_state),
            abstract(device_full),
            jax.ShapeDtypeStruct(perms.shape, jnp.int32),
        ).as_text()
        gen_txts = []
        for (_settings, shape, _pk), fn in trainer._generate_fns.items():
            ids = jax.ShapeDtypeStruct(shape, jnp.int32)
            gen_txts.append(
                fn.lower(abstract(trainer.params), ids, ids, rng).as_text()
            )
    return train_txt, gen_txts, device_full


def _assert_spread(trainer, device_full, n_devices, sharded) -> None:
    """The epoch batch and a fresh rollout really are spread over every
    chip — and so are parameters and optimizer state when the mesh
    shards them (fsdp / tp; under pure dp they are replicas by design)."""
    import jax
    import numpy as np

    log(f"  mesh devices (id, coords): "
        f"{[(d.id, getattr(d, 'coords', None)) for d in trainer.mesh.devices.flat]}")

    def big_leaves(tree):
        return [
            x for x in jax.tree_util.tree_leaves(tree)
            if hasattr(x, "sharding") and x.size >= 1 << 20
        ]

    state = (("params", trainer.params), ("opt_state", trainer.opt_state))
    for name, tree in state if sharded else ():
        leaves = big_leaves(tree)
        assert leaves, f"{name}: no large leaves to check"
        for x in leaves:
            assert len(x.sharding.device_set) == n_devices, (name, x.shape, x.sharding)
        split = [x for x in leaves if x.sharding.shard_shape(x.shape) != x.shape]
        assert len(split) == len(leaves), (
            f"{name}: {len(leaves) - len(split)} of {len(leaves)} large "
            "leaves are replicated, not sharded"
        )
        log(f"  {name}: {len(leaves)} large leaves, all split over {n_devices} chips")
    for x in jax.tree_util.tree_leaves(device_full):
        if x.ndim >= 1 and x.shape[0] % n_devices == 0:
            assert len(x.sharding.device_set) == n_devices
            assert not x.sharding.is_fully_replicated, ("batch", x.shape)
    cfg = trainer.config
    prompt_len = cfg.train.seq_length - cfg.method.gen_kwargs["max_new_tokens"]
    prompts = seeded_prompts(cfg.method.chunk_size, prompt_len, SEED)
    seq = trainer.generate(
        np.asarray(trainer.tokenizer(prompts)["input_ids"], np.int32)
    )["sequences"]
    assert len(seq.sharding.device_set) == n_devices, seq.sharding
    assert not seq.sharding.is_fully_replicated, (
        f"rollout output is replicated on every chip: {seq.sharding}"
    )
    log(f"  rollouts: sequences {tuple(seq.shape)} sharded as {seq.sharding.spec}")
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.local_devices()
    ]
    log(f"  per-device bytes_in_use: {in_use}")
    if all(b is not None for b in in_use):
        assert max(in_use) <= 1.5 * min(in_use), (
            f"per-device memory is lopsided: {in_use}"
        )


def run_train_leg(ctx, leg: str) -> dict:
    import jax

    import trlx_tpu

    cfg, prompts, cycles, sharded = build_config(ctx, leg)
    ckpt_dir = cfg.train.checkpoint_dir
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    tcfg = cfg.model.model_extra_configs["transformer"]
    log(
        f"leg {leg}: {tcfg['n_layer']}L x {tcfg['hidden_size']} x "
        f"{tcfg['n_head']}h vocab {VOCAB} seq {cfg.train.seq_length} "
        f"batch {cfg.train.batch_size} mesh {cfg.train.mesh} "
        f"optimizer {cfg.optimizer.name} attention "
        f"{tcfg.get('attention_impl', 'xla')} steps {cfg.train.total_steps}"
    )
    t0 = time.time()
    trainer = trlx_tpu.train(reward_fn=reward_fn, prompts=prompts, config=cfg)
    wall = time.time() - t0
    try:
        assert trainer.iter_count == cfg.train.total_steps, (
            f"trained {trainer.iter_count} of {cfg.train.total_steps} steps"
        )
        # the loss / reward stream the tracker saw
        with open(os.path.join(ckpt_dir, "logs", "metrics.jsonl")) as f:
            recs = [json.loads(line) for line in f]
        losses = [r["losses/total_loss"] for r in recs if "losses/total_loss" in r]
        rewards = [r["reward/mean"] for r in recs if "reward/mean" in r]
        assert losses and all(math.isfinite(x) for x in losses), f"losses {losses}"
        # rewards are clipped to +-10 (method.cliprange_reward), which
        # bounds the returns; a loss beyond 1e3 means the value head has
        # left that range by an order of magnitude: diverged, not trained
        assert max(losses) < 1e3, f"loss diverged: {losses}"
        assert rewards and all(math.isfinite(x) for x in rewards), f"rewards {rewards}"
        log(f"  losses {[round(x, 4) for x in losses]} reward/mean {rewards}")
        # the flight recorder's device stamp and token ledger
        with open(os.path.join(ckpt_dir, "flight", "telemetry.json")) as f:
            telem = json.load(f)
        prov, head = telem["provenance"], telem["headline"]
        assert prov["backend"] == jax.default_backend(), prov
        assert ctx["rehearse"] or prov["backend"] == "tpu", prov
        assert prov["device_count"] == ctx["n_devices"], prov
        assert head["total_real_tokens"] > 0, head
        assert head["cycles"] >= cycles, head
        walls = [c["wall_s"] for c in telem["cycles"]]
        log(
            f"  telemetry: backend {prov['backend']} {prov['device_kind']} x"
            f"{prov['device_count']}, decode_impl {prov.get('decode_impl')}, "
            f"{head['total_samples']} samples, "
            f"{head['total_real_tokens']:.0f} tokens, cycle walls {walls} s "
            "(smoke walls)"
        )
        # checkpoint layout (leg A; leg B writes none)
        ckpts = sorted(glob.glob(os.path.join(ckpt_dir, "checkpoint_*")))
        if cfg.train.checkpoint_interval > 0:
            assert len(ckpts) >= 2, f"checkpoints {os.listdir(ckpt_dir)}"
            for c in ckpts + [os.path.join(ckpt_dir, "best_checkpoint")]:
                for part in ("COMMIT", "state.json", "telemetry.json"):
                    assert os.path.exists(os.path.join(c, part)), f"{c} lacks {part}"
            sizes = [
                os.path.getsize(os.path.join(d, f))
                for d, _, fs in os.walk(ckpt_dir) for f in fs
            ]
            log(
                f"  checkpoints: {[os.path.basename(c) for c in ckpts]} + "
                f"best_checkpoint ({sum(sizes) / 1e6:.0f} MB under "
                f"{os.path.basename(ckpt_dir)}, largest file "
                f"{max(sizes) / 1e6:.0f} MB)"
            )
        else:
            left = [d for d in os.listdir(ckpt_dir) if "checkpoint" in d]
            assert not left, f"checkpointing was off, yet: {left}"
        # which attention ran: Mosaic custom calls in the lowered programs
        train_txt, gen_txts, device_full = _lowered_texts(trainer)
        n_train = train_txt.count("tpu_custom_call")
        n_gen = [t.count("tpu_custom_call") for t in gen_txts]
        log(f"  tpu_custom_call: fused train step {n_train}, samplers {n_gen}")
        want_pallas = tcfg.get("attention_impl") == "pallas"
        if want_pallas and not ctx["rehearse"]:
            assert n_train > 0, "attention_impl=pallas but the train step has no Mosaic call"
            assert gen_txts and min(n_gen) > 0, "attention_impl=pallas but a prefill has no Mosaic call"
        if not want_pallas:
            assert n_train == 0 and not any(n_gen), "Mosaic call without attention_impl=pallas"
        if ctx["n_devices"] > 1:
            _assert_spread(trainer, device_full, ctx["n_devices"], sharded)
    finally:
        # what comes back from the chip machine is capped: keep the
        # logs and the telemetry only
        for c in glob.glob(os.path.join(ckpt_dir, "*checkpoint*")):
            shutil.rmtree(c, ignore_errors=True)
    del trainer
    gc.collect()
    return {"wall_s": round(wall, 1), "cycle_walls_s": walls}


LEGS = {
    "C": leg_c,
    "A": functools.partial(run_train_leg, leg="A"),
    "B": functools.partial(run_train_leg, leg="B"),
}


def parse_mesh(text):
    if not text:
        return None
    return {k: int(v) for k, v in (kv.split("=") for kv in text.split(","))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--legs", default="C,A,B",
                    help="subset/order of legs, for a builder's debugging")
    ap.add_argument("--mesh", default="",
                    help="mesh override, e.g. fsdp=2,tp=2 (default: from the chips found)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; never passes")
    args = ap.parse_args()

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    log(
        f"platform: {device['platform']} device_kind: {device['kind']} "
        f"count: {device['count']} jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu}"
    )
    if dev.platform != "tpu" and not args.rehearse:
        log("no TPU: refusing to run (a CPU run proves nothing about the chip)")
        return 2

    from trlx_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache: {cache_dir} ({n_cached} entries at start)")
    os.makedirs(OUT, exist_ok=True)
    walls_path = os.path.join(OUT, "walls.json")
    previous = {}
    if os.path.exists(walls_path):
        with open(walls_path) as f:
            previous = json.load(f)

    ctx = {
        "rehearse": args.rehearse, "n_devices": device["count"],
        "mesh": parse_mesh(args.mesh),
    }
    meter = CompileMeter()
    report, failed = {}, None
    for name in [x.strip().upper() for x in args.legs.split(",") if x.strip()]:
        s0, h0, m0 = meter.snapshot()
        t0 = time.time()
        try:
            extra = LEGS[name](ctx) or {}
            status = "ok"
        except Exception:
            traceback.print_exc()
            extra, status, failed = {}, "FAILED", name
        s1, h1, m1 = meter.snapshot()
        report[name] = {
            "status": status, "leg_wall_s": round(time.time() - t0, 1),
            "compile_s": round(s1 - s0, 1), "cache_hits": h1 - h0,
            "cache_misses": m1 - m0, "peak_bytes_in_use": peak_bytes(), **extra,
        }
        line = f"leg {name} {status}: {json.dumps(report[name])}"
        prev = previous.get(name)
        if prev:
            line += (
                f" | previous run in this directory: compile_s "
                f"{prev['compile_s']} ({prev['cache_hits']} hits, "
                f"{prev['cache_misses']} misses)"
            )
        log(line)
        if failed:
            break  # fail fast: a later leg would burn its compile for nothing
    if not args.rehearse:
        with open(walls_path, "w") as f:
            json.dump(report, f, indent=1)
    if args.rehearse:
        log("REHEARSAL only: no result")
        return 3
    if failed:
        log(f"FAILED in leg {failed}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
