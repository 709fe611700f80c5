"""The Xing4.0 family's file: its work against counts made by hand from the
published keys, the rehearsal of its cell, and its metric readers on a hand-made
`Reading` (a number under 100 where there is something to read, None on a
Pythia reading or a program without the scopes and counters)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import cells, flops
from benchmark.layer_metrics import (
    expert_load_max_over_mean, hc_mix_share, latent_decode_attn_roofline,
    latent_flash_bwd_roofline, latent_flash_fwd_roofline, moe_experts_roofline)
from benchmark.reference import xing4_ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "xing4.0-29b-a4b.ppo-dialogue-b32"
READERS = [moe_experts_roofline, latent_decode_attn_roofline, latent_flash_fwd_roofline,
           latent_flash_bwd_roofline, hc_mix_share, expert_load_max_over_mean]

with open(os.path.join(ROOT, "benchmark", "configs", "xing4.0-29b-a4b.json")) as _f:
    HF = json.load(_f)

# -- the layer by hand, from the published keys ------------------------------
D, HEADS, STREAMS = 3584, 32, 4
Q_RANK, KV_RANK, NOPE, ROPE, VAL = 768, 512, 128, 64, 128
# W_dq, W_uq, W_dkv, W_ukv, W_o
MLA = D * Q_RANK + Q_RANK * HEADS * (NOPE + ROPE) + D * (KV_RANK + ROPE) \
    + KV_RANK * HEADS * (NOPE + VAL) + HEADS * VAL * D
DENSE_MLP = 3 * D * 9216
EXPERT = 3 * D * 1024
ROUTER = D * 64
MIXING = 2 * STREAMS * D * (STREAMS * STREAMS + 2 * STREAMS)  # Phi of both sub-layers
# norms: q and kv latents, the two pre-norms; the mixing scalars and biases; the router's bias
SMALL = Q_RANK + KV_RANK + 2 * D + 2 * (3 + 2 * STREAMS + STREAMS * STREAMS)


REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"}
# the catalog beside the `model-configs` guide: mounted where PRs are written, not everywhere
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_benchmark_json_names_the_keys_the_configuration_says_it_reduced():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "xing4.0-29b-a4b")
    assert set(entry["reduced"]) == set(HF["reduced"]) == REDUCED and entry["source"] == HF["source"]
    for key, cut in HF["reduced"].items():
        assert cut["run"] == HF[key] != cut["published"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the model catalog is not mounted here")
def test_the_configuration_keeps_every_published_number_but_the_reduced_ones():
    with open(CATALOG) as f:
        published = next(row for row in map(json.loads, f) if row["name"] == "Xing4.0-29B-A4B")
    assert HF["source"] == published["source_url"]
    assert {k for k, v in published["config"].items() if HF.get(k) != v} == REDUCED
    for key, cut in HF["reduced"].items():
        assert (cut["published"], cut["run"]) == (published["config"][key], HF[key])


def test_parameters_held_are_the_issues_hand_counts():
    held = xing4_ref.params_held(HF)
    assert MLA == 28_409_856  # 28.4M
    assert held["dense_layer"] == MLA + DENSE_MLP + MIXING + SMALL
    assert held["routed_layer"] == MLA + MIXING + SMALL + ROUTER + 64 + (8 + 1) * EXPERT
    assert round(held["dense_layer"] / 1e6, 1) == 128.2
    assert round(held["routed_layer"] / 1e6, 1) == 128.4
    assert held["embed_and_head"] == 2 * 16384 * D  # 117.4M
    assert held["total"] == held["dense_layer"] + 6 * held["routed_layer"] + 2 * 16384 * D + D
    assert round(held["total"] / 1e9, 3) == 1.016


def test_work_counts_what_a_token_a_pair_and_a_cached_position_cost_here():
    w = xing4_ref.work(HF)
    dense, routed = w["layers"][0], w["layers"][1]
    assert len(w["layers"]) == 7 and w["leading"] == 1 and w["layers"][1:] == [routed] * 6
    assert "routed" not in dense
    for layer in (dense, routed):
        assert layer["cache_elems"] == 576  # the latent and the shared rotary key
        assert layer["pair_flops"] == 20_480  # 2 x 32 heads x (192-wide score + 128-wide value)
    assert dense["linear_flops"] == 2 * (MLA + MIXING + DENSE_MLP)
    # the shared expert and the router run for every token; the held experts are `routed`
    assert routed["linear_flops"] == 2 * (MLA + MIXING + EXPERT + ROUTER)
    assert routed["routed"] == {"expert_flops": 2 * EXPERT, "expert_elems": EXPERT,
                                "published": 64, "held": 8, "per_token": 4}
    assert w["head"] == {"flops": 2 * D * 16384, "weight_elems": D * 16384}
    # the decode step reads int8 what `quantize_decode_weights` rewrites; W_ukv stays bf16
    # (two bytes an element), the router and the mixing matrices float32 (four)
    ukv = KV_RANK * HEADS * (NOPE + VAL)
    assert routed["weight_elems"] == (MLA - ukv) + 2 * ukv + 4 * MIXING + EXPERT + 4 * ROUTER
    # a token meets 4 x 8 / 64 = half a held expert a layer: about 0.9 GFLOP a token forward
    layers = dense["linear_flops"] + 6 * (routed["linear_flops"] + 0.5 * 2 * EXPERT)
    assert flops.causal_forward_flops(flops.work(xing4_ref, HF), 1) == layers + 20_480 * 7
    assert 0.85e9 < layers + w["head"]["flops"] < 0.95e9
    assert flops.trainable_layers(flops.work(xing4_ref, HF), 2) == 2


def test_toy_sizes_keep_every_mechanism():
    toy = dict(HF, **xing4_ref.toy_sizes(HF))
    w = xing4_ref.work(toy)
    assert w["leading"] == 1 and len(w["layers"]) == 3 and "routed" in w["layers"][-1]
    assert toy["n_routed_experts"] < toy["n_routed_experts_published"]  # a share, still
    assert xing4_ref.system_config(toy)["residual_streams"] == 4


def test_rehearsal_of_the_cell_exits_3_with_every_comparison_printed():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    tail = p.stdout.strip().splitlines()[-1]
    assert "REHEARSAL only" in tail
    line = json.loads(tail[tail.index("{"):])
    assert line["failed"] == 0 and line["attempted"] >= 1
    # the toy's 16-wide router leaves few decisive positions; the errors themselves are float32's
    assert {"logprob_rms", "tie_logprob_rms", "decisive_share_at_least"} <= set(line["compared"])
    assert line["compared"]["logprob_rms"][0] < line["compared"]["logprob_rms"][1]
    # the flight stream's counters reach the readers (no device seconds on a CPU)
    assert {"expert_load_max_over_mean", "hbm_peak_gib"} <= set(line["metrics"])
    assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1.0


# -- the readers on a hand-made Reading --------------------------------------

# uniform routing at the cell's sizes: a token meets 4 x 8 / 64 held experts a routed layer
TOKENS = {"sampler": 32 * (640 + 383), "scorer": 32 * 1024, "train": 8 * 1024}
HERE = {"sampler": TOKENS["sampler"] * 6 // 2, "scorer": TOKENS["scorer"] * (6 + 2) // 2,
        "train": TOKENS["train"] * 6 // 2}


def reading(cell_name=CELL, trace=None, flight=()):
    cell = cells.load_cell(cell_name)
    return SimpleNamespace(
        cell=cell, hf=cell.config, traffic=cell.traffic, chips=1,
        peaks=cells.peaks_for("TPU v5 lite"), unfrozen=2, trace=trace, flight=list(flight),
        cycles=[{"step": 32, "wall_s": 7.0}], wall_s=7.0, run_dir=None)


def flight_rows():
    counters = {}
    for program in ("scorer", "train"):
        counters[f"moe/assignments_here.{program}"] = float(HERE[program])
        counters[f"moe/assignments.{program}"] = HERE[program] * 8.0
        counters[f"moe/load_max_over_mean.{program}"] = 1.25
    sampler = {"rows": 32, "tokens": 32 * 384,
               "moe/assignments_here.sampler": float(HERE["sampler"]),
               "moe/assignments.sampler": HERE["sampler"] * 8.0,
               "moe/load_max_over_mean.sampler": 1.5}
    return [
        {"kind": "cycle", "step": 16, "counters": {"moe/load_max_over_mean.train": 9.0}},  # warm-up
        {"kind": "cycle", "step": 32, "counters": counters,
         "spans": [["generate", 0.0, 0.1, "rollout", {}],
                   ["tokens_wait", 0.1, 3.0, "rollout", sampler]]},
    ]


TRACE = {
    "busy_s": 6.5,
    "scopes_by_self_time": [
        ["jit(generate)/while/body/decode_step/blocks/attn/latent_decode_attn", 0.30],
        ["jit(generate)/while/body/decode_step/blocks/moe/moe_experts", 0.45],
        ["jit(fused_train_step)/transpose(jvp(moe_experts))/ragged_dot", 0.35],
        ["jit(ppo_experience_fwd)/blocks/moe/moe_experts", 0.10],
        ["jit(fused_train_step)/blocks/hc_mix", 0.50],
        ["jit(fused_train_step)/blocks/moe/moe_shared", 0.20],  # another scope
        ["jit(generate)/while/body/decode_step/blocks/attn/decode_attn_v2", 9.0],
    ],
    "ops_by_self_time": [
        ["%flash_fwd.3 custom-call (bf16[256,1024,128], f32[256,1024,1])", 0.20],
        ["%flash_fwd.9 custom-call bf16[1024,640,128]", 0.05],
        ["%flash_bwd_dq.1 custom-call bf16[256,1024,192]", 0.06],
        ["%flash_bwd_dkv.1 custom-call (bf16[256,1024,192], bf16[256,1024,128])", 0.08],
    ],
}


def test_readers_count_by_hand_and_stay_under_100():
    r = reading(trace=TRACE, flight=flight_rows())
    peak, hbm = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]

    # a decode step reads, of every row, the positions written so far (576 bf16 numbers each)
    # and W_ukv once, in each of the 7 layers; 383 steps after the prefill's token
    positions = sum(640 + i for i in range(1, 384))
    least = 7 * 2 * (576 * 32 * positions + KV_RANK * HEADS * (NOPE + VAL) * 383) / hbm
    assert latent_decode_attn_roofline.read(r) == pytest.approx(100 * least / 0.30)

    # flash forward: 7 layers of prefill at 640; 7 + 2 of scoring and 4 x 7 of training at 1024
    def fwd(seq):
        return max(20_480 * 32 * seq * (seq + 1) / 2 / peak,
                   2 * 32 * seq * HEADS * (2 * 192 + 2 * 128) / hbm)

    assert latent_flash_fwd_roofline.read(r) == pytest.approx(100 * (7 * fwd(640) + 37 * fwd(1024)) / 0.25)
    # the backward of the 2 trainable layers in each of 4 epochs, 2.5 forwards each
    assert latent_flash_bwd_roofline.read(r) == pytest.approx(
        100 * 8 * 2.5 * 20_480 * 32 * 1024 * 1025 / 2 / peak / 0.14)

    assert hc_mix_share.read(r) == pytest.approx(100 * 0.50 / 6.5)
    assert expert_load_max_over_mean.read(r) == 1.5  # the worst program, window cycles only

    share = moe_experts_roofline.read(r)
    # at least the counted pairs' FLOPs: sampler and scorer once, 16 train steps with the
    # backward (twice the forward) of 2 of the 6 routed layers
    pairs = HERE["sampler"] + HERE["scorer"] + 16 * HERE["train"] * (1 + 2 * 2 / 6)
    assert share >= 100 * pairs * 2 * EXPERT / peak / 0.90
    for module in READERS[:4]:
        assert 0 < module.read(r) < 100, module.__name__


@pytest.mark.parametrize("module", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_readers_read_nothing_where_there_is_nothing(module):
    # no trace (a `--trace 0` run), no flight stream
    assert module.read(reading()) is None
    # a program without the scopes, the kernels' names or the counters (the parent)
    bare = {"busy_s": 6.5, "scopes_by_self_time": [["jit(generate)/while/body/decode_step", 1.0]],
            "ops_by_self_time": [["%fusion.1 fusion kOutput bf16[8,1024,3584]", 0.4]]}
    assert module.read(reading(trace=bare, flight=[{"kind": "cycle", "step": 32, "spans": []}])) is None
    # a Pythia reading, with its own scopes and kernels in the trace
    pythia = {"busy_s": 4.0,
              "scopes_by_self_time": [["jit(generate)/while/body/decode_step/blocks/attn/decode_attn", 1.0]],
              "ops_by_self_time": [["%flash_fwd.3 custom-call bf16[128,2048,128]", 0.3],
                                   ["%flash_bwd_dq.1 custom-call bf16[128,2048,128]", 0.2]]}
    assert module.read(reading("pythia-1.4b.ppo-longprompt", trace=pythia)) is None
