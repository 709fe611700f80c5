"""The Kimi-Linear family's file: its configuration against the catalog, its work
against counts made by hand from the published keys, the rehearsal of its cell, and
its metric readers on a hand-made `Reading` (a number under 100 where there is
something to read, None on another family's reading or a program without the scopes)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import cells, flops
from benchmark.layer_metrics import (
    _kda, kda_chunk_roofline, kda_decode_roofline, kda_share, latent_decode_attn_roofline,
    latent_flash_bwd_roofline, latent_flash_fwd_roofline)
from benchmark.reference import kimi_linear_ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kimi-linear-48b-a3b.ppo-longgen-b32"
READERS = [kda_chunk_roofline, kda_decode_roofline, kda_share]

with open(os.path.join(ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json")) as _f:
    HF = json.load(_f)

# -- the layers by hand, from the published keys ------------------------------
D, HEADS, KH, KD, TAPS = 2304, 32, 32, 128, 4
KV_RANK, NOPE, ROPE, VAL = 512, 128, 64, 128
W = KH * KD  # 4096
KDA_WRITTEN = 4 * D * W  # q, k, v, o
KDA_SMALL = 2 * (D * KD + KD * W) + D * KH  # the two low-rank pairs, beta
KDA_VECTORS = 3 * W * TAPS + KH + W + KD  # taps, A_log, dt_bias, the output norm
KDA = KDA_WRITTEN + KDA_SMALL + KDA_VECTORS
MLA_WRITTEN = D * HEADS * (NOPE + ROPE) + D * (KV_RANK + ROPE) + HEADS * VAL * D  # q, kv_a, o
MLA_UKV = KV_RANK * HEADS * (NOPE + VAL)
MLA = MLA_WRITTEN + MLA_UKV + KV_RANK  # and the latent's norm
DENSE_MLP = 3 * D * 9216
EXPERT = 3 * D * 1024
ROUTER = D * 256

REDUCED = {"num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"}
# the catalog beside the `model-configs` guide: mounted where PRs are written, not everywhere
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_benchmark_json_names_the_keys_the_configuration_says_it_reduced():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "kimi-linear-48b-a3b")
    assert set(entry["reduced"]) == set(HF["reduced"]) == REDUCED and entry["source"] == HF["source"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert HF["reduced"][key]["run"] == HF[key] != HF["reduced"][key]["published"]
    lists = HF["reduced"]["linear_attn_config"]
    for name in ("kda_layers", "full_attn_layers"):
        assert lists["run"][name] == HF["linear_attn_config"][name]
        assert lists["run"][name] == [layer for layer in lists["published"][name] if layer <= 9]
    # one cell, on one chip, listed by the three new readers and by three that were there
    (cell,) = [w for w in bench["workloads"] if w["config"] == "kimi-linear-48b-a3b"]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (CELL, 1, "ppo-longgen-b32")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"kda_chunk_roofline", "kda_decode_roofline", "kda_share", "score_device_share",
                      "moe_experts_roofline", "expert_load_max_over_mean"}
    for m in bench["per_layer"]:
        if m["name"].startswith("kda_"):
            assert m["workloads"] == [CELL] and m["moves"] == "samples_per_s"


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the model catalog is not mounted here")
def test_the_configuration_keeps_every_published_number_but_the_reduced_ones():
    with open(CATALOG) as f:
        published = next(row for row in map(json.loads, f) if row["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert HF["source"] == published["source_url"]
    assert {k for k, v in published["config"].items() if HF.get(k) != v} == REDUCED
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert HF["reduced"][key]["published"] == published["config"][key]
    group, run = published["config"]["linear_attn_config"], HF["linear_attn_config"]
    assert HF["reduced"]["linear_attn_config"]["published"] == {
        k: group[k] for k in ("kda_layers", "full_attn_layers")}
    # no width of the nested group changed: heads, head size and taps as published
    assert {k: run[k] for k in ("head_dim", "num_heads", "short_conv_kernel_size")} == {
        k: group[k] for k in ("head_dim", "num_heads", "short_conv_kernel_size")}


def test_parameters_held_are_the_issues_hand_counts():
    held = kimi_linear_ref.params_held(HF)
    assert held["kda_mixer"] == KDA and round(KDA / 1e6, 2) == 39.51
    assert held["mla_mixer"] == MLA and round(MLA / 1e6, 2) == 29.11
    experts = ROUTER + 256 + (8 + 1) * EXPERT
    assert held["leading_layer"] == KDA + 2 * D + DENSE_MLP
    assert held["routed_kda_layer"] == KDA + 2 * D + experts
    assert held["routed_mla_layer"] == MLA + 2 * D + experts
    assert [round(held[k] / 1e6, 1) for k in ("leading_layer", "routed_kda_layer", "routed_mla_layer",
                                              "embed_and_head")] == [103.2, 103.8, 93.4, 94.4]
    assert round(8 * EXPERT / 1e6, 1) == 56.6
    assert held["total"] == (held["leading_layer"] + 6 * held["routed_kda_layer"]
                             + 2 * held["routed_mla_layer"] + 2 * 20480 * D + D)
    assert round(held["total"] / 1e9, 3) == 1.007


def test_work_counts_what_a_token_a_pair_and_a_cached_position_cost_here():
    w = kimi_linear_ref.work(HF)
    lead, kda, mla = w["layers"][0], w["layers"][1], w["layers"][3]
    assert len(w["layers"]) == 9 and w["leading"] == 1
    assert [layer["cache_elems"] for layer in w["layers"]] == [0, 0, 0, 576, 0, 0, 0, 576, 0]
    assert w["layers"][7] == mla and all(w["layers"][i] == kda for i in (2, 4, 5, 6, 8))
    assert "routed" not in lead and kda["pair_flops"] == lead["pair_flops"] == 0
    assert mla["pair_flops"] == 20_480  # 2 x 32 heads x (192-wide score + 128-wide value)
    # a KDA layer: its eight projections and the recurrence's three products a head
    recurrence = 6 * KH * KD * KD
    assert lead["linear_flops"] == 2 * (KDA_WRITTEN + KDA_SMALL + DENSE_MLP) + recurrence
    assert kda["linear_flops"] == 2 * (KDA_WRITTEN + KDA_SMALL + EXPERT + ROUTER) + recurrence
    assert mla["linear_flops"] == 2 * (MLA_WRITTEN + MLA_UKV + EXPERT + ROUTER)
    assert kda["routed"] == mla["routed"] == {"expert_flops": 2 * EXPERT, "expert_elems": EXPERT,
                                              "published": 256, "held": 8, "per_token": 8}
    assert w["head"] == {"flops": 2 * D * 20480, "weight_elems": D * 20480}
    # the decode step reads int8 what `quantize_decode_weights` rewrites (q, k, v, o of a KDA
    # layer; q, kv_a, o of an MLA layer; the shared expert); the low-rank pairs and W_ukv stay
    # bf16 (two bytes an element), the taps, gates' vectors and the router float32 (four)
    assert kda["weight_elems"] == KDA_WRITTEN + 2 * KDA_SMALL + 4 * KDA_VECTORS + EXPERT + 4 * ROUTER
    assert mla["weight_elems"] == MLA_WRITTEN + 2 * MLA_UKV + EXPERT + 4 * ROUTER
    # a token meets 8 x 8 / 256 = a quarter of a held expert a routed layer
    layers = (lead["linear_flops"] + 6 * (kda["linear_flops"] + 0.25 * 2 * EXPERT)
              + 2 * (mla["linear_flops"] + 0.25 * 2 * EXPERT))
    assert flops.causal_forward_flops(flops.work(kimi_linear_ref, HF), 1) == layers + 20_480 * 2
    assert flops.trainable_layers(flops.work(kimi_linear_ref, HF), 2) == 2
    # what a decode step at 32 rows must read without the recurrent state, which
    # `flops.decode_step_bytes` cannot express: 0.9 GB of weights (the experts 32 rows are
    # expected to reach among them) and 21 MB of latent rows at 576 positions; the state
    # is 0.94 GB more
    step = flops.decode_step_bytes(flops.work(kimi_linear_ref, HF), 32, 576, 1, 2, 2)
    assert 0.90e9 < step < 0.95e9


def test_toy_sizes_keep_every_mechanism():
    toy = dict(HF, **kimi_linear_ref.toy_sizes(HF))
    w = kimi_linear_ref.work(toy)
    assert w["leading"] == 1 and len(w["layers"]) == 5 and "routed" in w["layers"][-1]
    assert toy["num_experts"] < toy["num_experts_published"]  # a share, still
    kw = kimi_linear_ref.system_config(toy)
    assert kw["mixer_layers"] == ("delta", "delta", "delta", "latent", "delta") and kw["pos_embed"] == "none"
    assert kw["q_lora_rank"] is None and kw["delta_conv"] == 4
    with pytest.raises(ValueError, match="both or in neither"):
        kimi_linear_ref.mixers(dict(toy, num_hidden_layers=6))


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
    assert {"logprob_rms", "tie_logprob_rms", "decisive_share_at_least", "sampler_surprise"} <= set(line["compared"])
    assert line["compared"]["logprob_rms"][0] < line["compared"]["logprob_rms"][1]
    assert line["compared"]["sampler_surprise"][0] < line["compared"]["sampler_surprise"][1]
    # the flight stream's counters reach the readers (no device seconds by scope on a CPU)
    assert {"expert_load_max_over_mean", "hbm_peak_gib"} <= set(line["metrics"])
    assert not {"latent_decode_attn_roofline", "latent_flash_fwd_roofline"} & set(line["metrics"])


# -- the readers on a hand-made Reading --------------------------------------


def reading(cell_name=CELL, trace=None, flight=()):
    cell = cells.load_cell(cell_name)
    return SimpleNamespace(
        cell=cell, hf=cell.config, traffic=cell.traffic, chips=1,
        peaks=cells.peaks_for("TPU v5 lite"), unfrozen=2, trace=trace, flight=list(flight),
        cycles=[{"step": 32, "wall_s": 10.0}], wall_s=10.0, run_dir=None)


TRACE = {
    "busy_s": 9.5,
    "scopes_by_self_time": [
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/attn/kda_step", 3.0],
        ["jit(generate)/prefill/while/body/closed_call/Block/attn/kda_chunk/while/body", 0.1],
        ["jit(fused_train_step)/while/body/checkpoint/Block/attn/kda_chunk/while/body/checkpoint", 0.9],
        ["jit(fused_train_step)/transpose(jvp(kda_chunk))/while/body", 0.4],
        ["jit(ppo_experience_fwd)/Block/attn/kda_chunk/while/body", 0.2],
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/attn/kda_conv", 0.3],
        ["jit(fused_train_step)/Block/attn/kda_gate", 0.25],
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/attn/latent_decode_attn", 0.05],
        ["jit(fused_train_step)/Block/moe/moe_experts", 0.5],
        ["jit(generate)/while/body/decode_step/kda_step_v2", 9.0],  # another scope
    ],
    "ops_by_self_time": [["%flash_fwd.3 custom-call (bf16[256,1024,128], f32[256,1024,1])", 0.05]],
}


def test_readers_count_by_hand_and_stay_under_100(capsys):
    r = reading(trace=TRACE)
    peak, hbm = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    assert _kda.layers(r)[:2] == (7, 1)  # seven KDA layers run; the top 2 hold one of them

    # a token and a KDA layer: 6 x 128 x 128 FLOPs a head; q, k, v, o in 2 bytes, g and beta in 4
    tok_flops, tok_bytes = 6 * 32 * 128 * 128, 32 * (4 * 128 * 2 + 128 * 4 + 4)
    assert _kda.token_work(r.hf["linear_attn_config"]) == {"flops": tok_flops, "bytes": tok_bytes}
    # prefill 32 x 128 over 7 layers; the scorer 32 x 1024 over 7 and the reference branch's 1;
    # 16 train steps of 8 x 1024: the forward over 7 and the backward, twice that, over 1
    tokens = 32 * 128 * 7 + 32 * 1024 * (7 + 1) + 16 * 8 * 1024 * (7 + 2)
    least = max(tokens * tok_flops / peak, tokens * tok_bytes / hbm)
    assert least == tokens * tok_bytes / hbm  # bound by its bytes
    assert kda_chunk_roofline.read(r) == pytest.approx(100 * least / (0.1 + 0.9 + 0.4 + 0.2))

    # a decode step and a KDA layer: 32 rows x (32 x 128 x 128 float32 of state and 3 x 12,288
    # bf16 convolution inputs), read once and written once; 895 steps, 7 layers
    row = 4 * 32 * 128 * 128 + 2 * 3 * 12288
    assert 2 * 4 * 32 * 128 * 128 * 32 * 7 == 939_524_096  # the ISSUE's 0.94 GB of state a step
    assert 2 * row * 32 * 7 == 972_554_240  # with the convolutions' inputs
    assert kda_decode_roofline.read(r) == pytest.approx(100 * 2 * row * 32 * 895 * 7 / hbm / 3.0)

    assert kda_share.read(r) == pytest.approx(100 * (3.0 + 1.6 + 0.3 + 0.25) / 9.5)
    assert "kda_conv 0.3000 s, kda_gate 0.2500 s, kda_chunk 1.6000 s, kda_step 3.0000 s" in capsys.readouterr().out
    for module in READERS:
        assert 0 < module.read(r) < 100, module.__name__


@pytest.mark.parametrize("module", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_readers_read_nothing_where_there_is_nothing(module):
    # no trace (a `--trace 0` run)
    assert module.read(reading()) is None
    # a program without the scopes (the parent)
    bare = {"busy_s": 6.5, "scopes_by_self_time": [["jit(generate)/while/body/decode_step", 1.0]],
            "ops_by_self_time": [["%fusion.1 fusion kOutput bf16[8,1024,2304]", 0.4]]}
    assert module.read(reading(trace=bare)) is None
    # another family's reading, even with such a scope in its trace
    other = dict(bare, scopes_by_self_time=[["jit(generate)/while/body/decode_step/kda_step", 1.0],
                                            ["jit(fused_train_step)/kda_chunk", 1.0]])
    if module is not kda_share:  # a share of busy time needs only the scopes
        assert module.read(reading("xing4.0-29b-a4b.ppo-dialogue-b32", trace=other)) is None
        assert module.read(reading("pythia-1.4b.ppo-longprompt", trace=other)) is None


@pytest.mark.parametrize("module", [latent_decode_attn_roofline, latent_flash_fwd_roofline,
                                    latent_flash_bwd_roofline], ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_the_three_latent_readers_are_not_listed_for_this_cell(module):
    """They multiply ONE layer's attention by every layer of the stack: where 2 of 9
    layers are latent they would read 4.5 times too high. `BENCHMARK.json` keeps the
    cell off their lists, so the harness never calls them here."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == module.__name__.rsplit(".", 1)[-1])
    assert CELL not in entry["workloads"]
    assert module.__name__.rsplit(".", 1)[-1] not in {m["name"] for m in cells.load_cell(CELL).per_layer}
