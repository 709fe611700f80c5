"""The Qwen3-Next family's file: its configuration against the published config, its work
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
from benchmark.layer_metrics import _gdn, _kda, gdn_chunk_roofline, gdn_decode_roofline, gdn_share
from benchmark.reference import qwen3_next_ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "qwen3-next-80b-a3b.ppo-longgen-b32"
READERS = [gdn_chunk_roofline, gdn_decode_roofline, gdn_share]

with open(os.path.join(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.json")) as _f:
    HF = json.load(_f)

# -- the layers by hand, from the published keys ------------------------------
D, HEADS, KV, HD = 2048, 16, 2, 256
KH, VH, KD = 16, 32, 128
CONV = (2 * KH + VH) * KD  # 8192: q, k and v
GDN_WRITTEN = D * (CONV + VH * KD) + VH * KD * D  # W_qkvz, W_out
GDN_BA = 2 * D * VH
GDN_VECTORS = 4 * CONV + 2 * VH + KD  # the taps, A_log, dt_bias, the output norm
ATTENTION = D * HD * (2 * HEADS + 2 * KV) + HEADS * HD * D  # q with its gate, k, v, o
EXPERT = 3 * D * 512
SHARED = 3 * D * 512 + D  # and its gate
ROUTER = D * 512

REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}
# the published config.json's keys that size the model (the source's numbers, as read)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}


def test_benchmark_json_names_the_keys_the_configuration_says_it_reduced():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3-next-80b-a3b")
    assert set(entry["reduced"]) == set(HF["reduced"]) == REDUCED and entry["source"] == HF["source"]
    for key in REDUCED:
        assert HF["reduced"][key]["run"] == HF[key] != HF["reduced"][key]["published"]
    # one cell, on one chip, listed by the three new readers and by four that were there
    (cell,) = [w for w in bench["workloads"] if w["config"] == "qwen3-next-80b-a3b"]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (CELL, 1, "ppo-longgen-b32")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"gdn_chunk_roofline", "gdn_decode_roofline", "gdn_share", "score_device_share",
                      "moe_experts_roofline", "expert_load_max_over_mean", "optimizer_update_share"}
    for m in bench["per_layer"]:
        if m["name"].startswith("gdn_"):
            assert m["workloads"] == [CELL] and m["moves"] == "samples_per_s"


def test_the_configuration_keeps_every_published_number_but_the_reduced_ones():
    assert HF["source"] == "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    assert {k for k, v in PUBLISHED.items() if HF.get(k) != v} == REDUCED
    for key in REDUCED:
        assert HF["reduced"][key]["published"] == PUBLISHED[key]


def test_parameters_held_are_the_issues_hand_counts():
    held = qwen3_next_ref.params_held(HF)
    gdn = GDN_WRITTEN + GDN_BA + GDN_VECTORS
    assert held["gdn_mixer"] == gdn and round(gdn / 1e6, 2) == 33.72
    assert held["attention_mixer"] == ATTENTION + 2 * HD and round(ATTENTION / 1e6, 2) == 27.26
    assert held["router_and_shared"] == ROUTER + SHARED and round((ROUTER + SHARED) / 1e6, 2) == 4.20
    assert held["routed_expert"] == EXPERT and round(EXPERT / 1e6, 3) == 3.146
    assert held["embed_and_head"] == 2 * 18992 * D and round(held["embed_and_head"] / 1e6, 1) == 77.8
    layer = ROUTER + SHARED + 32 * EXPERT + 2 * D
    assert held["total"] == 6 * (gdn + layer) + 2 * (ATTENTION + 2 * HD + layer) + 2 * 18992 * D + D
    assert round(held["total"] / 1e9, 2) == 1.17
    published = dict(HF, num_hidden_layers=48, num_experts=512, vocab_size=151936)
    assert round(qwen3_next_ref.params_held(published)["total"] / 1e9, 1) == 79.7


def test_work_counts_what_a_token_a_pair_and_a_cached_position_cost_here():
    w = qwen3_next_ref.work(HF)
    gdn, att = w["layers"][0], w["layers"][3]
    assert len(w["layers"]) == 8 and w["leading"] == 0
    assert [layer["cache_elems"] for layer in w["layers"]] == [0, 0, 0, 1024, 0, 0, 0, 1024]
    assert all(w["layers"][i] == gdn for i in (1, 2, 4, 5, 6)) and w["layers"][7] == att
    assert gdn["pair_flops"] == 0 and att["pair_flops"] == 2 * 16 * (256 + 256)
    recurrence = 6 * 32 * 128 * 128
    assert gdn["linear_flops"] == 2 * (GDN_WRITTEN + GDN_BA + ROUTER + SHARED) + recurrence
    assert att["linear_flops"] == 2 * (ATTENTION + ROUTER + SHARED)
    assert gdn["routed"] == att["routed"] == {"expert_flops": 2 * EXPERT, "expert_elems": EXPERT,
                                              "published": 512, "held": 32, "per_token": 10}
    assert w["head"] == {"flops": 2 * D * 18992, "weight_elems": D * 18992}
    # int8 what `quantize_decode_weights` rewrites (W_qkvz, W_out; q, k, v, o; the shared
    # expert's three matrices); W_ba and the shared gate bf16, the vectors and router float32
    assert gdn["weight_elems"] == GDN_WRITTEN + 2 * GDN_BA + 4 * GDN_VECTORS + 4 * ROUTER + SHARED - D + 2 * D
    assert att["weight_elems"] == ATTENTION + 4 * 2 * HD + 4 * ROUTER + SHARED - D + 2 * D
    # a token meets 10 x 32 / 512 = 0.625 of a held expert a layer
    layers = 6 * gdn["linear_flops"] + 2 * att["linear_flops"] + 8 * 0.625 * 2 * EXPERT
    assert flops.causal_forward_flops(flops.work(qwen3_next_ref, HF), 1) == layers + 16_384 * 2
    assert flops.trainable_layers(flops.work(qwen3_next_ref, HF), 2) == 2


def test_toy_sizes_keep_every_mechanism():
    toy = dict(HF, **qwen3_next_ref.toy_sizes(HF))
    kw = qwen3_next_ref.system_config(toy)
    assert kw["mixer_layers"] == ("delta", "softmax", "delta", "softmax")
    assert kw["delta_value_heads"] == 2 * kw["delta_heads"] and kw["rotary_dim"] == 4
    assert toy["num_experts"] < toy["num_experts_published"]  # a share, still
    assert kw["router_score"] == "softmax" and kw["shared_expert_gate"] and kw["norm_zero_centred"]
    with pytest.raises(NotImplementedError, match="dense layers"):
        qwen3_next_ref.system_config(dict(toy, mlp_only_layers=[0]))


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
    assert {"expert_load_max_over_mean", "hbm_peak_gib"} <= set(line["metrics"])
    assert "router gates balanced on 32 prompts" in p.stdout + p.stderr


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
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/attn/gdn_step", 1.2],
        ["jit(generate)/prefill/while/body/closed_call/Block/attn/gdn_chunk/while/body", 0.1],
        ["jit(fused_train_step)/while/body/checkpoint/Block/attn/gdn_chunk/while/body/checkpoint", 0.9],
        ["jit(fused_train_step)/transpose(jvp(gdn_chunk))/while/body", 0.4],
        ["jit(ppo_experience_fwd)/Block/attn/gdn_chunk/while/body", 0.2],
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/attn/gdn_conv", 0.3],
        ["jit(fused_train_step)/Block/attn/gdn_gate", 0.25],
        ["jit(fused_train_step)/Block/attn/attn_out_gate", 0.05],
        ["jit(fused_train_step)/Block/moe/moe_experts", 0.5],
        ["jit(generate)/while/body/decode_step/kda_step", 9.0],  # another family's scope
    ],
    "ops_by_self_time": [["%flash_fwd.3 custom-call (bf16[256,1024,256], f32[256,1024,1])", 0.05]],
}


def test_readers_count_by_hand_and_stay_under_100(capsys):
    r = reading(trace=TRACE)
    peak, hbm = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    assert _gdn.layers(r) == (6, 1)  # six GDN layers run; the top 2 hold one of them
    # a token and a GDN layer: 6 x 128 x 128 FLOPs a value head; q, k (16 heads) and v,
    # o (32) in 2 bytes; g and beta one a value head in 4
    tok_flops, tok_bytes = 6 * 32 * 128 * 128, 2 * (2 * 16 * 128 + 2 * 32 * 128) + 4 * 2 * 32
    assert _gdn.token_work(r.hf) == {"flops": tok_flops, "bytes": tok_bytes}
    # prefill 32 x 128 over 6 layers; the scorer 32 x 1024 over 6 and the reference branch's 1;
    # 16 train steps of 8 x 1024: the forward over 6 and the backward, twice that, over 1
    tokens = 32 * 128 * 6 + 32 * 1024 * (6 + 1) + 16 * 8 * 1024 * (6 + 2)
    least = max(tokens * tok_flops / peak, tokens * tok_bytes / hbm)
    assert gdn_chunk_roofline.read(r) == pytest.approx(100 * least / (0.1 + 0.9 + 0.4 + 0.2))
    # a decode step and a GDN layer: 32 rows x (32 x 128 x 128 float32 of state and 3 x 8,192
    # bf16 convolution inputs), read once and written once; 895 steps, 6 layers
    row = 4 * 32 * 128 * 128 + 2 * 3 * 8192
    assert 2 * 4 * 32 * 128 * 128 * 32 * 6 == 805_306_368  # 0.40 GB read and 0.40 GB written a step
    assert gdn_decode_roofline.read(r) == pytest.approx(100 * 2 * row * 32 * 895 * 6 / hbm / 1.2)
    assert gdn_share.read(r) == pytest.approx(100 * (1.2 + 1.6 + 0.3 + 0.25) / 9.5)
    assert "gdn_conv 0.3000 s, gdn_gate 0.2500 s, gdn_chunk 1.6000 s, gdn_step 1.2000 s" in capsys.readouterr().out
    for module in READERS:
        assert 0 < module.read(r) < 100, module.__name__
    assert _kda.layers(r) is None  # the KDA readers find nothing of theirs here


@pytest.mark.parametrize("module", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_readers_read_nothing_where_there_is_nothing(module):
    # no trace (a `--trace 0` run)
    assert module.read(reading()) is None
    # a program without the scopes (the parent)
    bare = {"busy_s": 6.5, "scopes_by_self_time": [["jit(generate)/while/body/decode_step", 1.0]],
            "ops_by_self_time": [["%fusion.1 fusion kOutput bf16[8,1024,2048]", 0.4]]}
    assert module.read(reading(trace=bare)) is None
    # another family's reading, even with such a scope in its trace
    other = dict(bare, scopes_by_self_time=[["jit(generate)/while/body/decode_step/gdn_step", 1.0],
                                            ["jit(fused_train_step)/gdn_chunk", 1.0]])
    if module is not gdn_share:  # a share of busy time needs only the scopes
        assert module.read(reading("kimi-linear-48b-a3b.ppo-longgen-b32", trace=other)) is None
        assert module.read(reading("pythia-1.4b.ppo-longprompt", trace=other)) is None
