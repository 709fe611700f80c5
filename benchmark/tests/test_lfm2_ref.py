"""The LFM2-MoE family's file: its configuration against the catalog, its work and the
parameters it holds against counts made by hand from the published keys, its toy sizes,
and its two metric readers on a hand-made `Reading` (a number under 100 where there is
something to read, None on another family's reading or a program without the scopes)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import cells, flops
from benchmark.layer_metrics import _conv, conv_mix_roofline, conv_share
from benchmark.reference import lfm2_ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "lfm2-24b-a2b"
CELL = CONFIG + ".ppo-hh-b32"
READERS = [conv_mix_roofline, conv_share]

with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as _f:
    HF = json.load(_f)

# -- the layers by hand, from the published keys ------------------------------
D, HEADS, KV, HD, TAPS = 2048, 32, 8, 64, 3
CONV = 3 * D * D + D * D + TAPS * D  # W_in, W_out, the taps
ATTENTION = 2 * D * HEADS * HD + 2 * D * KV * HD + 2 * HD  # q and o; k and v; the QK norms
EXPERT = 3 * D * 1536
ROUTER = D * 64 + 64  # the gate and the selection bias
DENSE = 3 * D * 11776

REDUCED = {"num_experts", "vocab_size", "num_hidden_layers", "layer_types", "num_dense_layers"}
# the published config.json of LiquidAI/LFM2-24B-A2B, as the model catalog records it
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
PUBLISHED = {
    "conv_L_cache": 3,
    "conv_bias": False,
    "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"],
    "max_position_embeddings": 128000,
    "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_dense_layers": 2,
    "num_experts": 64,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "vocab_size": 65536,
}


def test_benchmark_json_names_the_keys_the_configuration_says_it_reduced():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(HF["reduced"]) == REDUCED and entry["source"] == HF["source"]
    for key in REDUCED:
        assert HF["reduced"][key]["run"] == HF[key] != HF["reduced"][key]["published"]
    # the leading layer, then published layers 2-13 in their order; or by the rule 2-9
    published = HF["reduced"]["layer_types"]["published"]
    assert HF["layer_types"] in (["conv"] + published[2:14], ["conv"] + published[2:10])
    assert len(HF["layer_types"]) == HF["num_hidden_layers"] and HF["layer_types"][-2:] == ["conv", "conv"]
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (CELL, 1, "ppo-hh-b32")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"conv_share", "conv_mix_roofline", "score_device_share", "moe_experts_roofline",
                      "expert_load_max_over_mean", "optimizer_update_share"}
    for m in bench["per_layer"]:
        if m["name"].startswith("conv_"):
            assert m["workloads"] == [CELL] and m["moves"] == "samples_per_s"
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    # the mix: HH-shaped, 32 rollouts of 896 + 128 as one decode batch, 4 epochs at batch 8
    t = cells.load_cell(CELL).traffic
    assert (t["prompt_tokens"], t["new_tokens"], t["rollouts"], t["chunk"], t["batch"]) == (896, 128, 32, 32, 8)
    assert t["method_kwargs"]["ppo_epochs"] == 4 and t["gen_kwargs"]["eos_token_id"] == -1


def test_the_configuration_keeps_every_published_number_but_the_reduced_ones():
    assert HF["source"] == SOURCE
    assert {k for k, v in PUBLISHED.items() if HF.get(k) != v} == REDUCED
    for key in REDUCED:
        assert HF["reduced"][key]["published"] == PUBLISHED[key]
    assert HF["num_experts_published"] == 64 and HF["vocab_size_published"] == 65536
    assert "tie_word_embeddings" not in PUBLISHED and HF["tie_word_embeddings"] is True


def test_parameters_held_are_the_issues_hand_counts():
    held = lfm2_ref.params_held(HF)
    assert held["conv_mixer"] == CONV and round(CONV / 1e6, 2) == 16.78
    assert held["attention_mixer"] == ATTENTION and round(ATTENTION / 1e6, 2) == 10.49
    assert held["routed_expert"] == EXPERT and round(EXPERT / 1e6, 2) == 9.44
    assert held["router"] == ROUTER and round(ROUTER / 1e6, 2) == 0.13
    assert held["dense_mlp"] == DENSE and round(DENSE / 1e6, 2) == 72.35
    assert held["dense_layer"] == CONV + DENSE + 2 * D and round(held["dense_layer"] / 1e6, 1) == 89.1
    routed = ROUTER + 8 * EXPERT
    assert held["conv_routed_layer"] == CONV + routed + 2 * D and round(held["conv_routed_layer"] / 1e6, 1) == 92.4
    assert held["attention_routed_layer"] == ATTENTION + routed + 2 * D
    assert round(held["attention_routed_layer"] / 1e6, 1) == 86.1
    assert held["embed_and_head"] == 8192 * D and round(held["embed_and_head"] / 1e6, 1) == 16.8
    kinds = HF["layer_types"]
    assert held["total"] == (held["dense_layer"] + (kinds.count("conv") - 1) * held["conv_routed_layer"]
                             + kinds.count("full_attention") * held["attention_routed_layer"]
                             + held["embed_and_head"] + D)
    assert round(held["total"] / 1e9, 3) == {13: 1.196, 9: 0.833}[len(kinds)]
    # the other plan of the depth rule, and the uncut routed layer no chip holds (served in bf16)
    nine = dict(HF, num_hidden_layers=9, layer_types=HF["layer_types"][:9])
    assert round(lfm2_ref.params_held(nine)["total"] / 1e9, 2) == 0.83
    assert round((CONV + ROUTER + 64 * EXPERT + 2 * D) / 1e6) == 621
    assert round(64 * EXPERT / 1e6) == 604


def test_work_counts_what_a_token_a_pair_and_a_cached_position_cost_here():
    w = lfm2_ref.work(HF)
    kinds = HF["layer_types"]
    assert len(w["layers"]) == len(kinds) and w["leading"] == 1
    assert [layer["cache_elems"] for layer in w["layers"]] == [
        2 * KV * HD if kind == "full_attention" else 0 for kind in kinds]
    lead, conv, attention = w["layers"][0], w["layers"][-1], w["layers"][kinds.index("full_attention")]
    written = 4 * D * D
    assert lead["linear_flops"] == 2 * written + 2 * DENSE and "routed" not in lead
    assert conv["linear_flops"] == 2 * written + 2 * D * 64 and conv["pair_flops"] == 0
    assert attention["linear_flops"] == 2 * (2 * D * HEADS * HD + 2 * D * KV * HD) + 2 * D * 64
    assert attention["pair_flops"] == 4 * HEADS * HD
    assert conv["routed"] == {"expert_flops": 2 * EXPERT, "expert_elems": EXPERT,
                              "published": 64, "held": 8, "per_token": 4}
    assert w["head"] == {"flops": 2 * D * 8192, "weight_elems": D * 8192}
    # the decode step reads int8 what `quantize_decode_weights` rewrites; the taps, the QK
    # norms and the router stay float32 (four bytes an element)
    assert conv["weight_elems"] == written + 4 * TAPS * D + 4 * D * 64
    assert flops.trainable_layers(w, 2) == 2
    # a token meets 4 x 8 / 64 of a held expert a routed layer
    met = 4 * 8 / 64
    assert flops.layer_linear_flops(conv) == conv["linear_flops"] + met * 2 * EXPERT


def test_toy_sizes_keep_every_mechanism():
    toy = dict(HF, **lfm2_ref.toy_sizes(HF))
    w = lfm2_ref.work(toy)
    assert len(w["layers"]) == 7 and w["leading"] == 1 and "routed" in w["layers"][-1]
    assert w["layers"][1]["cache_elems"] > 0 and w["layers"][-1]["cache_elems"] == 0
    assert toy["num_experts"] < toy["num_experts_published"] and toy["num_experts_per_tok"] < toy["num_experts"]
    kw = lfm2_ref.system_config(toy)
    assert kw["mixer_layers"] == ("conv", "softmax", "conv", "conv", "softmax", "conv", "conv")
    assert kw["n_kv_head"] < kw["n_head"] and kw["qk_norm"] and kw["first_k_dense"] == 1
    assert kw["mixer_layers"][-2:] == ("conv", "conv")  # the top-2 branch is conv + routed twice
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_ref.layer_types(dict(toy, num_hidden_layers=6))
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_ref.layer_types(dict(toy, layer_types=["conv", "mamba"] + toy["layer_types"][2:]))


# -- the readers on a hand-made Reading --------------------------------------


def reading(cell_name=CELL, trace=None, flight=()):
    cell = cells.load_cell(cell_name)
    return SimpleNamespace(
        cell=cell, hf=cell.config, traffic=cell.traffic, chips=1,
        peaks=cells.peaks_for("TPU v5 lite"), unfrozen=2, trace=trace, flight=list(flight),
        cycles=[{"step": 32, "wall_s": 3.0}], wall_s=3.0, run_dir=None)


TRACE = {
    "busy_s": 3.0,
    "scopes_by_self_time": [
        ["jit(generate)/prefill/while/body/closed_call/Block/conv/conv_mix/conv_proj/in_proj", 0.04],
        ["jit(generate)/prefill/while/body/closed_call/Block/conv/conv_mix", 0.002],
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/conv/conv_step/conv_proj/in_proj", 0.02],
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/conv/conv_step", 0.003],
        ["jit(fused_train_step)/while/body/checkpoint/Block/conv/conv_mix/conv_proj/in_proj", 0.08],
        ["jit(fused_train_step)/transpose(jvp())/checkpoint/Block/conv/conv_mix/conv_proj/out_proj", 0.1],
        ["jit(fused_train_step)/transpose(jvp(conv_mix))", 0.03],
        ["jit(ppo_experience_fwd)/Block/conv/conv_mix/conv_proj/in_proj", 0.05],
        ["jit(ppo_experience_fwd)/Block/conv/conv_mix/conv_proj/out_proj", 0.028],
        ["jit(fused_train_step)/Block/moe/moe_experts", 0.5],
        ["jit(generate)/while/body/decode_step/conv_mix_v2", 9.0],  # another scope
    ],
    "ops_by_self_time": [["%flash_fwd.3 custom-call (bf16[256,1024,64], f32[256,1024,1])", 0.05]],
}


def test_readers_count_by_hand_and_stay_under_100(capsys):
    r = reading(trace=TRACE)
    peak = r.peaks["bf16_flops_per_s"]
    assert _conv.layers(r) == (10, 2)  # the conv layers run; the top 2 are both
    # a token and a conv layer: W_in (d -> 3d) and W_out (d -> d), 8 d^2 FLOPs; x read and
    # the output written, 2 d numbers of 2 bytes: bound by the FLOPs by far
    assert _conv.token_work(r.hf) == {"flops": 8 * 2048 * 2048, "bytes": 2 * 2 * 2048}
    # prefill 32 x 896 over the 10; the scorer 32 x 1024 over them and the branch's 2; the
    # trunk's 8 once a block over the 32 rollouts; 16 steps of 8 x 1024 forward and backward
    # (three forwards' worth) over the 2
    tokens = 32 * 896 * 10 + 32 * 1024 * 12 + 32 * 1024 * 8 + 16 * 8 * 1024 * 3 * 2
    least = tokens * 8 * 2048 * 2048 / peak
    assert least == pytest.approx(0.2944, abs=1e-4)
    teacher_forced = 0.04 + 0.002 + 0.08 + 0.1 + 0.03 + 0.05 + 0.028
    assert conv_mix_roofline.read(r) == pytest.approx(100 * least / teacher_forced)
    # every instruction of the mixers once, the nested projections with them
    assert conv_share.read(r) == pytest.approx(100 * (teacher_forced + 0.02 + 0.003) / 3.0)
    out = capsys.readouterr().out
    assert "conv_proj 0.3180 s, conv_mix 0.3300 s, conv_step 0.0230 s; together 0.3530 s" in out
    for module in READERS:
        assert 0 < module.read(r) < 100, module.__name__


@pytest.mark.parametrize("module", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_readers_read_nothing_where_there_is_nothing(module):
    # no trace (a `--trace 0` run)
    assert module.read(reading()) is None
    # a program without the scopes (the parent)
    bare = {"busy_s": 6.5, "scopes_by_self_time": [["jit(generate)/while/body/decode_step", 1.0]],
            "ops_by_self_time": [["%fusion.1 fusion kOutput bf16[8,1024,2048]", 0.4]]}
    assert module.read(reading(trace=bare)) is None
    # another family's reading, even with such a scope in its trace
    other = dict(bare, scopes_by_self_time=[["jit(fused_train_step)/conv_mix", 1.0]])
    if module is not conv_share:  # a share of busy time needs only the scopes
        assert module.read(reading("nemotron-3-super-120b-a12b.ppo-longgen-b32", trace=other)) is None
        assert module.read(reading("pythia-1.4b.ppo-longprompt", trace=other)) is None
