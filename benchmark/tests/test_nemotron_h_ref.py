"""The Nemotron-H family's file: its configuration against the catalog, its work
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
    _ssm, flash_fwd_roofline, kda_chunk_roofline, latent_decode_attn_roofline, ssm_chunk_roofline,
    ssm_decode_roofline, ssm_share)
from benchmark.reference import nemotron_h_ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = "nemotron-3-super-120b-a12b"
CELL = CONFIG + ".ppo-longgen-b32"
READERS = [ssm_chunk_roofline, ssm_decode_roofline, ssm_share]

with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as _f:
    HF = json.load(_f)

# -- the layers by hand, from the published keys ------------------------------
D, H, P, G, N, TAPS = 4096, 128, 64, 8, 128, 4
INNER = H * P  # 8192
CONV = INNER + 2 * G * N  # 10240: x', B and C
W_IN = D * (INNER + CONV + H)  # 4096 x 18,560
W_OUT = INNER * D
SSM_VECTORS = CONV * (TAPS + 1) + 3 * H + INNER  # taps and bias; A_log, D, dt_bias; the gated norm
ATTENTION = 2 * D * 32 * 128 + 2 * D * 2 * 128  # q and o; k and v
ROUTER, LATENT_PAIR, SHARED = D * 512, 2 * D * 1024, 2 * D * 5376
EXPERT = 2 * 1024 * 2688

REDUCED = {"n_routed_experts", "vocab_size", "num_hidden_layers", "hybrid_override_pattern",
           "num_nextn_predict_layers"}
# the catalog beside the `model-configs` guide: mounted where PRs are written, not everywhere
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_benchmark_json_names_the_keys_the_configuration_says_it_reduced():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(HF["reduced"]) == REDUCED and entry["source"] == HF["source"]
    for key in REDUCED:
        assert HF["reduced"][key]["run"] == HF[key] != HF["reduced"][key]["published"]
    # layers 1-11 of the published pattern, nothing re-ordered; or by the rule 3-11
    published = HF["reduced"]["hybrid_override_pattern"]["published"]
    assert HF["hybrid_override_pattern"] in (published[:11], published[2:11])
    assert len(HF["hybrid_override_pattern"]) == HF["num_hidden_layers"]
    assert HF["hybrid_override_pattern"].endswith("*EME")  # the top two: one `M`, one `E`
    # one cell, on one chip, listed by the three new readers and by three that were there
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (CELL, 1, "ppo-longgen-b32")
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [])}
    assert listed == {"ssm_chunk_roofline", "ssm_decode_roofline", "ssm_share", "score_device_share",
                      "moe_experts_roofline", "expert_load_max_over_mean"}
    for m in bench["per_layer"]:
        if m["name"].startswith("ssm_"):
            assert m["workloads"] == [CELL] and m["moves"] == "samples_per_s"
    # the form the driver holds an entry to before any run (a `why` of 202 characters
    # refused this PR once): one line of 1 to 200 printable characters, just the keys shown
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    # both plans for the depth stand in the configuration's file, the one taken in the entry
    assert "1-11" in entry["why"] and "3-11" in HF["reduced"]["num_hidden_layers"]["why"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="the model catalog is not mounted here")
def test_the_configuration_keeps_every_published_number_but_the_reduced_ones():
    with open(CATALOG) as f:
        published = next(row for row in map(json.loads, f)
                         if row["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert HF["source"] == published["source_url"]
    assert {k for k, v in published["config"].items() if HF.get(k) != v} == REDUCED
    for key in REDUCED:
        assert HF["reduced"][key]["published"] == published["config"][key]
    assert HF["n_routed_experts_published"] == 512 and HF["vocab_size_published"] == 131072


def test_parameters_held_are_the_issues_hand_counts():
    held = nemotron_h_ref.params_held(HF)
    assert held["ssm_layer"] == W_IN + W_OUT + SSM_VECTORS + D and round(held["ssm_layer"] / 1e6, 1) == 109.6
    assert (round(W_IN / 1e6, 1), round(W_OUT / 1e6, 1)) == (76.0, 33.6)
    assert held["attention_layer"] == ATTENTION + D and round(held["attention_layer"] / 1e6, 1) == 35.7
    rest = ROUTER + 512 + LATENT_PAIR + SHARED + D
    assert held["expert_layer_without_routed"] == rest and round(rest / 1e6, 1) == 54.5
    assert held["routed_expert"] == EXPERT and round(EXPERT / 1e6, 1) == 5.5
    assert held["expert_layer"] == rest + 8 * EXPERT and round(held["expert_layer"] / 1e6, 1) == 98.6
    assert held["embed_and_head"] == 2 * 16384 * D and round(held["embed_and_head"] / 1e6, 1) == 134.2
    letters = HF["hybrid_override_pattern"]
    assert held["total"] == (letters.count("M") * held["ssm_layer"] + letters.count("E") * held["expert_layer"]
                             + held["attention_layer"] + held["embed_and_head"] + D)
    assert round(held["total"] / 1e9, 3) == {11: 1.211, 9: 1.003}[len(letters)]
    # the other plan of the depth rule, and the uncut expert layer no chip holds
    nine = dict(HF, num_hidden_layers=9, hybrid_override_pattern="MEMEM*EME")
    eleven = dict(HF, num_hidden_layers=11, hybrid_override_pattern="MEMEMEM*EME")
    assert round(nemotron_h_ref.params_held(nine)["total"] / 1e9, 3) == 1.003
    assert round(nemotron_h_ref.params_held(eleven)["total"] / 1e9, 3) == 1.211
    assert round((rest + 512 * EXPERT) / 1e9, 2) == 2.87


def test_work_counts_what_a_token_a_pair_and_a_cached_position_cost_here():
    w = nemotron_h_ref.work(HF)
    letters = HF["hybrid_override_pattern"]
    by_letter = {letter: w["layers"][letters.index(letter)] for letter in "M*E"}
    assert len(w["layers"]) == len(letters) and w["leading"] == 0
    assert [layer["cache_elems"] for layer in w["layers"]] == [512 if c == "*" else 0 for c in letters]
    assert all(w["layers"][i] == by_letter[c] for i, c in enumerate(letters))
    ssm, attention, experts = by_letter["M"], by_letter["*"], by_letter["E"]
    # an `M` layer: its two projections and, a head, the rank-one update and the read-out
    assert ssm["linear_flops"] == 2 * (W_IN + W_OUT) + 128 * 4 * 64 * 128 and ssm["pair_flops"] == 0
    assert "routed" not in ssm and "routed" not in attention
    assert attention["linear_flops"] == 2 * ATTENTION and attention["pair_flops"] == 4 * 32 * 128
    assert experts["linear_flops"] == 2 * (ROUTER + LATENT_PAIR + SHARED) and experts["pair_flops"] == 0
    assert experts["routed"] == {"expert_flops": 2 * 2 * 1024 * 2688, "expert_elems": EXPERT,
                                 "published": 512, "held": 8, "per_token": 22}
    assert w["head"] == {"flops": 2 * D * 16384, "weight_elems": D * 16384}
    # the decode step reads int8 what `quantize_decode_weights` rewrites (W_in, W_out, q, k, v, o,
    # W_down, W_up, the shared expert, the held experts); taps, A_log, D, dt_bias, the gated norm
    # and the router stay float32 (four bytes an element)
    assert ssm["weight_elems"] == W_IN + W_OUT + 4 * SSM_VECTORS
    assert attention["weight_elems"] == ATTENTION
    assert experts["weight_elems"] == LATENT_PAIR + SHARED + 4 * ROUTER
    # a token meets 22 x 8 / 512 of a held expert an `E` layer
    met = 22 * 8 / 512
    layers = (letters.count("M") * ssm["linear_flops"] + attention["linear_flops"]
              + letters.count("E") * (experts["linear_flops"] + met * 2 * EXPERT))
    assert flops.causal_forward_flops(flops.work(nemotron_h_ref, HF), 1) == layers + 4 * 32 * 128
    # `num_layers_unfrozen` 2 is two PUBLISHED layers, each one sub-layer
    assert flops.trainable_layers(flops.work(nemotron_h_ref, HF), 2) == 2
    # what a decode step at 32 rows must read without the recurrent state, which
    # `flops.decode_step_bytes` cannot express: 1.2 GB of weights (1.0 GB of them int8, the experts
    # 32 rows are expected to reach among them; the head in two bytes) and 9 MB of k and v rows
    # at 576 positions; the state is 1.36 GB more
    if len(letters) == 11:
        step = flops.decode_step_bytes(flops.work(nemotron_h_ref, HF), 32, 576, 1, 2, 2)
        assert 1.15e9 < step < 1.25e9


def test_toy_sizes_keep_every_mechanism():
    toy = dict(HF, **nemotron_h_ref.toy_sizes(HF))
    w = nemotron_h_ref.work(toy)
    assert len(w["layers"]) == 7 and "routed" in w["layers"][-1] and w["layers"][3]["cache_elems"] == 64
    assert toy["n_routed_experts"] < toy["n_routed_experts_published"]  # a share, still
    kw = nemotron_h_ref.system_config(toy)
    assert kw["mixer_layers"] == ("ssm", "none", "ssm", "softmax", "none", "ssm", "none")
    assert kw["ffn_layers"] == ("none", "routed", "none", "none", "routed", "none", "routed")
    assert kw["pos_embed"] == "none" and kw["activation"] == "relu2" and not kw["moe_gated"]
    with pytest.raises(ValueError, match="names one of M"):
        nemotron_h_ref.pattern(dict(toy, num_hidden_layers=6))
    with pytest.raises(ValueError, match="names one of M"):
        nemotron_h_ref.pattern(dict(toy, hybrid_override_pattern="MEM-EME"))


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
    assert not {"kda_share", "flash_fwd_roofline", "latent_decode_attn_roofline"} & set(line["metrics"])


# -- the readers on a hand-made Reading --------------------------------------


def reading(cell_name=CELL, trace=None, flight=()):
    cell = cells.load_cell(cell_name)
    return SimpleNamespace(
        cell=cell, hf=cell.config, traffic=cell.traffic, chips=1,
        peaks=cells.peaks_for("TPU v5 lite"), unfrozen=2, trace=trace, flight=list(flight),
        cycles=[{"step": 32, "wall_s": 10.0}], wall_s=10.0, run_dir=None)


TRACE = {
    "busy_s": 11.5,
    "scopes_by_self_time": [
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/ssm/ssm_step", 4.0],
        ["jit(generate)/prefill/while/body/closed_call/Block/ssm/ssm_chunk/while/body", 0.1],
        ["jit(fused_train_step)/while/body/checkpoint/Block/ssm/ssm_chunk/while/body/checkpoint", 0.9],
        ["jit(fused_train_step)/transpose(jvp(ssm_chunk))/while/body", 0.4],
        ["jit(ppo_experience_fwd)/Block/ssm/ssm_chunk/while/body", 0.2],
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/ssm/ssm_conv", 0.3],
        ["jit(fused_train_step)/Block/ssm/ssm_gate", 0.25],
        ["jit(generate)/while/body/decode_step/while/body/closed_call/Block/attn/decode_attn", 0.05],
        ["jit(fused_train_step)/Block/moe/moe_experts", 0.5],
        ["jit(fused_train_step)/Block/moe/moe_latent", 0.2],
        ["jit(generate)/while/body/decode_step/ssm_step_v2", 9.0],  # another scope
    ],
    "ops_by_self_time": [["%flash_fwd.3 custom-call (bf16[256,1024,128], f32[256,1024,1])", 0.05]],
}


def test_readers_count_by_hand_and_stay_under_100(capsys):
    r = reading(trace=TRACE)
    peak, hbm = r.peaks["bf16_flops_per_s"], r.peaks["hbm_bytes_per_s"]
    n = HF["hybrid_override_pattern"].count("M")
    assert _ssm.layers(r) == (n, 1)  # the `M` layers run; the top 2 hold one of them

    # a token and an `M` layer: 128 heads x 4 x 64 x 128 FLOPs; x', y, z in 2 bytes, B and C (8
    # groups of 128) in 2, dt (one a head) in 4
    tok_flops, tok_bytes = 128 * 4 * 64 * 128, 3 * 8192 * 2 + 2 * 1024 * 2 + 128 * 4
    assert _ssm.token_work(r.hf) == {"flops": tok_flops, "bytes": tok_bytes}
    # prefill 32 x 128 over the `M` layers; the scorer 32 x 1024 over them and the reference
    # branch's 1; 16 train steps of 8 x 1024: the forward over all and the backward, twice that, over 1
    tokens = 32 * 128 * n + 32 * 1024 * (n + 1) + 16 * 8 * 1024 * (n + 2)
    least = max(tokens * tok_flops / peak, tokens * tok_bytes / hbm)
    assert least == tokens * tok_bytes / hbm  # bound by its bytes
    assert ssm_chunk_roofline.read(r) == pytest.approx(100 * least / (0.1 + 0.9 + 0.4 + 0.2))

    # a decode step and an `M` layer: 32 rows x (128 x 64 x 128 float32 of state and 3 x 10,240
    # bf16 convolution inputs), read once and written once; 895 steps
    row = 4 * 128 * 64 * 128 + 2 * 3 * 10240
    assert _ssm.state_row_bytes(r.hf) == row == 4_255_744  # the ISSUE's 4.26 MB a row and layer
    assert 2 * row * 32 * 5 == 1_361_838_080  # and its 1.36 GB a step at five layers
    assert ssm_decode_roofline.read(r) == pytest.approx(100 * 2 * row * 32 * 895 * n / hbm / 4.0)

    assert ssm_share.read(r) == pytest.approx(100 * (4.0 + 1.6 + 0.3 + 0.25) / 11.5)
    assert "ssm_conv 0.3000 s, ssm_gate 0.2500 s, ssm_chunk 1.6000 s, ssm_step 4.0000 s" in capsys.readouterr().out
    for module in READERS:
        assert 0 < module.read(r) < 100, module.__name__


@pytest.mark.parametrize("module", READERS, ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_readers_read_nothing_where_there_is_nothing(module):
    # no trace (a `--trace 0` run)
    assert module.read(reading()) is None
    # a program without the scopes (the parent)
    bare = {"busy_s": 6.5, "scopes_by_self_time": [["jit(generate)/while/body/decode_step", 1.0]],
            "ops_by_self_time": [["%fusion.1 fusion kOutput bf16[8,1024,4096]", 0.4]]}
    assert module.read(reading(trace=bare)) is None
    # another family's reading, even with such a scope in its trace
    other = dict(bare, scopes_by_self_time=[["jit(generate)/while/body/decode_step/ssm_step", 1.0],
                                            ["jit(fused_train_step)/ssm_chunk", 1.0]])
    if module is not ssm_share:  # a share of busy time needs only the scopes
        assert module.read(reading("kimi-linear-48b-a3b.ppo-longgen-b32", trace=other)) is None
        assert module.read(reading("pythia-1.4b.ppo-longprompt", trace=other)) is None


@pytest.mark.parametrize("module", [flash_fwd_roofline, latent_decode_attn_roofline, kda_chunk_roofline],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_the_flash_latent_and_kda_readers_are_not_listed_for_this_cell(module):
    """`_flash.py` wants the family's `dims` (one kind of layer), the latent readers a
    latent cache, the `kda_*` readers a delta rule: `BENCHMARK.json` keeps the cell off
    their lists, so the harness never calls them here."""
    name = module.__name__.rsplit(".", 1)[-1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    assert CELL not in entry["workloads"]
    assert name not in {m["name"] for m in cells.load_cell(CELL).per_layer}
    if module is kda_chunk_roofline:  # and finds nothing even if asked
        assert module.read(reading(trace=TRACE)) is None
