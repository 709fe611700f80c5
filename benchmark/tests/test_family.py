"""A family is a file: what a configuration of a family the harness has never
seen gets through its reference module alone (its work, its decode bytes, its
toy sizes, its decisive positions), and that the families that are here count
what they counted before the interface (golden values from the parent of PR 27).
"""

import json
import os
import types

import numpy as np
import pytest

from benchmark import cells, correct, flops, run
from benchmark.end_to_end import mfu
from benchmark.layer_metrics import _flash, _program_spans, generate_roofline
from benchmark.reference import neox_ref

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
PEAKS = cells.peaks_for("TPU v5 lite")


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def reading(reference, hf, traffic, chips=1, cycle_s=2.0, **more):
    cell = types.SimpleNamespace(name="toy.cell", reference=reference, config=hf)
    return types.SimpleNamespace(
        cell=cell, hf=hf, traffic=traffic, chips=chips, peaks=PEAKS, cycle_s=cycle_s,
        unfrozen=hf["recipe"]["model"]["num_layers_unfrozen"], **more)


# -- (i) the families that are here count what they counted -----------------

with open(os.path.join(HERE, "data", "golden_pr26.json")) as _f:
    GOLDEN = json.load(_f)


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_pythia_counts_are_the_parents_to_the_last_digit(key):
    config, mix = key.split("|")
    hf, t, want = _json("configs", config + ".json"), _json("traffic", mix + ".json"), GOLDEN[key]
    p, n, rows = t["prompt_tokens"], t["new_tokens"], t["rollouts"]
    unfrozen = hf["recipe"]["model"]["num_layers_unfrozen"]
    w, d = flops.work(neox_ref, hf), neox_ref.dims(hf)
    assert not hasattr(neox_ref, "work")  # derived from dims: neox_ref.py is as it was
    got = {
        "cycle": flops.ppo_cycle_flops(w, t, unfrozen),
        "cycle_all_train": flops.ppo_cycle_flops(d, t, -1)["total"],  # a dims dict still counts
        "decode_int8": flops.decode_step_bytes(w, rows, p + 1, 1, 1),
        "decode_bf16": flops.decode_step_bytes(w, rows, p + n - 1, 2, 2),
        "flash_fwd": flops.flash_fwd(rows, d["n_head"], d["n_kv_head"], p + n, d["head_dim"]),
        "flash_bwd": flops.flash_bwd(rows, d["n_head"], d["n_kv_head"], p + n, d["head_dim"]),
        "trainable": flops.trainable_layers(w, unfrozen),
    }
    for chips in (1, 4):
        r = reading(neox_ref, hf, t, chips)
        got[f"generate_least_s_chips{chips}"] = generate_roofline.least_seconds(r)
        got[f"flash_fwd_least_s_chips{chips}"] = _flash.least_seconds(
            r, flops.flash_fwd, p + n, d["n_layer"])
    assert got == want  # floats compared exactly


# -- (ii) a toy family that lives here, counted by hand ----------------------
# Hidden 16; one leading dense layer, then two layers with 32 routed experts
# published, 8 held here, 4 a token, and a shared expert; low-rank attention
# projections, 2 heads, 6 query/key channels and 4 value channels a head, a
# cache of one 5-wide latent and 2 shared rotary channels a position; a
# vocabulary of 40 rows sliced out of 160.

E, HEADS, QK, V, Q_RANK, KV_RANK, ROPE = 16, 2, 6, 4, 8, 5, 2
DENSE_FF, EXPERT_FF, VOCAB_HELD = 24, 12, 40
PUBLISHED, HELD, PER_TOKEN = 32, 8, 4
# elements of the attention projections: x -> q latent -> heads; x -> kv
# latent + rotary; kv latent -> keys' non-rotary part and values; heads -> x
ATTN = E * Q_RANK + Q_RANK * HEADS * QK + E * (KV_RANK + ROPE) + KV_RANK * HEADS * (QK - ROPE + V) + HEADS * V * E
DENSE_MLP = 3 * E * DENSE_FF  # gate, up, down
EXPERT = 3 * E * EXPERT_FF
ROUTER = E * PUBLISHED
PAIR = 2 * HEADS * (QK + V)  # a score over QK channels, a weighted value over V
CACHE = KV_RANK + ROPE


class toy_family:
    """What a reference module of a new family defines for the counts: no
    file of the harness names it."""

    @staticmethod
    def work(hf):
        dense = {"linear_flops": 2.0 * (ATTN + DENSE_MLP), "pair_flops": float(PAIR),
                 "weight_elems": ATTN + DENSE_MLP, "cache_elems": CACHE}
        routed = {"linear_flops": 2.0 * (ATTN + ROUTER + EXPERT), "pair_flops": float(PAIR),
                  "weight_elems": ATTN + ROUTER + EXPERT, "cache_elems": CACHE,
                  "routed": {"expert_flops": 2.0 * EXPERT, "expert_elems": EXPERT,
                             "published": hf["n_routed_experts_published"],
                             "held": hf["n_routed_experts"], "per_token": hf["num_experts_per_tok"]}}
        return {"layers": [dense] + [routed] * (hf["num_hidden_layers"] - 1), "leading": 1,
                "head": {"flops": 2.0 * E * hf["vocab_size"], "weight_elems": E * hf["vocab_size"]}}

    @staticmethod
    def toy_sizes(hf):
        return {"hidden_size": 8, "moe_intermediate_size": 4, "q_lora_rank": 4, "vocab_size": 32}


TOY_HF = {"num_hidden_layers": 3, "n_routed_experts": HELD, "n_routed_experts_published": PUBLISHED,
          "num_experts_per_tok": PER_TOKEN, "vocab_size": VOCAB_HELD,
          "recipe": {"model": {"num_layers_unfrozen": 2, "model_extra_configs": {
              "transformer": {"decode_weights_quant": "int8", "kv_cache_quant": "int8"}}}}}
TOY_TRAFFIC = {"method": "ppo", "method_kwargs": {"ppo_epochs": 2}, "prompt_tokens": 3,
               "new_tokens": 3, "rollouts": 4}


def _forward(tokens, pairs, layers):
    """FLOPs of `tokens` tokens and `pairs` (query, key) pairs through the
    named layers, written out: a token meets 4 x 8 / 32 = 1 routed expert."""
    dense = 2 * (ATTN + DENSE_MLP) * tokens + PAIR * pairs
    routed = 2 * (ATTN + ROUTER + EXPERT) * tokens + 1 * 2 * EXPERT * tokens + PAIR * pairs
    return {"all": dense + 2 * routed, "top2": 2 * routed, "top1": routed}[layers]


def test_toy_family_cycle_flops_by_hand():
    w = toy_family.work(TOY_HF)
    head = 2 * E * VOCAB_HELD
    # generation: prefill of 3 (1 + 2 + 3 = 6 pairs), then steps that see 4 and 5 keys
    generation = _forward(3, 6, "all") + head + _forward(2, 4 + 5, "all") + 2 * head
    # scoring at 6 tokens (21 pairs): the policy, the top-2 reference branch, both heads at 3 positions
    scoring = _forward(6, 21, "all") + _forward(6, 21, "top2") + 2 * 3 * head
    training = _forward(6, 21, "all") + 2 * _forward(6, 21, "top2") + 3 * 3 * head
    got = flops.ppo_cycle_flops(w, TOY_TRAFFIC, 2)
    assert got["generation"] == 4 * generation
    assert got["scoring"] == 4 * scoring
    assert got["training"] == 2 * 4 * training
    assert got["total"] == 4 * (generation + scoring + 2 * training)


def test_hydra_branch_never_reaches_into_the_leading_layers():
    w = toy_family.work(TOY_HF)
    assert [flops.trainable_layers(w, u) for u in (1, 2, 3, 7, -1, None)] == [1, 2, 2, 2, 3, 3]
    assert flops.causal_forward_flops(w, 6, 1) == _forward(6, 21, "top1")


def test_toy_family_decode_bytes_by_hand():
    w = toy_family.work(TOY_HF)
    batch, keys = 4, 10
    # of the 8 experts held, those the 4 rows are expected to reach: each row
    # misses a given expert with 1 - 4/32
    reached = HELD * (1 - (1 - PER_TOKEN / PUBLISHED) ** batch)
    weights = (ATTN + DENSE_MLP) + 2 * (ATTN + ROUTER + EXPERT + reached * EXPERT)
    want = weights * 1 + E * VOCAB_HELD * 2 + 3 * CACHE * batch * keys * 1
    assert flops.decode_step_bytes(w, batch, keys) == pytest.approx(want, rel=1e-12)
    # at bf16 weights and cache, everything but the head doubles
    assert flops.decode_step_bytes(w, batch, keys, 2, 2) == pytest.approx(
        2 * (want - E * VOCAB_HELD * 2) + E * VOCAB_HELD * 2, rel=1e-12)


def test_toy_family_reaches_mfu_and_generate_roofline_through_its_module_alone():
    r = reading(toy_family, TOY_HF, TOY_TRAFFIC, chips=1, cycle_s=2.0)
    cycle = flops.ppo_cycle_flops(toy_family.work(TOY_HF), TOY_TRAFFIC, 2)["total"]
    assert mfu.read(r) == 100.0 * cycle / 2.0 / PEAKS["bf16_flops_per_s"]
    w = toy_family.work(TOY_HF)
    prefill = 4 * (flops.causal_forward_flops(w, 3) + 2 * E * VOCAB_HELD)
    decode = flops.decode_step_bytes(w, 4, 4) + flops.decode_step_bytes(w, 4, 5)
    assert generate_roofline.least_seconds(r) == pytest.approx(
        prefill / PEAKS["bf16_flops_per_s"] + decode / PEAKS["hbm_bytes_per_s"], rel=1e-12)


def test_the_rehearsal_takes_its_toy_sizes_from_the_family():
    toy = run.rehearsal_scale(types.SimpleNamespace(reference=toy_family, config=TOY_HF))
    assert toy["config"] == toy_family.toy_sizes(TOY_HF) and toy["traffic"] == run.REHEARSAL["traffic"]
    neox = run.rehearsal_scale(types.SimpleNamespace(reference=neox_ref, config={}))
    assert neox == run.REHEARSAL


# -- (iii) check (a) knows a decisive position from a tie --------------------

TOL = {"logprob_rms_tol": 0.016, "logprob_max_tol": 0.1, "tie_logprob_rms_tol": 1.0,
       "tie_logprob_max_tol": 4.0, "decisive_share_min": 0.75}


def _arrays(n=400, swapped=40, seed=0):
    rng = np.random.default_rng(seed)
    reference = -rng.random(n) * 3
    system = reference + rng.normal(0, 0.008, n)  # bf16's error everywhere
    hit = rng.choice(n, swapped, replace=False)
    system[hit] += rng.choice([-1.0, 1.0], swapped) * 0.7  # one expert swapped
    ties = np.zeros(n, bool)
    ties[hit] = True
    return system, reference, ties


def test_errors_confined_to_undecisive_positions_pass():
    system, reference, ties = _arrays()
    out = correct.scorer_check(system, reference, ~ties, TOL)
    assert out["scorer_ok"] and out["decisive_share"] == 0.9
    assert out["logprob_rms_err"] < 0.016 and 0.5 < out["tie_logprob_rms_err"] < 1.0
    assert out["tie_logprob_max_err"] > out["logprob_max_err"]


def test_the_same_errors_on_decisive_positions_fail():
    system, reference, ties = _arrays()
    out = correct.scorer_check(system, reference, ~np.roll(ties, 1), TOL)  # the ties marked elsewhere
    assert out["decisive_share"] == 0.9 and not out["scorer_ok"] and out["logprob_max_err"] > 0.5


def test_undecisive_positions_are_compared_too():
    system, reference, ties = _arrays()
    system[np.flatnonzero(ties)[0]] += 9.0  # no tie explains this
    assert not correct.scorer_check(system, reference, ~ties, TOL)["scorer_ok"]


def test_a_decisive_share_under_the_stated_least_fails():
    system, reference, ties = _arrays(swapped=120)
    out = correct.scorer_check(system, reference, ~ties, TOL)
    assert out["decisive_share"] == 0.7 and not out["scorer_ok"]
    assert out["logprob_rms_err"] < 0.016 and out["tie_logprob_max_err"] < 4.0  # the share alone


def test_a_module_without_the_mask_is_checked_as_before():
    system, reference, ties = _arrays(swapped=0)
    out = correct.scorer_check(system, reference, None, TOL)
    diff = system - reference
    assert out == {"logprob_rms_err": float(np.sqrt(np.mean(diff**2))),
                   "logprob_max_err": float(np.max(np.abs(diff))), "scorer_ok": True}
    system, reference, _ = _arrays()
    assert not correct.scorer_check(system, reference, None, TOL)["scorer_ok"]
    system[0] = np.nan
    assert not correct.scorer_check(system, reference, None, TOL)["scorer_ok"]


def test_compared_lists_every_number_beside_its_limit():
    ref = dict(correct.scorer_check(*_arrays()[:2], ~_arrays()[2], TOL),
               sampler_mean_surprise=-0.05, sampler_bound=0.4)
    win = {"failed": 0, "generated_tokens": 64, "expected_tokens": 64}
    out = correct.compared(ref, win, {"compiles": 0, "cache_misses": 0}, TOL)
    assert list(out) == ["logprob_rms", "logprob_max", "tie_logprob_rms", "tie_logprob_max",
                         "decisive_share_at_least", "sampler_surprise", "generated_tokens_exactly",
                         "failed_cycles", "built_in_window"]
    assert out["sampler_surprise"] == [0.05, 0.4] and out["logprob_rms"][1] == 0.016


# -- what a new reader finds on the reading ---------------------------------

def test_the_window_rows_of_the_flight_stream(tmp_path):
    os.makedirs(tmp_path / "flight")
    rows = [{"kind": "run_start", "step": 0},
            {"kind": "gauge", "step": 0, "model/layers": 22},
            {"kind": "cycle", "step": 4, "spans": [["generate", 0.0, 1.0, "rollout", {"rows": 8}]]},
            {"kind": "cycle", "step": 8, "real_tokens": 64.0,
             "spans": [["generate", 0.5, 1.0, "rollout", {}], ["tokens_wait", 1.0, 3.0, "rollout", {}],
                       ["detokenize", 3.0, 3.5, "rollout", {}]]},
            {"kind": "cycle", "step": 12, "spans": []}]
    with open(tmp_path / "flight" / "flight-00001.jsonl", "w") as f:
        f.write("".join(json.dumps(r) + "\n" for r in rows) + '{"kind": "cyc')  # a torn last line
    r = types.SimpleNamespace(flight=run.flight_rows(str(tmp_path)), cycles=[{"step": 8}, {"step": 12}])
    assert r.flight == rows
    assert [row["step"] for row in _program_spans.window_rows(r)] == [8, 12]
    assert _program_spans.window_rows(r)[0]["real_tokens"] == 64.0
    assert _program_spans.span_seconds(r, ("generate", "tokens_wait")) == 2.5
    assert _program_spans.span_seconds(r, ("block_wait",)) is None
    assert [row["model/layers"] for row in r.flight if row["kind"] == "gauge"] == [22]
