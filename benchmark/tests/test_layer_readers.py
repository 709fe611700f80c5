"""The readers of the flash-attention, generation and sampler metrics on a
hand-made `Reading`: what they count, that a share stays under 100, that they
return None where there is nothing to read."""

from types import SimpleNamespace

import pytest

from benchmark import cells, flops
from benchmark.layer_metrics import (
    _program_spans, decode_attn_share, flash_bwd_roofline, flash_fwd_roofline,
    generate_roofline, sample_wall_share, score_device_share)


def reading(trace=None, cycles=None, chips=1, flight=()):
    cell = cells.load_cell("pythia-1.4b.ppo-longprompt")
    return SimpleNamespace(
        cell=cell, hf=cell.config, traffic=cell.traffic, chips=chips,
        peaks=cells.peaks_for("TPU v5 lite"), unfrozen=2, trace=trace, flight=list(flight),
        cycles=cycles or [{"step": 12, "wall_s": 7.0}, {"step": 16, "wall_s": 7.0}],
        wall_s=14.0)


def test_span_seconds_keeps_the_windows_cycles_and_the_named_spans():
    r = reading(flight=[
        {"kind": "run_start", "step": 0},
        {"kind": "cycle", "step": 8, "spans": [["generate", 0.0, 9.0, "rollout", {}]]},  # warm-up
        {"kind": "cycle", "step": 12, "spans": [
            ["block_wait", 0.0, 5.0, "fused_block", {}],
            ["generate", 5.0, 5.5, "rollout", {"rows": 8}],
            ["tokens_wait", 5.5, 6.5, "rollout", {"rows": 8, "tokens": 1024}]]},
        {"kind": "cycle", "step": 16, "spans": [["tokens_wait", 1.0, 2.5, "rollout", {}]]},
    ])
    assert _program_spans.span_seconds(r, ("generate", "tokens_wait")) == pytest.approx(3.0)
    assert _program_spans.span_seconds(r, ("no_such_span",)) is None
    # a program that writes no spans (the parent of the PR that added them)
    r.cycles = [{"step": 99, "wall_s": 1.0}]
    assert _program_spans.span_seconds(r, ("generate",)) is None


def test_sampler_readers_on_hand_made_spans():
    r = reading(flight=[
        {"kind": "cycle", "step": s, "spans": [
            ["generate", 0.0, 0.5, "rollout", {}], ["tokens_wait", 0.5, 1.5, "rollout", {}]]}
        for s in (12, 16)])
    assert sample_wall_share.read(r) == pytest.approx(100.0 * 3.0 / 14.0)
    # 8 rows of 1920 + 128 on the 22-layer 1.4B: 0.186 s of prefill FLOPs and
    # 0.425 s of decode bytes (int8 weights and cache) a cycle, by hand
    assert generate_roofline.least_seconds(r) == pytest.approx(0.6116, rel=1e-3)
    share = generate_roofline.read(r)
    assert share == pytest.approx(100.0 * 2 * 0.6116 / 3.0, rel=1e-3) and share < 100
    r4 = reading(chips=4)
    assert generate_roofline.least_seconds(r4) == pytest.approx(0.6116 / 4, rel=1e-3)
    assert sample_wall_share.read(r4) is None and generate_roofline.read(r4) is None  # no spans


def test_device_seconds_by_scope_and_by_program():
    trace = {
        "busy_s": 4.0,
        "scopes_by_self_time": [
            ["jit(generate)/while/body/decode_step/blocks/attn/decode_attn", 1.0],
            ["jit(generate)/while/body/decode_step/blocks/attn/decode_attn/inner", 0.5],
            ["jit(generate)/while/body/decode_step/blocks/attn", 0.7],  # not under the scope
            ["jit(step)/transpose(jvp(decode_attn))/x", 0.1],  # wrapped by a transform
            ["jit(generate)/decode_attn_v2", 9.0],  # another name
            ["", 0.2]],
        "programs_s": {"jit_generate": 2.5, "jit_ppo_experience_fwd": 0.375,
                       "jit_ppo_score_inject": 0.025, "jit_fused_train_step": 1.0},
    }
    r = reading(trace=trace)
    assert decode_attn_share.read(r) == pytest.approx(100 * 1.6 / 4.0)
    assert score_device_share.read(r) == pytest.approx(100 * 0.4 / 4.0)
    # nothing to read is nothing, never a share of 0
    other = dict(trace, scopes_by_self_time=[["jit(generate)/paged_decode_attn", 1.0]],
                 programs_s={"jit_grpo_experience_fwd": 1.0})
    assert decode_attn_share.read(reading(trace=other)) is None
    assert score_device_share.read(reading(trace=other)) is None
    assert decode_attn_share.read(reading()) is None and score_device_share.read(reading()) is None


def test_flash_readers_count_what_the_algorithm_requires():
    ops = [["%flash_fwd.3 custom-call (bf16[128,2048,128], f32[128,2048,1])", 0.30],
           ["%flash_fwd.7 custom-call bf16[128,1920,128]", 0.20],
           ["%flash_bwd_dq.1 custom-call bf16[128,2048,128]", 0.25],
           ["%flash_bwd_dkv.1 custom-call (bf16[128,2048,128], bf16[128,2048,128])", 0.30],
           ["%flash_bias_fwd.1 custom-call bf16[8,512,64]", 9.0],  # another kernel
           ["%fusion.1 fusion kOutput bf16[8,2048,2048]", 0.4]]
    r = reading(trace={"ops_by_self_time": ops})
    peak = r.peaks["bf16_flops_per_s"]
    one = flops.flash_fwd(8, 16, 16, 2048, 128)["flops"] / peak
    prefill = flops.flash_fwd(8, 16, 16, 1920, 128)["flops"] / peak
    # 22 layers of prefill; 22 + 2 of scoring and 4 x 22 of training at 2048
    assert flash_fwd_roofline.read(r) == pytest.approx(100 * (22 * prefill + 112 * one) / 0.5)
    # the backward of 2 trainable layers in each of 4 steps, 2.5 forwards each
    assert flash_bwd_roofline.read(r) == pytest.approx(100 * 8 * 2.5 * one / 0.55)
    assert 0 < flash_bwd_roofline.read(r) < flash_fwd_roofline.read(r) < 100
    # on four chips the same work takes a quarter of the time on each
    assert flash_fwd_roofline.read(reading(trace={"ops_by_self_time": ops}, chips=4)) == \
        pytest.approx(flash_fwd_roofline.read(r) / 4)


@pytest.mark.parametrize("module", [flash_fwd_roofline, flash_bwd_roofline])
def test_flash_readers_read_nothing_without_a_trace_or_a_named_kernel(module):
    assert module.read(reading(trace=None)) is None
    unnamed = {"ops_by_self_time": [["%attn.89 custom-call bf16[128,2048,128]", 0.2]]}
    assert module.read(reading(trace=unnamed)) is None
