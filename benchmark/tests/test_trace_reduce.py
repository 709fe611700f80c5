"""The trace reduction: interval arithmetic on made-up events, then the whole
reduction on a small trace recorded on a v5e (tests/record_trace.py)."""

import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "v5e_small.xplane.pb")
SCOPES = os.path.join(os.path.dirname(DATA), "v5e_scopes.xplane.pb")


def test_union_subtract_clip():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)])
    assert u == [(0, 3), (5, 7)]
    assert tr.total(u) == 5
    assert tr.subtract([(0, 10)], u) == [(3, 5), (7, 10)]
    assert tr.subtract(u, [(1, 6)]) == [(0, 1), (6, 7)]
    assert tr.clip(u, 2, 6) == [(2, 3), (5, 6)]


def test_short_names():
    text = ('%fusion.8 = (f32[512,512]{1,0:T(8,128)S(1)}, bf16[8]{0}) fusion(f32[512,512]{1,0:T(8,128)S(1)} '
            '%copy.11), kind=kOutput, calls=%fused_computation.1.clone.clone')
    assert tr.short(text) == "%fusion.8 fusion kOutput (f32[512,512], bf16[8])"
    assert tr.short("phase:rollout") == "phase:rollout"
    assert tr.is_mosaic('%attn.1 = f32[8] custom-call(f32[8] %x), custom_call_target="tpu_custom_call"')
    assert not tr.is_mosaic(text)


def test_self_time_takes_enclosed_events_out():
    # a while loop of 10 that encloses two body ops of 3 and 4
    events = [(0.0, 10.0, "while.1"), (1.0, 4.0, "fusion.1"), (5.0, 9.0, "fusion.2"),
              (12.0, 13.0, "copy.1")]
    got = {n: (self_t, leaf) for _, _, n, self_t, leaf in tr.self_times(events)}
    assert got == {"while.1": (3.0, False), "fusion.1": (3.0, True),
                   "fusion.2": (4.0, True), "copy.1": (1.0, True)}


class _Event:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns, self.stats = name, start, dur, list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduction_on_a_made_up_two_chip_trace():
    ms = 1e6
    host = _Plane("/host:CPU", [_Line("main", [
        _Event("bench:traced", 0, 100 * ms),
        _Event("phase:rollout", 0, 40 * ms),
        _Event("phase:reward", 20 * ms, 10 * ms),  # nested: wins its gap
        _Event("phase:fused_block", 40 * ms, 60 * ms),
    ])])
    chip0 = _Plane("/device:TPU:0", [
        _Line("XLA Ops", [
            _Event("fusion.1", 0, 20 * ms),
            # idle 20-30 under reward
            _Event("all-gather.1", 30 * ms, 20 * ms),  # 30-50, compute covers 40-50
            _Event('%attn.7 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), '
                   'custom_call_target="tpu_custom_call"', 40 * ms, 30 * ms),  # 40-70
            # idle 70-100 under fused_block
        ]),
        _Line("Steps", [_Event("step", 0, 100 * ms)]),  # not an op line
    ])
    chip1 = _Plane("/device:TPU:1", [_Line("XLA Ops", [
        _Event("all-gather.1", 0, 50 * ms),  # all of it exposed
        _Event("fusion.1", 50 * ms, 50 * ms),
    ])])
    r = tr.reduce_profile(_Profile([host, chip0, chip1]))
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s_per_device"] == pytest.approx([0.060, 0.100])
    assert r["busy_s"] == pytest.approx(0.080)
    assert r["collective_exposed_s"] == pytest.approx(0.050)  # the worse chip
    assert r["mosaic_s"] == pytest.approx(0.030 / 2)  # chip 0 only, mean over chips
    gaps = dict((name.split(" @")[0], s) for name, s in r["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"fused_block": 0.030, "reward": 0.010})
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(0.035)]


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_reduction_on_the_recorded_v5e_trace():
    import jax

    from benchmark.tests.record_trace import SLEEP_S

    r = tr.reduce_profile(jax.profiler.ProfileData.from_file(DATA))
    assert r["device_planes"] == ["/device:TPU:0"]
    assert 0 < r["busy_s"] < r["window_s"]
    # the host slept inside phase:rollout with the device idle
    name, seconds = r["breakdown"]["idle_gaps"][0]
    assert name.startswith("rollout") and seconds >= SLEEP_S
    # the pallas kernel is found, and is a small part of the busy time
    assert r["mosaic_names"] and 0 < r["mosaic_s"] < r["busy_s"]
    assert r["mosaic_names"] == ["%work.1 custom-call f32[512,512]"]
    # the loop's body ops are not counted twice: self times add up to busy
    assert r["self_time_s"] == pytest.approx(r["busy_s"], rel=0.02)
    assert any(n.startswith("%while while") for n, _ in r["ops_by_self_time"])


def test_wire_format_fields():
    # field 1 varint 300; field 2 bytes "ab"; field 3 fixed64 and field 4 fixed32 passed
    # over; field 5 a nested message holding field 1 varint 7
    message = (b"\x08\xac\x02" + b"\x12\x02ab" + b"\x19" + bytes(8) + b"\x25" + bytes(4)
               + b"\x2a\x02\x08\x07")
    got = [(k, v if isinstance(v, int) else bytes(v)) for k, v in tr.fields(message)]
    assert got == [(1, 300), (2, b"ab"), (5, b"\x08\x07")]
    assert list(tr.fields(got[2][1])) == [(1, 7)]
    assert tr.scope_of("jit(f)/while/body/decode_attn/dot_general:") == "jit(f)/while/body/decode_attn"
    assert tr.scope_of("jit(f)/pallas_call:") == "jit(f)" and tr.scope_of("x") == ""


def test_scopes_and_programs_on_a_made_up_two_chip_trace():
    ms = 1e6
    host = _Plane("/host:CPU", [_Line("main", [_Event("bench:traced", 0, 100 * ms)])])

    def chip(n, shift):
        # program a runs twice: a loop of 30 that encloses an op of 20 under a scope,
        # then an op of 10 with the same text as an op of program b
        return _Plane(f"/device:TPU:{n}", [
            _Line("XLA Modules", [_Event("jit_a(11)", shift, 40 * ms),
                                  _Event("jit_b(22)", shift + 50 * ms, 10 * ms),
                                  _Event("jit_a(11)", shift + 60 * ms, 40 * ms)]),  # cut by the window on chip 1
            _Line("XLA Ops", [
                _Event("%while.1", shift, 30 * ms), _Event("%fusion.1", shift + 5 * ms, 20 * ms),
                _Event("%copy.1", shift + 30 * ms, 10 * ms),
                _Event("%copy.1", shift + 50 * ms, 10 * ms),
                _Event("%while.1", shift + 60 * ms, 30 * ms), _Event("%fusion.1", shift + 65 * ms, 20 * ms),
                _Event("%copy.1", shift + 90 * ms, 10 * ms)])])

    scopes = {11: {"%while.1": "jit(a)", "%fusion.1": "jit(a)/while/body/known", "%copy.1": "jit(a)/known/deeper"},
              22: {"%copy.1": "jit(b)"}}
    r = tr.reduce_profile(_Profile([host, chip(0, 0), chip(1, 10 * ms)]), scopes)
    # chip 0 runs a for 80 ms, chip 1 for 40 + 30 inside the window: the mean
    assert r["programs_s"] == pytest.approx({"jit_a": 0.075, "jit_b": 0.010})
    got = dict(r["scopes_by_self_time"])
    # chip 1's last copy starts as the window ends and is left out
    assert got == pytest.approx({"jit(a)/while/body/known": 0.040, "jit(a)": 0.020,
                                 "jit(a)/known/deeper": 0.015, "jit(b)": 0.010})
    assert tr.scope_seconds(r, "known") == pytest.approx(0.055)  # nested scopes count under it
    assert tr.scope_seconds(r, "deeper") == pytest.approx(0.015)
    assert tr.scope_seconds(r, "know") is None
    assert sum(got.values()) == pytest.approx(r["self_time_s"])
    # without the file's metadata every op is under no scope
    assert dict(tr.reduce_profile(_Profile([host, chip(0, 0)]))["scopes_by_self_time"]) == \
        pytest.approx({"": 0.09})  # 40 + 20 + 20 + 10 ms


@pytest.mark.skipif(not os.path.exists(SCOPES), reason="no recorded trace")
def test_scopes_and_programs_on_the_recorded_v5e_trace():
    import jax

    from benchmark.tests.record_trace import PLAIN_RUNS, SCOPED_RUNS

    with open(SCOPES, "rb") as f:
        scopes = tr.event_scopes(f.read())
    assert len(scopes) == 2  # two programs, by their ids
    r = tr.reduce_profile(jax.profiler.ProfileData.from_file(SCOPES), scopes)
    assert list(r["programs_s"]) == ["jit_scoped_matmuls", "jit_plain_sum"]
    # a program's event spans its ops: the programs add up to the busy time
    assert sum(r["programs_s"].values()) == pytest.approx(r["busy_s"], rel=0.02)
    assert r["programs_s"]["jit_plain_sum"] / PLAIN_RUNS < r["programs_s"]["jit_scoped_matmuls"] / SCOPED_RUNS
    # the loop's matmul is the one instruction under the scope; `tanh` under
    # known_scope/inner was fused into it and goes by the matmul's op_name
    ops = dict(r["ops_by_self_time"])
    matmul = ops["%fusion.10 fusion kOutput f32[1024,1024]"]
    assert tr.scope_seconds(r, "known_scope") == pytest.approx(matmul)
    assert dict(r["scopes_by_self_time"])["jit(scoped_matmuls)/while/body/closed_call/known_scope"] == \
        pytest.approx(matmul)
    assert tr.scope_seconds(r, "inner") is None
    # eight matmuls a run under the scope, the ninth (as large) under none
    outside = ops["%convolution_add_fusion fusion kOutput f32[1024,1024]"]
    assert 4 < matmul / outside < 12
    assert sum(t for _, t in r["scopes_by_self_time"]) == pytest.approx(r["self_time_s"])
