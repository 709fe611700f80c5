"""The trace reduction: interval arithmetic on made-up events, then the whole
reduction on a small trace recorded on a v5e (tests/record_trace.py)."""

import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "v5e_small.xplane.pb")


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
