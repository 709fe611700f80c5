"""Flight recorder / observability suite (ISSUE 11).

Unit level (fake clocks, no trainers): span-partition invariants, JSONL
rotation + torn-tail tolerance (the mid-write-kill contract),
correlation-id stability across resume, the telemetry.json field
golden, profiler arming off-TPU, and the Tracker.close()
deferred-stats drain.

Integration (ONE tiny learn(), the acceptance criterion): a fault-free
PPO run on a test-config-shaped tiny model emits a flight-recorder
stream whose per-cycle phase walls sum to the cycle wall, commits a
provenance-stamped telemetry.json alongside its checkpoints whose
samples/s matches the trainer's own rollout accounting, and renders
through scripts/flight_report.py.
"""

import json
import os

import numpy as np
import pytest

from trlx_tpu.obs.config import ObsConfig, ProfileConfig
from trlx_tpu.obs.observer import RunObserver
from trlx_tpu.obs.recorder import FlightRecorder, flight_files, iter_rows
from trlx_tpu.obs.spans import SpanTracer
from trlx_tpu.obs.telemetry import TelemetryAggregator, tree_param_count
from trlx_tpu.obs.profiler import ProfilerArm


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_partition_sums_to_wall_with_nesting():
    t = SpanTracer()
    t.start_cycle(10.0)
    t.on_beat(11.0, "rollout", "start")       # 10..11 -> other
    t.on_beat(12.0, "rollout", "point")       # 11..12 -> rollout
    t.on_beat(12.5, "reward", "start")        # 12..12.5 -> rollout
    t.on_beat(14.0, "reward", "end")          # 12.5..14 -> reward (inner)
    t.on_beat(15.0, "rollout", "end")         # 14..15 -> rollout
    wall, phases = t.snapshot_cycle(16.0)     # 15..16 -> other
    assert wall == pytest.approx(6.0)
    assert phases["reward"] == pytest.approx(1.5)
    assert phases["rollout"] == pytest.approx(2.5)
    assert phases["other"] == pytest.approx(2.0)
    # the invariant the acceptance criterion holds telemetry to
    assert sum(phases.values()) == pytest.approx(wall, abs=1e-9)


def test_span_open_phase_straddles_cycles():
    t = SpanTracer()
    t.start_cycle(0.0)
    t.on_beat(1.0, "fused_block", "start")
    wall1, p1 = t.snapshot_cycle(3.0)  # block still open
    t.on_beat(4.0, "fused_block", "end")
    wall2, p2 = t.snapshot_cycle(5.0)
    assert p1["fused_block"] == pytest.approx(2.0)
    assert p2["fused_block"] == pytest.approx(1.0)
    assert sum(p1.values()) == pytest.approx(wall1)
    assert sum(p2.values()) == pytest.approx(wall2)
    assert t.open_phases == []


def test_span_mismatched_end_is_harmless():
    t = SpanTracer()
    t.start_cycle(0.0)
    t.on_beat(1.0, "eval", "end")  # never started
    t.on_beat(2.0, "rollout", "start")
    wall, phases = t.snapshot_cycle(3.0)
    assert sum(phases.values()) == pytest.approx(wall)


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


def _beat_cycle(tracer, clock, with_spans):
    """One cycle's beats, optionally with work-site spans inside it."""
    import contextlib

    def span(name, **counts):
        return tracer.span(name, **counts) if with_spans else contextlib.nullcontext({})

    def at(t):
        clock.t = t
        return t

    tracer.start_cycle(at(10.0))
    tracer.on_beat(at(11.0), "rollout", "start")
    with span("generate", rows=8):
        at(11.5)
    with span("tokens_wait", rows=8) as counts:
        at(13.0)
        counts["tokens"] = 64
    with span("score_dispatch"):
        at(13.25)
        with span("inner"):
            at(13.75)
        at(14.0)
    tracer.on_beat(at(14.0), "reward", "start")
    tracer.on_beat(at(14.5), "reward", "end")
    tracer.on_beat(at(15.0), "rollout", "end")
    tracer.on_beat(at(15.0), "fused_block", "start")
    with span("block_wait"):
        at(15.5)
    tracer.on_beat(at(16.0), "fused_block", "end")
    return tracer.snapshot_cycle(at(17.0))


def test_work_site_spans_parent_counts_and_self_time():
    from trlx_tpu.obs.spans import span_self_times

    clock = _FakeClock()
    t = SpanTracer(clock=clock, annotate=None)
    _beat_cycle(t, clock, with_spans=True)
    rows = {r[0]: r for r in t.cycle_spans}
    # seconds from the cycle's start, on the beats' clock
    assert rows["generate"][1:3] == [1.0, 1.5]
    assert rows["tokens_wait"][1:3] == [1.5, 3.0]
    # parent: the enclosing span, else the innermost open phase
    assert rows["generate"][3] == "rollout"
    assert rows["inner"][3] == "score_dispatch"
    assert rows["block_wait"][3] == "fused_block"
    # a count known only at the end of the block still lands in the row
    assert rows["tokens_wait"][4] == {"rows": 8, "tokens": 64}
    assert rows["score_dispatch"][4] == {}
    own = span_self_times(t.cycle_spans)
    assert own["score_dispatch"] == pytest.approx(0.5)  # 1.0 less inner's 0.5
    assert own["inner"] == pytest.approx(0.5)
    assert own["tokens_wait"] == pytest.approx(1.5)
    # handed over once: the next cycle starts with none
    t.snapshot_cycle(18.0)
    assert t.cycle_spans == []


def test_spans_leave_the_phase_partition_as_it_is():
    """The trap a phase named `tokens_wait` would spring: spans are a
    second level and never take wall away from the phase around them."""
    results = []
    for with_spans in (False, True):
        clock = _FakeClock()
        t = SpanTracer(clock=clock, annotate=None)
        results.append(_beat_cycle(t, clock, with_spans))
    (wall0, phases0), (wall1, phases1) = results
    assert wall0 == wall1 == pytest.approx(7.0)
    assert phases0 == phases1  # bit for bit
    assert phases1["rollout"] == pytest.approx(3.5)
    assert "tokens_wait" not in phases1
    assert sum(phases1.values()) == pytest.approx(wall1, abs=1e-9)


def test_phases_and_spans_are_mirrored_as_trlx_annotations():
    """What a profiler capture sees: `trlx:<name>` entered and left in
    order, phases (from the beats) and spans alike."""
    seen = []

    class Ann:
        def __init__(self, name):
            self.name = name
            seen.append(("enter", name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    clock = _FakeClock()
    t = SpanTracer(clock=clock, annotate=Ann)
    _beat_cycle(t, clock, with_spans=True)
    assert seen[:4] == [("enter", "rollout"), ("enter", "generate"),
                        ("exit", "generate"), ("enter", "tokens_wait")]
    assert ("exit", "rollout") in seen and ("exit", "block_wait") in seen
    assert sum(k == "enter" for k, _ in seen) == sum(k == "exit" for k, _ in seen)
    # the default mirror is a real jax.profiler.TraceAnnotation
    from trlx_tpu.obs.spans import ANNOTATION_PREFIX, _annotation

    ann = _annotation("probe")
    ann.__exit__(None, None, None)
    assert type(ann).__name__ == "TraceAnnotation" and ANNOTATION_PREFIX == "trlx:"


def test_observer_span_is_null_when_off_and_never_raises(tmp_path):
    off = RunObserver(ObsConfig.from_dict({"enabled": False}), str(tmp_path / "a"))
    with off.span("generate", rows=8) as counts:
        counts["tokens"] = 1  # a throwaway dict
    assert off.tracer.cycle_spans == [] and off.tracer._spans == []
    assert not os.path.exists(tmp_path / "a")

    on = RunObserver(ObsConfig.from_dict({}), str(tmp_path / "b"))
    # the body's exception passes through, and the span still closes
    with pytest.raises(KeyError):
        with on.span("generate"):
            raise KeyError("from the body")
    assert [r["name"] for r in on.tracer._spans] == ["generate"]
    # the tracer's own failure disarms the observer, the body runs on
    on.tracer.open_span = None
    ran = []
    with on.span("tokens_wait"):
        ran.append(1)
    assert ran == [1] and not on.active
    on.finish()


# ---------------------------------------------------------------------------
# set-up spans and compile events (ISSUE 37)
# ---------------------------------------------------------------------------

# the keys of a `cycle` row that compiled nothing, as the parent commit
# wrote them (engine, counters and samples_per_sec come and go with the run)
_STEADY_CYCLE_KEYS = {
    "t", "run", "kind", "cycle", "step", "pv", "wall_s", "phases", "samples",
    "real_tokens", "train_steps", "spans",
}


def _compile(tracer, name, seconds, hit=False, trace_s=0.0, lower_s=0.0):
    """The events JAX fires for one compilation, as the listener hands
    them to the tracer."""
    from trlx_tpu.obs import spans as S

    if trace_s:
        tracer.on_compile_duration(S.TRACE_EVENT, trace_s, name)
    if lower_s:
        tracer.on_compile_duration(S.LOWER_EVENT, lower_s, f"jit({name})")
    tracer.on_compile_event(S.REQUEST_EVENT)
    if hit:
        tracer.on_compile_event(S.HIT_EVENT)
    tracer.on_compile_duration(S.BACKEND_EVENT, seconds, f"jit({name})")


def _probe_program(tag):
    """A jitted function nothing else in the process has compiled."""
    import jax

    def obs_probe(x):
        return x * 2.0 + float(tag)

    obs_probe.__name__ = f"obs_probe_{tag}"
    return jax.jit(obs_probe), f"jit_obs_probe_{tag}"


def test_spans_closed_before_the_first_cycle_reach_setup_and_not_cycle_one():
    clock = _FakeClock(100.0)
    t = SpanTracer(clock=clock, annotate=None)
    with t.span("model_init", params=9):
        clock.t = 103.0
        with t.span("ref_init"):
            clock.t = 104.0
    clock.t = 105.0
    t.start_cycle(105.0)
    # seconds from the tracer's birth, handed over as a cycle's are
    assert t.cycle_spans == [["ref_init", 3.0, 4.0, "model_init", {}],
                             ["model_init", 0.0, 4.0, None, {"params": 9}]]
    with t.span("generate"):
        clock.t = 106.0
    t.snapshot_cycle(107.0)
    assert [r[0] for r in t.cycle_spans] == ["generate"]


def test_setup_row_is_written_once_and_before_the_first_cycle_opens(tmp_path):
    clock = _FakeClock(10.0)
    obs = RunObserver(ObsConfig(), str(tmp_path), clock=clock)
    with obs.span("model_init", params=5):
        clock.t = 14.0
        _compile(obs.tracer, "_normal", 1.5, trace_s=0.25, lower_s=0.25)
    clock.t = 16.0
    obs.end_init()
    with obs.span("prompt_pipeline", prompts=8):
        clock.t = 17.0
    obs.start(trainer="T", step=0)
    clock.t = 18.0
    obs.end_cycle(step=1)
    obs.finish()
    # a second learn() of the same process: nothing recorded since, no row;
    # then something is, and the row holds it alone
    obs.start(trainer="T", step=1)
    obs.end_cycle(step=2)
    _compile(obs.tracer, "add", 0.5)
    obs.start(trainer="T", step=2)
    obs.finish()
    rows = list(iter_rows(str(tmp_path)))
    kinds = [r["kind"] for r in rows]
    assert kinds[:3] == ["setup", "run_start", "cycle"] and kinds.count("setup") == 2
    first, second = (r for r in rows if r["kind"] == "setup")
    assert first["cycle"] == 1 and first["init_s"] == 6.0 and first["since_import_s"] > 0
    assert first["spans"] == [["model_init", 0.0, 4.0, None, {"params": 5}],
                              ["prompt_pipeline", 6.0, 7.0, None, {"prompts": 8}]]
    assert first["compiles"] == {
        "requests": 1, "built": 1, "read": 0, "written": 0, "trace_s": 0.25,
        "lower_s": 0.25, "build_s": 1.5, "read_s": 0.0, "built_s": 2.0}
    # the record starts where the trace did: 2 s before the event
    assert first["programs"] == [["jit__normal", 1, 2.0, 1]]
    assert first["by_span"] == {"model_init": [2.0, 1]}
    assert second["programs"] == [["jit_add", 1, 0.5, 1]] and second["spans"] == []
    assert "init_s" not in second and "since_import_s" not in second
    # nothing happened to the run: a monitor that counts the events tail
    # (the benchmark's `correct`) must not see the row
    assert "setup" not in obs.events_tail()


def test_a_program_compiled_inside_a_span_is_recorded_under_it(tmp_path):
    obs = RunObserver(ObsConfig(), str(tmp_path))
    program, name = _probe_program(1)
    with obs.span("generate"):
        program(np.ones(3, np.float32))
    (record,) = [c for c in obs.tracer._compiles if c[0] == name]
    _, t0, t1, built, parent = record
    assert t1 > t0 and parent == "generate" and built in (True, False)
    totals = obs.tracer._compile_totals
    assert totals["built"] + totals["read"] == len(obs.tracer._compiles) >= 1
    assert totals["trace_s"] > 0 and totals["lower_s"] > 0
    obs.finish()


def test_a_second_call_of_a_compiled_program_records_nothing(tmp_path):
    obs = RunObserver(ObsConfig(), str(tmp_path))
    program, name = _probe_program(2)
    x = np.ones(3, np.float32)
    program(x)
    seen = len(obs.tracer._compiles)
    assert [c[0] for c in obs.tracer._compiles].count(name) == 1
    program(x)
    assert len(obs.tracer._compiles) == seen
    obs.finish()


def test_a_cycle_without_compiles_writes_the_row_it_wrote_before(tmp_path):
    obs = RunObserver(ObsConfig(), str(tmp_path), clock=_FakeClock(1.0))
    obs.start(trainer="T", step=0)
    _compile(obs.tracer, "generate", 2.0, hit=True)
    obs.end_cycle(step=1, policy_version=1)
    obs.end_cycle(step=2, policy_version=2)
    obs.finish()
    compiled, steady, _final = (r for r in iter_rows(str(tmp_path)) if r["kind"] == "cycle")
    assert set(steady) == _STEADY_CYCLE_KEYS
    assert set(compiled) == _STEADY_CYCLE_KEYS | {"compiles", "compile_totals"}
    assert compiled["compiles"] == [["jit_generate", -2.0, 0.0, False, None]]
    assert compiled["compile_totals"]["read"] == 1 and compiled["compile_totals"]["read_s"] == 2.0


def test_cycle_row_keeps_the_64_longest_compiles_and_counts_the_rest(tmp_path):
    clock = _FakeClock(0.0)
    obs = RunObserver(ObsConfig(), str(tmp_path), clock=clock)
    obs.start(trainer="T", step=0)
    for i in range(70):
        clock.t += 1.0
        _compile(obs.tracer, f"p{i}", 0.001 * (i + 1))
    obs.end_cycle(step=1)
    obs.finish()
    row = next(r for r in iter_rows(str(tmp_path)) if r["kind"] == "cycle")
    assert len(row["compiles"]) == 64 and row["compiles_more"] == 6
    # the six shortest went, the rest stay in time order; the totals hold all
    assert [c[0] for c in row["compiles"]] == [f"jit_p{i}" for i in range(6, 70)]
    assert row["compile_totals"]["built"] == 70
    assert row["compile_totals"]["build_s"] == pytest.approx(0.001 * 70 * 71 / 2)


def test_programs_by_name_keeps_the_totals_under_forty_names_and_others():
    from trlx_tpu.obs.spans import OTHERS, compiles_by_span, programs_by_name

    records = [[f"jit_p{i % 50}", 0.0, 0.5 + (i % 50), i % 3 == 0, "model_init" if i % 2 else None]
               for i in range(120)]
    rows = programs_by_name(records)
    assert len(rows) == 41 and rows[-1][0] == OTHERS
    assert [r[0] for r in rows[:3]] == ["jit_p49", "jit_p48", "jit_p47"]
    assert sum(r[1] for r in rows) == 120
    assert sum(r[2] for r in rows) == pytest.approx(sum(r[2] - r[1] for r in records))
    assert sum(r[3] for r in rows) == sum(bool(r[3]) for r in records) == 40
    assert programs_by_name(records[:30])[-1][0] != OTHERS  # thirty names: no such line
    by_span = compiles_by_span(records)
    assert set(by_span) == {"model_init", "(no span)"}
    assert sum(v[0] for v in by_span.values()) == pytest.approx(sum(r[2] for r in rows))
    assert sum(v[1] for v in by_span.values()) == 40


def test_compile_events_reach_the_observer_built_last_through_one_listener(tmp_path):
    import jax._src.monitoring as monitoring

    from trlx_tpu.obs import observer as O

    first = RunObserver(ObsConfig(), str(tmp_path / "a"))
    second = RunObserver(ObsConfig(), str(tmp_path / "b"))
    program, name = _probe_program(3)
    program(np.ones(3, np.float32))
    assert first.tracer._compiles == [] and not any(first.tracer._compile_totals.values())
    assert name in [c[0] for c in second.tracer._compiles]
    assert monitoring.get_event_duration_listeners().count(O._forward_compile_duration) == 1
    assert monitoring.get_event_listeners().count(O._forward_compile_event) == 1
    # the target is held weakly: a trainer that is gone takes its observer along
    del second
    import gc

    gc.collect()
    assert O._compile_target() is None
    _probe_program(4)[0](np.ones(3, np.float32))  # nobody listens, nothing breaks
    first.finish()


def test_a_listener_that_raises_disarms_the_observer_and_the_compile_goes_through(tmp_path):
    obs = RunObserver(ObsConfig(), str(tmp_path))

    def broken(*args):
        raise RuntimeError("from the listener")

    obs.tracer.on_compile_duration = broken
    program, _ = _probe_program(5)
    out = program(np.ones(3, np.float32))
    assert np.allclose(np.asarray(out), 7.0) and not obs.active
    # disarmed: later events are dropped before they reach the tracer
    obs.tracer.on_compile_event = broken
    _probe_program(6)[0](np.ones(3, np.float32))
    obs.finish()


def test_obs_disabled_registers_no_compile_listener(tmp_path, monkeypatch):
    import jax.monitoring

    from trlx_tpu.obs import observer as O

    calls = []
    monkeypatch.setattr(O, "_forwarders_registered", False)
    monkeypatch.setattr(O, "_compile_target", None)
    monkeypatch.setattr(jax.monitoring, "register_event_duration_secs_listener", calls.append)
    monkeypatch.setattr(jax.monitoring, "register_event_listener", calls.append)
    off = RunObserver(ObsConfig.from_dict({"enabled": False}), str(tmp_path / "a"))
    reader = RunObserver(ObsConfig(), str(tmp_path / "b"), is_writer=False)  # not process 0
    assert calls == [] and O._compile_target is None
    _probe_program(7)[0](np.ones(3, np.float32))
    assert off.tracer._compiles == [] and reader.tracer._compiles == []
    on = RunObserver(ObsConfig(), str(tmp_path / "c"))
    assert calls == [O._forward_compile_duration, O._forward_compile_event]
    assert O._compile_target() is on
    on.finish()


def test_compiles_from_several_threads_land_in_one_list_with_whole_totals():
    import sys
    import threading

    t = SpanTracer(clock=_FakeClock(0.0), annotate=None)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(200):
                _compile(t, f"w{k}", 0.5, hit=bool(i % 2), trace_s=0.25, lower_s=0.25)

        threads = [threading.Thread(target=work, args=(k,)) for k in range(16)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    t.start_cycle(1.0)
    totals = t.cycle_compile_totals
    assert len(t.cycle_compiles) == 3200 and totals["requests"] == 3200
    # a thread's hit marks its own compile alone: half read, half built
    assert totals["built"] == totals["read"] == 1600
    assert totals["built_s"] == pytest.approx(1600 * 1.0)
    assert all(c[2] - c[1] == pytest.approx(1.0) for c in t.cycle_compiles)


# ---------------------------------------------------------------------------
# flight recorder: rotation + atomic append + torn-tail tolerance
# ---------------------------------------------------------------------------


def test_recorder_rotation_and_retention(tmp_path):
    rec = FlightRecorder(str(tmp_path), "runA", rotate_bytes=4096, keep_files=3)
    for i in range(400):
        rec.append("cycle", cycle=i, payload="x" * 64)
    rec.close()
    files = flight_files(str(tmp_path))
    assert 1 < len(files) <= 3, files
    rows = list(iter_rows(str(tmp_path)))
    assert rows and all(r["run"] == "runA" for r in rows)
    # rotation order preserved within the retained window
    cycles = [r["cycle"] for r in rows if r["kind"] == "cycle"]
    assert cycles == sorted(cycles)


def test_recorder_survives_torn_tail_and_resumes_stream(tmp_path):
    """The chaos-sigterm-mid-write contract: a kill can tear at most
    the final line; the reader skips it and a relaunched recorder
    APPENDS to the same stream."""
    rec = FlightRecorder(str(tmp_path), "runA")
    for i in range(5):
        rec.append("cycle", cycle=i + 1)
    rec.close()
    path = flight_files(str(tmp_path))[-1]
    # simulate the SIGTERM landing mid-os.write: a torn, unparseable
    # final line (json cut at an arbitrary byte)
    with open(path, "a") as f:
        f.write('{"t": 1.0, "run": "runA", "kind": "cyc')
    rows = list(iter_rows(str(tmp_path)))
    assert len(rows) == 5  # torn tail skipped, nothing else lost
    # relaunch: same directory, restored run id -> same stream
    rec2 = FlightRecorder(str(tmp_path), "runA")
    rec2.append("cycle", cycle=6)
    rec2.close()
    rows = list(iter_rows(str(tmp_path)))
    assert [r["cycle"] for r in rows if r["kind"] == "cycle"] == [1, 2, 3, 4, 5, 6]
    assert len(flight_files(str(tmp_path))) == 1  # appended, not forked


def test_observer_correlation_ids_stable_across_resume(tmp_path):
    """run_id + cycle numbering survive a state_dict round trip (what
    state.json persists), so a resumed run's events correlate into the
    same stream instead of restarting at cycle 1."""
    clock = iter(np.arange(0.0, 1000.0, 0.5))
    obs = RunObserver(
        ObsConfig(), str(tmp_path), clock=lambda: float(next(clock)),
    )
    obs.start(trainer="T")
    obs.note_samples(8)
    obs.end_cycle(step=1, policy_version=1)
    obs.note_samples(8)
    obs.end_cycle(step=2, policy_version=2)
    saved = obs.state_dict()
    obs.finish()

    obs2 = RunObserver(
        ObsConfig(), str(tmp_path), clock=lambda: float(next(clock)),
    )
    assert obs2.run_id != obs.run_id  # fresh id until the restore
    obs2.load_state_dict(saved)
    assert obs2.run_id == obs.run_id
    obs2.start(trainer="T")
    obs2.note_samples(8)
    obs2.end_cycle(step=3, policy_version=3)
    obs2.finish()

    rows = list(iter_rows(str(tmp_path)))
    assert {r["run"] for r in rows} == {obs.run_id}
    cycles = [r["cycle"] for r in rows if r["kind"] == "cycle"]
    # numbering CONTINUES across the resume (the final partial cycles
    # from each finish() ride along after the real ones)
    assert cycles[:2] == [1, 2] and cycles[-1] >= 4
    assert obs2.telemetry.total_samples == 24


def test_flight_report_overlay_survives_duplicate_cycle_numbers(tmp_path):
    """A resume/rollback rewinds the cycle counter, so one run's stream
    can hold two cycle rows with the same number: the report must
    attach events by STREAM ORDER (an event belongs to the cycle row
    that closes after it), not by cycle number."""
    import importlib.util

    rec = FlightRecorder(str(tmp_path), "runA")
    rec.append("cycle", cycle=7, wall_s=1.0, phases={"rollout": 1.0})
    rec.append("restore", cycle=7, path="checkpoint_6")
    rec.append("guardrail_trip", cycle=7, signal="loss", detail="post-restore")
    rec.append("cycle", cycle=7, wall_s=2.0, phases={"fused_block": 2.0})
    rec.close()
    spec = importlib.util.spec_from_file_location(
        "flight_report_dup",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "flight_report.py",
        ),
    )
    fr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fr)
    out = fr.render(str(tmp_path))
    lines = out.splitlines()
    trip_ix = next(i for i, l in enumerate(lines) if "guardrail_trip" in l)
    second_cycle_ix = next(
        i for i, l in enumerate(lines) if "2.000" in l
    )
    first_cycle_ix = next(i for i, l in enumerate(lines) if "1.000" in l)
    # the post-restore trip renders AFTER the first cycle row and
    # BEFORE the re-run cycle row it happened inside
    assert first_cycle_ix < trip_ix < second_cycle_ix, out


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

# the telemetry.json contract: field golden for the committed artifact
TELEMETRY_TOP_KEYS = {"format", "provenance", "headline", "cycles"}
PROVENANCE_KEYS = {
    "run_id", "written_at", "backend", "device_kind", "device_count",
    "comparable", "param_count",
}
HEADLINE_KEYS = {
    "cycles", "total_samples", "total_real_tokens", "total_wall_s",
    "total_train_steps", "run_samples_per_sec", "samples_per_sec",
    "real_tokens_per_sec", "phase_s", "phase_share", "slowest_phase",
}


def test_telemetry_snapshot_golden_fields():
    agg = TelemetryAggregator(window=4)
    agg.set_param_count(1000)
    for i in range(3):
        agg.note_samples(16)
        agg.note_tokens(256.0)
        agg.close_cycle(
            2.0, {"rollout": 1.2, "fused_block": 0.6, "other": 0.2},
            step=i + 1, policy_version=i + 1, n_steps=2,
        )
    snap = agg.snapshot("abc123")
    assert TELEMETRY_TOP_KEYS <= set(snap)
    assert PROVENANCE_KEYS <= set(snap["provenance"])
    assert snap["provenance"]["run_id"] == "abc123"
    head = snap["headline"]
    assert HEADLINE_KEYS <= set(head) | {"samples_per_sec"}
    # headline samples/s excludes the compile-dominated first cycle
    assert head["samples_per_sec"] == pytest.approx(16 / 2.0)
    assert head["total_samples"] == 48
    assert head["slowest_phase"] == "rollout"
    # phase shares over the window sum to 1 (the partition invariant
    # carried through aggregation)
    assert sum(head["phase_share"].values()) == pytest.approx(1.0, abs=1e-3)
    # CPU backend: MFU honestly absent rather than fabricated
    assert "mfu_estimate" not in head


def test_mfu_estimate_needs_a_known_chip():
    """A device the peak table does not list gets no MFU (no default
    peak stands in for it); a listed one does."""

    def estimate(device_kind):
        agg = TelemetryAggregator(window=4)
        agg.set_param_count(1_000_000_000)
        agg.set_static(
            seq_length=64, batch_size=8,
            device={"backend": "tpu", "device_kind": device_kind,
                    "device_count": 1, "comparable": True},
        )
        for i in range(2):
            agg.note_samples(8)
            agg.note_tokens(256.0)
            agg.close_cycle(1.0, {"rollout": 1.0}, step=i + 1, n_steps=1)
        return agg.headline().get("mfu_estimate")

    assert estimate("TPU v99") is None
    assert estimate("TPU v5 lite") > 0


def test_telemetry_headline_without_samples_keeps_phase_attribution():
    """Offline trainers (DPO/SFT/ILQL) never collect rollout samples;
    the headline must still carry the phase breakdown."""
    agg = TelemetryAggregator(window=4)
    for i in range(3):
        agg.close_cycle(
            1.0, {"train_step": 0.8, "other": 0.2}, step=i + 1, n_steps=4,
        )
    head = agg.headline()
    assert head["slowest_phase"] == "train_step"
    assert head["phase_s"]["train_step"] > 0
    assert "samples_per_sec" not in head


def test_observer_malformed_saved_state_disarms_not_crashes(tmp_path):
    obs = RunObserver(ObsConfig(), str(tmp_path))
    obs.load_state_dict({"run_id": "x", "total_samples": None})
    assert not obs.active  # disarmed; the checkpoint restore survives
    obs.finish()  # still closes cleanly


def test_tree_param_count_counts_float_leaves_only():
    import jax.numpy as jnp

    tree = {"w": jnp.zeros((4, 8)), "ids": jnp.zeros((16,), jnp.int32),
            "b": jnp.zeros((8,))}
    assert tree_param_count(tree) == 4 * 8 + 8


# ---------------------------------------------------------------------------
# profiler arming
# ---------------------------------------------------------------------------


def test_profiler_arms_window_offtpu_creates_dir_no_trace(tmp_path):
    arm = ProfilerArm(
        ProfileConfig(start_cycle=2, stop_cycle=3), str(tmp_path)
    )
    arm.begin_cycle(1)
    assert not arm.capturing
    arm.begin_cycle(2)
    assert arm.capturing and arm.captures == 1
    assert os.path.isdir(os.path.join(str(tmp_path), "cycle-00002"))
    assert arm.traced == 0  # off-TPU: armed, dir created, no jax trace
    arm.end_cycle(2)
    assert arm.capturing  # window spans cycle 3
    arm.end_cycle(3)
    assert not arm.capturing
    arm.begin_cycle(4)
    assert not arm.capturing and arm.captures == 1


def test_profiler_one_shot_on_perf_trip(tmp_path):
    arm = ProfilerArm(ProfileConfig(on_trip=True), str(tmp_path))
    arm.begin_cycle(5)
    assert not arm.capturing
    arm.note_trip("loss")  # not a perf/memory signal
    arm.begin_cycle(6)
    assert not arm.capturing
    arm.note_trip("cycle_time")
    arm.begin_cycle(7)
    assert arm.capturing
    arm.end_cycle(7)
    assert not arm.capturing  # one shot


# ---------------------------------------------------------------------------
# Tracker.close() drains staged deferred stats (ISSUE 11 satellite)
# ---------------------------------------------------------------------------


def test_tracker_close_flushes_staged_deferred_stats(tmp_path):
    """The shutdown-ordering pin: metrics staged behind the async
    device->host copy but not yet flushed when the tracker tears down
    must still reach the backends — close() drains the attached
    flushers BEFORE closing, and is idempotent (a later log() is a
    silent no-op, not a crash)."""
    from trlx_tpu.utils.trackers import DeferredStats, Tracker

    class Cfg:
        pass

    cfg = Cfg()
    cfg.train = Cfg()
    cfg.train.tracker = "jsonl"
    cfg.train.run_name = "t"
    cfg.train.checkpoint_dir = str(tmp_path)
    cfg.train.logging_dir = None
    cfg.model = Cfg()
    cfg.model.model_path = "random"

    tracker = Tracker(cfg)
    deferred = DeferredStats()
    import jax.numpy as jnp

    deferred.stage({"losses/x": jnp.float32(1.5)}, step=7)

    def flush():
        for stats, step, _meta in deferred.flush():
            tracker.log(stats, step=step)

    tracker.attach_pending(flush)
    tracker.close()
    assert not deferred  # drained by close, not dropped
    with open(os.path.join(str(tmp_path), "logs", "metrics.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    assert any(r.get("losses/x") == 1.5 and r["_step"] == 7 for r in recs)
    tracker.close()  # idempotent
    tracker.log({"late": 1.0}, step=8)  # silent no-op after close


# ---------------------------------------------------------------------------
# integration: the acceptance criterion (one tiny fault-free learn())
# ---------------------------------------------------------------------------


def _tiny_ppo_config(ckpt_dir: str):
    from trlx_tpu.data.default_configs import default_ppo_config

    return default_ppo_config().evolve(
        train=dict(
            batch_size=8, total_steps=4, eval_interval=100,
            checkpoint_interval=2, seq_length=24, epochs=64,
            tracker="jsonl", checkpoint_dir=ckpt_dir, save_best=False,
        ),
        model=dict(
            model_path="random", num_layers_unfrozen=-1,
            model_extra_configs={
                "transformer": dict(
                    vocab_size=258, hidden_size=64, n_layer=2, n_head=2,
                    n_positions=64,
                )
            },
        ),
        tokenizer=dict(tokenizer_path="byte"),
        method=dict(
            num_rollouts=8, chunk_size=8, ppo_epochs=1,
            gen_kwargs=dict(max_new_tokens=8, top_k=0, top_p=1.0,
                            do_sample=True),
        ),
    )


@pytest.fixture(scope="module")
def faultfree_run(tmp_path_factory):
    """ONE tiny fault-free learn(), shared by the tests below."""
    import trlx_tpu

    ckpt_dir = str(tmp_path_factory.mktemp("faultfree") / "ckpts")
    prompts = ["hello world", "the cat", "a b", "xyz",
               "what is", "I am", "go", "ok"]

    def reward(samples, prompts, outputs, **kw):
        return [float(len(o)) for o in outputs]

    # no EOS: every row generates its whole budget, so token counts
    # are exact (as the benchmark's mixes do)
    config = _tiny_ppo_config(ckpt_dir)
    config.method.gen_kwargs["eos_token_id"] = -1
    trainer = trlx_tpu.train(reward_fn=reward, prompts=prompts, config=config)
    return trainer, ckpt_dir


def test_faultfree_learn_emits_flight_stream_and_telemetry(faultfree_run):
    trainer, ckpt_dir = faultfree_run
    flight_dir = os.path.join(ckpt_dir, "flight")
    rows = list(iter_rows(flight_dir))
    assert rows, "default-on obs produced no flight stream"
    kinds = {r["kind"] for r in rows}
    assert {"run_start", "cycle", "checkpoint", "run_end"} <= kinds, kinds

    # per-cycle phase walls sum to cycle wall (the span invariant,
    # end to end through a real learn)
    cycles = [r for r in rows if r["kind"] == "cycle"]
    assert cycles
    for c in cycles:
        assert sum(c["phases"].values()) == pytest.approx(
            c["wall_s"], rel=0.02, abs=0.02
        ), c
    # correlation: every cycle row carries the run id + policy version
    assert all(r["run"] == trainer.obs.run_id for r in rows)
    assert cycles[-1]["pv"] == trainer._policy_version

    # samples/s matches the trainer's existing rollout accounting:
    # every counted sample is an n_collected rollout (num_rollouts per
    # completed collection)
    total = sum(c["samples"] for c in cycles)
    assert total == trainer.obs.telemetry.total_samples
    assert total % 8 == 0 and total >= 8

    # telemetry.json committed alongside the checkpoint, provenance-
    # stamped, and hashed by the same integrity manifest
    steps = sorted(
        e for e in os.listdir(ckpt_dir) if e.startswith("checkpoint_")
    )
    assert steps
    telem_fp = os.path.join(ckpt_dir, steps[-1], "telemetry.json")
    with open(telem_fp) as f:
        telem = json.load(f)
    assert telem["provenance"]["run_id"] == trainer.obs.run_id
    assert telem["headline"]["total_samples"] >= 8
    with open(os.path.join(ckpt_dir, steps[-1], "integrity.json")) as f:
        manifest = json.load(f)
    assert any("telemetry.json" in k for k in manifest["files"]), (
        "telemetry.json escaped the integrity manifest"
    )

    # the guardrail trip tail rides state.json (empty here — fault-free
    # run with guardrails off ships no key; the restore path is pinned
    # by the observer round-trip test above)
    with open(os.path.join(ckpt_dir, steps[-1], "state.json")) as f:
        state = json.load(f)
    assert state["obs"]["run_id"] == trainer.obs.run_id

    # flight_report renders it
    fr = _flight_report()
    rendered = fr.render(flight_dir)
    assert "slowest-phase attribution" in rendered
    assert trainer.obs.run_id in rendered


def test_learn_writes_work_site_spans_into_every_cycle_row(faultfree_run):
    trainer, ckpt_dir = faultfree_run
    rows = list(iter_rows(os.path.join(ckpt_dir, "flight")))
    cycles = [r for r in rows if r["kind"] == "cycle" and r["samples"]]
    assert len(cycles) >= 2
    for c in cycles:
        by_name = {}
        for name, t0, t1, parent, counts in c["spans"]:
            assert t1 >= t0, c
            by_name.setdefault(name, []).append((t0, t1, parent, counts))
        assert {"generate", "tokens_wait", "score_dispatch", "block_wait"} <= set(by_name), c
        (t0, t1, parent, counts), = by_name["tokens_wait"]
        # 8 rollouts x 8 new tokens, counted where the rows land
        assert counts == {"rows": 8, "tokens": 64} and parent == "rollout"
        # the pull follows the sampler's dispatch; scoring is dispatched
        # only after the pull has returned
        assert by_name["generate"][0][1] <= t0
        assert t1 <= by_name["score_dispatch"][0][0]
        assert all(p == "fused_block" for *_, p, _ in by_name["block_wait"])
    # time/rollout_generate: the dispatch plus the wait for the tokens
    with open(os.path.join(ckpt_dir, "logs", "metrics.jsonl")) as f:
        logged = [json.loads(line) for line in f]
    gen_times = [r["time/rollout_generate"] for r in logged if "time/rollout_generate" in r]
    assert gen_times and all(t > 0 for t in gen_times)


def _flight_report():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "flight_report_obs",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "flight_report.py",
        ),
    )
    fr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fr)
    return fr


def test_learn_writes_the_constructors_spans_into_one_setup_row(faultfree_run):
    trainer, ckpt_dir = faultfree_run
    rows = list(iter_rows(os.path.join(ckpt_dir, "flight")))
    assert [r["kind"] for r in rows[:2]] == ["setup", "run_start"]
    (setup,) = [r for r in rows if r["kind"] == "setup"]
    spans = {}
    for name, t0, t1, parent, counts in setup["spans"]:
        assert t1 >= t0 >= 0.0
        spans.setdefault(name, []).append((t0, t1, parent, counts))
    assert set(spans) == {"model_init", "ref_init", "opt_init", "prompt_pipeline"}
    (m0, m1, parent, counts), = spans["model_init"]
    assert parent is None and counts == {"params": tree_param_count(trainer.params)}
    (r0, r1, parent, counts), = spans["ref_init"]
    assert parent == "model_init" and m0 <= r0 <= r1 <= m1
    assert counts == {"params": tree_param_count(trainer.ref_params)}
    assert m1 <= spans["opt_init"][0][0]
    # the prompts, then the evaluation prompts (the first batch of them)
    assert [c for *_, c in spans["prompt_pipeline"]] == [{"prompts": 8}] * 2
    # the constructor ends before the pipelines are built
    assert spans["opt_init"][0][1] <= setup["init_s"] <= spans["prompt_pipeline"][0][0]
    assert setup["since_import_s"] > 0
    # eager init compiles one small program an operation: all before cycle 1
    c = setup["compiles"]
    assert c["built"] + c["read"] == sum(n for _, n, *_ in setup["programs"]) > 10
    assert sum(v[0] for v in setup["by_span"].values()) == pytest.approx(
        sum(s for _, _, s, _ in setup["programs"]), abs=1e-4)
    assert "model_init" in setup["by_span"]
    # the sampler, the scorer and the block are built in cycle 1 and named there
    first = next(r for r in rows if r["kind"] == "cycle")
    named = {name: parent for name, _, _, _, parent in first["compiles"]}
    assert named["jit_generate"] == "generate"
    assert named["jit_ppo_experience_fwd"] == "score_dispatch"
    assert named["jit_fused_train_step"] == "fused_block"
    rendered = _flight_report().render(os.path.join(ckpt_dir, "flight"))
    assert "[setup] import to observer" in rendered and "compiled under: model_init" in rendered
    assert "largest programs: jit_" in rendered


def test_a_recompile_is_named_in_its_cycles_row_and_in_the_report(tmp_path):
    """A chunk with a new row count in the second cycle: that cycle's row
    names `jit_generate` under `generate`, the cycle after it compiles
    nothing, and the report has the operator's line."""
    import re

    from trlx_tpu.utils.loading import get_pipeline, get_trainer

    ckpt_dir = str(tmp_path / "ckpts")
    config = _tiny_ppo_config(ckpt_dir).evolve(
        train=dict(total_steps=5, checkpoint_interval=100))
    config.method.gen_kwargs["eos_token_id"] = -1
    trainer = get_trainer(config.train.trainer)(
        config=config,
        reward_fn=lambda samples, prompts, outputs, **kw: [float(len(o)) for o in outputs])
    prompts = ["hello world", "the cat", "a b", "xyz", "what is", "I am", "go", "ok"]
    pipeline = get_pipeline(config.train.pipeline)
    trainer.add_prompt_pipeline(pipeline(prompts, 16, trainer.tokenizer))
    trainer.add_eval_pipeline(pipeline(prompts, 16, trainer.tokenizer))
    chunks = trainer.prompt_iterator

    def ragged():  # 8 rows, then 16 at a time
        yield next(chunks)
        while True:
            a, b = next(chunks), next(chunks)
            yield a.replace(
                input_ids=np.concatenate([a.input_ids, b.input_ids]),
                attention_mask=np.concatenate([a.attention_mask, b.attention_mask]))

    trainer.prompt_iterator = ragged()
    trainer.learn()
    flight_dir = os.path.join(ckpt_dir, "flight")
    first, second, third = [r for r in iter_rows(flight_dir)
                            if r["kind"] == "cycle" and r["samples"]]
    assert (first["samples"], second["samples"], third["samples"]) == (8, 16, 16)
    recompiled = {name: parent for name, _, _, _, parent in second["compiles"]}
    assert recompiled["jit_generate"] == "generate"
    assert recompiled["jit_fused_train_step"] == "fused_block"  # two steps a block now
    assert all(0.0 <= t0 <= t1 <= second["wall_s"] for _, t0, t1, _, _ in second["compiles"])
    assert "compiles" not in third and "compile_totals" not in third
    # built outside `trlx_tpu.train()`: the constructor's end was never stamped
    setup = next(r for r in iter_rows(flight_dir) if r["kind"] == "setup")
    assert "init_s" not in setup and "since_import_s" in setup
    rendered = _flight_report().render(flight_dir)
    assert re.search(r"cycle 2: (built|read) jit_generate \d+\.\d\d s under generate", rendered)
    assert "cycle 3: " not in rendered


def test_backward_depth_gauges_once_per_built_step(faultfree_run, tmp_path):
    """`model/layers` and `model/backward_layers` land once per built
    train step, in the flight stream and in the tracker. All layers
    trainable: the backward runs through both. Hydra top-1 of 2: through
    one (the stop at the branch point is engaged)."""
    from trlx_tpu.utils.loading import get_trainer

    trainer, ckpt_dir = faultfree_run
    rows = list(iter_rows(os.path.join(ckpt_dir, "flight")))
    end = [r["kind"] for r in rows].index("run_end")
    built = [r for r in rows[:end] if r["kind"] == "gauge" and "model/layers" in r]
    # learn() builds the per-step program and the fused block, once each
    assert [(r["model/layers"], r["model/backward_layers"]) for r in built] == [(2, 2)] * 2
    with open(os.path.join(ckpt_dir, "logs", "metrics.jsonl")) as f:
        logged = [json.loads(line) for line in f]
    gauges = [r for r in logged if "model/backward_layers" in r]
    assert len(gauges) == 2 and gauges[0]["model/layers"] == 2.0
    # a fact of the program, not an event of the run: a monitor that
    # counts the events tail (the benchmark's `correct`) must not see it
    assert "gauge" not in trainer.obs.events_tail()

    hydra_dir = str(tmp_path / "ckpts")
    config = _tiny_ppo_config(hydra_dir).evolve(model=dict(num_layers_unfrozen=1))
    trainer = get_trainer(config.train.trainer)(config=config)
    trainer.make_fused_train_steps()
    trainer.make_train_step()
    built = [r for r in iter_rows(os.path.join(hydra_dir, "flight"))
             if r["kind"] == "gauge"]
    assert [(r["model/layers"], r["model/backward_layers"]) for r in built] == [(2, 1)] * 2


def test_head_gathered_once_gauge_follows_the_mesh(faultfree_run, tmp_path):
    """`model/head_gathered_once` lands in the row of `model/layers`: 0
    for the shared run (a tied head on a data-parallel mesh), 1 for an
    untied head with logit chunks on a mesh whose fsdp axis splits it."""
    from trlx_tpu.utils.loading import get_trainer

    _, ckpt_dir = faultfree_run
    rows = list(iter_rows(os.path.join(ckpt_dir, "flight")))
    assert {r["model/head_gathered_once"] for r in rows
            if r["kind"] == "gauge" and "model/layers" in r} == {0}

    mesh_dir = str(tmp_path / "ckpts")
    config = _tiny_ppo_config(mesh_dir).evolve(
        train=dict(mesh={"dp": 2, "fsdp": 4}, logit_chunks=2))
    config.model.model_extra_configs["transformer"]["tie_word_embeddings"] = False
    trainer = get_trainer(config.train.trainer)(config=config)
    trainer.make_fused_train_steps()
    built = [r for r in iter_rows(os.path.join(mesh_dir, "flight"))
             if r["kind"] == "gauge" and "model/layers" in r]
    assert [r["model/head_gathered_once"] for r in built] == [1]


def test_fused_decode_gauge_and_chunk_counts_reach_the_flight_stream(faultfree_run, tmp_path):
    """`gen/decode_attn_fused` lands once per built sampler, in the
    flight stream and the tracker: 0 for the shared run (no int8 cache),
    1 for a sampler over an int8 cache of whole 128-slot tiles, whose
    `tokens_wait` span then carries the chunks its decode steps
    streamed and held (7 steps of 8 rows in 2 layers, one chunk each)."""
    from trlx_tpu.utils.loading import get_trainer

    trainer, ckpt_dir = faultfree_run
    rows = list(iter_rows(os.path.join(ckpt_dir, "flight")))
    assert {r["gen/decode_attn_fused"] for r in rows if "gen/decode_attn_fused" in r} == {0}

    fused_dir = str(tmp_path / "ckpts")
    config = _tiny_ppo_config(fused_dir)
    config.model.model_extra_configs["transformer"].update(
        n_positions=128, kv_cache_quant="int8")
    config.method.gen_kwargs["eos_token_id"] = -1
    trainer = get_trainer(config.train.trainer)(config=config)
    trainer.obs.start(step=0)
    out = trainer.generate(np.ones((8, 120), np.int32))
    trainer.generate(np.ones((8, 120), np.int32))  # the sampler is built once
    trainer._pull_sampled_tokens(out, 8, {})
    trainer.obs.end_cycle(step=0)
    rows = list(iter_rows(os.path.join(fused_dir, "flight")))
    assert [r["gen/decode_attn_fused"] for r in rows if r["kind"] == "gauge"] == [1]
    (cycle,) = [r for r in rows if r["kind"] == "cycle"]
    (counts,) = [c for name, *_, c in cycle["spans"] if name == "tokens_wait"]
    assert counts == {"rows": 8, "tokens": 64, "cache_chunks_read": 112, "cache_chunks_held": 112}


def test_stationary_decode_gauge_follows_the_mesh(faultfree_run, tmp_path):
    """`gen/decode_weights_stationary` lands with `gen/decode_attn_fused`,
    once per built sampler: 0 for the shared run (data parallel alone:
    no axis shards a kernel), 1 on a mesh whose fsdp axis does and
    divides the rows, 0 again for rows it does not divide."""
    from trlx_tpu.models.generation import SamplerSettings
    from trlx_tpu.utils.loading import get_trainer

    trainer, ckpt_dir = faultfree_run
    rows = list(iter_rows(os.path.join(ckpt_dir, "flight")))
    assert {r["gen/decode_weights_stationary"] for r in rows if r["kind"] == "gauge"
            and "gen/decode_attn_fused" in r} == {0}

    mesh_dir = str(tmp_path / "ckpts")
    config = _tiny_ppo_config(mesh_dir).evolve(train=dict(mesh={"dp": 2, "fsdp": 4}))
    trainer = get_trainer(config.train.trainer)(config=config)
    assert trainer._lm().mesh is trainer.mesh
    settings = SamplerSettings(max_new_tokens=8)
    trainer._get_generate_fn(settings, (8, 16))
    trainer._get_generate_fn(settings, (8, 16))  # built once
    trainer._get_generate_fn(settings, (12, 16))  # 12 rows over dp x fsdp = 8
    gauges = [r for r in iter_rows(os.path.join(mesh_dir, "flight")) if r["kind"] == "gauge"]
    assert [r["gen/decode_weights_stationary"] for r in gauges] == [1, 0]
    assert [r["gen/decode_attn_fused"] for r in gauges] == [0, 0]
    with open(os.path.join(mesh_dir, "logs", "metrics.jsonl")) as f:
        logged = [json.loads(line) for line in f]
    assert [r["gen/decode_weights_stationary"] for r in logged
            if "gen/decode_weights_stationary" in r] == [1.0, 0.0]


def test_cycle_programs_carry_their_own_names(faultfree_run):
    """`XLA Modules` in a trace and the compile log name a program by
    its function: generation, scoring and the train step each have one."""
    import jax
    import jax.numpy as jnp

    trainer, _ = faultfree_run
    assert [f.__name__ for f in trainer._generate_fns.values()] == ["generate"]
    names = {getattr(f, "__name__", "") for f in trainer._experience_fns.values()}
    assert {"ppo_experience_fwd", "ppo_score_inject"} <= names

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)

    full, n = trainer._fused_epoch_batch()
    perms = trainer._epoch_perms(n)
    with trainer.mesh:
        text = trainer._fused_train_step.lower(
            abstract(trainer.params), abstract(trainer.opt_state),
            abstract(trainer.place_batch(full)),
            jax.ShapeDtypeStruct(perms.shape, jnp.int32),
        ).as_text(debug_info=True)
    assert "module @jit_fused_train_step" in text
    # the stages inside it that flax does not name (the loss and the
    # value head are differentiated: their scope rides inside jvp())
    for scope in ("jvp(loss)/", "jvp(value_head)/", "optimizer_update/"):
        assert scope in text, scope
    assert "jit_train_step" in trainer.make_train_step().lower(
        abstract(trainer.params), abstract(trainer.opt_state),
        abstract(jax.tree_util.tree_map(lambda x: x[:8], trainer.place_batch(full))),
    ).as_text()


def test_obs_disabled_restores_pre_obs_behavior(tmp_path):
    """{enabled: false} = no flight dir, no telemetry in checkpoints,
    no listeners — the pre-obs surface exactly."""
    import trlx_tpu

    ckpt_dir = str(tmp_path / "ckpts")
    config = _tiny_ppo_config(ckpt_dir).evolve(
        train=dict(obs=dict(enabled=False), total_steps=2)
    )
    prompts = ["hello world", "the cat", "a b", "xyz"]
    trainer = trlx_tpu.train(
        reward_fn=lambda samples, prompts, outputs, **kw: [1.0] * len(outputs),
        prompts=prompts, config=config,
    )
    assert not trainer.obs.active
    assert not os.path.isdir(os.path.join(ckpt_dir, "flight"))
    steps = [e for e in os.listdir(ckpt_dir) if e.startswith("checkpoint_")]
    assert steps
    assert not os.path.exists(
        os.path.join(ckpt_dir, sorted(steps)[-1], "telemetry.json")
    )
    # no obs blob in state.json either: verify_ckpt.py must not
    # advertise a flight stream that was never written
    with open(os.path.join(ckpt_dir, sorted(steps)[-1], "state.json")) as f:
        assert "obs" not in json.load(f)
