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
