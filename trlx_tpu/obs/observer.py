"""RunObserver: the glue between the training loop and the flight
recorder / span tracer / telemetry / profiler.

Wiring (all in trainer/base.py and trainer/online.py, each a one-liner
at an existing site):

- beat sites: registered as a sibling listener on the hang doctor's
  heartbeat registry (``HangWatchdog.add_listener``) — the span tracer
  consumes the SAME beats the stall detector does, so phase
  instrumentation lands once;
- guardrail trips: a listener on ``GuardrailMonitor`` — every trip
  signal (loss/kl/reward/grad_norm/cycle_time/truncation/consistency/
  staleness/fleet/memory/stall/peer) lands in the stream the moment it
  is recorded, and perf/memory trips arm the one-shot profiler;
- chaos injections: ``ChaosMonkey.on_fire``;
- compiles: ONE process-wide pair of listeners on JAX's own monitoring
  events (``jax.monitoring``), forwarding to the observer built last
  through a weak reference; the tracer turns them into named, timed
  records, marked read or built, which ride the ``setup`` row and each
  ``cycle`` row that compiled;
- everything else (cycle boundaries, samples, OOM-ladder rungs,
  watermark crossings, checkpoint commits/restores, cross-host rows)
  is an explicit ``obs.*`` call from the trainer.

Contract: NO method here ever raises into the training loop. The first
failure logs, flips the observer broken, and every later call is a
cheap no-op — observability must never be the thing that kills a run.
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
import uuid
import weakref
from collections import deque
from typing import Any, Dict, Optional

from trlx_tpu.obs.config import ObsConfig
from trlx_tpu.obs.profiler import ProfilerArm
from trlx_tpu.obs.recorder import FlightRecorder
from trlx_tpu.obs.spans import (
    COMPILE_EVENTS,
    SpanTracer,
    compiles_by_span,
    longest_compiles,
    programs_by_name,
)
from trlx_tpu.obs.telemetry import TelemetryAggregator, device_provenance
from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def _no_raise(method):
    """Observability never breaks training: first failure logs and
    disarms the observer."""

    @functools.wraps(method)
    def wrapped(self, *args, **kwargs):
        if not self.active:
            return None
        try:
            return method(self, *args, **kwargs)
        except Exception as e:
            self._disarm(method.__name__, e)
            return None

    return wrapped


# A trainer has no end at which to un-register a listener, and a test
# process builds dozens: the two forwarders below are registered with
# JAX's monitoring once a process and hand each event to the observer
# built last, while it lives.
_compile_target: Optional["weakref.ref[RunObserver]"] = None
_forwarders_registered = False


def _forward_compile_duration(event: str, seconds: float, **kwargs: Any) -> None:
    if event in COMPILE_EVENTS and _compile_target is not None:
        obs = _compile_target()
        if obs is not None:
            obs._on_compile_duration(event, seconds, str(kwargs.get("fun_name", "?")))


def _forward_compile_event(event: str, **kwargs: Any) -> None:
    if event in COMPILE_EVENTS and _compile_target is not None:
        obs = _compile_target()
        if obs is not None:
            obs._on_compile_event(event)


def _listen_for_compiles(obs: "RunObserver") -> None:
    """Point the process's compile events at ``obs`` (lazy import: obs/
    stays jax-free at module scope)."""
    global _compile_target, _forwarders_registered
    _compile_target = weakref.ref(obs)
    if not _forwarders_registered:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_forward_compile_duration)
        jax.monitoring.register_event_listener(_forward_compile_event)
        _forwarders_registered = True


def _rounded(totals: Dict[str, Any]) -> Dict[str, Any]:
    return {k: round(v, 6) if isinstance(v, float) else v for k, v in totals.items()}


def _timed_rows(rows: list) -> list:
    """Span rows and compile records as written: times to the microsecond."""
    return [[name, round(t0, 6), round(t1, 6), *rest] for name, t0, t1, *rest in rows]


class RunObserver:
    """One per trainer; owns the run's flight stream + telemetry."""

    def __init__(
        self,
        cfg: ObsConfig,
        flight_dir: str,
        is_writer: bool = True,
        clock=time.monotonic,
        run_id: Optional[str] = None,
    ):
        self.cfg = cfg
        self.flight_dir = flight_dir
        # non-main hosts accumulate nothing: process 0 owns the stream
        # (cross-host rows arrive through the consensus-cadence gather)
        self.active = bool(cfg.enabled and is_writer)
        self._clock = clock
        self.run_id = run_id or uuid.uuid4().hex[:12]
        self.tracer = SpanTracer(clock=clock)
        self.telemetry = TelemetryAggregator(window=cfg.telemetry_window)
        self.recorder = FlightRecorder(
            flight_dir, self.run_id,
            rotate_bytes=cfg.rotate_bytes, keep_files=cfg.keep_files,
        )
        self.profiler = ProfilerArm(
            cfg.profile, os.path.join(flight_dir, "profiles"),
            enabled=self.active,
        )
        self._events: Dict[str, deque] = {}
        self._step: Optional[int] = None
        self._policy_version: Optional[int] = None
        self._started = False
        # the `setup` row's two figures no span can hold: the package's
        # import to here, and here to the constructor's return (end_init)
        import trlx_tpu

        self._since_import_s = time.monotonic() - trlx_tpu.IMPORTED_AT
        self._init_t0 = clock()
        self._init_s: Optional[float] = None
        if self.active:
            try:
                _listen_for_compiles(self)
            except Exception as e:
                self._disarm("compile listener", e)

    # -- attachment ------------------------------------------------------

    def attach(self, watchdog=None, guardrails=None, chaos=None) -> None:
        """Register as a sibling consumer on the existing telemetry
        islands (no-op when disabled, so default-off subsystems and
        ``train.obs.enabled: false`` both cost nothing)."""
        if not self.active:
            return
        if watchdog is not None:
            watchdog.add_listener(self._on_beat)
        if guardrails is not None:
            guardrails.add_listener(self._on_guardrail_trip)
        if chaos is not None:
            chaos.on_fire = self._on_chaos
        # keep beat timestamps and cycle boundaries on one timebase
        if watchdog is not None:
            self._clock = watchdog.clock
            self.tracer.set_clock(watchdog.clock)
            self._init_t0 = self._clock()

    # -- listeners -------------------------------------------------------

    def _on_beat(self, now, phase, event, step=None, count=1) -> None:
        if not self.active:
            return
        try:
            self.tracer.on_beat(now, phase, event, step, count)
        except Exception as e:
            # same contract as _no_raise: log ONCE, then go quiet — a
            # silently frozen stream is undebuggable
            self._disarm("span tracer on a beat", e)

    @contextlib.contextmanager
    def span(self, name: str, **counts: Any):
        """Work-site span around code the trainer already has:
        ``with self.obs.span("tokens_wait", rows=8) as counts: ...``
        (see :meth:`SpanTracer.span`). A null context when the
        observer is off. Same contract as ``_no_raise``: the tracer's
        own failures disarm the observer and never reach the body;
        the body's exceptions pass through untouched."""
        rec = None
        if self.active:
            try:
                rec = self.tracer.open_span(name, dict(counts))
            except Exception as e:
                self._disarm("span", e)
        try:
            yield rec["counts"] if rec is not None else {}
        finally:
            if rec is not None:
                try:
                    self.tracer.close_span(rec)
                except Exception as e:
                    self._disarm("span", e)

    @_no_raise
    def _on_compile_duration(self, event: str, seconds: float, fun_name: str) -> None:
        self.tracer.on_compile_duration(event, seconds, fun_name)

    @_no_raise
    def _on_compile_event(self, event: str) -> None:
        self.tracer.on_compile_event(event)

    def _disarm(self, what: str, e: Exception) -> None:
        self.active = False
        logger.error(
            "obs: %s failed (%s) — flight recorder disarmed for the "
            "rest of the run; training continues", what, e,
        )

    @_no_raise
    def _on_guardrail_trip(self, signal: str, detail: str) -> None:
        self.record("guardrail_trip", signal=signal, detail=detail)
        self.profiler.note_trip(signal)

    @_no_raise
    def _on_chaos(self, fired: Dict[str, Any]) -> None:
        self.record("chaos", **fired)

    # -- correlation + events --------------------------------------------

    @property
    def cycle(self) -> int:
        """The OPEN cycle's 1-based index."""
        return self.telemetry.cycle_count + 1

    def _remember(self, kind: str, row: Dict[str, Any]) -> None:
        tail = self._events.setdefault(kind, deque(maxlen=self.cfg.events_tail))
        tail.append(row)

    def events_tail(self) -> Dict[str, list]:
        return {k: list(v) for k, v in self._events.items()}

    def _correlated(self, fields: Dict[str, Any]) -> Dict[str, Any]:
        row = {"cycle": self.cycle, "step": self._step,
               "pv": self._policy_version}
        row.update(fields)  # caller's fields win (e.g. run_start's step)
        return row

    @_no_raise
    def record(self, kind: str, **fields: Any) -> None:
        """One correlated event row (run_id / cycle / step / policy
        version stamped here)."""
        row = self._correlated(fields)
        self.recorder.append(kind, **row)
        self._remember(kind, {"t": round(time.time(), 3), **row})

    @_no_raise
    def gauge(self, **values: Any) -> None:
        """One correlated `gauge` row: static facts of the program as
        built (`model/backward_layers`). Nothing happened to the run, so
        unlike `record` it stays out of the events tail, which a
        monitor may count as incidents."""
        self.recorder.append("gauge", **self._correlated(values))

    # -- run / cycle lifecycle -------------------------------------------

    @_no_raise
    def end_init(self) -> None:
        """The trainer's constructor has returned (``api.train()`` says
        so: nothing inside the constructor can)."""
        self._init_s = self._clock() - self._init_t0

    def _write_setup(self, first: bool) -> None:
        """One ``setup`` row from what the tracer recorded outside any
        cycle: the set-up spans, and every compile named, timed and marked
        read or built. Not an event of the run (nothing happened to it),
        so like a gauge it stays out of the events tail. A later
        ``learn()`` of the same process writes one only if something was
        recorded since the last cycle closed."""
        t = self.tracer
        totals = t.cycle_compile_totals
        if not (first or t.cycle_spans or any(totals.values())):
            return
        fields: Dict[str, Any] = {
            "spans": _timed_rows(t.cycle_spans),
            "compiles": _rounded(totals),
            "programs": [
                [name, n, round(seconds, 6), n_built]
                for name, n, seconds, n_built in programs_by_name(t.cycle_compiles)
            ],
            "by_span": {
                k: [round(seconds, 6), n_built]
                for k, (seconds, n_built) in compiles_by_span(t.cycle_compiles).items()
            },
        }
        if first:
            fields["since_import_s"] = round(self._since_import_s, 6)
            if self._init_s is not None:
                fields["init_s"] = round(self._init_s, 6)
        self.recorder.append("setup", **self._correlated(fields))

    @_no_raise
    def start(self, **meta: Any) -> None:
        """Arm at the top of learn(): stamps provenance, writes the
        ``setup`` row, opens the first cycle, and records ``run_start``
        (a resumed run appends to the same stream under the restored
        run_id)."""
        self.telemetry.set_static(device=device_provenance(), **meta)
        self._step = meta.get("step")
        # the time since the tracer was built, or between two learn()s,
        # belongs to no cycle
        self.tracer.start_cycle(self._clock())
        self._write_setup(first=not self._started)
        self._started = True
        self.record("run_start", **{k: v for k, v in meta.items() if v is not None})
        self.profiler.begin_cycle(self.cycle)

    @_no_raise
    def set_param_count(self, n: int) -> None:
        self.telemetry.set_param_count(n)

    @_no_raise
    def note_samples(self, n: int) -> None:
        self.telemetry.note_samples(n)

    @_no_raise
    def note_tokens(self, n: float) -> None:
        self.telemetry.note_tokens(n)

    @_no_raise
    def observe_stats(self, stats: Dict[str, Any], step: int) -> None:
        """Tap on the trainer's single ``_tracker_log`` funnel: every
        flushed host scalar the run already produces (the telemetry
        accounting reuses, never re-derives)."""
        self.telemetry.observe_stats(stats)

    @_no_raise
    def end_cycle(
        self, step: Optional[int] = None,
        policy_version: Optional[int] = None, n_steps: int = 0,
        final: bool = False,
    ) -> None:
        """Close one optimization cycle: snapshot the span partition,
        fold it into telemetry, write the ``cycle`` row, advance the
        profiler window. ``final`` (the finish() path) skips re-arming
        the profiler — a capture must not start for a cycle that will
        never run."""
        self._step = step
        self._policy_version = policy_version
        if not self._started:
            self.tracer.start_cycle(self._clock())
            self._started = True
            return
        closing = self.cycle
        wall, breakdown = self.tracer.snapshot_cycle(self._clock())
        row = self.telemetry.close_cycle(
            wall, breakdown, step=step, policy_version=policy_version,
            n_steps=n_steps,
        )
        # the cycle's work-site spans ride the same row, seconds from
        # the cycle's start: [name, t0, t1, parent, counts]
        spans = _timed_rows(self.tracer.cycle_spans)
        # and what it compiled, the key ABSENT from a cycle that compiled
        # nothing: [fun_name, t0, t1, built, parent], the longest
        compiled: Dict[str, Any] = {}
        if any(self.tracer.cycle_compile_totals.values()):
            records, more = longest_compiles(self.tracer.cycle_compiles)
            compiled = {
                "compiles": _timed_rows(records) or None,
                "compiles_more": more or None,
                "compile_totals": _rounded(self.tracer.cycle_compile_totals),
            }
        self.recorder.append("cycle", **row, spans=spans, **compiled)
        self.profiler.end_cycle(closing)
        if not final:
            self.profiler.begin_cycle(self.cycle)

    @_no_raise
    def record_hosts(self, ages: Dict[str, float], detail: Optional[str]) -> None:
        """Cross-host row at the consensus cadence: the local phase
        counters (equal beat counts at a lockstep gather; wall totals
        name the slow host) plus the straggler verdict, in the same
        correlated stream as everything else."""
        self.record(
            "hosts",
            ages={k: round(float(v), 1) for k, v in sorted(ages.items())},
            straggler=detail,
        )

    # -- artifacts -------------------------------------------------------

    @_no_raise
    def write_telemetry(self, path: str) -> None:
        """Commit a provenance-stamped ``telemetry.json`` snapshot
        (atomic tmp+rename — same pattern as state.json)."""
        from trlx_tpu.utils.checkpointing import atomic_json_write

        atomic_json_write(
            path, self.telemetry.snapshot(self.run_id, self.events_tail())
        )

    def finish(self) -> None:
        """learn()-exit hook: close the open cycle, refresh the
        flight-dir telemetry snapshot, stop any profiler capture.
        Deliberately NOT gated on ``active``: even after a mid-run
        disarm, an in-flight profiler trace must stop and the recorder
        fd must close — only the writes are skipped."""
        try:
            if self.active and self._started:
                self.end_cycle(step=self._step,
                               policy_version=self._policy_version,
                               final=True)
                self.record("run_end")
                self.write_telemetry(
                    os.path.join(self.flight_dir, "telemetry.json")
                )
        except Exception as e:
            logger.error("obs: finish failed (%s); closing anyway", e)
        finally:
            try:
                self.profiler.close()
            except Exception:
                pass
            self.recorder.close()

    # -- resumable state -------------------------------------------------

    def state_dict(self) -> Dict[str, Any]:
        return {"run_id": self.run_id, **self.telemetry.state_dict()}

    @_no_raise
    def load_state_dict(self, state: Optional[Dict[str, Any]]) -> None:
        """Adopt a checkpoint's observer state so correlation ids (and
        run totals) stay stable across resume: the relaunched process
        keeps appending to the same stream under the same run_id, and
        cycle numbering continues instead of restarting at 1. A
        malformed ``obs`` blob (hand-edited state.json, format drift)
        disarms the observer instead of crashing the restore — every
        other field of the checkpoint still loads."""
        if not state or not isinstance(state, dict):
            return
        rid = state.get("run_id")
        if rid:
            self.run_id = str(rid)
            self.recorder.run_id = self.run_id
        self.telemetry.load_state_dict(state)


def build_observer(
    train_config,
    checkpoint_dir: Optional[str] = None,
    is_writer: bool = True,
    watchdog=None,
    guardrails=None,
    chaos=None,
    clock=time.monotonic,
) -> RunObserver:
    """TrainConfig -> observer, attached to the run's telemetry
    islands (the ``obs`` field is a plain dict so the flat config
    dataclass stays YAML/back-compatible)."""
    cfg = ObsConfig.from_dict(getattr(train_config, "obs", None))
    root = checkpoint_dir or getattr(train_config, "checkpoint_dir", "ckpts")
    flight_dir = cfg.dir or os.path.join(root, "flight")
    obs = RunObserver(cfg, flight_dir, is_writer=is_writer, clock=clock)
    obs.attach(watchdog=watchdog, guardrails=guardrails, chaos=chaos)
    return obs
