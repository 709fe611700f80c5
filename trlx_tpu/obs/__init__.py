"""Flight recorder: unified run telemetry, phase tracing, and
self-documenting perf artifacts (``train.obs.*``).

The repo grew five telemetry islands — watchdog phase beats, guardrail
trip history, memdoctor watermarks/OOM events, fleet membership and
broadcast records, and the supervisor's JSONL ledger — with no shared
timeline; and a run's speed was known only if someone benchmarked it
by hand. This subsystem closes both gaps:

  SpanTracer (obs/spans.py)
      a sibling consumer of the hang doctor's existing beat sites
      (``utils/watchdog.py`` — instrumentation lands ONCE): partitions
      host wall time into the phases the trainers already beat
      (rollout, reward, fused_block, train_step, checkpoint, eval,
      experience, exp_wait), innermost-phase attribution, per cycle.
      By construction the phase walls sum to the cycle wall exactly.
      Below the phases, work-site spans (``obs.span(name, **counts)``
      around code the trainer already has: generate, tokens_wait,
      score_dispatch, block_wait, ...) ride each ``cycle`` row without
      touching the partition; phases and spans are mirrored into a
      profiler capture as ``trlx:<name>`` annotations. The observer is
      built before the weights, so the constructor's set-up spans
      (model_init, opt_init, ...) and every compilation (named, timed,
      marked read or built, from JAX's own monitoring events) are kept:
      one ``setup`` row at the top of learn(), a ``compiles`` key in
      each ``cycle`` row that compiled.
  FlightRecorder (obs/recorder.py)
      ONE size-rotated JSONL event stream under
      ``<checkpoint_dir>/flight/``: per-cycle phase breakdowns plus
      typed events — guardrail trips and ladder actions, chaos
      injections, memdoctor watermark crossings and OOM-ladder rungs,
      fleet degradations, staleness rejections, supervisor restarts,
      checkpoint commits/restores — every row correlated by
      run_id / cycle / policy_version. Appends are single-write
      (crash-torn tails are skipped by the reader); rotation is by
      size with bounded retention.
  TelemetryAggregator (obs/telemetry.py)
      continuously derives the bench-comparable headline numbers from
      the trainer's OWN flushed stats (honest mask-weighted tokens/s,
      samples/s, phase breakdown, engine occupancy/refills/reclaimed
      pages, an analytic-FLOPs MFU estimate reusing the memory
      doctor's param accounting) and commits a ``telemetry.json``
      snapshot alongside every checkpoint — so every run records its
      own numbers, stamped with the device they were taken on.
  ProfilerArm (obs/profiler.py)
      on-demand ``jax.profiler`` window capture for cycles N..M
      (``train.obs.profile.*``), or one-shot on a guardrail
      perf/memory trip; no-op off-TPU.

Everything here is host-side, jax-free at module scope, never syncs
the device, and NEVER raises into the training loop (a broken
recorder logs once and goes quiet). Default ON with bounded host
cost; ``train.obs.enabled: false`` restores pre-obs behavior exactly.

Render a recorded stream with ``python scripts/flight_report.py
<checkpoint_dir>``; the runbook is docs/observability.md.
"""

from trlx_tpu.obs.config import ObsConfig, ProfileConfig
from trlx_tpu.obs.observer import RunObserver, build_observer
from trlx_tpu.obs.recorder import FlightRecorder, append_external, iter_rows
from trlx_tpu.obs.spans import SpanTracer
from trlx_tpu.obs.telemetry import TelemetryAggregator

__all__ = [
    "ObsConfig",
    "ProfileConfig",
    "RunObserver",
    "build_observer",
    "FlightRecorder",
    "append_external",
    "iter_rows",
    "SpanTracer",
    "TelemetryAggregator",
]
