"""Self-documenting perf artifacts: the bench-comparable headline
numbers, derived continuously from the trainer's OWN flushed stats.

The aggregator consumes exactly two inputs, both already produced by
the training loop (so the telemetry accounting CANNOT drift from the
trainer's accounting):

- per-cycle span snapshots (wall + phase partition) and sample/token
  counts from the rollout loop's honest mask-weighted ledger
  (``rollout/real_tokens`` — pad emissions are NOT tokens);
- the flushed tracker stats (engine occupancy / refills / reclaimed
  pages, losses), tapped at the single ``_tracker_log`` funnel.

``telemetry.json`` is committed alongside every checkpoint and
refreshed at the flight-dir root, provenance-stamped (run id, device
kind+count, backend, model geometry, timestamp), so every run carries
its own record of where its numbers came from.

The MFU estimate is analytic (2P FLOPs/token forward, 6P train
fwd+bwd, ref/experience forwards counted once each), reusing the
memory doctor's param accounting (:func:`tree_param_count`) for P —
an ESTIMATE for trend lines, not a profiler measurement; the field is
named ``mfu_estimate`` accordingly.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

# bf16 dense-matmul peak per chip, by device kind — the one table
# (bench.py reads it through chip_peak_tflops). A device that is not
# listed has no peak: no default stands in for it.
PEAK_TFLOPS = {
    "TPU v4": 275.0, "TPU v5 lite": 197.0, "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
}


def tree_param_count(tree) -> int:
    """Float-leaf element count of a param tree — the memory doctor's
    param accounting (``memdoctor._float_leaves``) reduced to a count
    instead of bytes, so the MFU numerator and the HBM plan size the
    same tree the same way."""
    import numpy as np

    from trlx_tpu.utils.memdoctor import _float_leaves

    total = 0
    for leaf in _float_leaves(tree):
        shape = getattr(leaf, "shape", ())
        total += int(np.prod(shape, dtype=np.int64)) if shape else 1
    return total


def chip_peak_tflops(device_kind: str) -> Optional[float]:
    """Peak for ``device_kind`` (longest matching prefix of the table),
    or None for a device the table does not know."""
    for key, peak in sorted(PEAK_TFLOPS.items(), key=lambda kv: -len(kv[0])):
        if device_kind.startswith(key):
            return peak
    return None


def device_provenance() -> Dict[str, Any]:
    """Best-effort device stamp (a CPU run stamps honestly as cpu: a
    non-TPU artifact must SAY so)."""
    try:
        import jax

        devs = jax.devices()
        return {
            "backend": jax.default_backend(),
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "comparable": jax.default_backend() == "tpu",
        }
    except Exception:
        return {"backend": "unknown", "device_kind": "unknown",
                "device_count": 0, "comparable": False}


# tracker-stat keys mirrored into the per-cycle rows / headline (means
# over the cycle's chunks, flush-cadence attribution)
_COUNTER_PREFIXES = ("moe/",)
_ENGINE_KEYS = (
    "rollout/engine_occupancy",
    "rollout/engine_refills",
    "rollout/engine_decode_steps",
    "rollout/engine_reclaimed_pages",
    "rollout/token_occupancy",
    "rollout/truncation_rate",
)


class TelemetryAggregator:
    """Rolling per-cycle ledger + run totals + headline derivation."""

    def __init__(self, window: int = 8, max_cycles: int = 64):
        self.window = max(int(window), 1)
        self.max_cycles = max(int(max_cycles), self.window + 1)
        self.cycles: List[Dict[str, Any]] = []  # bounded tail
        self.cycle_count = 0  # total cycles ever (survives the tail)
        # run totals (persisted across resume so the trajectory point
        # covers the whole run, not just the last incarnation)
        self.total_samples = 0
        self.total_real_tokens = 0.0
        self.total_wall_s = 0.0
        self.total_train_steps = 0
        # staged by the rollout loop, consumed by the next cycle close
        self._pending_samples = 0
        self._pending_tokens = 0.0
        self._last_stats: Dict[str, float] = {}
        self._pending_counters: Dict[str, float] = {}
        # model/static facts, set once by the trainer
        self.static: Dict[str, Any] = {}
        self._param_count: Optional[int] = None

    # -- inputs ----------------------------------------------------------

    def set_static(self, **facts: Any) -> None:
        self.static.update({k: v for k, v in facts.items() if v is not None})

    def set_param_count(self, n: int) -> None:
        self._param_count = int(n)

    def note_samples(self, n: int) -> None:
        self._pending_samples += int(n)

    def note_tokens(self, n: float) -> None:
        self._pending_tokens += float(n)

    def observe_stats(self, stats: Dict[str, Any]) -> None:
        for k in _ENGINE_KEYS:
            v = stats.get(k)
            if isinstance(v, (int, float)):
                self._last_stats[k.split("/", 1)[1]] = float(v)
        # counters the jitted programs carry out with their deferred stats
        # (a routed model's moe/*): they land in the row of the cycle in
        # which they were flushed, the one after the work they count
        for k, v in stats.items():
            if k.startswith(_COUNTER_PREFIXES):
                try:
                    self._pending_counters[k] = float(v)
                except (TypeError, ValueError):
                    pass

    def close_cycle(
        self, wall_s: float, breakdown: Dict[str, float],
        step: Optional[int] = None, policy_version: Optional[int] = None,
        n_steps: int = 0,
    ) -> Dict[str, Any]:
        """Fold one closed cycle in; returns the cycle row (what the
        flight recorder writes)."""
        self.cycle_count += 1
        samples, self._pending_samples = self._pending_samples, 0
        tokens, self._pending_tokens = self._pending_tokens, 0.0
        self.total_samples += samples
        self.total_real_tokens += tokens
        self.total_wall_s += wall_s
        self.total_train_steps += int(n_steps)
        row: Dict[str, Any] = {
            "cycle": self.cycle_count,
            "step": step,
            "pv": policy_version,
            "wall_s": round(wall_s, 4),
            "phases": {k: round(v, 4) for k, v in sorted(breakdown.items())},
            "samples": samples,
            "real_tokens": round(tokens, 1),
            "train_steps": int(n_steps),
        }
        if samples and wall_s > 0:
            row["samples_per_sec"] = round(samples / wall_s, 3)
        if self._pending_counters:
            row["counters"], self._pending_counters = self._pending_counters, {}
        if self._last_stats:
            row["engine"] = {
                k: round(v, 4) for k, v in sorted(self._last_stats.items())
            }
            # provenance: WHICH decode implementation (static sampler /
            # engine xla gather / engine pallas kernel, x lane groups)
            # produced the tokens behind these numbers
            if self.static.get("decode_impl"):
                row["engine"]["decode_impl"] = self.static["decode_impl"]
        self.cycles.append(row)
        del self.cycles[: max(len(self.cycles) - self.max_cycles, 0)]
        return row

    # -- derivation ------------------------------------------------------

    def _window_rows(self) -> List[Dict[str, Any]]:
        # exclude cycle 1 (compile-dominated) from the steady-state
        # headline whenever later cycles exist
        rows = [
            r for r in self.cycles
            if r["cycle"] > 1 and r.get("samples", 0) > 0
        ]
        if not rows:
            rows = [r for r in self.cycles if r.get("samples", 0) > 0]
        if not rows:
            # offline trainers (DPO/SFT/ILQL) never collect rollout
            # samples — the phase attribution must still ride the
            # headline, just without the samples/s keys
            rows = [r for r in self.cycles if r["cycle"] > 1] or list(self.cycles)
        return rows[-self.window:]

    def headline(self) -> Dict[str, Any]:
        rows = self._window_rows()
        out: Dict[str, Any] = {
            "cycles": self.cycle_count,
            "total_samples": self.total_samples,
            "total_real_tokens": round(self.total_real_tokens, 1),
            "total_wall_s": round(self.total_wall_s, 3),
            "total_train_steps": self.total_train_steps,
        }
        if self.total_wall_s > 0 and self.total_samples:
            out["run_samples_per_sec"] = round(
                self.total_samples / self.total_wall_s, 3
            )
        wall = sum(r["wall_s"] for r in rows)
        samples = sum(r.get("samples", 0) for r in rows)
        tokens = sum(r.get("real_tokens", 0.0) for r in rows)
        if wall > 0 and samples:
            out["samples_per_sec"] = round(samples / wall, 3)
        if wall > 0 and tokens:
            out["real_tokens_per_sec"] = round(tokens / wall, 1)
        # aggregate phase breakdown over the window (seconds + share)
        phases: Dict[str, float] = {}
        for r in rows:
            for k, v in r.get("phases", {}).items():
                phases[k] = phases.get(k, 0.0) + v
        if phases and wall > 0:
            out["phase_s"] = {k: round(v, 3) for k, v in sorted(phases.items())}
            out["phase_share"] = {
                k: round(v / wall, 4) for k, v in sorted(phases.items())
            }
            out["slowest_phase"] = max(phases.items(), key=lambda kv: kv[1])[0]
        if self._last_stats:
            out["engine"] = {
                k: round(v, 4) for k, v in sorted(self._last_stats.items())
            }
        # kernel attribution for the headline: a recorded telemetry.json
        # must say which decode implementation its tok/s number came
        # from (static sampler vs engine-paged-xla vs engine-paged-
        # pallas, x lane groups) — the same honesty rule as the bench
        # pillars' per-pillar attribution
        if self.static.get("decode_impl"):
            out["decode_impl"] = self.static["decode_impl"]
        mfu = self.mfu_estimate(rows)
        if mfu is not None:
            out["mfu_estimate"] = mfu
        return out

    def mfu_estimate(self, rows: List[Dict[str, Any]]) -> Optional[float]:
        """Analytic model-FLOPs utilization over the window: generated
        tokens pay one policy forward (2P), experience pays policy+ref
        teacher-forced forwards (4P per sample-token), train steps pay
        fwd+bwd (6P per trained token). P from the memory doctor's
        param accounting; peak from the device kind. None when any
        input is unknown (a CPU run or a chip missing from PEAK_TFLOPS
        reports no MFU rather than a fake)."""
        if not self._param_count or not rows:
            return None
        prov = self.static.get("device") or {}
        if not prov.get("comparable"):
            return None
        seq = self.static.get("seq_length") or 0
        batch = self.static.get("batch_size") or 0
        if not (seq and batch):
            return None
        wall = sum(r["wall_s"] for r in rows)
        if wall <= 0:
            return None
        p = float(self._param_count)
        gen_tokens = sum(r.get("real_tokens", 0.0) for r in rows)
        exp_tokens = sum(r.get("samples", 0) for r in rows) * seq
        train_tokens = sum(r.get("train_steps", 0) for r in rows) * batch * seq
        flops = 2.0 * p * gen_tokens + 4.0 * p * exp_tokens + 6.0 * p * train_tokens
        chip_peak = chip_peak_tflops(prov.get("device_kind", ""))
        if chip_peak is None:
            return None
        peak = chip_peak * 1e12 * max(int(prov.get("device_count", 1)), 1)
        return round(flops / wall / peak, 4)

    # -- snapshot / persistence ------------------------------------------

    def snapshot(
        self, run_id: str, events_tail: Optional[Dict[str, list]] = None,
    ) -> Dict[str, Any]:
        """The ``telemetry.json`` payload: provenance + headline +
        per-cycle tail + recent events."""
        device = self.static.get("device") or device_provenance()
        snap: Dict[str, Any] = {
            "format": 1,
            "provenance": {
                "run_id": run_id,
                "written_at": round(time.time(), 3),
                **device,
                **{k: v for k, v in self.static.items() if k != "device"},
                "param_count": self._param_count,
            },
            "headline": self.headline(),
            "cycles": self.cycles[-self.window:],
        }
        if events_tail:
            snap["events"] = events_tail
        return snap

    def state_dict(self) -> Dict[str, Any]:
        return {
            "cycle_count": self.cycle_count,
            "total_samples": self.total_samples,
            "total_real_tokens": self.total_real_tokens,
            "total_wall_s": self.total_wall_s,
            "total_train_steps": self.total_train_steps,
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        self.cycle_count = int(state.get("cycle_count", 0))
        self.total_samples = int(state.get("total_samples", 0))
        self.total_real_tokens = float(state.get("total_real_tokens", 0.0))
        self.total_wall_s = float(state.get("total_wall_s", 0.0))
        self.total_train_steps = int(state.get("total_train_steps", 0))
