"""Metric trackers: tensorboard / wandb / jsonl / console.

Parity: the reference routes metrics through
`accelerator.init_trackers`/`accelerator.log`
(/root/reference/trlx/trainer/accelerate_base_trainer.py:95-136) with
wandb or tensorboard backends and auto-composed run names. Here a thin
`Tracker` owns the same role; a JSONL file is always written under
`logging_dir` so benchmark tooling can scrape metrics without a tracker
dependency (reference scripts/benchmark.sh scrapes W&B instead).
"""

from __future__ import annotations

import json
import os
import sys
import time
from numbers import Number
from typing import Any, Dict, Optional

from trlx_tpu.utils import logging

logger = logging.get_logger(__name__)


def _run_name(config) -> str:
    script = os.path.basename(getattr(sys.modules.get("__main__"), "__file__", "run") or "run")
    model = config.model.model_path.rstrip("/").split("/")[-1]
    import jax

    return config.train.run_name or f"{script}/{model}/{len(jax.devices())}dev"


class DeferredStats:
    """One-cycle-delayed metric staging for device-resident scalars.

    `stage()` packs every jax.Array scalar in a stats dict into ONE
    stacked device array and starts its device->host copy
    asynchronously; `flush()` materializes the staged dicts (blocking
    only if a copy hasn't landed yet — normally it streamed under
    whatever the device ran next) and returns `[(stats, step, meta),
    ...]` in stage order, all values as host floats.

    This is how the trainers keep the hot path dispatch-free: each
    blocking per-stat read is a device sync, so rollout and fused-train
    metrics stay on device until the next cycle boundary consumes
    them."""

    def __init__(self):
        self._pending = []

    def stage(self, stats: Dict[str, Any], step: int, meta: Any = None) -> None:
        import jax
        import jax.numpy as jnp

        keys = list(stats)
        vals = [stats[k] for k in keys]
        dev_ix = [i for i, v in enumerate(vals) if isinstance(v, jax.Array)]
        stacked = None
        if dev_ix:
            stacked = jnp.stack([vals[i] for i in dev_ix])
            try:
                stacked.copy_to_host_async()
            except Exception:
                pass  # transfer still happens at materialization
        self._pending.append((keys, vals, dev_ix, stacked, step, meta))

    def __bool__(self) -> bool:
        return bool(self._pending)

    def flush(self):
        import numpy as np

        out = []
        for keys, vals, dev_ix, stacked, step, meta in self._pending:
            if dev_ix:
                fetched = np.asarray(stacked)
                for i, f in zip(dev_ix, fetched.tolist()):
                    vals[i] = f
            out.append(
                ({k: float(v) for k, v in zip(keys, vals)}, step, meta)
            )
        self._pending.clear()
        return out


class Tracker:
    """Dispatches scalar stats to the configured backend + a JSONL log."""

    def __init__(self, config):
        train = config.train
        self.backend = train.tracker
        self.run_name = _run_name(config)
        self.logging_dir = train.logging_dir or os.path.join(
            train.checkpoint_dir, "logs"
        )
        self._tb = None
        self._wandb = None
        self._jsonl = None
        # deferred-stats flush hooks (trainer registers its
        # DeferredStats flushers): close() drains them BEFORE tearing
        # down backends, so the last cycle's async metrics — staged
        # behind a device->host copy and normally consumed one cycle
        # later — can never be dropped by shutdown ordering
        self._pending_flushes = []
        # multi-host: only process 0 writes (parity: reference gates all
        # trackers on accelerator.is_main_process)
        try:
            import jax

            self.enabled = jax.process_index() == 0
        except Exception:
            self.enabled = True
        if not self.enabled:
            self.backend = None
            return
        os.makedirs(self.logging_dir, exist_ok=True)
        self._jsonl = open(os.path.join(self.logging_dir, "metrics.jsonl"), "a")

        if self.backend == "tensorboard":
            try:
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(
                    log_dir=os.path.join(self.logging_dir, self.run_name.replace("/", "_"))
                )
            except Exception as e:  # tensorboard is optional
                logger.warning("tensorboard unavailable (%s); falling back to jsonl", e)
        elif self.backend == "wandb":
            try:
                import wandb

                self._wandb = wandb.init(
                    project=train.project_name,
                    name=self.run_name,
                    entity=train.entity_name,
                    group=train.group_name,
                    tags=train.tags,
                    config=config.to_dict(),
                )
            except Exception as e:
                logger.warning("wandb unavailable (%s); falling back to jsonl", e)
        elif self.backend not in (None, "jsonl"):
            raise ValueError(
                f"unknown tracker {self.backend!r} (tensorboard | wandb | jsonl | None)"
            )

    def log(self, stats: Dict[str, Any], step: int) -> None:
        if self._jsonl is None:  # non-main process
            return
        scalars = {k: float(v) for k, v in stats.items() if isinstance(v, Number)}
        rec = dict(scalars, _step=step, _time=time.time())
        self._jsonl.write(json.dumps(rec) + "\n")
        self._jsonl.flush()
        if self._tb is not None:
            for k, v in scalars.items():
                self._tb.add_scalar(k, v, step)
        if self._wandb is not None:
            self._wandb.log(stats, step=step)

    def attach_pending(self, flush_fn) -> None:
        """Register a callable that materializes + logs any still-staged
        deferred stats (idempotent). Run by close() before the backends
        tear down."""
        self._pending_flushes.append(flush_fn)

    def close(self) -> None:
        """Flush staged deferred stats, then tear down backends.
        Idempotent: backends are dropped after closing, and log() on a
        closed tracker is a silent no-op (same as a non-main process) —
        a learn() that already closed cannot crash a later stray log."""
        flushes, self._pending_flushes = self._pending_flushes, []
        for flush in flushes:
            try:
                flush()
            except Exception as e:
                logger.error(
                    "tracker.close: deferred-stats flush failed (%s); "
                    "closing backends anyway", e,
                )
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None
        if self._wandb is not None:
            self._wandb.finish()
            self._wandb = None
