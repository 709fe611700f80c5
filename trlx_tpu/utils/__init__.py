"""Cross-cutting utilities.

Parity: /root/reference/trlx/utils/__init__.py (set_seed, Clock,
optimizer/scheduler registries :83-146, :149-187) — rebuilt on
jax.random / optax instead of torch.
"""

from __future__ import annotations

import math
import random
import time
from enum import Enum
from numbers import Number
from typing import Any, Dict, Iterable, Iterator

import numpy as np
import optax


def set_seed(seed: int) -> None:
    """Seed host-side RNGs. Device-side randomness is explicit via
    jax.random keys threaded through the trainers (no global device seed —
    functional JAX style, unlike reference utils/__init__.py:57-66)."""
    random.seed(seed)
    np.random.seed(seed % (2**32))


def significant(x: Any, ndigits: int = 2) -> Any:
    """Round a number to `ndigits` significant figures (for log display)."""
    if not isinstance(x, Number) or x == 0 or not math.isfinite(x):
        return x
    return round(x, ndigits - int(math.floor(math.log10(abs(x)))) - 1)


def infinite_loader(loader: Iterable) -> Iterator:
    """Cycle a dataloader forever (prompt iterator for rollouts)."""
    while True:
        yield from loader


def to_scalar(x) -> float:
    """Pull a device scalar to host float (single sync point for logging)."""
    return float(np.asarray(x))


class Clock:
    """Wall-clock tick timer emitting seconds-per-unit (parity:
    reference utils/__init__.py:149-187 — feeds `time/*` metrics)."""

    def __init__(self):
        self.start = time.time()
        self.total_time = 0.0
        self.total_samples = 0

    def tick(self, samples: int = 0) -> float:
        end = time.time()
        delta = end - self.start
        self.start = end
        if samples:
            self.total_time += delta
            self.total_samples += samples
        return delta

    def get_stat(self, n_samp: int = 1000, reset: bool = False) -> float:
        """Seconds per `n_samp` samples."""
        stat = self.total_time * n_samp / max(self.total_samples, 1)
        if reset:
            self.total_time = 0.0
            self.total_samples = 0
        return stat


# ---------------------------------------------------------------------------
# Optimizer / scheduler registries (optax)
# ---------------------------------------------------------------------------


class OptimizerName(str, Enum):
    ADAM = "adam"
    ADAMW = "adamw"
    ADAMW_8BIT_BNB = "adamw_8bit_bnb"  # first-party int8-state adamw (ops/adam8bit.py)
    # fused apply variant: dequantize->update->requantize->param write
    # streamed per block chunk, no fp32 moment/updates tree — the
    # memory-tight large-model recipe (configs/mesh/single_chip_1p3b.yml)
    ADAMW_8BIT_FUSED = "adamw_8bit_fused"
    SGD = "sgd"
    LION = "lion"


def get_optimizer_class(name: str):
    """Return an optax optimizer factory for a registry name.

    The factory accepts torch-style kwargs (lr, betas, eps, weight_decay)
    and returns an `optax.GradientTransformation`; `lr` may be a schedule.
    """
    name = OptimizerName(name.lower() if isinstance(name, str) else name)

    def _adamish(base):
        def make(lr, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0, **kw):
            return base(
                learning_rate=lr, b1=betas[0], b2=betas[1], eps=eps,
                weight_decay=weight_decay, **kw,
            )

        return make

    if name == OptimizerName.ADAM:
        def make_adam(lr, betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0, **kw):
            if weight_decay:
                return optax.adamw(lr, b1=betas[0], b2=betas[1], eps=eps,
                                   weight_decay=weight_decay, **kw)
            return optax.adam(lr, b1=betas[0], b2=betas[1], eps=eps, **kw)

        return make_adam
    if name == OptimizerName.ADAMW:
        return _adamish(optax.adamw)
    if name == OptimizerName.ADAMW_8BIT_BNB:
        from trlx_tpu.ops.adam8bit import adamw_8bit

        return _adamish(adamw_8bit)
    if name == OptimizerName.ADAMW_8BIT_FUSED:
        from trlx_tpu.ops.adam8bit import FusedAdamW8bit

        return _adamish(FusedAdamW8bit)
    if name == OptimizerName.LION:
        def make_lion(lr, betas=(0.9, 0.99), weight_decay=0.0, **kw):
            return optax.lion(lr, b1=betas[0], b2=betas[1], weight_decay=weight_decay, **kw)

        return make_lion
    if name == OptimizerName.SGD:
        def make_sgd(lr, momentum=0.0, weight_decay=0.0, **kw):
            tx = optax.sgd(lr, momentum=momentum or None, **kw)
            if weight_decay:
                tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
            return tx

        return make_sgd
    raise ValueError(f"unknown optimizer {name}")


class SchedulerName(str, Enum):
    COSINE_ANNEALING = "cosine_annealing"
    LINEAR = "linear"
    CONSTANT = "constant"


def get_scheduler_class(name: str):
    """Return an optax schedule factory for a registry name.

    Factories take torch-style kwargs (T_max/eta_min for cosine, matching
    reference utils/__init__.py:126-146) plus the peak lr, and return an
    `optax.Schedule` mapping step -> lr.
    """
    name = SchedulerName(name.lower() if isinstance(name, str) else name)

    if name == SchedulerName.COSINE_ANNEALING:
        def make_cos(lr, T_max, eta_min=0.0, warmup_steps: int = 0, **_):
            # reference configs ship T_max=1e12 ("effectively constant");
            # without x64 the step counter traces as int32 and optax's
            # jnp.minimum(count, decay_steps) overflows on it — clamp to
            # the largest representable step
            decay_steps = int(min(max(int(T_max), 1), np.iinfo(np.int32).max))
            cos = optax.cosine_decay_schedule(
                init_value=lr, decay_steps=decay_steps,
                alpha=(eta_min / lr) if lr else 0.0,
            )
            if warmup_steps:
                warm = optax.linear_schedule(0.0, lr, warmup_steps)
                return optax.join_schedules([warm, cos], [warmup_steps])
            return cos

        return make_cos
    if name == SchedulerName.LINEAR:
        def make_lin(lr, total_steps, final_lr=0.0, warmup_steps: int = 0, **_):
            steps = int(min(max(int(total_steps), 1), np.iinfo(np.int32).max))
            lin = optax.linear_schedule(lr, final_lr, steps)
            if warmup_steps:
                warm = optax.linear_schedule(0.0, lr, warmup_steps)
                return optax.join_schedules([warm, lin], [warmup_steps])
            return lin

        return make_lin
    if name == SchedulerName.CONSTANT:
        return lambda lr, **_: optax.constant_schedule(lr)
    raise ValueError(f"unknown scheduler {name}")


def build_optimizer(opt_cfg, sched_cfg) -> tuple:
    """Resolve (OptimizerConfig, SchedulerConfig) -> (tx, schedule_fn).

    The schedule is injected as the optimizer's learning rate so a single
    optax transformation carries both (fused, state lives in one pytree —
    it shards along `fsdp` with the params for ZeRO-3 parity).
    """
    opt_kwargs = dict(opt_cfg.kwargs)
    lr = opt_kwargs.pop("lr")
    sched_kwargs = dict(sched_cfg.kwargs)
    schedule = get_scheduler_class(sched_cfg.name)(lr, **sched_kwargs)
    tx = get_optimizer_class(opt_cfg.name)(schedule, **opt_kwargs)
    return tx, schedule
