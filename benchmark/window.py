"""The measured window, put around a trainer from outside.

`learn()` runs as it always does. The harness only listens and stamps:

* a listener on the trainer's heartbeat registry (`watchdog.add_listener`,
  the registry the program's own span tracer listens to) collects every
  beat: these are the program's spans;
* `post_backward_callback` is wrapped to stamp a cycle boundary after the
  last optimizer step of each fused block, once the device has finished
  it (`block_until_ready` on the parameters, booked as the phase
  `train_wait`), so that asynchronous dispatch cannot move work across a
  boundary. One cycle is therefore: collect the rollouts (generate, reward,
  score), then every PPO epoch on them;
* `make_experience` is wrapped so that the `correct` checks can look at
  the first rollouts while the parameters are still the seeded ones.

The first two fused blocks are the warm-up: the second boundary opens the
window. One block is not enough, because the first cycle that follows a
block runs host code the start-up path never ran (the deferred stats
flush, clearing the store) and that code builds a dozen small programs.
The boundary at which `seconds` have passed closes the window, by lowering
`total_steps` to the step count reached: `learn()` then ends as any run
ends, with its final evaluation, which falls outside the window.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional

OTHER = "other"  # host time inside no phase
WARMUP_BLOCKS = 2  # fused blocks before the window opens
WAIT = "train_wait"  # the harness's own wait for the fused block
TRACED = "bench:traced"  # the annotation around the traced cycles


class CompileMeter:
    """Compile seconds and persistent-cache traffic from JAX's own
    monitoring events (copied from chip_smoke.py, which may change)."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring as mon

        self.seconds, self.hits, self.misses, self.backend_compiles = 0.0, 0, 0, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs
        if event == self._DURATIONS[2]:
            self.backend_compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "cache_hits": self.hits,
                "cache_misses": self.misses, "compiles": self.backend_compiles}


def partition(beats, t0: float, t1: float) -> Dict[str, float]:
    """Host wall of [t0, t1] by phase: every instant belongs to the
    innermost phase open at it, or to `other`. `beats` is the time-ordered
    list of (t, phase, event). The shares sum to t1 - t0 by construction.
    (The arithmetic of the program's `obs/spans.py`, kept here so that the
    yardstick does not move with it.)"""
    acc: Dict[str, float] = {}
    stack: List[str] = []
    last = t0

    def book(until: float) -> None:
        nonlocal last
        until = min(max(until, t0), t1)
        if until > last:
            key = stack[-1] if stack else OTHER
            acc[key] = acc.get(key, 0.0) + (until - last)
            last = until

    for t, phase, event in beats:
        book(t)
        if event == "start":
            stack.append(phase)
        elif event == "end" and phase in stack:
            del stack[len(stack) - 1 - stack[::-1].index(phase)]
    book(t1)
    return acc


class Window:
    def __init__(
        self,
        trainer,
        seconds: float,
        meter: CompileMeter,
        on_first_experience: Callable[[], None],
        trace_dir: Optional[str] = None,
        trace_cycles: int = 0,
        log: Callable[[str], None] = print,
    ):
        self.trainer = trainer
        self.seconds = seconds
        self.meter = meter
        self.on_first_experience = on_first_experience
        self.trace_dir = trace_dir
        self.trace_cycles = trace_cycles if trace_dir else 0
        self.log = log
        self.clock = trainer.watchdog.clock
        self.beats: List[tuple] = []
        self.cycles: List[Dict] = []  # one row per whole cycle in the window
        self.opened_at: Optional[float] = None  # clock() at the window's start
        self.compile_in_window: Optional[Dict[str, float]] = None
        self._cycle_t0 = 0.0
        self._compile0: Dict[str, float] = {}
        self._compiles_seen = 0
        self._blocks = 0
        self._callbacks = 0
        self.experiences = 0
        self._tracing = False
        self._annotations: Dict[str, list] = {}

    # -- installation ----------------------------------------------------

    def install(self) -> None:
        t = self.trainer
        t.watchdog.add_listener(self._on_beat)
        for name, wrapper in (
            ("make_experience", self._make_experience),
            ("post_backward_callback", self._post_backward),
        ):
            setattr(t, name, functools.partial(wrapper, getattr(t, name)))

    def _on_beat(self, now, phase, event="point", step=None, count=1) -> None:
        if event == "point":
            return
        self.beats.append((now, phase, event))
        if self._tracing:
            self._annotate(phase, event)

    def _annotate(self, phase: str, event: str) -> None:
        """Mirror a phase into the profiler's trace as a host span, so
        that idle gaps of the device can be named by what the host did."""
        import jax

        open_ = self._annotations.setdefault(phase, [])
        if event == "start":
            ann = jax.profiler.TraceAnnotation(phase if phase == TRACED else f"phase:{phase}")
            ann.__enter__()
            open_.append(ann)
        elif open_:
            open_.pop().__exit__(None, None, None)

    # -- wrapped methods -------------------------------------------------

    def _make_experience(self, original, *args, **kwargs):
        out = original(*args, **kwargs)
        self.experiences += 1
        if self.experiences == 1:
            self.on_first_experience()
        return out

    def _post_backward(self, original, *args, **kwargs):
        out = original(*args, **kwargs)
        self._callbacks += 1
        if self._callbacks % self.trainer.n_inner_epochs == 0:
            self._boundary()
        return out

    # -- boundaries ------------------------------------------------------

    def _boundary(self) -> None:
        import jax

        t = self.trainer
        self._on_beat(self.clock(), WAIT, "start")
        jax.block_until_ready(t.params)
        now = self.clock()
        self._on_beat(now, WAIT, "end")
        self._blocks += 1
        if self._blocks < WARMUP_BLOCKS:
            self.log(f"warm-up block {self._blocks} done, {self.meter.backend_compiles} "
                     "programs built or loaded so far")
            return
        if self.opened_at is None:
            self.opened_at = now
            self._compile0 = self.meter.snapshot()
            self.log(f"window opens at step {t.iter_count}")
        else:
            wall = now - self._cycle_t0
            self.cycles.append({
                "wall_s": wall,
                "phases": partition(self.beats, self._cycle_t0, now),
                "step": t.iter_count,
                "compiles": self.meter.backend_compiles - self._compiles_seen,
            })
            self.log(f"cycle {len(self.cycles)}: {wall:.4f} s, phases "
                     f"{ {k: round(v, 4) for k, v in self.cycles[-1]['phases'].items()} }, "
                     f"{self.cycles[-1]['compiles']} programs built or loaded")
        self._compiles_seen = self.meter.backend_compiles
        self.beats = []  # a boundary lies inside no phase: nothing carries over
        n = len(self.cycles)
        measured = self.wall_s
        closing = measured >= self.seconds
        if self._tracing and (n >= self.trace_cycles or closing):
            self._annotate(TRACED, "end")
            jax.profiler.stop_trace()
            self._tracing = False
        if closing:
            snap = self.meter.snapshot()
            self.compile_in_window = {k: snap[k] - self._compile0[k] for k in snap}
            # ends the loop after this block: learn() sees its budget met
            t.total_steps = t.iter_count
            self.log(f"window closes: {n} cycles in {measured:.3f} s")
            return
        if self.trace_cycles and n == 0:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self._tracing = True
            self._annotate(TRACED, "start")
        # tracing starts and stops between cycles, outside any cycle's wall
        self._cycle_t0 = self.clock()

    # -- results ---------------------------------------------------------

    @property
    def wall_s(self) -> float:
        return sum(c["wall_s"] for c in self.cycles)

    def phase_walls(self) -> Dict[str, float]:
        total: Dict[str, float] = {}
        for c in self.cycles:
            for k, v in c["phases"].items():
                total[k] = total.get(k, 0.0) + v
        return total
