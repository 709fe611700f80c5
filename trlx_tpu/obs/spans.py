"""Span tracer: per-cycle wall-time partition over the watchdog's beat
sites.

The hang doctor's beat calls already mark every phase boundary the
trainers have (rollout start/end, per-chunk refills, reward, fused
block, per-step train, checkpoint, eval, transport waits). Rather than
instrumenting a second time, the tracer registers as a sibling
listener on those SAME sites (``HangWatchdog.add_listener``) and turns
the beat stream into an exact partition of host wall time:

- every instant belongs to exactly ONE phase — the innermost
  in-progress one (phases nest: PPO's reward call runs inside the
  rollout phase; its time is attributed to ``reward``, not double-
  counted under ``rollout``) — or to ``other`` when no phase is open
  (host bookkeeping between phases);
- therefore the per-cycle phase walls SUM TO THE CYCLE WALL by
  construction (float addition error only), which is the invariant
  tests and the flight-report sanity check hold it to.

Below the phases sits a second level, the WORK-SITE SPANS
(:meth:`SpanTracer.span`): plain ``with`` blocks around code the
trainer already has, each ending where the program already waits for
the device (the host pull of the sampled tokens, the flush of the
fused block's deferred stats). A span is a record ``{name, t0, t1,
parent, counts}`` on the same clock; it never touches the phase stack,
so the partition above, its sum-to-cycle-wall invariant and every
number read from the beats are what they are without spans.

Every phase start/end and every span is mirrored into a
``jax.profiler.TraceAnnotation`` named ``trlx:<name>``, so a profiler
capture (``ProfilerArm``, or any outer ``start_trace``) carries the
program's own spans beside the device lines. Outside a capture an
annotation costs well under a microsecond.

Host-side only, no locks on the beat path (beats come from the
training thread; the monitor thread never beats), fake-clock testable:
timestamps arrive from the watchdog's injectable clock.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

# the bucket for wall time outside any open phase (host bookkeeping,
# dataloader pulls, tracker writes between phases)
OTHER = "other"


# prefix of the tracer's annotations in a profiler capture (`phase:` is
# the benchmark harness's own mirror of the same beats)
ANNOTATION_PREFIX = "trlx:"


def _annotation(name: str):
    """An entered ``TraceAnnotation`` for ``trlx:<name>`` (lazy import:
    obs/ stays jax-free at module scope)."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(ANNOTATION_PREFIX + name)
    ann.__enter__()
    return ann


def span_self_times(rows: Iterable) -> Dict[str, float]:
    """Self seconds by span name over the rows of one cycle
    (``[name, t0, t1, parent, counts]``): a span's duration less what
    the spans nested directly inside it cover. Spans come from one
    thread's ``with`` blocks, so they nest properly."""
    spans = sorted(rows, key=lambda r: (r[1], -r[2]))
    child = [0.0] * len(spans)
    stack: List[int] = []
    for i, (_, t0, t1, *_rest) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= t0:
            stack.pop()
        if stack:
            child[stack[-1]] += t1 - t0
        stack.append(i)
    out: Dict[str, float] = {}
    for (name, t0, t1, *_rest), c in zip(spans, child):
        out[name] = out.get(name, 0.0) + (t1 - t0) - c
    return out


class SpanTracer:
    """Partitions beat-site timestamps into per-phase wall seconds, and
    records the work-site spans below the phases."""

    def __init__(
        self,
        clock: Callable[[], float] = time.monotonic,
        annotate: Optional[Callable[[str], Any]] = _annotation,
    ):
        self._stack: list = []  # innermost phase = last element
        self._last: Optional[float] = None
        self._acc: Dict[str, float] = {}
        self._cycle_t0: Optional[float] = None
        self.beats = 0  # total beat events observed (cost accounting)
        # the spans' clock: the observer points it at the watchdog's, so
        # spans, beats and cycle boundaries share one timebase
        self.clock = clock
        self._annotate = annotate  # None: no mirror into the profiler
        self._phase_annotations: Dict[str, list] = {}
        self._open_spans: List[Dict[str, Any]] = []  # innermost last
        self._spans: List[Dict[str, Any]] = []  # closed in the open cycle
        # rows of the cycle snapshot_cycle closed last:
        # [name, t0 - cycle_t0, t1 - cycle_t0, parent, counts]
        self.cycle_spans: List[list] = []

    # -- beat consumption ------------------------------------------------

    def on_beat(
        self, now: float, phase: str, event: str = "point",
        step=None, count: int = 1,
    ) -> None:
        """Sibling-listener entry point (HangWatchdog.add_listener
        signature). Attributes the elapsed time since the previous
        event to the CURRENT innermost phase, then applies the stack
        transition. ``point`` beats only advance the clock attribution
        (a many-chunk rollout keeps charging ``rollout``)."""
        self.beats += count
        self._attribute(now)
        if event == "start":
            self._stack.append(phase)
            if self._annotate is not None:
                self._phase_annotations.setdefault(phase, []).append(
                    self._annotate(phase)
                )
        elif event == "end":
            open_ = self._phase_annotations.get(phase)
            if open_:
                open_.pop().__exit__(None, None, None)
            # pop the innermost occurrence of this phase; exceptions
            # unwind via the watchdog's phase() finally, so ends arrive
            # innermost-first in practice — the reverse search keeps a
            # mismatched end from corrupting unrelated open phases
            for i in range(len(self._stack) - 1, -1, -1):
                if self._stack[i] == phase:
                    del self._stack[i]
                    break

    def _attribute(self, now: float) -> None:
        if self._last is not None and now > self._last:
            bucket = self._stack[-1] if self._stack else OTHER
            self._acc[bucket] = self._acc.get(bucket, 0.0) + (now - self._last)
        self._last = now

    # -- work-site spans -------------------------------------------------

    def open_span(self, name: str, counts: Dict[str, Any]) -> Dict[str, Any]:
        """Start a span; the phase stack is not touched. ``parent`` is
        the enclosing span, else the innermost open phase."""
        if self._open_spans:
            parent = self._open_spans[-1]["name"]
        else:
            parent = self._stack[-1] if self._stack else None
        rec = {
            "name": name, "t0": self.clock(), "t1": None, "parent": parent,
            "counts": counts,
            "annotation": (
                self._annotate(name) if self._annotate is not None else None
            ),
        }
        self._open_spans.append(rec)
        return rec

    def close_span(self, rec: Dict[str, Any]) -> None:
        rec["t1"] = self.clock()
        ann = rec.pop("annotation")
        if ann is not None:
            ann.__exit__(None, None, None)
        self._open_spans = [r for r in self._open_spans if r is not rec]
        self._spans.append(rec)

    @contextlib.contextmanager
    def span(self, name: str, **counts: Any):
        """``with tracer.span("tokens_wait", rows=8) as counts:`` —
        yields the span's ``counts`` dict, so a count known only at the
        end of the block (tokens, once the rows are on the host) can
        still be written into it."""
        rec = self.open_span(name, dict(counts))
        try:
            yield rec["counts"]
        finally:
            self.close_span(rec)

    # -- cycle boundaries ------------------------------------------------

    def start_cycle(self, now: float) -> None:
        """Open the first cycle (subsequent cycles open implicitly at
        :meth:`snapshot_cycle`)."""
        self._cycle_t0 = now
        self._last = now
        self._acc = {}
        self._spans = []

    def snapshot_cycle(self, now: float) -> Tuple[float, Dict[str, float]]:
        """Close the current cycle at ``now``: returns ``(wall_s,
        {phase: seconds})`` — the partition of [cycle start, now] —
        leaves the cycle's spans in :attr:`cycle_spans`, and opens the
        next cycle. The stack (open phases) carries
        across the boundary, so a phase spanning two cycles is charged
        to each for exactly the time it spent inside it."""
        self._attribute(now)
        t0 = self._cycle_t0 if self._cycle_t0 is not None else now
        wall = max(now - t0, 0.0)
        breakdown = {k: v for k, v in self._acc.items() if v > 0.0}
        # the spans closed in this cycle, handed over beside the
        # partition (a span still open belongs to the cycle it ends in)
        self.cycle_spans = [
            [r["name"], r["t0"] - t0, r["t1"] - t0, r["parent"], r["counts"]]
            for r in self._spans
        ]
        self._spans = []
        self._cycle_t0 = now
        self._acc = {}
        return wall, breakdown

    @property
    def open_phases(self) -> list:
        return list(self._stack)
