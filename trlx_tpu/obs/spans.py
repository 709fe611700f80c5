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

What closes before the first cycle opens (the constructor's set-up spans)
is kept for the ``setup`` row. Beside the spans the tracer keeps the
COMPILE RECORDS ``[fun_name, t0, t1, built, parent]`` that the observer's
listener on JAX's own monitoring events hands it (:meth:`on_compile_event`,
:meth:`on_compile_duration`): same clock, same hand-over at a cycle's end.

Host-side only, no locks on the beat path (beats come from the
training thread; the monitor thread never beats), fake-clock testable:
timestamps arrive from the watchdog's injectable clock.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

# the bucket for wall time outside any open phase (host bookkeeping,
# dataloader pulls, tracker writes between phases)
OTHER = "other"


# prefix of the tracer's annotations in a profiler capture (`phase:` is
# the benchmark harness's own mirror of the same beats)
ANNOTATION_PREFIX = "trlx:"

# JAX's monitoring events of one compilation, in the order they fire
# (jax/_src/dispatch.py, compiler.py): the jaxpr trace, the lowering, then
# the cache's request with a hit where the executable was read, then the
# backend's duration (the read's, on a hit) and, from the cache's writer, a
# miss where a built executable was large and slow enough to be kept
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
HIT_EVENT = "/jax/compilation_cache/cache_hits"
MISS_EVENT = "/jax/compilation_cache/cache_misses"
COMPILE_EVENTS = frozenset(
    (TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT, REQUEST_EVENT, HIT_EVENT, MISS_EVENT)
)

CYCLE_COMPILES = 64  # compile records a `cycle` row carries, the longest
SETUP_PROGRAMS = 40  # names a `setup` row carries, the largest by seconds
OTHERS = "(others)"  # the rest of them, as one line
NO_SPAN = "(no span)"  # `by_span`'s key for compiles under no span or phase


def new_compile_totals() -> Dict[str, Any]:
    """What a stretch of the run compiled: cache requests, programs built
    and read, built programs the cache kept (``written``), and seconds.
    ``trace_s`` and ``lower_s`` hold EVERY trace and lowering (those that
    led to no backend compile too); ``build_s`` and ``read_s`` the
    backend's seconds of programs built and read; ``built_s`` what the
    built programs cost in all (their trace, lowering and build)."""
    return {"requests": 0, "built": 0, "read": 0, "written": 0, "trace_s": 0.0,
            "lower_s": 0.0, "build_s": 0.0, "read_s": 0.0, "built_s": 0.0}


def _program_name(fun_name: str) -> Tuple[str, str]:
    """``jit(generate)`` -> ``("jit_generate", "generate")``: the name the
    program has everywhere else (`XLA Modules`, the compile log), and the
    function's own, under which its trace was timed."""
    if fun_name.endswith(")") and "(" in fun_name:
        wrapper, _, inner = fun_name[:-1].partition("(")
        return f"{wrapper}_{inner}", inner
    return fun_name, fun_name


def longest_compiles(records: List[list], cap: int = CYCLE_COMPILES):
    """``(the cap longest records in time order, how many were left out)``."""
    if len(records) <= cap:
        return records, 0
    kept = sorted(records, key=lambda r: r[1] - r[2])[:cap]
    return sorted(kept, key=lambda r: r[1]), len(records) - cap


def _sum_by(records: Iterable[list], column: int) -> Dict[Any, list]:
    """``{records' column: [n, seconds, n_built]}``."""
    out: Dict[Any, list] = {}
    for record in records:
        row = out.setdefault(record[column], [0, 0.0, 0])
        row[0] += 1
        row[1] += record[2] - record[1]
        row[2] += bool(record[3])
    return out


def programs_by_name(records: Iterable[list], top: int = SETUP_PROGRAMS) -> List[list]:
    """Compile records by program: ``[fun_name, n, seconds, n_built]``, the
    ``top`` largest by seconds and one ``"(others)"`` line for the rest, so
    that the columns still sum to the records'."""
    rows = sorted(([name, *sums] for name, sums in _sum_by(records, 0).items()),
                  key=lambda r: -r[2])
    if len(rows) > top:
        rest = rows[top:]
        rows[top:] = [[OTHERS, *(sum(r[i] for r in rest) for i in (1, 2, 3))]]
    return rows


def compiles_by_span(records: Iterable[list]) -> Dict[str, list]:
    """``{parent: [seconds, n_built]}``: which span paid for which compiles."""
    return {parent or NO_SPAN: sums[1:] for parent, sums in _sum_by(records, 4).items()}


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
        # the spans' clock: the observer points it at the watchdog's, so
        # spans, beats and cycle boundaries share one timebase
        self.clock = clock
        # start of the open cycle; before the first one, of the stretch
        # that belongs to no cycle (set-up), which begins with the tracer
        self._cycle_t0: float = clock()
        self._annotate = annotate  # None: no mirror into the profiler
        self._phase_annotations: Dict[str, list] = {}
        self._open_spans: List[Dict[str, Any]] = []  # innermost last
        self._spans: List[Dict[str, Any]] = []  # closed since the last boundary
        # compile records since the last boundary, [fun_name, t0, t1, built,
        # parent] on the clock, with their totals; whichever thread compiled
        # (the loop's, a prefetch or serve thread) writes them under the lock
        self._compile_lock = threading.Lock()
        self._compiles: List[list] = []
        self._compile_totals = new_compile_totals()
        # per compiling thread: trace seconds by function, the lowering
        # that followed, whether the cache answered the request
        self._compiling: Dict[int, Dict[str, Any]] = {}
        # what the last boundary closed, seconds from that stretch's start:
        # rows [name, t0, t1, parent, counts], records as above, totals
        self.cycle_spans: List[list] = []
        self.cycle_compiles: List[list] = []
        self.cycle_compile_totals = new_compile_totals()

    def set_clock(self, clock: Callable[[], float]) -> None:
        """Another clock (the watchdog's), before anything is recorded:
        the stretch before the first cycle restarts on it."""
        self.clock = clock
        self._cycle_t0 = clock()

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

    def _innermost(self) -> Optional[str]:
        """What a new span or compile record lies under: the innermost
        open span, else the innermost open phase, else None."""
        if self._open_spans:
            return self._open_spans[-1]["name"]
        return self._stack[-1] if self._stack else None

    def open_span(self, name: str, counts: Dict[str, Any]) -> Dict[str, Any]:
        """Start a span; the phase stack is not touched. ``parent`` is
        the enclosing span, else the innermost open phase."""
        rec = {
            "name": name, "t0": self.clock(), "t1": None, "parent": self._innermost(),
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

    # -- compile events --------------------------------------------------

    def on_compile_event(self, event: str) -> None:
        """A cache event of JAX's monitoring, on the compiling thread."""
        with self._compile_lock:
            if event == REQUEST_EVENT:
                self._compile_totals["requests"] += 1
            elif event == HIT_EVENT:
                self._compiling.setdefault(threading.get_ident(), {})["hit"] = True
            elif event == MISS_EVENT:
                self._compile_totals["written"] += 1

    def on_compile_duration(self, event: str, seconds: float, fun_name: str) -> None:
        """A duration event of JAX's monitoring; it fires at the END of
        what it times. Trace and lowering seconds wait, by thread, for the
        backend compile of the same function and are folded into its
        record, which starts ``seconds`` before now. ``built`` is false
        where the cache answered the request (a read). ``parent`` is the
        innermost open span, else the innermost open phase, else None."""
        name, inner = _program_name(fun_name)
        with self._compile_lock:
            totals = self._compile_totals
            state = self._compiling.setdefault(threading.get_ident(), {})
            if event == TRACE_EVENT:
                totals["trace_s"] += seconds
                traces = state.setdefault("traces", {})
                traces[inner] = traces.get(inner, 0.0) + seconds
            elif event == LOWER_EVENT:
                totals["lower_s"] += seconds
                # traces nest and end innermost first, so the function
                # lowered is the outermost: the others were traced INTO it
                # (or into a jaxpr nobody compiled) and pay for no program
                traced = state.pop("traces", {}).get(inner, 0.0)
                state["lowered"] = (name, traced + seconds)
            elif event == BACKEND_EVENT:
                lowered = state.pop("lowered", None)
                before = lowered[1] if lowered and lowered[0] == name else 0.0
                built = not state.pop("hit", False)
                if built:
                    totals["built"] += 1
                    totals["build_s"] += seconds
                    totals["built_s"] += before + seconds
                else:
                    totals["read"] += 1
                    totals["read_s"] += seconds
                t1 = self.clock()
                self._compiles.append(
                    [name, t1 - before - seconds, t1, built, self._innermost()])

    # -- cycle boundaries ------------------------------------------------

    def _hand_over(self, now: float) -> None:
        """Close the stretch [``_cycle_t0``, now]: its spans, compile
        records and totals move to the ``cycle_*`` attributes, seconds
        from the stretch's start, and the next stretch opens at ``now``
        (a span still open belongs to the stretch it ends in)."""
        t0 = self._cycle_t0
        self.cycle_spans = [
            [r["name"], r["t0"] - t0, r["t1"] - t0, r["parent"], r["counts"]]
            for r in self._spans
        ]
        with self._compile_lock:
            compiles, self._compiles = self._compiles, []
            self.cycle_compile_totals = self._compile_totals
            self._compile_totals = new_compile_totals()
        self.cycle_compiles = [
            [name, c0 - t0, c1 - t0, built, parent]
            for name, c0, c1, built, parent in compiles
        ]
        self._spans = []
        self._cycle_t0 = now
        self._acc = {}

    def start_cycle(self, now: float) -> None:
        """Open a cycle at ``now``: the first, or the first of another
        ``learn()`` (subsequent cycles open implicitly at
        :meth:`snapshot_cycle`). What closed since the tracer was built,
        or since the last cycle closed, belongs to no cycle: it is handed
        over as a cycle's is, for the observer's ``setup`` row."""
        self._hand_over(now)
        self._last = now

    def snapshot_cycle(self, now: float) -> Tuple[float, Dict[str, float]]:
        """Close the current cycle at ``now``: returns ``(wall_s,
        {phase: seconds})`` — the partition of [cycle start, now] —
        leaves the cycle's spans in :attr:`cycle_spans` and its compile
        records in :attr:`cycle_compiles`, and opens the next cycle. The
        stack (open phases) carries
        across the boundary, so a phase spanning two cycles is charged
        to each for exactly the time it spent inside it."""
        self._attribute(now)
        wall = max(now - self._cycle_t0, 0.0)
        breakdown = {k: v for k, v in self._acc.items() if v > 0.0}
        self._hand_over(now)
        return wall, breakdown

    @property
    def open_phases(self) -> list:
        return list(self._stack)
