"""From a profiler trace (`*.xplane.pb`, read with `jax.profiler.ProfileData`)
to the numbers the per-layer readers and the run line need.

What the trace holds (looked at by hand on a v5e, libtpu 0.0.34): one plane
per chip, `/device:TPU:<n>`, with the lines `XLA Modules` (one event per
executed program), `XLA Ops`, `Async XLA Ops`, `Steps`, `Scalar Unit` and
`TC Overlay`. `XLA Ops` has one event per executed HLO instruction, named by
the instruction's whole text (`%fusion.8 = f32[512,512]{...} fusion(...),
kind=kOutput, calls=...`); a `while` is an event that encloses its body's
events on the same line. A pallas kernel is a `custom-call` with
`custom_call_target="tpu_custom_call"`, named after the innermost scope it
was traced in (`%attn.18` for the flax module `attn`), not after the kernel
(since PR 25 the program names its kernels: `%flash_fwd.29`).

What names an op's `jax.named_scope` (looked at by hand, PR 27, same libtpu):
an event of `XLA Ops` carries only `device_offset_ps`, `device_duration_ps` and
`Time Scale Multiplier` itself. Its EVENT METADATA, shared by every execution
of the instruction and not shown by `ProfileData`, has the stats
`hlo_category`, `program_id`, `symbol_id`, `flops`, `model_flops`,
`bytes_accessed`, `raw_bytes_accessed`, `memory_access_breakdown`,
`shape_with_layout`, `source`, `source_stack` and `tf_op`. `tf_op` is the
instruction's `op_name` metadata, a colon and its `op_type`:
`jit(work)/while/body/known_scope/dot_general:`. That is the scope stat: the
path is `op_name` less its last component, the primitive; a fusion carries the
`op_name` of one instruction it fused, its root as a rule, and an instruction
the compiler made itself (a `copy`, a `bitcast`) may carry none. The plane
`/host:metadata` also holds each program's optimised HLO (`Hlo Proto`), whose
instructions carry the same `op_name`; it is not read. `event_scopes` reads
the metadata from the file's bytes with a decoder of the protobuf wire format
kept here (`fields`), since nothing lighter than TensorFlow parses XSpace.
An event of `XLA Modules` is named `<program>(<program_id>)`,
`jit_generate(8462451966697418619)`, lasts from the program's first
instruction on that chip to its last, and encloses its instructions in time:
that is how an op finds its program.
Host threads are lines of the plane `/host:CPU`; the harness's
`TraceAnnotation`s (`phase:<name>` for each of the program's phases,
`bench:traced` around the traced cycles) are events of the line `python3`,
on the same clock.

* window: the `bench:traced` annotation, else first to last device event.
* busy: union of a chip's op intervals inside the window; mean over chips.
* self time of an op: its duration less the events it encloses.
* Mosaic time: self time of the events `is_mosaic` accepts.
* exposed collective time: union of collective leaf events less the union
  of every other leaf event on that chip; the worst chip.
* idle gaps: the window less the busy union on chip 0, each gap named by
  the `phase:` annotation that covers most of it (`other` if none).
* seconds by scope: self time of every op, grouped by its scope path (the rule
  above; `""` where it has none); mean over chips. `scope_seconds` sums the
  paths that hold a scope's name, so a nested scope counts under each scope
  around it and a `while` only with what its body does not cover.
* seconds by program: the `XLA Modules` events inside the window by program
  name, the id taken off; mean over chips. They add up to the busy time less
  the gaps inside a program.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_ANNOTATION = "bench:traced"
PHASE_PREFIX = "phase:"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)")
HLO = re.compile(r"^(%[\w.\-]+) = (.*)$", re.S)
PROGRAM = re.compile(r"^(.*)\((\d+)\)$")  # an `XLA Modules` event: name(program_id)

Interval = Tuple[float, float]


def is_mosaic(name: str) -> bool:
    """A Mosaic (pallas) kernel, by the custom call's target."""
    return "tpu_custom_call" in name


def short(name: str) -> str:
    """`%fusion.8 = f32[512,512]{...} fusion(...), kind=kOutput, ...` ->
    `%fusion.8 fusion kOutput f32[512,512]`: the instruction, its opcode,
    the fusion kind and the output shape without layouts."""
    m = HLO.match(name)
    if not m:
        return name[:96]
    inst, rest = m.groups()
    op = re.search(r" ([a-z][a-z0-9\-]*)\(", " " + rest)
    kind = re.search(r"kind=(\w+)", rest)
    shape = re.sub(r"\{[^{}]*\}", "", (" " + rest)[: op.start()] if op else "").strip()
    parts = [inst, op.group(1) if op else "?"] + ([kind.group(1)] if kind else []) + [shape[:56]]
    return " ".join(parts)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        elif e > s:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the union `a` not covered by the union `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: List[Tuple[float, float, str]]):
    """[(start, end, name)] of one line -> [(start, end, name, self_ns,
    is_leaf)]: an event's self time is its duration less the events nested
    directly inside it."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    child = [0.0] * len(events)
    leaf = [True] * len(events)
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [(s, e, n, (e - s) - child[i], leaf[i]) for i, (s, e, n) in enumerate(events)]


def fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field (a string, bytes
    or a message to walk in turn); fixed-width fields are passed over."""
    buf = memoryview(buf)
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        key = varint()
        number, wire = key >> 3, key & 7
        if wire == 0:
            yield number, varint()
        elif wire == 2:
            size = varint()
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in a trace file")


def _field(buf, number, default=None):
    return next((v for k, v in fields(buf) if k == number), default)


def scope_of(tf_op: str) -> str:
    """`jit(f)/while/body/decode_attn/dot_general:` -> `jit(f)/while/body/decode_attn`."""
    return tf_op.rsplit(":", 1)[0].rpartition("/")[0]


def event_scopes(xspace: bytes) -> Dict[int, Dict[str, str]]:
    """{program_id: {instruction text: scope path}} from the event metadata
    of the device planes (XSpace.planes = 1; XPlane.name = 2, .event_metadata
    = 4 and .stat_metadata = 5, maps whose entries hold the value under 2;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1, .int64_value
    = 4 or .uint64_value = 3, .str_value = 5; XStatMetadata.id = 1, .name = 2)."""
    out: Dict[int, Dict[str, str]] = {}
    for number, plane in fields(xspace):
        if number != 1 or not bytes(_field(plane, 2, b"")).startswith(b"/device:TPU:"):
            continue
        stat_names, metadata = {}, []
        for number, entry in fields(plane):
            if number == 5:
                stat = _field(entry, 2)
                stat_names[_field(stat, 1, 0)] = bytes(_field(stat, 2, b""))
            elif number == 4:
                metadata.append(_field(entry, 2))
        for md in metadata:
            program, tf_op = None, None
            for number, stat in fields(md):
                if number != 5:
                    continue
                stat = dict(fields(stat))
                which = stat_names.get(stat.get(1))
                if which == b"program_id":
                    program = stat.get(4, stat.get(3))
                elif which == b"tf_op" and 5 in stat:
                    tf_op = bytes(stat[5]).decode()
            if program is not None and tf_op is not None:
                out.setdefault(program, {})[bytes(_field(md, 2, b"")).decode()] = scope_of(tf_op)
    return out


def scope_seconds(trace: Dict, scope: str):
    """Seconds of device self time under the `jax.named_scope` `scope` in a
    reduced trace: the paths in which it is a component, wrapped or not
    (`transpose(jvp(loss))` is under `loss`; the compiler joins the names of
    instructions it merged with `;`). None where no path holds it."""
    times = [t for path, t in trace["scopes_by_self_time"]
             if scope in re.split(r"[/();]", path)]
    return sum(times) if times else None


def _events(line, keep=lambda ev: True):
    return [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns), ev.name)
            for ev in line.events if keep(ev)]


def reduce(trace_dir: str) -> Dict:
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        scopes = event_scopes(f.read())
    return reduce_profile(jax.profiler.ProfileData.from_file(paths[-1]), scopes)


def reduce_profile(profile, scopes: Dict[int, Dict[str, str]] | None = None) -> Dict:
    """`scopes` is `event_scopes()` of the same file; without it every op
    falls under the scope `""`."""
    planes = list(profile.planes)
    layout = {p.name: [l.name for l in p.lines] for p in planes}
    device_planes = sorted(
        (p for p in planes if re.match(r"^/device:TPU:\d+$", p.name)), key=lambda p: p.name)
    host_events = [
        ev for p in planes if p.name.startswith("/host:") for l in p.lines for ev in _events(l)
        if ev[2] == WINDOW_ANNOTATION or ev[2].startswith(PHASE_PREFIX)
    ]
    raw = [[ev for l in p.lines if l.name == "XLA Ops" for ev in _events(l)]
           for p in device_planes]
    modules = [sorted(ev for l in p.lines if l.name == "XLA Modules" for ev in _events(l))
               for p in device_planes] or [[]]
    if not device_planes:
        # no TPU in the trace (the CPU rehearsal): the CPU client's thunks
        # carry an `hlo_op` stat and stand in for one device
        raw.append([ev for p in planes if p.name.startswith("/host:") for l in p.lines
                    for ev in _events(l, lambda ev: "hlo_op" in dict(ev.stats))])
    if not any(raw):
        raise ValueError(f"no device operations in the trace; planes and lines: {layout}")
    mosaic_names = {ev[2] for evs in raw for ev in evs if is_mosaic(ev[2])}
    per_device = [self_times(evs) for evs in raw]

    marks = [ev for ev in host_events if ev[2] == WINDOW_ANNOTATION]
    if marks:
        lo, hi = marks[0][0], marks[-1][1]
    else:
        lo = min(ev[0] for evs in per_device for ev in evs)
        hi = max(ev[1] for evs in per_device for ev in evs)
    window_s = (hi - lo) / 1e9

    busy, exposed, mosaic = [], [], []
    by_name: Dict[str, float] = {}
    by_scope: Dict[str, float] = {}
    by_program: Dict[str, float] = {}
    for evs, mods in zip(per_device, modules):
        starts = [m[0] for m in mods]
        named = [PROGRAM.match(m[2]) for m in mods]
        for (s, e, n), match in zip(mods, named):
            inside_s = (min(e, hi) - max(s, lo)) / 1e9 / len(per_device)
            if inside_s > 0:
                program = match.group(1) if match else n
                by_program[program] = by_program.get(program, 0.0) + inside_s

        def scope(start: float, name: str) -> str:
            """The scope path of the op `name` that starts at `start`,
            found through the program whose event encloses it."""
            k = bisect.bisect_right(starts, start) - 1
            if not scopes or k < 0 or start >= mods[k][1] or not named[k]:
                return ""
            return scopes.get(int(named[k].group(2)), {}).get(name, "")

        inside = [ev for ev in evs if ev[1] > lo and ev[0] < hi]
        busy.append(clip(union([(s, e) for s, e, *_ in inside]), lo, hi))
        leaves = [ev for ev in inside if ev[4]]
        coll = union([(s, e) for s, e, n, *_ in leaves if COLLECTIVE.match(n)])
        rest = union([(s, e) for s, e, n, *_ in leaves if not COLLECTIVE.match(n)])
        exposed.append(total(clip(subtract(coll, rest), lo, hi)) / 1e9)
        mosaic.append(sum(ev[3] for ev in inside if ev[2] in mosaic_names) / 1e9)
        for s, e, n, self_ns, _ in inside:
            by_name[n] = by_name.get(n, 0.0) + self_ns / 1e9 / len(per_device)
            path = scope(s, n)
            by_scope[path] = by_scope.get(path, 0.0) + self_ns / 1e9 / len(per_device)

    # idle gaps of the first chip, named by what the host was doing
    gaps = subtract([(lo, hi)], busy[0])
    busy = [total(b) / 1e9 for b in busy]
    phases = [ev for ev in host_events if ev[2].startswith(PHASE_PREFIX)]

    def owner(gap: Interval) -> str:
        best, cover = "other", 0.0
        for s, e, n in phases:
            c = min(e, gap[1]) - max(s, gap[0])
            # the innermost phase wins a tie of cover: it starts later
            if c > cover or (c == cover and c > 0 and s >= gap[0]):
                best, cover = n[len(PHASE_PREFIX):], c
        return best

    gaps = sorted(gaps, key=lambda g: g[0] - g[1])  # longest first
    owners = [owner(g) if i < 10 or g[1] - g[0] >= 1e6 else "gaps under 1 ms"
              for i, g in enumerate(gaps)]
    gap_by_owner: Dict[str, float] = {}
    for g, o in zip(gaps, owners):
        gap_by_owner[o] = gap_by_owner.get(o, 0.0) + (g[1] - g[0]) / 1e9
    top_ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "mosaic_s": sum(mosaic) / len(mosaic),
        "mosaic_names": sorted(short(n) for n in mosaic_names),
        "collective_exposed_s": max(exposed),
        "device_planes": [p.name for p in device_planes],
        "n_device_events": [len(evs) for evs in per_device],
        "layout": layout,
        "ops_by_self_time": [[short(n), t] for n, t in top_ops[:200]],
        "self_time_s": sum(by_name.values()),
        "scopes_by_self_time": sorted(by_scope.items(), key=lambda kv: -kv[1]),
        "programs_s": dict(sorted(by_program.items(), key=lambda kv: -kv[1])),
        "breakdown": {
            "device_ops": [[short(n), t] for n, t in top_ops[:10]],
            "idle_gaps": [[f"{o} @{(g[0] - lo) / 1e9:.3f}s", (g[1] - g[0]) / 1e9]
                          for g, o in list(zip(gaps, owners))[:10]],
        },
        "idle_by_host_phase": gap_by_owner,
    }
