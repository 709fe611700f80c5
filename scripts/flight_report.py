#!/usr/bin/env python
"""Render a flight-recorder stream into a human timeline.

Input: a checkpoint dir (reads ``<dir>/flight/``), a flight dir, or a
single ``flight-*.jsonl`` file's directory. Output: per-run summary —
the set-up (its spans as self time, what it compiled: built against
read, by span and by program), a per-cycle table (wall, samples/s, phase
breakdown, the cycle's work-site spans as self time and one line for
every program it compiled), the event overlay (guardrail
trips/actions, chaos injections, OOM-ladder rungs, watermark crossings,
checkpoints/restores, supervisor records) keyed into the cycles they
happened in, and slowest-phase attribution.

Pure stdlib + the jax-free ``trlx_tpu.obs.recorder`` reader, so it
runs on any login node against a live run's directory.

Usage:
    python scripts/flight_report.py ckpts
    python scripts/flight_report.py ckpts/flight --last 20
    python scripts/flight_report.py ckpts --run <run_id>
Exit code 0 = rendered; 1 = no flight stream found.
"""

from __future__ import annotations

import argparse
import datetime
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from trlx_tpu.obs.recorder import flight_files, iter_rows  # noqa: E402
from trlx_tpu.obs.spans import span_self_times  # noqa: E402

# event kinds rendered in the overlay (cycle rows are the table)
_EVENT_ORDER = (
    "run_start", "restore", "guardrail_trip", "guardrail_action", "chaos",
    "oom", "memory_watermark", "hosts", "checkpoint", "supervisor",
    "run_end",
)


def _resolve_dir(path: str) -> str:
    for candidate in (path, os.path.join(path, "flight")):
        if flight_files(candidate):
            return candidate
    return path


def _fmt_t(t) -> str:
    try:
        return datetime.datetime.fromtimestamp(float(t)).strftime("%H:%M:%S")
    except Exception:
        return "?"


def _event_lines(row: dict) -> list:
    kind = row.get("kind", "?")
    if kind == "setup":
        return _setup_lines(row)
    skip = {"t", "run", "kind", "cycle", "step", "pv"}
    detail = " ".join(
        f"{k}={row[k]}" for k in row if k not in skip
    )
    return [f"    {_fmt_t(row.get('t'))}  [{kind}] {detail}".rstrip()]


def _self_times(spans) -> str:
    """Work-site spans as self time (a span's duration less what its
    children cover), largest first."""
    own = span_self_times(spans)
    return ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(own.items(), key=lambda kv: -kv[1])
    )


def _setup_lines(row: dict) -> list:
    """The `setup` row: why was this start slow, and did the cache work."""
    c = row.get("compiles") or {}
    head = f"    {_fmt_t(row.get('t'))}  [setup]"
    if "since_import_s" in row:
        head += f" import to observer {row['since_import_s']:.1f} s,"
    if "init_s" in row:
        head += f" trainer init {row['init_s']:.1f} s,"
    lines = [
        f"{head} {c.get('requests', 0)} compile requests: "
        f"{c.get('built', 0)} built ({c.get('build_s', 0.0):.1f} s, "
        f"{c.get('written', 0)} kept by the cache), "
        f"{c.get('read', 0)} read ({c.get('read_s', 0.0):.1f} s); "
        f"trace {c.get('trace_s', 0.0):.1f} s, lowering {c.get('lower_s', 0.0):.1f} s"
    ]
    if row.get("spans"):
        lines.append("        spans (self s): " + _self_times(row["spans"]))
    if row.get("by_span"):
        lines.append("        compiled under: " + ", ".join(
            f"{k} {v[0]:.1f} s ({v[1]} built)"
            for k, v in sorted(row["by_span"].items(), key=lambda kv: -kv[1][0])
        ))
    if row.get("programs"):
        lines.append("        largest programs: " + ", ".join(
            f"{name} x{n} {seconds:.1f} s ({n_built} built)"
            for name, n, seconds, n_built in row["programs"][:10]
        ))
    return lines


def _compile_lines(c: dict) -> list:
    """One line for every program a cycle compiled: which cycle
    recompiled, what, and under which span."""
    lines = [
        f"        cycle {c.get('cycle', '?')}: {'built' if built else 'read'} "
        f"{name} {t1 - t0:.2f} s" + (f" under {parent}" if parent else "")
        for name, t0, t1, built, parent in c.get("compiles") or []
    ]
    if c.get("compiles_more"):
        lines.append(f"        cycle {c.get('cycle', '?')}: and "
                     f"{c['compiles_more']} shorter ones")
    return lines


def render(directory: str, last: int = 0, run: str = "") -> str:
    rows = list(iter_rows(directory))
    if not rows:
        return ""
    runs = list(dict.fromkeys(r.get("run", "?") for r in rows))
    if run:
        runs = [r for r in runs if r.startswith(run)]
    lines = [f"flight stream: {directory} ({len(rows)} rows, "
             f"{len(runs)} run(s))"]
    # external rows (supervisor) carry their own run id: fold them into
    # every rendered run's overlay by time — they describe the stream,
    # not one incarnation
    external = [r for r in rows if r.get("kind") == "supervisor"]
    for run_id in runs:
        rrows = [r for r in rows if r.get("run") == run_id]
        if all(r.get("kind") == "supervisor" for r in rrows):
            continue
        merged = rrows + external
        merged.sort(key=lambda r: r.get("t", 0))
        # group by STREAM ORDER, not cycle number: a cycle row is
        # written when its cycle CLOSES, so the events preceding it
        # happened inside it — and cycle numbers can repeat within one
        # run after a resume/rollback rewinds the counter, so they
        # cannot key the overlay
        groups = []    # (cycle_row, events that happened inside it)
        pending = []
        for r in merged:
            if r.get("kind") == "cycle":
                groups.append((r, pending))
                pending = []
            else:
                pending.append(r)
        cycles = [c for c, _ in groups]
        n_events = len(merged) - len(cycles)
        lines.append(f"\nrun {run_id}: {len(cycles)} cycles, "
                     f"{n_events} events")
        shown = groups[-last:] if last else groups
        # table columns: the union of phases, widest totals first
        totals: dict = {}
        for c in cycles:
            for k, v in (c.get("phases") or {}).items():
                totals[k] = totals.get(k, 0.0) + float(v)
        phase_cols = [k for k, _ in sorted(totals.items(),
                                           key=lambda kv: -kv[1])][:6]
        header = (
            f"  {'cycle':>5} {'step':>6} {'wall_s':>8} {'smp':>5} "
            f"{'smp/s':>7} " + " ".join(f"{p[:10]:>10}" for p in phase_cols)
            + "  slowest"
        )
        lines.append(header)
        for c, events in shown:
            for e in events:
                lines.extend(_event_lines(e))
            phases = c.get("phases") or {}
            slowest = max(phases.items(), key=lambda kv: kv[1])[0] if phases else "-"
            cells = " ".join(
                f"{phases.get(p, 0.0):>10.3f}" for p in phase_cols
            )
            lines.append(
                f"  {c.get('cycle', '?'):>5} {str(c.get('step', '-')):>6} "
                f"{c.get('wall_s', 0.0):>8.3f} {str(c.get('samples', '-')):>5} "
                f"{str(c.get('samples_per_sec', '-')):>7} {cells}  {slowest}"
            )
            if c.get("spans"):
                lines.append("        spans (self s): " + _self_times(c["spans"]))
            lines.extend(_compile_lines(c))
        if pending:  # events after the last cycle row (run_end, ...)
            lines.append("  events after the last cycle:")
            for e in pending:
                lines.extend(_event_lines(e))
        # attribution summary
        if totals:
            wall_total = sum(float(c.get("wall_s", 0.0)) for c in cycles)
            top = sorted(totals.items(), key=lambda kv: -kv[1])[:3]
            lines.append(
                "  slowest-phase attribution: "
                + ", ".join(
                    f"{k} {v:.3f}s"
                    + (f" ({v / wall_total:.0%})" if wall_total else "")
                    for k, v in top
                )
            )
        if cycles:
            worst = max(cycles, key=lambda c: float(c.get("wall_s", 0.0)))
            lines.append(
                f"  worst cycle: #{worst.get('cycle')} "
                f"wall {worst.get('wall_s')}s "
                f"(step {worst.get('step')}, phases {worst.get('phases')})"
            )
    return "\n".join(lines)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("path", help="checkpoint dir or flight dir")
    parser.add_argument("--last", type=int, default=0,
                        help="render only the last N cycles per run")
    parser.add_argument("--run", default="",
                        help="render only run ids starting with this prefix")
    args = parser.parse_args(argv)
    directory = _resolve_dir(os.path.abspath(args.path))
    out = render(directory, last=args.last, run=args.run)
    if not out:
        print(f"no flight-recorder stream under {args.path} "
              "(expected flight-*.jsonl; is train.obs enabled?)")
        return 1
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
