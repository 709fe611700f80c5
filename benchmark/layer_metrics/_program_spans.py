"""The program's own work-site spans (`trlx_tpu/obs/spans.py`, written into
the `cycle` rows of the run's flight stream) for the cycles of the window.

A stopgap: `run.Reading` carries no run directory, so it is rebuilt here by
`run.py`'s own formula from the same command line. The next benchmark PR
puts `run_dir` on `Reading` and this module shrinks to the row filter.
A program without such spans (the parent of the PR that added them) gives
None, and the metric is left out of the line."""

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_dir(cell_name, argv=None):
    """`.benchmark_runs/<cell>.seed<seed>.trace<trace>`, as run.py makes it."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--trace", type=int, default=0)
    args, _ = ap.parse_known_args(sys.argv[1:] if argv is None else argv)
    if args.seed is None:
        return None
    return os.path.join(ROOT, ".benchmark_runs", f"{cell_name}.seed{args.seed}.trace{args.trace}")


def cycle_rows(directory):
    """The `cycle` rows of `<directory>/flight/*.jsonl`, as plain JSON."""
    rows = []
    for path in sorted(glob.glob(os.path.join(directory, "flight", "*.jsonl"))):
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue  # a torn last line
                if isinstance(row, dict) and row.get("kind") == "cycle":
                    rows.append(row)
    return rows


def span_seconds(r, names, directory=None):
    """Seconds inside the named spans over the cycles of the window, or
    None where the program wrote no such span. The program closes its
    `cycle` row just before the harness stamps the boundary, so a window
    cycle and the row that holds its rollout's spans carry the same step."""
    directory = directory or run_dir(r.cell.name)
    if not directory:
        return None
    steps = {c["step"] for c in r.cycles}
    total, found = 0.0, False
    for row in cycle_rows(directory):
        if row.get("step") not in steps:
            continue
        for name, t0, t1, *_ in row.get("spans") or []:
            if name in names:
                total += t1 - t0
                found = True
    return total if found else None
