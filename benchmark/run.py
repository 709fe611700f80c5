"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by the names in BENCHMARK.json (see
benchmark/README.md). The run refuses any platform but `tpu` and any device
count but the cell's, builds the program's config, hands it to
`trlx_tpu.train()` with a trainer that differs from the configured one only
in putting `benchmark/window.py` around itself before `learn()`, and prints
one JSON object as the last line of standard output. With `--trace 0` the
metrics are the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics, read from the spans, the counters and a profiler trace of the
first `trace_cycles` cycles of the window.

`--rehearse` runs the same code at toy sizes on whatever backend JAX has,
to debug the harness; it prints what it read, never the result line, and
exits 3.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, for setup_s

import argparse
import glob
import importlib
import json
import os
import shutil
import statistics
import sys
from dataclasses import dataclass
from typing import Any, Dict, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# sizes of the rehearsal: every code path of a run, none of its cost. A
# family whose widths go by other keys states its own: `toy_sizes(hf)` of
# the configuration's reference module takes the place of "config".
REHEARSAL = {
    "config": {"hidden_size": 128, "num_attention_heads": 2, "intermediate_size": 256,
               "num_hidden_layers": 4, "vocab_size": 512},
    "traffic": {"prompt_tokens": 120, "new_tokens": 8, "reference_rows": 2},
}


def rehearsal_scale(cell) -> Dict[str, Dict[str, Any]]:
    toy = getattr(cell.reference, "toy_sizes", None)
    return dict(REHEARSAL, config=toy(cell.config) if toy else REHEARSAL["config"])


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", flush=True)


@dataclass
class Reading:
    """What a metric reader may look at."""

    cell: Any  # cells.Cell
    run_dir: str  # the run's files: the program's logs/, flight/, trace_summary.json
    flight: list  # every row of the program's flight stream, as plain JSON: `cycle`
    # rows (step, phases, spans with their counts, the cycle's counters), `gauge`
    # rows, run_start and run_end; layer_metrics/_program_spans.py picks the window's
    hf: Dict[str, Any]  # the configuration as run
    traffic: Dict[str, Any]  # the traffic mix as run
    chips: int
    peaks: Dict[str, float]
    unfrozen: int  # model.num_layers_unfrozen as run
    setup_s: float
    cycles: list  # window.cycles: wall_s, phases, step, loss
    cycle_s: float  # median wall of a cycle in the window
    wall_s: float  # sum of the cycles' walls
    phases: Dict[str, float]  # host wall by phase over the window
    steps_per_cycle: int
    trace: Optional[Dict[str, Any]]  # trace_reduce.reduce(), or None
    memory: Optional[Dict[str, float]]  # compiled memory_analysis(), or None


def device_line(devices) -> Dict[str, Any]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def flight_rows(run_dir: str) -> list:
    """The rows of `<run_dir>/flight/*.jsonl`, in the order written."""
    rows = []
    for path in sorted(glob.glob(os.path.join(run_dir, "flight", "*.jsonl"))):
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue  # a torn last line
                if isinstance(row, dict):
                    rows.append(row)
    return rows


def compiled_memory(trainer) -> Optional[Dict[str, float]]:
    """`memory_analysis()` of the fused train step, the cycle's largest
    program, rebuilt from abstract arguments after the window (the backend
    compile is a cache hit). None where the trainer has no such step."""
    import jax
    import jax.numpy as jnp

    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)

    try:
        full, n = trainer._fused_epoch_batch()
        perms = trainer._epoch_perms(n)
        with trainer.mesh:
            compiled = trainer._fused_train_step.lower(
                abstract(trainer.params), abstract(trainer.opt_state),
                abstract(trainer.place_batch(full)),
                jax.ShapeDtypeStruct(perms.shape, jnp.int32),
            ).compile()
    except (AttributeError, TypeError) as e:
        log(f"no compiled memory figure: {e!r}")
        return None
    m = compiled.memory_analysis()
    return {"peak": float(m.peak_memory_in_bytes), "argument": float(m.argument_size_in_bytes),
            "temp": float(m.temp_size_in_bytes)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from benchmark import cells, correct
    from benchmark.window import CompileMeter, Window

    cell = cells.load_cell(args.workload)

    import jax

    devices = jax.devices()
    found = f"{devices[0].platform} x{len(devices)} ({devices[0].device_kind})"
    log(f"cell {cell.name}: wants tpu x{cell.chips}, found {found}")
    if not args.rehearse and (devices[0].platform != "tpu" or len(devices) != cell.chips):
        log("refusing to run: a number from another device is not this cell's")
        return 2

    import trlx_tpu
    from trlx_tpu.trainer import register_trainer
    from trlx_tpu.utils.loading import get_trainer

    run_dir = os.path.join(ROOT, ".benchmark_runs", f"{cell.name}.seed{args.seed}.trace{args.trace}")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    cfg, prompts, traffic, hf = cells.build_config(
        cell, args.seed, run_dir, rehearsal_scale(cell) if args.rehearse else None)
    meter = CompileMeter()
    state: Dict[str, Any] = {}

    def first_experience() -> None:
        t0 = time.monotonic()
        state["reference"] = correct.reference_check(
            state["trainer"], cell, hf, traffic["reference_rows"])
        log(f"reference check ({time.monotonic() - t0:.1f} s): {json.dumps(state['reference'])}")

    @register_trainer("Benchmarked" + cfg.train.trainer)
    class Benchmarked(get_trainer(cfg.train.trainer)):
        def learn(self):
            state["trainer"] = self
            state["window"] = Window(
                self, args.seconds, meter, first_experience,
                trace_dir=os.path.join(run_dir, "trace") if args.trace else None,
                trace_cycles=traffic["trace_cycles"], log=log,
            )
            state["window"].install()
            state["built_s"] = time.monotonic() - T0
            return super().learn()

    cfg.train.trainer = "Benchmarked" + cfg.train.trainer
    trainer = trlx_tpu.train(reward_fn=cells.reward_fn, prompts=prompts, config=cfg)
    window = state["window"]
    log(f"compile meter over the run: {json.dumps(meter.snapshot())}; "
        f"inside the window: {json.dumps(window.compile_in_window)}")

    # -- correct ---------------------------------------------------------
    ref = state["reference"]
    win = correct.window_check(
        correct.read_stream(run_dir), window.cycles, traffic,
        window.experiences, trainer.obs.events_tail())
    log(f"window check: {json.dumps(win)}; losses {[c['loss'] for c in window.cycles]}")
    is_correct = bool(
        ref["scorer_ok"] and ref["sampler_ok"] and win["tokens_ok"]
        and win["failed"] == 0 and window.cycles
        and correct.compile_check(window.compile_in_window)
    )

    # -- metrics ---------------------------------------------------------
    device = device_line(devices)
    trace = None
    if args.trace:
        from benchmark import trace_reduce

        trace = trace_reduce.reduce(os.path.join(run_dir, "trace"))
        with open(os.path.join(run_dir, "trace_summary.json"), "w") as f:
            json.dump(trace, f, indent=1)
        shutil.rmtree(os.path.join(run_dir, "trace"))  # tens to hundreds of MB a run
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
    peaks_kind = "TPU v5 lite" if args.rehearse else device["kind"]
    reading = Reading(
        cell=cell, run_dir=run_dir, flight=flight_rows(run_dir),
        hf=hf, traffic=traffic, chips=cell.chips,
        peaks=cells.peaks_for(peaks_kind),
        unfrozen=trainer.config.model.num_layers_unfrozen,
        setup_s=window.opened_at - T0, cycles=window.cycles,
        cycle_s=statistics.median(c["wall_s"] for c in window.cycles), wall_s=window.wall_s,
        phases=window.phase_walls(),
        steps_per_cycle=trainer.n_inner_epochs * max(traffic["rollouts"] // traffic["batch"], 1),
        trace=trace, memory=compiled_memory(trainer) if args.trace else None,
    )
    wanted, package = (
        (cell.per_layer, "layer_metrics") if args.trace else (cell.end_to_end, "end_to_end"))
    metrics = {}
    for m in wanted:
        value = importlib.import_module(f"benchmark.{package}.{m['name']}").read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"set-up {reading.setup_s:.1f} s (trainer built after {state['built_s']:.1f} s), "
        f"{len(window.cycles)} cycles in {window.wall_s:.3f} s (median {reading.cycle_s:.4f} s), whole run "
        f"{time.monotonic() - T0:.1f} s")

    line = {"correct": is_correct, "attempted": len(window.cycles), "failed": win["failed"],
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = trace["breakdown"]
    # every number compared beside its limit: last in the line, last on stderr
    line["compared"] = correct.compared(ref, win, window.compile_in_window, cell.config["correct"])
    for name, (number, limit) in line["compared"].items():
        print(f"[benchmark] compared {name}: {number} limit {limit}", file=sys.stderr, flush=True)
    if args.rehearse:
        log("REHEARSAL only, nothing below is a measurement: " + json.dumps(line))
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
