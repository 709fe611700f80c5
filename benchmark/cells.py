"""A cell's files, found by the names in BENCHMARK.json, and the run's
inputs made from them and the seed: the program's config, the prompts and
the reward function. Nothing here names a cell, a model or a metric."""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # benchmark/configs/<config>.json
    traffic: Dict[str, Any]  # benchmark/traffic/<mix>.json
    end_to_end: List[Dict[str, Any]]  # this cell's entries of BENCHMARK.json
    per_layer: List[Dict[str, Any]]
    reference: Any  # the module benchmark/reference/<config["reference"]>.py


def load_cell(name: str) -> Cell:
    bench = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    entry = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    config = _load_json(ROOT, cfg_entry["file"])

    def in_cell(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name,
        chips=entry["chips"],
        config=config,
        traffic=_load_json(HERE, "traffic", entry["traffic"] + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if in_cell(m)],
        per_layer=[m for m in bench["per_layer"] if in_cell(m)],
        reference=importlib.import_module("benchmark.reference." + config["reference"]),
    )


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = _load_json(HERE, "peaks.json")
    if device_kind not in table or device_kind == "source":
        raise SystemExit(f"device kind {device_kind!r} is not in benchmark/peaks.json")
    return table[device_kind]


def seeded_prompts(n: int, n_bytes: int, seed: int) -> List[str]:
    """`n` lowercase pseudo-text prompts of exactly `n_bytes` bytes: the
    byte tokenizer then fills the whole prompt window, no padding.
    (Copied from chip_smoke.py, which a later PR may change.)"""
    import numpy as np

    rng = np.random.default_rng(seed)
    alphabet = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz     ", dtype=np.uint8)
    return [bytes(rng.choice(alphabet, size=n_bytes)).decode("ascii") for _ in range(n)]


def reward_fn(samples, prompts, outputs, **_):
    """A host reward that is string arithmetic: no model, no device."""
    return [float(o.count("a")) - 0.1 * len(o) for o in outputs]


def build_config(cell: Cell, seed: int, run_dir: str, scale: Dict[str, Any] | None = None):
    """The program's TRLConfig for this cell: the method's default config,
    the configuration's recipe, the model at the configuration's sizes and
    the traffic mix's shapes, with everything that is not the cycle
    (checkpoints, interval evals, trackers beyond the loss stream) off.

    `scale` is the rehearsal's toy override of sizes and is never set in
    a measured run."""
    from trlx_tpu.data import default_configs

    traffic = dict(cell.traffic, **(scale or {}).get("traffic", {}))
    hf = dict(cell.config, **(scale or {}).get("config", {}))
    base = getattr(default_configs, f"default_{traffic['method']}_config")()
    recipe = cell.config["recipe"]
    transformer = dict(
        recipe.get("model", {}).get("model_extra_configs", {}).get("transformer", {}),
        **cell.reference.system_config(hf),
    )
    seq = traffic["prompt_tokens"] + traffic["new_tokens"]
    transformer["n_positions"] = seq
    never = 10**9
    cfg = base.evolve(**recipe).evolve(
        train=dict(
            seed=seed, batch_size=traffic["batch"], seq_length=seq,
            total_steps=never, epochs=never, eval_interval=never,
            checkpoint_interval=0, save_best=False, checkpoint_dir=run_dir,
            # the program's own loss stream, one line a cycle: the
            # harness reads it back for the finite-loss check
            tracker="jsonl",
        ),
        model=dict(
            model_path="random",
            model_extra_configs={"transformer": transformer},
        ),
        tokenizer=dict(
            tokenizer_path="byte",
            tokenizer_extra_configs=dict(vocab_size=hf["vocab_size"]),
        ),
        method=dict(
            num_rollouts=traffic["rollouts"], chunk_size=traffic["chunk"],
            **traffic["method_kwargs"],
            gen_kwargs=dict(traffic["gen_kwargs"], max_new_tokens=traffic["new_tokens"]),
        ),
    ).evolve(**traffic["overrides"])
    prompts = seeded_prompts(traffic["prompt_pool"], traffic["prompt_tokens"], seed)
    return cfg, prompts, traffic, hf
