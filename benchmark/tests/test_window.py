"""The host-wall partition and the cell loader."""

import json
import os
import re

import pytest

from benchmark import cells
from benchmark.window import OTHER, partition

ROOT = cells.ROOT


def test_partition_innermost_phase_wins_and_shares_sum_to_the_wall():
    beats = [
        (1.0, "rollout", "start"), (2.0, "reward", "start"), (2.5, "reward", "end"),
        (4.0, "rollout", "end"), (4.5, "fused_block", "start"), (5.0, "fused_block", "end"),
        (5.0, "train_wait", "start"), (9.0, "train_wait", "end"),
    ]
    got = partition(beats, 0.0, 10.0)
    assert got == {OTHER: 1.0 + 0.5 + 1.0, "rollout": 1.0 + 1.5, "reward": 0.5,
                   "fused_block": 0.5, "train_wait": 4.0}
    assert sum(got.values()) == pytest.approx(10.0)


def test_partition_clips_to_the_cycle():
    beats = [(0.0, "rollout", "start"), (3.0, "rollout", "end")]
    assert partition(beats, 1.0, 2.0) == {"rollout": 1.0}
    assert partition(beats, 2.0, 5.0) == {"rollout": 1.0, OTHER: 2.0}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_every_cell_finds_its_files_and_readers(cell):
    c = cells.load_cell(cell)
    assert c.chips == c.config["chips"]
    assert {"setup_s"} < {m["name"] for m in c.end_to_end}
    for package, metrics in (("end_to_end", c.end_to_end), ("layer_metrics", c.per_layer)):
        for m in metrics:
            assert os.path.exists(os.path.join(cells.HERE, package, m["name"] + ".py")), m["name"]
    for m in c.per_layer:
        assert m["moves"] in {e["name"] for e in c.end_to_end}
    t = c.traffic
    assert t["rollouts"] % t["chunk"] == 0 and t["rollouts"] % t["batch"] == 0
    # pallas kernels need the sequence in whole 128-slot tiles
    assert (t["prompt_tokens"] + t["new_tokens"]) % 128 == 0 and t["prompt_tokens"] % 8 == 0
    assert t["prompt_tokens"] + t["new_tokens"] <= c.config["max_position_embeddings"]


def test_contract_shapes():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in b[k]]
    assert all(name.match(n) for n in names) and len(set(names)) == len(names)
    assert all(len(x["why"]) <= 200 for k in ("configs", "workloads") for x in b[k])
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    assert all(0.01 <= m["bound"] <= 0.1 for m in b["end_to_end"])
    runs = 2 + 14 * 24  # the check at the full 24 cells must fit 43200 s
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            assert sorted(json.load(f)["reduced"]) == sorted(c["reduced"])


def test_an_unknown_device_has_no_peak():
    assert cells.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        cells.peaks_for("TPU v9")
