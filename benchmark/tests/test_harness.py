"""The harness end to end, as a process: refusals, and the rehearsal at toy
sizes on the CPU, which runs every step of a measured run and can never print
a result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(*args, cwd=ROOT, devices=1, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def last_line_is_a_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    try:
        return "correct" in json.loads(lines[-1])
    except (IndexError, ValueError, TypeError):
        return False


def test_refuses_the_cpu():
    p = run("--workload", "pythia-1.4b.ppo-longprompt", "--seed", "1", "--seconds", "1")
    assert p.returncode == 2 and not last_line_is_a_result(p.stdout)
    assert "refusing to run" in p.stdout


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run("--workload", "pythia-1.4b.ppo-longprompt", "--seed", "1", "--seconds", "1",
            "--rehearse", cwd=str(tmp_path))
    assert p.returncode not in (0, 3) and not last_line_is_a_result(p.stdout)


@pytest.mark.parametrize("cell, devices, trace", [
    ("pythia-1.4b.ppo-longprompt", 1, 0),
    ("pythia-1.4b.ppo-longgen", 1, 1),
    ("pythia-6.9b.ppo-hh-fsdp4", 4, 1),  # the mesh, on four virtual CPU devices
])
def test_rehearsal_runs_every_step_and_prints_no_result(cell, devices, trace):
    p = run("--workload", cell, "--seed", "7", "--seconds", "3", "--trace", str(trace),
            "--rehearse", devices=devices)
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    assert not last_line_is_a_result(p.stdout)
    tail = p.stdout.strip().splitlines()[-1]
    assert "REHEARSAL only" in tail
    line = json.loads(tail[tail.index("{"):])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert line["device"]["count"] == devices
    wanted = ({"rollout_wall_share", "train_wall_share", "device_idle_share", "hbm_peak_gib"}
              if trace else {"samples_per_s", "mfu", "setup_s"})
    assert wanted <= set(line["metrics"])
    assert all(m["value"] >= 0 for m in line["metrics"].values())  # no Mosaic time on a CPU
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert ("collective_exposed_share" in line["metrics"]) == (devices == 4)
