"""Multi-host (multi-process) execution: a REAL 2-process
jax.distributed run on CPU — per-process prompt sharding, global-array
generation/experience, process-0-gated tracker and checkpoint metadata.

Parity target: the reference's multi-node paths
(accelerate_ppo_trainer.py:292-341 scatter/gather choreography,
nemo_ppo_trainer.py:344-362); here every process runs the same SPMD
program over one global mesh (SURVEY.md §2.8).
"""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER = os.path.join(REPO, "tests", "multihost_driver.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_ppo_learn_two_processes(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # driver sets its own device count
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [sys.executable, DRIVER, str(pid), "2", str(port), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=560)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-4000:]}"
        assert f"MULTIHOST_OK pid={pid}" in out, out[-2000:]

    # both processes converged on identical replicated params
    sums = sorted(
        line.split("paramsum=")[1]
        for out in outs
        for line in out.splitlines()
        if "MULTIHOST_OK" in line
    )
    assert sums[0] == sums[-1], sums

    # process-0-only artifacts: metrics jsonl written exactly once with
    # a real reward/mean
    metrics_fp = os.path.join(str(tmp_path), "ckpts", "logs", "metrics.jsonl")
    recs = [json.loads(l) for l in open(metrics_fp)]
    assert any("reward/mean" in r for r in recs)


@pytest.mark.slow
def test_sft_ilql_two_processes(tmp_path):
    # the offline trainers (SFT/ILQL): identical per-host datasets,
    # device_put row-sharding onto the global mesh
    driver = os.path.join(REPO, "tests", "multihost_offline_driver.py")
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [sys.executable, driver, str(pid), "2", str(port), str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
        )
        for pid in range(2)
    ]
    outs = [p.communicate(timeout=560)[0] for p in procs]
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-4000:]}"
        assert f"SFT_MH_OK pid={pid}" in out
        assert f"ILQL_MH_OK pid={pid}" in out
        # RFT: generation pooling gathered every process's slice (the
        # driver asserts pool size) and selection/threshold math agreed
        assert f"RFT_MH_OK pid={pid}" in out
    rft_lines = sorted(
        line for out in outs for line in out.splitlines() if "RFT_MH_OK" in line
    )
    sums = {line.split("paramsum=")[1] for line in rft_lines}
    assert len(sums) == 1, rft_lines


@pytest.mark.slow
def test_ppo_learn_two_processes_pp_stages(tmp_path):
    """pp spans the two processes (process 0 = stage 0, process 1 = stage
    1): row helpers must treat them as ONE data group holding identical
    rows, and the pipelined PPO step must converge to identical params."""
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [sys.executable, DRIVER, str(pid), "2", str(port), str(tmp_path), "pp"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=560)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-4000:]}"
        assert f"MULTIHOST_OK pid={pid}" in out, out[-2000:]
    sums = sorted(
        line.split("paramsum=")[1]
        for out in outs
        for line in out.splitlines()
        if "MULTIHOST_OK" in line
    )
    assert sums[0] == sums[-1], sums


def test_ppo_ragged_two_processes(tmp_path):
    """Ragged per-group shapes on multi-host: 3 local rows over 4 local
    data ways on every rollout chunk and eval batch. Both processes must
    finish training (no divisibility ValueError), agree on params, and
    record a real reward/mean — parity with the reference's
    pad_across_processes handling of ragged ends
    (accelerate_ppo_trainer.py:292-300)."""
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = [
        subprocess.Popen(
            [sys.executable, DRIVER, str(pid), "2", str(port), str(tmp_path),
             "ragged"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, text=True,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=560)
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out[-4000:]}"
        assert f"MULTIHOST_OK pid={pid}" in out, out[-2000:]
    sums = sorted(
        line.split("paramsum=")[1]
        for out in outs
        for line in out.splitlines()
        if "MULTIHOST_OK" in line
    )
    assert sums[0] == sums[-1], sums
    metrics_fp = os.path.join(str(tmp_path), "ckpts", "logs", "metrics.jsonl")
    recs = [json.loads(l) for l in open(metrics_fp)]
    assert any("reward/mean" in r for r in recs)
