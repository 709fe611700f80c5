"""Utils coverage (reference analog: tests/test_utils.py — optimizer/
scheduler getters, RunningMoments vs torch.var_mean, Clock)."""

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from trlx_tpu.ops.common import RunningMoments, running_moments_update
from trlx_tpu.utils import (
    Clock,
    get_optimizer_class,
    get_scheduler_class,
    significant,
)


@pytest.mark.parametrize(
    "name", ["adam", "adamw", "adamw_8bit_bnb", "sgd", "lion"]
)
def test_optimizer_getters(name):
    make = get_optimizer_class(name)
    tx = make(1e-4)
    assert isinstance(tx, optax.GradientTransformation)
    p = {"w": jnp.ones((4, 4))}
    st = tx.init(p)
    g = jax.tree_util.tree_map(jnp.ones_like, p)
    u, _ = tx.update(g, st, p)
    assert jax.tree_util.tree_leaves(u)[0].shape == (4, 4)


@pytest.mark.parametrize(
    "name", ["cosine_annealing", "linear", "constant"]
)
def test_scheduler_getters(name):
    make = get_scheduler_class(name)
    if name == "cosine_annealing":
        sched = make(1e-3, T_max=100, eta_min=1e-5)
        assert abs(float(sched(0)) - 1e-3) < 1e-9
        assert float(sched(100)) <= 1e-3
    elif name == "linear":
        sched = make(1e-3, total_steps=100)
        assert float(sched(0)) >= float(sched(99))
    else:
        sched = make(1e-3)
        assert float(sched(0)) == float(sched(50))


def test_running_moments_matches_torch_var_mean():
    # parity target: reference utils/modeling.py RunningMoments.update,
    # asserted against torch.var_mean in reference tests/test_utils.py:95-112
    torch = pytest.importorskip("torch")

    rng = np.random.default_rng(0)
    rm = RunningMoments(
        mean=jnp.float32(0.0), std=jnp.float32(1.0),
        var=jnp.float32(1.0), count=jnp.float32(1e-24),
    )
    all_xs = []
    for _ in range(5):
        xs = rng.normal(size=(64,)).astype(np.float32) * 2.0 + 0.5
        all_xs.append(xs)
        rm, batch_mean, batch_std = running_moments_update(rm, jnp.asarray(xs))
        t_var, t_mean = torch.var_mean(torch.tensor(xs), unbiased=True)
        np.testing.assert_allclose(float(batch_mean), t_mean.item(), rtol=1e-5)
        np.testing.assert_allclose(
            float(batch_std), t_var.sqrt().item(), rtol=1e-3
        )
    full = np.concatenate(all_xs)
    t_var, t_mean = torch.var_mean(torch.tensor(full), unbiased=True)
    np.testing.assert_allclose(float(rm.mean), t_mean.item(), rtol=1e-4)
    np.testing.assert_allclose(
        float(rm.std), t_var.sqrt().item(), rtol=1e-2
    )


def test_clock_ticks():
    clock = Clock()
    dt = clock.tick()
    assert dt >= 0.0
    assert clock.tick() >= 0.0


def test_significant():
    assert significant(0.123456) == 0.12
    assert significant(1234.5) == 1200.0
    assert significant(0.0) == 0.0
    assert significant("str") == "str"


# -- bring-up: where the compile cache goes, and no CPU fallback ---------

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd, **env):
    full = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=full, capture_output=True,
        text=True, timeout=120,
    )


def test_compile_cache_env_wins_and_sets_no_dir(monkeypatch):
    from trlx_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    # JAX reads the variable itself; setting it in code would override it
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 2.0


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch, tmp_path):
    from trlx_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update", updates.__setitem__)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    here = compile_cache.enable_compile_cache()
    assert here == os.path.join(REPO, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == here
    # the path is part of the cache key: another process, started
    # somewhere else, must land on the same directory
    r = _run(
        ["-c", "from trlx_tpu.utils.compile_cache import enable_compile_cache as e;"
               " import jax; print(e()); print(jax.config.jax_compilation_cache_dir)"],
        cwd=str(tmp_path),
    )
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [here, here]


def test_chip_smoke_refuses_cpu_before_building_anything(tmp_path):
    r = _run([os.path.join(REPO, "chip_smoke.py")], cwd=str(tmp_path))
    assert r.returncode != 0
    assert "platform: cpu" in r.stdout
    assert '"ok"' not in r.stdout
    assert "leg " not in r.stdout  # no leg started, no model built


def test_bench_default_flow_exits_nonzero_off_the_chip(tmp_path):
    r = _run([os.path.join(REPO, "bench.py")], cwd=str(tmp_path))
    assert r.returncode != 0
    import json

    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert "headline_error" in result and "refusing to record" in result["headline_error"]
    # no CPU number under a device metric's name
    assert "value" not in result and "mfu" not in result


def test_unknown_device_kind_has_no_peak():
    sys.path.insert(0, REPO)
    import bench
    from trlx_tpu.obs.telemetry import chip_peak_tflops

    assert chip_peak_tflops("TPU v5 lite") == 197.0
    assert chip_peak_tflops("TPU v5p") == 459.0  # longest prefix wins
    assert chip_peak_tflops("TPU v99") is None
    with pytest.raises(ValueError, match="no bf16 peak known"):
        bench.chip_peak_tflops()  # this process's device_kind is "cpu"
