"""Test harness: force an 8-device CPU mesh before JAX initializes.

This is the JAX-native answer to "test multi-device without a cluster"
(SURVEY.md §4): every test sees 8 virtual devices, so dp/fsdp/tp sharding
paths are exercised on any machine, matching how the driver dry-runs the
multi-chip path.
"""

import os
import re

# CPU with 8 virtual devices, set in the environment before jax is
# imported anywhere (the device-count flag only takes effect before the
# backend initializes). Flag-merge logic mirrors
# __graft_entry__._force_device_count_flag (kept inline here: this file
# must not import anything that pulls in jax).
flags = os.environ.get("XLA_FLAGS", "")
m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
if m and int(m.group(1)) < 8:
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "--xla_force_host_platform_device_count=8",
        flags,
    )
elif not m:
    flags += " --xla_force_host_platform_device_count=8"
os.environ["XLA_FLAGS"] = flags.strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# the repo root on sys.path regardless of invocation style: bare
# `pytest tests/` (the CI workflow) doesn't put the cwd there, and the
# example-surface tests import `examples.*` (a plain directory, not an
# installed package)
import sys  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)
