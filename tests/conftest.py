"""Test config: force an 8-device virtual CPU mesh BEFORE jax import.

Mirrors the reference's single-host multi-process test pattern
(test_parallel_dygraph_dataparallel.py start_local_trainers) with JAX's
host-device-count trick — 8 virtual CPU devices simulate the TPU slice.
"""
import os

# Hard override: tests run on the virtual 8-device CPU mesh whatever
# the machine has; spawned workers inherit the variable.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# a plugin may have imported jax before this file ran; the config
# update (not just the env var) forces CPU regardless.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu as pt
    pt.seed(2024)
    np.random.seed(2024)
    yield
