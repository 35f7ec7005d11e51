"""Test harness: force JAX onto a virtual 8-device CPU platform.

The standard JAX fake-multi-device trick (SURVEY.md §4): all sharding /
collective tests run on ``--xla_force_host_platform_device_count=8`` CPU
devices, so the full multi-chip code path executes without TPU hardware.

Both the env vars (for any subprocesses) and jax.config (for this process,
in case jax was imported before the env was set) pin the platform.  Must run
before any backend is initialized — conftest import time is early enough.
"""

import os

from swiftmpi_tpu.utils.xla_env import ensure_cpu_mesh_flags

ensure_cpu_mesh_flags(n_devices=8, force_device_count=True)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from swiftmpi_tpu import obs
from swiftmpi_tpu.utils import reset_global_config, reset_global_random


@pytest.fixture(autouse=True)
def _clean_globals():
    """Each test starts with fresh config/RNG/telemetry singletons."""
    reset_global_config()
    reset_global_random()
    obs.reset_for_tests()
    yield
    reset_global_config()
    reset_global_random()
    obs.reset_for_tests()


@pytest.fixture
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]
