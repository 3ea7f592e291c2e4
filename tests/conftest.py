"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of testing "distributed" behavior on a
local multi-threaded context (SparkTestUtils.sparkTest with master=local[4]):
we force 8 virtual CPU devices so mesh/sharding/collective paths are
exercised without TPU hardware.
"""

import os

# Hard-set (not setdefault): unit tests never run on an accelerator, whatever
# the environment says (the chip check is chip_smoke.py, not this suite).
# The config update below covers a pytest plugin having imported jax before
# this file ran, when the env var alone would be too late.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", "tests run on the CPU backend"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)
