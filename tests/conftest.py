"""Test harness config.

The reference's distributed tests run the REAL DistriOptimizer on a
``local[4]`` Spark master — no fake comms backend (SURVEY.md §4.5).  The
rebuild's identical trick: force 8 virtual CPU devices so the real
shard_map + psum_scatter/all_gather path executes in one process.

The platform is pinned in code as well as by the tier-1 command's
``JAX_PLATFORMS=cpu``, so a bare ``pytest`` on a machine with a chip
still runs on the CPU; it is pinned before ``bigdl_tpu`` is imported,
so the package places no compile cache for these runs.  XLA_FLAGS has
to be set before the CPU backend initializes.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    from bigdl_tpu.common import RandomGenerator

    RandomGenerator.RNG.set_seed(1)
    yield


def pytest_configure(config):
    assert jax.default_backend() == "cpu", "tests must run on CPU devices"
    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
