"""Test config: run on a virtual 8-device CPU platform.

Mirrors the reference's strategy of simulating multi-device on one host
(SURVEY.md §4): instead of spawning NCCL subprocess rings
(test_collective_base.py), we give XLA 8 virtual CPU devices so sharding /
collective tests compile and run the same SPMD programs as a real pod slice.
"""
import os

_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
# unit tests run on the CPU backend (8 virtual devices above): fast,
# deterministic, no accelerator needed. The env var covers child processes;
# the config update covers a jax that was imported before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# no persistent compile cache under test: a unit test must not depend on what
# an earlier run (or another xdist worker) left in <checkout>/.jax_cache. The
# env var carries the same choice into the child processes tests start.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu

    paddle_tpu.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture(autouse=True, scope="module")
def _no_mesh_from_another_file():
    """A global mesh (fleet.init / init_mesh) left installed by one test file
    must not reach the next file the same xdist worker runs: a single-device
    step then trips over sharding constraints on a mesh it never asked for."""
    from paddle_tpu.parallel import topology

    topology.set_mesh(None)
    yield


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: spawns real subprocesses")
