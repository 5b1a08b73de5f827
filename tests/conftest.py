"""Test fixtures: run everything on a virtual 8-device CPU mesh.

Reference analog: tests/multinode_helpers/mpi_wrapper (fake multi-node on one
machine, SURVEY.md §4). The environment is set BEFORE jax is imported and is
inherited by every child a test starts: the CPU platform, 8 virtual devices,
and the persistent compilation cache OFF — tests must never populate the
checkout's .jax_cache (flexflow_tpu.config.ensure_compile_cache), and a
described-chip compile (tests/test_chip_compile.py) could not read an entry
back anyway.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="session")
def _hermetic_strategy_cache(tmp_path_factory):
    """Point the persistent strategy cache (search/strategy_cache.py, on by
    default) at a per-session temp dir: the suite must never read stale
    strategies from — or write into — the checkout's .ff_cache store, or a
    cost-model change could be masked by a warm hit. Tests that exercise
    the cache itself pass an explicit strategy_cache_dir (which wins)."""
    os.environ["FF_STRATEGY_CACHE_DIR"] = str(
        tmp_path_factory.mktemp("strategy_cache"))
    yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {devs}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)
