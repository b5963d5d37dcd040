"""Test fixtures: simulate an 8-device TPU pod slice on CPU.

Mirrors the reference's test mechanism (SURVEY §4): the reference runs one
suite either single-process (1-rank world) or under ``mpirun -np 2``; we run
the same suite over an XLA-simulated 8-device CPU mesh — the TPU-native
analog of a multi-rank world on one host.

The suite never runs on an accelerator: the platform is pinned to the CPU
here, in code, before any backend exists, so it does not depend on what
``JAX_PLATFORMS`` the caller exported (a machine with a chip defaults to
the TPU). ``JAX_PLATFORMS=cpu`` is also written to the environment for the
subprocesses the tests spawn. float64 is enabled because the numerical
references are computed in it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def pytest_configure(config):
    # tier-1 (ROADMAP.md) runs -m 'not slow'; registered so filtering
    # never silently no-ops on a misspelled mark.
    config.addinivalue_line(
        "markers", "slow: >5s tests excluded from the tier-1 suite")


@pytest.fixture
def world():
    """Initialized default (single global group) runtime; shuts down after."""
    hvd.shutdown()
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture
def grouped_world():
    """The README's overlapping-groups example [[0,1,2],[2,3,4]]
    (reference README.md:10) over the 8-device world."""
    hvd.shutdown()
    hvd.init([[0, 1, 2], [2, 3, 4]])
    yield hvd
    hvd.shutdown()
