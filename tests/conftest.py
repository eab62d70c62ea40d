"""Test harness: an 8-device virtual CPU mesh stands in for the multi-chip
TPU slice (and for the reference's mpirun-oversubscribed localhost cluster,
reference: src/README.md:8-11).

The XLA_FLAGS env must be set before jax initialises. The tests run on the
CPU backend wherever they are started — also on a machine with a chip.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
    config.addinivalue_line(
        "markers",
        "core: fast semantic lane (`pytest -m core`) — coding, vote, "
        "aggregation, native-oracle, and op-level tests, plus the program "
        "linter's --fast sweep + negative controls (the lane's longest "
        "test: 33 programs at about 4 s apiece); the subset that gates "
        "every commit",
    )


# Three tiers (the suite is compile-bound: what a test compares is one
# compiled program a (function, shapes), tests/parity.py):
#   pytest -m core         — the algorithmic heart (these modules + explicit
#                            core marks incl. the program-lint fast sweep)
#   pytest -m "not slow"   — adds the jitted train-step / parallel-topology
#                            integration layer and the parity files; what
#                            the driver runs, six workers, `--dist loadfile`,
#                            a 1 470 s limit (ROADMAP D9 has the last runs'
#                            seconds and worker-seconds)
#   pytest                 — everything, incl. subprocess multihost drivers
_CORE_MODULES = {
    "test_coding_cyclic",
    "test_repetition_and_aggregation",
    "test_native",
    "test_ops",
    "test_straggler",
}
_SLOW_MODULES = {"test_multihost"}  # every test spawns real processes
_SLOW_TESTS = {  # individually >1 min wall: subprocess drivers of chip tools
    "test_dryrun_multichip_subprocess",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in _CORE_MODULES:
            item.add_marker(pytest.mark.core)
        if mod in _SLOW_MODULES or item.originalname in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
