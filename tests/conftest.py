"""Test harness config: force JAX onto a virtual 8-device CPU mesh.

Tests force the CPU because they must be hermetic — the same result on
a laptop and on a TPU host, never holding a chip another process needs.
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` must be in the
environment before the CPU backend is *initialized* (it is read at
client creation, which is lazy — so setting it here, before any test
touches jax, is early enough).
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Keep test compiles fast and deterministic.
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Hermetic too: the persistent compile cache every driver run arms
# (utils/compile_cache.py) stays off under test, here and in every
# child, so no test writes into the checkout or passes on what an
# earlier run left there.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


@pytest.fixture(scope="session")
def bench_history(tmp_path_factory):
    """A directory holding the synthetic five-round bench history
    (tests/bench_history.py) — what the artifact-reading tests parse in
    place of records committed at the repo root.  Read-only by
    convention: tests that write artifacts use their own tmp_path."""
    from bench_history import write_history

    return write_history(tmp_path_factory.mktemp("bench_history"))


def pytest_generate_tests(metafunc):
    """A family suite's cases that follow the family (a gradient leaf a
    case; tests/family_suite.py ``per_preset``) come from the preset of
    the class that inherits the test."""
    for argname, field in getattr(metafunc.function, "per_preset", ()):
        metafunc.parametrize(argname,
                             list(getattr(metafunc.cls.preset, field)))


# -- smoke tier ------------------------------------------------------------
# `pytest -m smoke` is the time-boxed CI selection (< 2 min on one core):
# the pure-math and protocol modules below, minus anything marked slow.
# Heavier end-to-end coverage stays in the default/-m slow tiers.

import pytest  # noqa: E402

_SMOKE_MODULES = {
    "test_vtrace",
    "test_losses",
    "test_distributions",
    "test_utils_algo",
    "test_utils_misc",
    "test_batcher",
    "test_sequence_parallel",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        module = getattr(item, "module", None)
        if (module is not None
                and module.__name__ in _SMOKE_MODULES
                and "slow" not in item.keywords):
            item.add_marker(pytest.mark.smoke)
