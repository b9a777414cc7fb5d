"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's two-tier test scheme (SURVEY.md §4): pure-math units
run on numpy; planner/kernel/sharding suites run the real code paths on a
virtual 8-device CPU mesh (the analog of Accumulo's MockInstance in-JVM
backend), so multi-chip behavior is exercised without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: tests always run the CPU mesh
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# a plugin may have imported jax before this conftest set the env var: pin
# the config too, before any backend initializes
jax.config.update("jax_platforms", "cpu")

# tpurace dynamic prong: GEOMESA_TPU_SANITIZE=1 wraps every lock the repo
# creates in an Eraser-style lock-order recorder (see
# geomesa_tpu/analysis/race/sanitizer.py). Install BEFORE any geomesa_tpu
# submodule import so module-level and instance locks all land in the
# graph (geomesa_tpu/__init__ itself is lazy and creates none).
_sanitizer = None
if os.environ.get("GEOMESA_TPU_SANITIZE", "") not in ("", "0"):
    from geomesa_tpu.analysis.race import sanitizer as _sanitizer

    _sanitizer.install()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _fresh_cost_state():
    """Per-test isolation of the adaptive-planner feedback state: the
    process-global plan table accumulates across queries (probe ticks,
    learned route verdicts, calibration, latency history, rollups), so a
    suite run would otherwise leak one test's training into the next
    test's strategy choices — order-fragile by construction (the same
    lesson test_geoblocks learned for the agg route). Tests that manage
    their own installs simply stack on top; both restore on teardown."""
    from geomesa_tpu.obs import plantable

    prev = plantable.install()
    yield
    plantable.install(prev)


@pytest.fixture(scope="session", autouse=True)
def _lock_order_gate():
    """Under GEOMESA_TPU_SANITIZE=1, fail the run if real execution ever
    acquired repo locks in cycle-forming orders (the schedule that
    actually deadlocks never needs to happen — opposite orders on any
    two threads are enough to flag)."""
    yield
    if _sanitizer is not None:
        _sanitizer.check()
