"""Test harness: run JAX on a virtual 8-device CPU mesh.

Must set the XLA flags *before* jax is imported anywhere, so this executes at
conftest import time.  This fakes the 8-bank (and 2x4 band,bank) topology the
same way SURVEY.md §4 prescribes for testing the multi-chip path without
multi-chip hardware.
"""

import os

# Force CPU for tests even when the session env points JAX at real
# hardware: the suite runs on the virtual 8-device mesh; the chip is
# exercised by chip_smoke.py, one process at a time.  What the session
# asked for is remembered so test_tpu_smoke.py can find the chip again.
os.environ.setdefault("BLIT_HW_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# A shell-exported staging budget (the hostmem.py A/B lever) must not
# reshape SlabPool behavior under test.
os.environ.pop("BLIT_STAGING_BYTES", None)

import logging
import subprocess

import pytest

# Build blit/native once per session from the committed sources (ROADMAP
# C7): the bitshuffle codec and the threaded GUPPI reader are C++ with no
# Python twin for ENCODING, and a fresh checkout has no build/ directory.
# `make` is a no-op when the libraries are current.  Without a toolchain
# the tests that need the codec skip with the reason instead of failing.
_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "blit", "native")
try:
    _build = subprocess.run(["make", "-C", _NATIVE_DIR],
                            capture_output=True, text=True)
    NATIVE_BUILD_ERROR = (
        None if _build.returncode == 0
        else (_build.stderr.strip().splitlines() or ["make failed"])[-1])
except OSError as e:  # no make on this machine
    NATIVE_BUILD_ERROR = str(e)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    outcome = yield
    if NATIVE_BUILD_ERROR and outcome.excinfo is not None:
        exc = outcome.excinfo[1]
        if isinstance(exc, RuntimeError) and "blit/native" in str(exc):
            outcome.force_exception(pytest.skip.Exception(
                f"blit/native could not be built here "
                f"({NATIVE_BUILD_ERROR}): {exc}"))


def pytest_sessionfinish(session, exitstatus):
    """Publish the run's merged telemetry report (ISSUE 5 CI satellite):
    when BLIT_TELEMETRY_OUT is set (the tier-1 CI job points it at a
    workspace file uploaded as an artifact), the whole suite's process
    timeline, fault counters and spans land there as one fleet report."""
    if os.environ.get("BLIT_TELEMETRY_OUT"):
        from blit import observability

        observability.maybe_write_report()


@pytest.fixture
def blit_logger_restored():
    """Snapshot + restore the 'blit' logger around tests that call
    configure_logging (which sets propagate=False — that must not leak into
    caplog-based tests)."""
    root = logging.getLogger("blit")
    handlers, propagate, level = list(root.handlers), root.propagate, root.level
    yield
    root.handlers = handlers
    root.propagate = propagate
    root.setLevel(level)
