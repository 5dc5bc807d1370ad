"""The chip check, reachable from the suite.

The suite itself runs on the virtual CPU mesh (conftest.py); the chip is
proven by ``chip_smoke.py`` at the repo root — the reduction of a
recorder-width recording through the CLI plus every Pallas kernel compiled
and checked against its reference.  This test runs that script in a
subprocess (one process per chip: the suite's own process never touches
the TPU).  One rule: skipped when no TPU is found, and when one is found
ANY failure or timeout fails — a dead chip must not read as a skip.
"""

import functools
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hw_env() -> dict:
    """The session's environment as it was before conftest forced the CPU
    (``BLIT_HW_PLATFORMS`` holds the original ``JAX_PLATFORMS``)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "BLIT_HW_PLATFORMS", "XLA_FLAGS")}
    hw = os.environ.get("BLIT_HW_PLATFORMS", "")
    if hw:
        env["JAX_PLATFORMS"] = hw
    return env


@functools.lru_cache(maxsize=1)
def tpu_found() -> bool:
    if os.environ.get("BLIT_HW_PLATFORMS", "") == "cpu":
        return False  # the session named the CPU
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.devices()[0].platform)"],
        env=_hw_env(), capture_output=True, text=True, timeout=180,
    )
    lines = probe.stdout.strip().splitlines()
    return bool(lines) and lines[-1] == "tpu"


@pytest.mark.slow  # minutes on a chip; tier-1 runs on the CPU and skips it
def test_chip_smoke_passes_on_the_chip():
    if not tpu_found():
        pytest.skip("no TPU found (chip_smoke.py is the chip check)")
    # The contract's limit; a timeout raises and FAILS.
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=_hw_env(), capture_output=True, text=True, timeout=1200,
    )
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-4000:]
    assert proc.stdout.strip().splitlines()[-1].startswith('{"ok": true')
