"""CPU rehearsals of the benchmark (``python -m pytest benchmark/tests -q``).
They prove the harness's control flow, sizing and arithmetic; they prove
nothing of the chip.  Not part of tier-1 (``tests/``)."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]


def run_harness(*args, root=ROOT, prelude="", timeout=300):
    """``benchmark/run.py`` as a fresh process (the harness sets JAX's
    platform and device count, so it cannot share this one).  ``prelude``
    is Python run before ``main`` — how a test injects a file-size cap."""
    code = ("import sys; sys.path[:0] = [%r, %r]; import run\n%s\n"
            "sys.exit(run.main(%r))" % (os.path.join(root, "benchmark"), root,
                                        prelude, list(args)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    out = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, out


@pytest.fixture(scope="session")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
