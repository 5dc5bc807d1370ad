"""Each driver end to end at toy size on the CPU (four virtual devices for
``scan``), and a cell added as files only."""

import json
import os
import shutil

import pytest
from conftest import BENCH, ROOT, run_harness

E2E = {"bank.hires": ["first_product_s", "reduce_rate", "setup_s"],
       "bank.lowres": ["first_product_s", "reduce_rate", "setup_s"],
       # two 27 s passes to a run: too unsteady to carry a bound (PERF.md)
       "band4.hires": ["reduce_rate", "setup_s"]}


def last_doc(out):
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", ["bank.hires", "bank.lowres", "band4.hires"])
def test_end_to_end_run_at_toy_size(cell):
    p, out = run_harness("--workload", cell, "--seed", "3", "--seconds",
                         "0.05", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = last_doc(out)
    assert doc["rehearsal"] is True and doc["platform"] == "cpu"
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1 and doc["metric_names"] == E2E[cell]
    # no metric value under any name: a CPU run has none to give
    assert "metrics" not in doc
    assert any(ln.startswith("[check.reference]") for ln in out)
    assert any(ln.startswith("[reduced]") for ln in out)


@pytest.mark.parametrize("cell, names", [
    ("bank.hires", ["host_cpu_s_per_GB", "readback_s_per_GB",
                    "write_s_per_GB"]),
    ("band4.hires", ["first_product_wait_s", "host_cpu_s_per_GB", "read_rate",
                     "readback_s_per_GB", "write_s_per_GB"]),
])
def test_traced_run_reports_only_what_a_cpu_can(cell, names):
    """Host-side readers find their spans; the device readers find no
    device plane in a CPU trace and return nothing."""
    p, out = run_harness("--workload", cell, "--seed", "4", "--seconds",
                         "0.05", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = last_doc(out)
    assert doc["correct"] is True and doc["metric_names"] == names
    assert doc["breakdown"] is False


def test_no_accelerator_exits_nonzero_before_any_work():
    p, out = run_harness("--workload", "bank.hires", "--seed", "1",
                         "--seconds", "1", "--trace", "0",
                         prelude="import os; os.environ['JAX_PLATFORMS']='cpu'")
    assert p.returncode == 2
    assert "no CPU continuation" in p.stderr.strip().splitlines()[-1]
    assert not any(ln.startswith(("{", "[synth]", "[plan]")) for ln in out)


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p, out = run_harness("--workload", "bank.hires", "--seed", "1",
                         "--seconds", "1", "--trace", "0", root=str(tmp_path))
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in out)


def test_a_cell_added_as_files_only_is_found(tmp_path):
    """A later PR adds a traffic mix, a cell and a per-layer metric with
    its reader as new files and new entries, editing no file that is
    there.  The harness finds them by name."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "blit"), tmp_path / "blit")
    b = tmp_path / "benchmark"
    with open(b / "traffic" / "lowres-6s.json") as f:
        t = json.load(f)
    t.update(name="lowres-2s", blocks=25)
    (b / "traffic" / "lowres-2s.json").write_text(json.dumps(t))
    (b / "readers" / "passes.py").write_text(
        "def read(args, ev):\n"
        "    return ev['window_raw_bytes'] / ev['traced_raw_bytes']\n")
    (b / "layer_metrics" / "passes_in_window.json").write_text(json.dumps(
        {"name": "passes_in_window", "reader": "passes", "args": {}}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "bank.lowres2s", "config": "gbt-bank",
                               "traffic": "lowres-2s", "chips": 1,
                               "why": "added by a test, as files only"})
    bench["per_layer"].append({
        "name": "passes_in_window", "unit": "passes", "better": "higher",
        "source": "program_counter", "layer": "whole host path",
        "moves": "reduce_rate", "workloads": ["bank.lowres2s"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    p, out = run_harness("--workload", "bank.lowres2s", "--seed", "5",
                         "--seconds", "0.05", "--trace", "1", "--rehearse",
                         root=str(tmp_path))
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = last_doc(out)
    assert doc["correct"] is True
    assert "passes_in_window" in doc["metric_names"]
    plan = json.loads(next(ln for ln in out if ln.startswith("[plan]"))[7:])
    assert plan["blocks"] == 25
