"""Each driver end to end at toy size on the CPU (four virtual devices for
``scan``), and a cell added as files only."""

import json
import os
import shutil

import pytest
from conftest import (BENCH, EVERY_PASS, LOCAL_DRIVER, NO_TWIN, PUMP_CALL,
                      PUMP_WAITS, ROOT, lines_of, products_traffic,
                      run_harness, run_line, tree_with)

E2E = {"bank.hires": ["first_product_s", "setup_s"],   # rate: `pass_rate`
       # its first rows come in two modes of pass: `first_product_wait_s`
       "bank.lowres": ["reduce_rate", "setup_s"],
       # two 27 s passes to a run: too unsteady to carry a bound (PERF.md)
       "band4.hires": ["reduce_rate", "setup_s"]}


def last_doc(out):
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", ["bank.hires", "bank.lowres", "band4.hires"])
def test_end_to_end_run_at_toy_size(cell):
    # one of the three runs the harness as it is: its own `make -B` of
    # blit/native (the others find the session's build, conftest.py)
    rebuild = cell == "bank.lowres"
    p, out = run_harness("--workload", cell, "--seed", "3", "--seconds",
                         "0.05", "--trace", "0", "--rehearse",
                         rebuild=rebuild)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert (run_line(p)["build_s"] > 0.2) == rebuild
    doc = last_doc(out)
    assert doc["rehearsal"] is True and doc["platform"] == "cpu"
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1 and doc["metric_names"] == E2E[cell]
    # no metric value under any name: a CPU run has none to give
    assert "metrics" not in doc
    assert any(ln.startswith("[check.reference]") for ln in out)
    assert any(ln.startswith("[reduced]") for ln in out)
    # one placement rule for every cell: products on $TMPDIR, the
    # recording on RAM-backed scratch
    (plan,) = lines_of(out, "plan")
    assert os.path.dirname(plan["outdir"]) != os.path.dirname(plan["rawdir"])
    # the reference once a run: a child per checked channel (two of the
    # bank, one per bank of the band), all joined inside set-up
    (ref,) = lines_of(out, "reference")
    assert ref["launched"] == ref["tasks"] == run_line(p)["reference"][
        "tasks"] == (4 if cell == "band4.hires" else 2)
    assert not ref["failed"]


@pytest.mark.parametrize("cell, names", [
    # `wait.link` is a declared wait: 0 calls on the CPU, and so 0.0 s/GB
    ("bank.hires", [n + ".first" for n in EVERY_PASS + PUMP_WAITS
                    if n not in NO_TWIN] + ["pass_rate"]),
    ("bank.lowres", EVERY_PASS + PUMP_WAITS + [PUMP_CALL,
                                               "first_product_wait_s"]),
    ("band4.hires", EVERY_PASS + ["first_product_wait_s"]),
])
def test_traced_run_reports_only_what_a_cpu_can(cell, names):
    """Host-side readers find their spans; the device readers (the
    ``idle_*`` of the parts and of the ends among them) find no device
    plane in a CPU trace and return nothing.  Every pass has its two ends,
    once, and its parts (PR 36; ``dispatch.call`` is the pump's)."""
    p, out = run_harness("--workload", cell, "--seed", "4", "--seconds",
                         "0.05", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = last_doc(out)
    assert doc["correct"] is True and doc["metric_names"] == sorted(names)
    assert doc["breakdown"] is False
    (traced,) = lines_of(out, "traced")
    st = traced["stages"]
    assert st["open"]["calls"] == st["close"]["calls"] == 1
    assert st["link.put"]["seconds"] > 0 and st["coeffs"]["calls"] >= 1
    assert st["write.digest"]["bytes"] > st["write"]["bytes"]
    assert ("dispatch.call" in st) == (cell != "band4.hires")


def test_a_native_build_that_fails_ends_the_run_with_no_result(tmp_path):
    """The harness's ``make -B`` as it is, in a tree whose ``blit/native``
    cannot be built: no pass is made, no result line is printed, the exit
    code is not 0 and nothing is left on scratch."""
    root = tree_with(tmp_path / "tree", traffic={}, workloads=[])
    os.remove(os.path.join(root, "blit"))
    os.mkdir(os.path.join(root, "blit"))
    for name in os.listdir(os.path.join(ROOT, "blit")):
        if name != "native":
            os.symlink(os.path.join(ROOT, "blit", name),
                       os.path.join(root, "blit", name))
    os.mkdir(os.path.join(root, "blit", "native"))
    with open(os.path.join(root, "blit", "native", "Makefile"), "w") as f:
        f.write("all:\n\t@echo this build fails >&2; false\n")
    scratch = tmp_path / "scratch"
    scratch.mkdir()
    p, out = run_harness("--workload", "bank.lowres", "--seed", "5",
                         "--seconds", "0.05", "--trace", "0", "--rehearse",
                         root=root, rebuild=True, timeout=120,
                         prelude=f"import tempfile; tempfile.tempdir = "
                                 f"{str(scratch)!r}")
    assert p.returncode not in (0, 2), p.stdout[-3000:] + p.stderr[-3000:]
    assert "this build fails" in p.stderr \
        and "CalledProcessError" in p.stderr.strip().splitlines()[-1]
    assert not any(ln.startswith(("{", "[plan]", "[synth]")) for ln in out)
    assert os.listdir(scratch) == []


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


ONE_PRODUCT = dict(
    cell={"name": "bank.lowres2s", "config": "gbt-bank",
          "traffic": "lowres-2s", "chips": 1,
          "why": "added by a test, as files only"},
    blocks=25, products={"product": 2})
# Three products a pass, as `rawspec -f 1024,8,64 -t 1,128,51` would make
# them from one read of the toy recording: a traffic file with a
# three-entry `products` list, a configuration file of its own, a driver
# file of its own (the command that makes three from one read does not
# exist yet, so the test's driver runs `blit reduce` per product).
THREE_PRODUCTS = dict(
    cell={"name": "rawspec3.toy", "config": "gbt-bank-three",
          "traffic": "three-products", "chips": 1,
          "why": "added by a test, as files only: three products a pass"},
    blocks=38, products={"0000": 16, "0001": 18, "0002": 5})


@pytest.mark.parametrize("case", [ONE_PRODUCT, THREE_PRODUCTS],
                         ids=["one_product", "three_products"])
def test_a_cell_added_as_files_only_is_found(tmp_path, case):
    """A later PR adds a traffic mix, a cell, a configuration and a
    per-layer metric with its reader as new files and new entries, editing
    no file that is there.  The harness finds them by name, sizes every
    product of the pass and verifies each.  ``reduce_rate`` lists its cells
    (every cell but ``bank.hires``): the new cell's name goes at the END of
    that list (the check refuses a new end-to-end entry from a PR that
    changes the program), the accepted readings that list no cell then
    come by themselves, and a second name for an accepted reading is a
    ``same_as`` file."""
    cell = case["cell"]
    rate = "reduce_rate"
    files = {
        "readers/passes_in_window.py":
            "def read(args, ev):\n"
            "    return ev['window_raw_bytes'] / ev['traced_raw_bytes']\n",
        "layer_metrics/passes_in_window.json": json.dumps(
            {"name": "passes_in_window", "reader": "passes_in_window",
             "args": {}}),
        "layer_metrics/read_rate.added.json": json.dumps(
            {"name": "read_rate.added", "same_as": "read_rate"})}
    drivers, configs = [], []
    if case is ONE_PRODUCT:
        with open(os.path.join(BENCH, "traffic", "lowres-6s.json")) as f:
            t = json.load(f)
        t.update(name="lowres-2s", blocks=25)
    else:
        t = products_traffic("three-products", [
            ("0000", 1024, 1), ("0001", 8, 128), ("0002", 64, 51)])
        drivers = [LOCAL_DRIVER]
        with open(os.path.join(BENCH, "configs", "gbt-bank.json")) as f:
            configs = [dict(json.load(f), name="gbt-bank-three")]
    root = tree_with(
        tmp_path, traffic={t["name"]: t}, workloads=[cell], files=files,
        drivers=drivers, configs=configs, listed_under=[rate],
        per_layer=[{
            "name": "passes_in_window", "unit": "passes", "better": "higher",
            "source": "program_counter", "layer": "whole host path",
            "moves": rate, "workloads": [cell["name"]]}, {
            "name": "read_rate.added", "unit": "GB/s", "better": "higher",
            "source": "program_span", "layer": "host read",
            "moves": rate, "workloads": [cell["name"]]}])
    p, out = run_harness("--workload", cell["name"], "--seed", "5",
                         "--seconds", "0.05", "--trace", "1", "--rehearse",
                         root=root)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = last_doc(out)
    assert doc["correct"] is True and doc["failed"] == 0
    # its own entries, what every pass can report, and nothing that lists
    # other cells or moves a metric it does not report
    assert doc["metric_names"] == sorted(
        EVERY_PASS + ["passes_in_window", "read_rate.added"])
    if case is ONE_PRODUCT:   # and untraced: its own rate, first rows, set-up
        p, out = run_harness("--workload", cell["name"], "--seed", "5",
                             "--seconds", "0.05", "--trace", "0",
                             "--rehearse", root=root)
        assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
        assert last_doc(out)["metric_names"] == [rate, "setup_s"]
    (plan,) = lines_of(out, "plan")
    assert plan["blocks"] == case["blocks"]
    assert {q["name"]: q["rows"] for q in plan["products"]} \
        == case["products"]
    assert [r["product"] for r in lines_of(out, "check.reference")] \
        == list(case["products"])
