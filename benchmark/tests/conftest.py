"""CPU rehearsals of the benchmark (``python -m pytest benchmark/tests -q``).
They prove the harness's control flow, sizing and arithmetic; they prove
nothing of the chip.  Not part of tier-1 (``tests/``)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]


# The harness rebuilds blit/native in every run (``make -B``: the library
# is built on the machine that runs).  Six rehearsals at once (``-n 6``)
# raced on that one directory, so the tests build it ONCE a session
# (``native_built``, under a lock the workers share) and a rehearsal
# started from here finds its ``make`` already done — but for the ones
# that ask for the harness as it is (``run_harness(rebuild=True)``:
# ``test_rehearse.py`` keeps one that builds and one whose build fails).
NO_REBUILD = (
    "import subprocess as _sp\n_run = _sp.run\n"
    "_sp.run = lambda cmd, *a, **kw: _sp.CompletedProcess(cmd, 0) "
    "if list(cmd)[:2] == ['make', '-B'] else _run(cmd, *a, **kw)\n")


@pytest.fixture(scope="session", autouse=True)
def native_built():
    import fcntl
    import tempfile

    native = os.path.join(ROOT, "blit", "native")
    with open(os.path.join(tempfile.gettempdir(),
                           "blit-bench-tests-native.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        subprocess.run(["make", "-C", native], check=True,
                       stdout=subprocess.DEVNULL)


def run_harness(*args, root=ROOT, prelude="", timeout=300, rebuild=False):
    """``benchmark/run.py`` as a fresh process (the harness sets JAX's
    platform and device count, so it cannot share this one).  ``prelude``
    is Python run before ``main`` — how a test injects a file-size cap.
    ``rebuild``: the harness's own ``make -B`` runs."""
    code = ("import sys; sys.path[:0] = [%r, %r]; import run\n%s\n"
            "sys.exit(run.main(%r))" % (
                os.path.join(root, "benchmark"), root,
                ("" if rebuild else NO_REBUILD) + prelude, list(args)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    p = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                       capture_output=True, text=True, timeout=timeout)
    out = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, out


def lines_of(out, phase):
    """The JSON of every ``[phase] {...}`` line the harness printed."""
    tag = f"[{phase}] "
    return [json.loads(ln[len(tag):]) for ln in out if ln.startswith(tag)]


# What a CPU rehearsal's traced run can report.  EVERY_PASS: the entries of
# ``BENCHMARK.json`` with no ``workloads`` key whose reading is a row of the
# stage table or a host clock: any ``blit reduce`` or ``blit scan`` pass has
# them, so a cell added later reports them with no entry of its own (PR 36
# added a pass's two ends and three parts).  PUMP_WAITS: the two waits only
# the ``blit reduce`` pump declares; PUMP_CALL: the part only its dispatch
# has.  NO_TWIN: the rows ``bank.hires`` has no ``.first`` entry for.
EVERY_PASS = ["close_s_per_GB", "coeffs_s_per_GB", "d2h_MB_per_GB",
              "dispatch_s_per_GB", "h2d_MB_per_GB", "host_cpu_s_per_GB",
              "link_wait_s_per_GB", "open_s_per_GB", "put_hold_s_per_GB",
              "read_rate", "readback_s_per_GB", "write_digest_s_per_GB",
              "write_s_per_GB"]
PUMP_WAITS = ["wait_chunk_s_per_GB", "wait_out_slot_s_per_GB"]
PUMP_CALL = "call_s_per_GB"
NO_TWIN = ("open_s_per_GB", "close_s_per_GB")


def run_line(p) -> dict:
    """The ``[run]`` line of a run's standard error: it stands ahead of
    the numbers compared, which are the last lines there."""
    lines = p.stderr.splitlines()
    (at,) = [i for i, ln in enumerate(lines) if ln.startswith("[run] ")]
    assert all(ln.startswith("compared ") for ln in lines[at + 1:])
    took = json.loads(lines[at][len("[run] "):])
    # every phase is there, none is negative, and they fit the whole
    phases = ["start_s", "build_s", "synth_s", "warmup_pass_s",
              "reference_wait_s", "other_checks_s", "window_s",
              "between_pass_checks_s", "traced_pass_s", "metrics_s",
              "cleanup_s"]
    assert all(took[k] >= 0 for k in phases), took
    assert sum(took[k] for k in phases) <= took["start_to_result_s"]
    # no child of the reference outlives set-up: none is alive beside a
    # measured or a traced pass
    assert took["reference"]["last_child_joined_at_s"] < took["setup_s"]
    return took


LOCAL_DRIVER = os.path.join(BENCH, "tests", "local_drivers", "reduce_each.py")


def tree_with(tmp_path, *, traffic: dict, workloads, per_layer=(),
              end_to_end=(), configs=(), files=None, drivers=(),
              listed_under=()):
    """A temporary checkout that holds the benchmark as committed plus what
    a later PR would ADD, as files and entries only: traffic mixes, cells,
    per-layer and end-to-end entries, configurations, driver files, any
    other file under ``benchmark/`` (``files``: relative path -> text), and
    the new cells' names at the END of the ``workloads`` of the accepted
    entries ``listed_under`` names.  No file that is there is edited.
    -> its root."""
    b = tmp_path / "benchmark"
    shutil.copytree(BENCH, b,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "blit"), tmp_path / "blit")
    for name, t in traffic.items():
        assert not (b / "traffic" / (name + ".json")).exists()
        (b / "traffic" / (name + ".json")).write_text(json.dumps(t))
    for path in drivers:
        shutil.copy(path, b / "drivers")
    for rel, text in (files or {}).items():
        assert not (b / rel).exists()
        (b / rel).write_text(text)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"] += list(workloads)
    bench["per_layer"] += list(per_layer)
    bench["end_to_end"] += list(end_to_end)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in listed_under:
            m["workloads"] += [w["name"] for w in workloads]
    for cfg in configs:   # a copy of an accepted one under a name of its own
        (b / "configs" / (cfg["name"] + ".json")).write_text(json.dumps(cfg))
        entry = next(c for c in bench["configs"] if c["source"]
                     == cfg["source"])
        bench["configs"].append(dict(
            entry, name=cfg["name"],
            file=f"benchmark/configs/{cfg['name']}.json"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def products_traffic(name: str, settings, **more) -> dict:
    """A toy traffic mix whose pass makes one product per ``(name, nfft,
    nint)`` of ``settings`` through the test-local ``reduce_each`` driver,
    on the hires rehearsal's recording (38 blocks of 512 samples, 4 coarse
    channels; the tone sits on the finest product's grid)."""
    return {
        "name": name, "driver": "reduce_each", "blocks": 38, "ntap": 4,
        "despike": False, "align_rows": 8, "pool_blocks": 7,
        "tones": [{"chan": 1, "fine_offset": 207, "also": [3]}],
        "products": [
            {"name": n, "nfft": nfft, "nint": nint, "tolerance": 0.012,
             "path": "{out}." + n + ".fil",
             "argv": ["reduce", "{raws}", "-o", "{path}", "--nfft",
                      str(nfft), "--nint", str(nint)]}
            for n, nfft, nint in settings],
        **more}


@pytest.fixture(scope="session")
def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
