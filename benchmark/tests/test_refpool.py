"""The reference's task pool (PR 39): one child process per (product,
checked channel), the unchanged ``reference.stokes_i`` in each, the rows
kept for the run.  Its rows are the direct call's, bit for bit; a task
runs once however often its rows are read; a child that raises, dies,
hangs or writes nothing makes the run incorrect with the child's own
words, inside the pool's time limit."""

import json
import sys
import time

import numpy as np
import pytest
from conftest import lines_of, run_harness

import reference
import refpool
from products import fil

# the three products of `rawspec3-t51`'s rehearsal
PRODUCTS = [{"name": "0000", "nfft": 1024, "nint": 3},
            {"name": "0001", "nfft": 8, "nint": 128},
            {"name": "0002", "nfft": 64, "nint": 51}]
SLOTS = (1, 3)


def streams():
    """Two channels of the toy recording's length (38 blocks of 512)."""
    rng = np.random.default_rng(39)
    return {slot: rng.integers(-40, 40, (38 * 512, 2, 2), dtype=np.int8)
            for slot in SLOTS}


def pool_of(tmp_path, volts, **kw):
    slices = [{"slot": slot, "volt": v.copy()} for slot, v in volts.items()]
    pool = refpool.ReferencePool([(p, fil, slices) for p in PRODUCTS],
                                 ntap=4, despike=False,
                                 workdir=str(tmp_path / "reference"), **kw)
    pool.start()
    # the harness's copy of a stream goes once the children can map it
    assert all("volt" not in s for s in slices)
    return pool


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    volts = streams()
    pool = pool_of(tmp_path_factory.mktemp("pool"), volts)
    yield volts, pool
    pool.close()


@pytest.mark.parametrize("product", PRODUCTS, ids=lambda p: p["name"])
def test_the_pools_rows_are_the_direct_calls_bit_for_bit(ran, product):
    volts, pool = ran
    for slot in SLOTS:
        want = reference.stokes_i(volts[slot], nfft=product["nfft"], ntap=4,
                                  nint=product["nint"], despike=False)
        got = pool.rows(product["name"], slot)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want)


def test_a_task_runs_once_however_often_its_rows_are_read(ran):
    _, pool = ran
    first = {(p["name"], s): pool.rows(p["name"], s)
             for p in PRODUCTS for s in SLOTS}
    for (name, slot), rows in first.items():   # a pass, the traced pass
        assert pool.rows(name, slot) is rows
    said = pool.wait()
    assert said["tasks"] == said["launched"] == pool.launched == 6
    assert sorted(said["child_s"]) == sorted(
        f"{p['name']}/{s}" for p in PRODUCTS for s in SLOTS)
    assert not said["failed"] and pool.joined_at >= pool.started_at
    # the longest tasks first: the seconds grow with nfft
    assert [t[2] for t in pool.tasks] == [1024, 1024, 64, 64, 8, 8]


def test_despike_reaches_the_children(tmp_path):
    volts = {1: streams()[1]}
    slices = [{"slot": 1, "volt": volts[1].copy()}]
    pool = refpool.ReferencePool([(PRODUCTS[2], fil, slices)], ntap=4,
                                 despike=True, workdir=str(tmp_path / "r"))
    pool.start()
    assert np.array_equal(pool.rows("0002", 1), reference.stokes_i(
        volts[1], nfft=64, ntap=4, nint=51, despike=True))


def test_no_child_starts_beside_another_under_the_memory_floor(tmp_path):
    """With free memory under the floor a task still runs — the guard
    watches it — but never two at once."""
    volts = streams()
    pool = pool_of(tmp_path, volts, mem_free=lambda: 0, mem_floor=1)
    said = pool.wait()
    assert said["most_at_once"] == 1 and said["launched"] == 6
    assert not said["failed"]
    assert np.array_equal(pool.rows("0001", 3), reference.stokes_i(
        volts[3], nfft=8, ntap=4, nint=128))


@pytest.mark.parametrize("child, limit_s, says", [
    ("raise RuntimeError('the child said this')", 60,
     "exit 1: Traceback"),
    ("import os, signal; os.kill(os.getpid(), signal.SIGKILL)", 60,
     "exit -9"),
    ("import time; time.sleep(60)", 1.0, "no rows 1 s after the pool"),
    ("print('wrote nothing')", 60, "exit 0: wrote nothing"),
], ids=["raises", "dies", "hangs", "writes_nothing"])
def test_a_child_that_fails_gives_no_rows_and_its_own_words(
        tmp_path, child, limit_s, says):
    t0 = time.perf_counter()
    pool = pool_of(tmp_path, streams(), limit_s=limit_s,
                   child=[sys.executable, "-c", child])
    said = pool.wait()
    assert time.perf_counter() - t0 < 20   # it does not hang
    assert len(said["failed"]) == 6 and not pool._alive
    with pytest.raises(refpool.ReferenceFailed) as e:
        pool.rows("0000", 1)
    assert says in str(e.value) and "product 0000, coarse slot 1" \
        in str(e.value)
    if "said this" in child:
        assert "the child said this" in str(e.value)
    pool.close()


def test_a_run_whose_reference_fails_is_incorrect_and_ends():
    """End to end: every comparison against the reference finds no rows,
    says whose they were and why, and the run prints ``correct: false``
    with its ``[run]`` line — it neither hangs nor computes them again."""
    p, out = run_harness(
        "--workload", "rawspec3.hires51", "--seed", "3900000007",
        "--seconds", "0.05", "--trace", "1", "--rehearse", timeout=120,
        prelude="import sys, refpool; refpool.CHILD[:] = [sys.executable, "
                "'-c', \"raise SystemExit('the child said this')\"]")
    assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is False and doc["failed"] == doc["attempted"]
    (ref,) = lines_of(out, "reference")
    assert ref["launched"] == ref["tasks"] == 6 == len(ref["failed"])
    wrong = lines_of(out, "INCORRECT")
    assert {w["pass_"] for w in wrong} >= {"warmup", "pass0", "traced"}
    assert all("the child said this" in w["problem"] for w in wrong)
    assert "compared rel_err.0000 None limit" in p.stderr
