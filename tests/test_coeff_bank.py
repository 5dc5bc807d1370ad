"""The coefficient bank is the PROCESS's (ISSUE 41): ``coeff_bank`` looks a
bank up before it builds one, so a worker that reduces scan after scan pays
the 2^20 bank's host arithmetic and its transfer once.  A hit is the same
device array the miss made; the part ``coeffs`` wraps the lookup, hit or
miss, and ``coeffs.hit`` counts the lookups that found their bank.

Only a test that asserts a MISS empties the store first (``fresh``): every
other pin holds whatever ran before in the process, as the program's do.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit.observability import Timeline  # noqa: E402
from blit.ops import channelize as ch  # noqa: E402
from blit.ops.channelize import coeff_bank, pfb_coeffs  # noqa: E402
from blit.parallel.scan import reduce_scan_mesh_to_files  # noqa: E402
from blit.pipeline import RawReducer  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NTAP = 4


@pytest.fixture
def fresh(monkeypatch):
    """An empty store, what a process's first pass finds; the process's
    own is back after the test."""
    monkeypatch.setattr(ch, "_BANKS", ch._BankStore())


def bank(ntap, nfft, window="hamming", timeline=None):
    return coeff_bank(ntap, nfft, window, timeline or Timeline())


def row(tl, name):
    r = tl.report()[name]
    return r["calls"], r["bytes"]


def test_a_second_ask_is_the_same_array_and_a_hit(fresh):
    tl = Timeline()
    first = bank(NTAP, 64, timeline=tl)
    assert row(tl, "coeffs") == (1, NTAP * 64 * 4)
    assert row(tl, "coeffs.hit") == (0, 0)  # the miss: the row is there
    assert bank(NTAP, 64, timeline=tl) is first
    assert bank(NTAP, 64) is first
    # The part is the lookup, hit or miss: banks asked for, and their bytes.
    assert row(tl, "coeffs") == (2, 2 * NTAP * 64 * 4)
    assert row(tl, "coeffs.hit") == (1, 0)


@pytest.mark.parametrize("other", [(NTAP, 128, "hamming"),
                                   (8, 64, "hamming"),
                                   (NTAP, 64, "hanning")],
                         ids=["nfft", "ntap", "window"])
def test_another_key_is_another_bank(fresh, other):
    tl = Timeline()
    mine = bank(NTAP, 64, timeline=tl)
    theirs = coeff_bank(*other, tl)
    assert theirs is not mine and theirs.shape == other[:2]
    assert row(tl, "coeffs.hit") == (0, 0)
    np.testing.assert_array_equal(np.asarray(theirs), pfb_coeffs(*other))


def test_another_default_device_is_another_bank():
    here = bank(NTAP, 64)
    elsewhere = jax.devices()[1]
    with jax.default_device(elsewhere):
        there = bank(NTAP, 64)
        assert bank(NTAP, 64) is there
    assert there is not here and there.devices() == {elsewhere}
    assert bank(NTAP, 64) is here
    assert here.devices() == {jax.devices()[0]}


@pytest.mark.parametrize("nfft", [8, 1024, 2 ** 16])
def test_a_hit_holds_pfb_coeffs_to_the_bit(nfft):
    tl = Timeline()
    bank(NTAP, nfft, timeline=tl)
    hit = bank(NTAP, nfft, timeline=tl)
    assert row(tl, "coeffs.hit")[0] >= 1
    want = pfb_coeffs(NTAP, nfft)
    assert hit.dtype == want.dtype and hit.shape == want.shape
    assert np.asarray(hit).tobytes() == want.tobytes()


def test_a_deleted_bank_is_built_again():
    first = bank(NTAP, 64)
    first.delete()  # as a cleared backend leaves it
    tl = Timeline()
    again = bank(NTAP, 64, timeline=tl)
    assert again is not first and not again.is_deleted()
    assert row(tl, "coeffs.hit") == (0, 0)
    np.testing.assert_array_equal(np.asarray(again), pfb_coeffs(NTAP, 64))
    assert bank(NTAP, 64) is again


def test_the_oldest_bank_goes_beyond_the_size(fresh):
    size = ch._BankStore.SIZE
    assert size >= 3  # rawspec's three products at once
    banks = [bank(NTAP, 8 * (k + 1)) for k in range(size)]
    assert bank(NTAP, 8) is banks[0]  # now the newest
    bank(NTAP, 8 * (size + 1))  # one too many
    tl = Timeline()
    assert bank(NTAP, 8, timeline=tl) is banks[0]
    for k in range(2, size):
        assert bank(NTAP, 8 * (k + 1), timeline=tl) is banks[k]
    assert row(tl, "coeffs.hit")[0] == size - 1
    # The least recently asked for went, and an evicted bank is not deleted
    # under whoever still holds it.
    assert bank(NTAP, 16, timeline=tl) is not banks[1]
    assert row(tl, "coeffs.hit")[0] == size - 1
    assert not banks[1].is_deleted()
    assert len(ch._BANKS._banks) == size


def test_threads_asking_at_once_get_one_build(fresh, monkeypatch):
    builds, real = [], ch.pfb_coeffs

    def slow(*a):
        builds.append(a)
        time.sleep(0.05)  # a window for a second builder to slip into
        return real(*a)

    monkeypatch.setattr(ch, "pfb_coeffs", slow)
    nthreads = 4 * (os.cpu_count() or 4)
    gate, tl, got = threading.Barrier(nthreads), Timeline(), []

    def ask():
        gate.wait(30)
        got.append(bank(NTAP, 256, timeline=tl))

    threads = [threading.Thread(target=ask, daemon=True)
               for _ in range(nthreads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert builds == [(NTAP, 256, "hamming")]
    assert len(got) == nthreads and all(b is got[0] for b in got)


# -- whole passes: a second one in the process finds its banks -----------------

PRODUCTS = [(1024, 3), (8, 128), (64, 51)]  # tests/test_reduce_fanout.py's toy


def read(path):
    with open(path, "rb") as f:
        return f.read()


def test_two_reducers_of_one_process_share_the_banks(tmp_path):
    raw = str(tmp_path / "r.raw")
    synth_raw(raw, nblocks=4, obsnchan=4, ntime_per_block=18 * 1024 // 4,
              seed=7, tone_chan=1)
    (nfft, nint), *also = PRODUCTS
    tables, outs = [], []
    for tag in "ab":
        # "matmul" is what the chip runs, and the one FFT whose bytes
        # repeat on the CPU at nfft 1024.
        red = RawReducer(nfft=nfft, nint=nint, also=tuple(also),
                         chunk_frames=4, fft_method="matmul")
        outs.append([str(tmp_path / f"{tag}{k}.fil") for k in range(3)])
        red.reduce_to_files(raw, outs[-1])
        tables.append(red.timeline.report())
    for first, second in zip(*outs):
        assert read(first) == read(second)
    for st in tables:  # as before the store, whatever ran before
        assert st["coeffs"]["calls"] == 3
        assert st["coeffs"]["bytes"] == NTAP * 4 * (1024 + 8 + 64)
    assert tables[1]["coeffs.hit"]["calls"] == 3


def test_two_mesh_scans_of_one_process_share_the_bank(tmp_path):
    bank_bw = -187.5 / 4
    grid = [[]]
    for k in range(4):
        grid[0].append(str(tmp_path / f"blc0{k}.raw"))
        synth_raw(grid[0][k], nblocks=4, obsnchan=2, ntime_per_block=1024,
                  seed=k, obsbw=bank_bw, obsfreq=8000.0 + (k + 0.5) * bank_bw)
    tables, outs = [], []
    for tag in "ab":
        os.makedirs(tmp_path / tag)
        tl = Timeline()
        written = reduce_scan_mesh_to_files(
            grid, out_dir=str(tmp_path / tag), nfft=64, nint=2,
            window_frames=16, timeline=tl)
        (out, _), = written.values()
        outs.append(out)
        tables.append(tl.report())
    assert read(outs[0]) == read(outs[1])
    for st in tables:
        assert (st["coeffs"]["calls"], st["coeffs"]["bytes"]) == (
            1, NTAP * 64 * 4)
        assert st["coeffs"]["seconds"] <= st["open"]["seconds"]
    assert tables[1]["coeffs.hit"]["calls"] == 1
