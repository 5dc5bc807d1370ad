"""Sizing a pass from what one file may hold: duration is cut, width
never, to whole chunks / windows where it can; a cap under one band
spectrum refuses with the numbers."""

import pytest
from conftest import run_harness

import run
from scratch import FIL_HEADER_ROOM

GiB = 1 << 30
NONE = 1 << 62


def plan(cell, cap):
    return run.plan_pass(run.load_cell(cell, rehearse=False), cap)


@pytest.mark.parametrize("cap, blocks, rows", [
    (NONE, 38, 16),          # as the traffic file asks: 19 frames
    (4 * GiB, 22, 8),        # 15 rows fit; one whole 8-frame chunk kept
    (2 * GiB, 20, 7),        # under one chunk: 7 rows, 10 frames
    (1 * GiB, 12, 3),
])
def test_bank_hires_under_a_cap(cap, blocks, rows):
    p = plan("bank.hires", cap)
    assert (p["blocks"], p["rows"]) == (blocks, rows)
    assert p["row_bytes"] == 64 * (1 << 20) * 4           # width uncut
    assert p["product_bytes"] + FIL_HEADER_ROOM <= cap
    assert p["raw_bytes"] == blocks * 134217728


@pytest.mark.parametrize("cap", [NONE, 4 * GiB, 2 * GiB, 1 * GiB])
def test_bank_lowres_is_never_cut_by_its_product(cap):
    p = plan("bank.lowres", cap)
    assert (p["blocks"], p["rows"], p["product_bytes"]) == (37, 6, 6 << 18)


@pytest.mark.parametrize("cap, blocks, rows", [
    (NONE, 14, 4),           # two 2-frame windows
    (4 * GiB, 10, 2),        # 3 rows fit; one whole window kept
    (2 * GiB, 8, 1),         # one band spectrum
])
def test_band_under_a_cap(cap, blocks, rows):
    p = plan("band4.hires", cap)
    assert (p["blocks"], p["rows"]) == (blocks, rows)
    assert p["row_bytes"] == GiB and p["raw_bytes"] == 4 * blocks * 134217728
    assert p["warm_rows"] == min(rows, 2)


def test_band_cap_under_one_spectrum_refuses_with_the_numbers():
    with pytest.raises(run.Refused) as e:
        plan("band4.hires", 1 * GiB)
    assert str(GiB) in str(e.value) and "1073741824 B" in str(e.value)


def test_refusal_is_the_last_stderr_line_and_prints_no_result():
    # The toy band row is 64 KiB: a 40,000 B cap cannot hold one.
    p, out = run_harness(
        "--workload", "band4.hires", "--seed", "1", "--seconds", "0.05",
        "--trace", "0", "--rehearse",
        prelude="import scratch; scratch.max_file_bytes = "
                "lambda d, want: min(want, 40000)")
    assert p.returncode == 2
    last = p.stderr.strip().splitlines()[-1]
    assert "65536 B" in last and "40000 B" in last and "refused" in last
    assert not any(ln.startswith("{") for ln in out)
