"""Sizing a pass from what one file may hold: duration is cut, width
never, to whole chunks / windows where it can; a cap under one band
spectrum refuses with the numbers.  A pass of several products is cut by
the one with the most bytes, all of them to the same blocks."""

import pytest
from conftest import products_traffic, run_harness

import run
from scratch import FIL_HEADER_ROOM

GiB = 1 << 30
NONE = 1 << 62


def plan(cell, cap):
    """The plan, with its one product's rows and bytes beside it."""
    p = run.plan_pass(run.load_cell(cell, rehearse=False), cap)
    (only,) = p["products"]
    assert p["product_bytes"] == only["bytes"] and p["sized_by"] == "product"
    return {**only, **p}


def plan_of(settings, cap, blocks=38):
    """A pass of one product per ``(name, nfft, nint)`` at the recorder's
    width (the ``gbt-bank`` geometry), ``blocks`` blocks wanted."""
    cell = run.load_cell("bank.hires", rehearse=False)
    cell["traffic"] = products_traffic("mix", settings, blocks=blocks)
    cell["kinds"] = run.product_kinds(cell["traffic"])
    return run.plan_pass(cell, cap)


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


def test_rawspec_three_products_from_one_read():
    """``rawspec -f 1048576,8,1024 -t 51,128,3072`` over 108 blocks: the
    sizes the next configuration will have (ISSUE 32)."""
    p = plan_of([("0000", 1 << 20, 51), ("0001", 8, 128),
                 ("0002", 1024, 3072)], NONE, blocks=108)
    assert [(q["name"], q["rows"], q["row_bytes"]) for q in p["products"]] \
        == [("0000", 1, 256 << 20), ("0001", 55295, 2048),
            ("0002", 17, 256 << 10)]
    assert p["product_bytes"] == (256 << 20) + 55295 * 2048 + 17 * (256 << 10)
    assert p["raw_bytes"] == 108 * 134217728 and p["sized_by"] == "0000"
    # a warm-up cut to one row of 0000 would be the whole pass
    assert [q["warm_rows"] for q in p["products"]] == [1, 55295, 17]


HI_LO = [("lo", 1024, 3072), ("hi", 1 << 20, 1)]


@pytest.mark.parametrize("cap, blocks, hi_rows, lo_rows", [
    (NONE, 38, 16, 6),
    (4 * GiB, 22, 8, 3),     # one whole 8-frame chunk of the larger kept
    (2 * GiB, 20, 7, 3),
    (1 * GiB, 12, 3, 1),
])
def test_a_cap_cuts_by_the_larger_product(cap, blocks, hi_rows, lo_rows):
    """The listed order does not matter: the product with the most bytes
    sets the cut, and the other gets the rows the same blocks hold."""
    p = plan_of(HI_LO, cap)
    lo, hi = p["products"]
    assert p["blocks"] == blocks and p["sized_by"] == "hi"
    assert (hi["rows"], lo["rows"]) == (hi_rows, lo_rows)
    assert (hi["rows_wanted"], lo["rows_wanted"]) == (16, 6)
    assert (hi["row_bytes"], lo["row_bytes"]) == (256 << 20, 256 << 10)
    assert all(q["bytes"] + FIL_HEADER_ROOM <= cap for q in p["products"])
    assert p["product_bytes"] == hi["bytes"] + lo["bytes"]
    assert p["raw_bytes"] == blocks * 134217728          # one read


def test_a_product_that_would_hold_no_row_refuses():
    # as asked: 38 blocks are 19453 frames of 1024 after the filter state
    with pytest.raises(run.Refused) as e:
        plan_of([("hi", 1 << 20, 1), ("never", 1024, 19454)], NONE)
    assert "'never'" in str(e.value) and "nint 19454" in str(e.value)
    # under a cap: 1 GiB leaves 12 blocks, 6141 frames of 1024
    wide = [("hi", 1 << 20, 1), ("lo", 1024, 6142)]
    assert [q["rows"] for q in plan_of(wide, NONE)["products"]] == [16, 3]
    with pytest.raises(run.Refused) as e:
        plan_of(wide, 1 * GiB)
    assert "'lo'" in str(e.value) and str(GiB) in str(e.value)
    assert "12 blocks" in str(e.value)
    # one row of a product over the cap: width is never cut
    with pytest.raises(run.Refused) as e:
        plan_of(HI_LO, 128 << 20)
    assert "'hi'" in str(e.value) and "268435456 B" in str(e.value)


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
