"""The cell ``rawspec3.hires51`` (PR 34): rawspec's three products from ONE
read, added as files only (a configuration, a traffic mix, a driver, a
reader, metric files, entries) and as a new name at the end of the
accepted lists it reports under: ``reduce_rate`` is its rate (a PR that
changes the program may add no end-to-end entry), the accepted readings
that list no cell come by themselves, five that list cells take its name.
Its toy run end to end, its plan at the real size, its new reader on
synthetic evidence, and which entries list no cell.  The pins of the
whole benchmark (its cells and configurations by name, the count of files
with a reader and of ``same_as`` twins) moved on with the newest cell:
``test_band_rawspec3_cell.py`` (PR 42)."""

import json
import os

import pytest
from conftest import (BENCH, EVERY_PASS, PUMP_CALL, PUMP_WAITS, ROOT, lines_of,
                      run_harness, run_line)
from test_layer_metrics import FIRST, FOLDED, UNSTEADY

from readers import carry, fanout, stage_bytes

CELL, RATE = "rawspec3.hires51", "reduce_rate"
LM = os.path.join(BENCH, "layer_metrics")
# the accepted readings that list no cell: they hold wherever the rate is
# reported, so the cell reports them with no entry of its own
LISTLESS = ["read_rate", "dispatch_s_per_GB", "idle_dispatch_s_per_GB",
            "link_wait_s_per_GB", "h2d_MB_per_GB", "d2h_MB_per_GB",
            "readback_s_per_GB", "write_s_per_GB", "device_busy_s_per_GB",
            "device_idle_share", "hbm_peak", "host_cpu_s_per_GB",
            "idle_named_share",
            # PR 36: a pass's two ends, the parts, the idle seconds in them
            "open_s_per_GB", "close_s_per_GB", "coeffs_s_per_GB",
            "put_hold_s_per_GB", "write_digest_s_per_GB",
            "idle_ends_s_per_GB", "idle_coeffs_s_per_GB",
            "idle_put_hold_s_per_GB", "idle_digest_s_per_GB"]
# the accepted readings that list their cells and took this one's name, each
# with the cells it listed before
APPENDED = {"hbm_roof_share": ["rawspec.hires51"],
            "wait_chunk_s_per_GB": ["bank.lowres", "rawspec.hires51"],
            "wait_out_slot_s_per_GB": ["bank.lowres", "rawspec.hires51"],
            "first_product_wait_s": ["band4.hires"],
            "carry_busy_s_per_GB": ["rawspec.hires51"]}
# PR 36's part of the pump's dispatch came with the three `reduce` cells
# listed, this one last
LISTED_SINCE = {PUMP_CALL: ["bank.lowres", "rawspec.hires51", CELL]}
# and the readings of what PR 34 added to the program
NEW = ["p0001_busy_s_per_GB", "p0002_busy_s_per_GB",
       "fanout_saved_MB_per_GB", "p0001_roof_share", "p0002_roof_share",
       "fold_roof_share"]
# of those, what a CPU rehearsal's traced run has something to read for
ON_A_CPU = sorted(EVERY_PASS + PUMP_WAITS + [
    PUMP_CALL, "first_product_wait_s", "fanout_saved_MB_per_GB"])


def spec(name):
    with open(os.path.join(LM, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_end_to_end_run_at_toy_size():
    p, out = run_harness("--workload", CELL, "--seed", "3400000005",
                         "--seconds", "0.05", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["rehearsal"] is True and doc["platform"] == "cpu"
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1
    assert doc["metric_names"] == [RATE, "setup_s"]
    assert "metrics" not in doc
    # every product against the plain reference, in the file's order
    assert [r["product"] for r in lines_of(out, "check.reference")] \
        == ["0000", "0001", "0002"]
    (plan,) = lines_of(out, "plan")
    assert {q["name"]: q["rows"] for q in plan["products"]} \
        == {"0000": 17, "0001": 53, "0002": 16}
    # ONE command made them: one stage table, the recording put once
    (warm,) = lines_of(out, "warmup")
    assert warm["whole_pass"] is True
    (ref,) = lines_of(out, "reference")
    assert ref["launched"] == ref["tasks"] == 6 == run_line(p)[
        "reference"]["tasks"] and not ref["failed"]
    compared = [ln for ln in p.stderr.splitlines()
                if ln.startswith("compared rel_err.")]
    assert [ln.split()[1] for ln in compared] \
        == ["rel_err.0000", "rel_err.0001", "rel_err.0002"]


def test_traced_run_reports_only_what_a_cpu_can():
    p, out = run_harness("--workload", CELL, "--seed", "3400000006",
                         "--seconds", "0.05", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is True and doc["breakdown"] is False
    assert doc["metric_names"] == ON_A_CPU
    (traced,) = lines_of(out, "traced")
    st = traced["stages"]
    (plan,) = lines_of(out, "plan")
    # read once, put once, and twice that not sent again
    assert st["ingest"]["bytes"] == st["link.put"]["bytes"] \
        == plan["raw_bytes"]
    assert st["fanout.share"]["bytes"] == 2 * plan["raw_bytes"]
    for q in plan["products"]:
        row = st["integrate.emit." + q["name"]]
        assert (row["calls"], row["bytes"]) == (q["rows"], q["bytes"])
    assert st["readback"]["bytes"] == st["write"]["bytes"] \
        == plan["product_bytes"]


def test_the_plan_at_the_real_size(bench):
    import run

    cell = run.load_cell(CELL, rehearse=False)
    plan = run.plan_pass(cell, 1 << 62)
    assert plan["blocks"] == 108 and plan["raw_bytes"] == 108 * 134217728
    assert [(q["name"], q["nfft"], q["nint"], q["rows"], q["row_bytes"])
            for q in plan["products"]] == [
        ("0000", 1 << 20, 51, 1, 256 << 20), ("0001", 8, 128, 55295, 2048),
        ("0002", 1024, 3072, 17, 256 << 10)]
    assert [q["tolerance"] for q in plan["products"]][::2] == [0.01, 0.004]
    t = cell["traffic"]
    assert t["argv"][-4:] == ["--nfft", "1048576,8,1024",
                              "--nint", "51,128,3072"]
    assert [q["path"] for q in t["products"]] == [
        "{out}.rawspec.000%d.fil" % k for k in range(3)]
    assert run.product_paths(cell, "/x/pass0") == [
        "/x/pass0.rawspec.000%d.fil" % k for k in range(3)]
    assert cell["driver"].new_out("/x", "pass0") == "/x/pass0"
    cfg = cell["config"]
    assert cfg["geometry"] == {"obsnchan": 64, "nbits": 8, "npol": 2,
                               "block_samples": 524288}
    assert sorted(cfg["reduced"]) == ["raw_medium", "scan_seconds"]
    (entry,) = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert cfg["name"] == "gbt-bank-rawspec3"
    assert entry["reduced"] == ["scan_seconds", "raw_medium"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert any("ONCE" in g for g in cfg["guarantees"])


def test_the_cells_metric_names_are_exactly_these(bench):
    import run

    cell = run.load_cell(CELL, rehearse=False)
    assert sorted(m["name"] for m in cell["end_to_end"]) \
        == [RATE, "setup_s"]
    assert sorted(m["name"] for m in cell["per_layer"]) \
        == sorted(LISTLESS + list(APPENDED) + list(LISTED_SINCE) + NEW)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for m in cell["per_layer"]:
        e, s = entries[m["name"]], spec(m["name"])
        assert e["moves"] == RATE
        assert s["name"] == m["name"] and "cells" not in s
        for k in ("unit", "layer", "better", "source", "moves"):
            assert s[k] == e[k], (m["name"], k)
    for name in LISTLESS:
        assert "workloads" not in entries[name]
    # a new name goes at the END of an accepted list, and nothing else
    # moves (a later cell's name follows it: PR 40's)
    for name, before in APPENDED.items():
        assert entries[name]["workloads"][:len(before) + 1] \
            == before + [CELL]
    for name, cells in LISTED_SINCE.items():
        assert entries[name]["workloads"] == cells
    for name in NEW:
        assert entries[name]["workloads"][0] == CELL
    # the new entries came in one block (PR 36's followed it)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    # no other cell gained or lost a reading
    for w in bench["workloads"]:
        if w["name"] == CELL:
            continue
        mine = run.load_cell(w["name"], rehearse=False)
        # (a cell added later may list itself under one: PR 40's does)
        assert not [m["name"] for m in mine["per_layer"] if m["name"] in NEW
                    and w["name"] not in entries[m["name"]]["workloads"]]
        assert [m["name"] for m in mine["per_layer"]] == [
            m["name"] for m in bench["per_layer"]
            if w["name"] in m.get("workloads", [w["name"]])
            and m["moves"] in {e["name"] for e in mine["end_to_end"]}]


# What ``test_layer_metrics.py`` pinned for five cells and PR 39 folded here
# for six (the cells and configurations by name, the files with a reader
# counted once, the ``same_as`` twins) is pinned for the SEVEN in
# ``test_band_rawspec3_cell.py`` (PR 42), each a case of one test.


def test_what_every_pass_can_report_lists_no_cell(bench):
    """An entry whose reading any ``blit reduce`` or ``blit scan`` pass
    has (a row of the stage table, a host clock, the device trace) has no
    ``workloads`` key: it holds for every cell that reports ``reduce_rate``,
    the ones a later PR adds too, which a PR that only adds could not
    append to a list.  What only the ``blit reduce`` pump has (its two
    waits, its dispatch's calls) lists the ``reduce`` cells."""
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert sorted(n for n, cells in listed.items() if cells is None) \
        == sorted(LISTLESS)
    assert set(EVERY_PASS) <= set(LISTLESS)
    for name in PUMP_WAITS + [PUMP_CALL]:
        assert listed[name] == ["bank.lowres", "rawspec.hires51", CELL]
    for name in PUMP_WAITS:
        assert listed[name + FIRST] == [UNSTEADY]
    # the output plane's stress cell reads the output plane's backpressure
    assert listed["idle_output_s_per_GB" + FIRST] == [UNSTEADY]


# -- the new reader -------------------------------------------------------------

RAW = 108 * 134217728
ROWS = {"0000": (1, 256 << 20), "0001": (55295, 2048), "0002": (17, 256 << 10)}


def evidence(per_op_s, dispatches=7):
    stages = {"dispatch": {"calls": dispatches, "seconds": 2.0},
              "fanout.share": {"calls": 32, "bytes": 2 * RAW}}
    for name, (rows, row) in ROWS.items():
        stages["integrate.emit." + name] = {"calls": rows,
                                            "bytes": rows * row}
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    return {"trace": {"per_op_s": per_op_s, "busy_s": 4.0, "window_s": 4.5},
            "stages": stages, "traced_raw_bytes": RAW,
            "device_kind": "TPU v5 lite", "peaks": peaks}


def test_least_bytes_of_a_leg_and_of_the_fold():
    assert fanout.leg_least_bytes(RAW, 55295 * 2048) == RAW + 113244160
    row = 256 << 20
    # 51 frames of power in, the row out, 7 x (read + write) of one row
    assert fanout.fold_least_bytes(1, row, 51, 7) == (51 + 1 + 14) * row
    assert fanout.fold_least_bytes(55295, 2048, 128, 7) \
        == (55295 * 129 + 14) * 2048


def test_a_roof_share_never_passes_100():
    """At the roof itself — each program taking exactly the seconds its
    least bytes need at 819 GB/s — every share reads 100; any real program
    is slower.  A share over 105 is a wrong count."""
    fold = sum(fanout.fold_least_bytes(rows, row, nint, 7)
               for (rows, row), nint in zip(ROWS.values(), (51, 128, 3072)))
    at_roof = {
        "jit_channelize_0001/fusion.1": (RAW + 55295 * 2048) / 819e9,
        "jit_channelize_0002/fusion.2": (RAW + 17 * (256 << 10)) / 819e9,
        "jit_integrate_carry/fusion.3": fold / 819e9,
        "jit_channelize_stream/fused1": 0.5}
    ev = evidence(at_roof)
    for name in ("p0001_roof_share", "p0002_roof_share", "fold_roof_share"):
        s = spec(name)
        assert s["reader"] == "fanout"
        assert fanout.read(s["args"], ev) == pytest.approx(100.0)
    # twice as slow (what a second pass over the power costs): half
    ev = evidence({k: 2 * v for k, v in at_roof.items()})
    for name in ("p0001_roof_share", "p0002_roof_share", "fold_roof_share"):
        assert fanout.read(spec(name)["args"], ev) == pytest.approx(50.0)
    # the fold's bytes are those of the three legs' frames, nothing else
    assert fold == (52 + 14) * (256 << 20) + (55295 * 129 + 14) * 2048 \
        + (17 * 3073 + 14) * (256 << 10)
    assert carry.read(spec("p0001_busy_s_per_GB")["args"], ev) \
        == pytest.approx(2 * at_roof["jit_channelize_0001/fusion.1"]
                         / (RAW / 1e9))
    assert stage_bytes.read(spec("fanout_saved_MB_per_GB")["args"], ev) \
        == pytest.approx(2000.0)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent (one product per command: neither the programs nor the
    counters), a CPU trace, a stage table without the rows: no value, and
    no exception."""
    parent = {"jit_channelize_stream/fused1": 0.5,
              "jit_integrate_carry/fusion": 0.1}
    for name in NEW:
        s = spec(name)
        reader = {"fanout": fanout, "carry": carry,
                  "stage_bytes": stage_bytes}[s["reader"]]
        ev = evidence(parent)
        ev["stages"] = {"dispatch": {"calls": 7, "seconds": 2.0},
                        "integrate.emit": {"calls": 1, "bytes": 256 << 20}}
        assert reader.read(s["args"], ev) is None, name
        assert reader.read(s["args"], dict(ev, trace=None)) is None, name
        assert reader.read(s["args"], dict(ev, stages={})) is None, name
    # the programs without the counters (and the other way round)
    ours = {"jit_channelize_0001/f": 0.3, "jit_channelize_0002/f": 0.3,
            "jit_integrate_carry/f": 0.1}
    for name in ("p0001_roof_share", "p0002_roof_share", "fold_roof_share"):
        assert fanout.read(spec(name)["args"],
                           dict(evidence(ours), stages={})) is None
        assert fanout.read(spec(name)["args"], evidence({})) is None
