"""The cell ``band4.rawspec3`` (PR 40): rawspec's three products on the
BAND from ONE read of a scan, added as files only (a configuration, a
traffic mix, a driver, a reader, seven metric files, entries) and as a new
name at the END of the accepted lists it reports under.  Its toy run end
to end on four virtual CPU devices, its plan at the real size, its new
reader on hand-made evidence.

The pins of ``test_rawspec3_cell.py`` that name six cells and five
configurations fail by their own wording since this cell was added as
files (a program PR edits nothing the benchmark has: PERF.md section 7
lists them for the next ``benchmark`` PR)."""

import json
import os

import pytest
from conftest import BENCH, EVERY_PASS, ROOT, lines_of, run_harness, run_line

from readers import band_fanout, stage_bytes

CELL, RATE = "band4.rawspec3", "reduce_rate"
CONFIG, TRAFFIC = "gbt-band4-rawspec3", "band-rawspec3-t51"
LM = os.path.join(BENCH, "layer_metrics")
# the accepted readings that list their cells and took this one's name, each
# with the cells it listed before
APPENDED = {
    "collective_s_per_GB": ["band4.hires", "band4.hires51"],
    "launch_skew_s_per_GB": ["band4.hires", "band4.hires51"],
    "idle_read_s_per_GB": ["bank.lowres", "band4.hires", "band4.hires51"],
    "idle_output_s_per_GB": ["band4.hires", "band4.hires51"],
    "first_product_wait_s": ["band4.hires", "rawspec3.hires51"],
    "fanout_saved_MB_per_GB": ["rawspec3.hires51"]}
# and the readings of what PR 40 added to the program
NEW = ["b3_fold_busy_s_per_GB", "b3_fold_roof_share",
       "b3_p0001_busy_s_per_GB", "b3_p0001_roof_share",
       "b3_p0002_busy_s_per_GB", "b3_p0002_roof_share",
       "b3_stitch_MB_per_GB"]
ROOFS = ["b3_p0001_roof_share", "b3_p0002_roof_share", "b3_fold_roof_share"]
# of the cell's per-layer readings, what a CPU rehearsal's traced run has
# something to read for
ON_A_CPU = sorted(EVERY_PASS + ["first_product_wait_s",
                                "fanout_saved_MB_per_GB",
                                "b3_stitch_MB_per_GB"])


def spec(name):
    with open(os.path.join(LM, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_end_to_end_run_at_toy_size():
    """``--rehearse`` on four virtual CPU devices exits 0 with its
    ``[reference]`` and ``[run]`` lines; one command makes the three band
    products, every mesh window read once and put once."""
    p, out = run_harness("--workload", CELL, "--seed", "4000000005",
                         "--seconds", "0.05", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["rehearsal"] is True and doc["platform"] == "cpu"
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1 and doc["breakdown"] is False
    assert doc["metric_names"] == ON_A_CPU
    # every product against the plain reference, in the file's order
    assert [r["product"] for r in lines_of(out, "check.reference")][:3] \
        == ["0000", "0001", "0002"]
    (plan,) = lines_of(out, "plan")
    assert plan["banks"] == 4
    assert {q["name"]: q["rows"] for q in plan["products"]} \
        == {"0000": 1, "0001": 53, "0002": 16}
    (warm,) = lines_of(out, "warmup")
    assert warm["whole_pass"] is True
    (ref,) = lines_of(out, "reference")
    # four checked channels x three products, every child joined in set-up
    assert ref["launched"] == ref["tasks"] == 12 == run_line(p)[
        "reference"]["tasks"] and not ref["failed"]
    compared = [ln.split()[1] for ln in p.stderr.splitlines()
                if ln.startswith("compared rel_err.")]
    assert compared == ["rel_err.0000", "rel_err.0001", "rel_err.0002"]
    (traced,) = lines_of(out, "traced")
    st = traced["stages"]
    # read once, put once; nearly twice that not sent again (the small
    # legs end before the 0000 leg's last window)
    assert st["feed.read"]["bytes"] == st["link.put"]["bytes"] \
        == st["read"]["bytes"] == plan["raw_bytes"]
    assert 1.9 * plan["raw_bytes"] < st["fanout.share"]["bytes"] \
        <= 2 * plan["raw_bytes"]
    assert st["dispatch"]["calls"] == st["read"]["calls"] == 26
    for q in plan["products"]:
        row = st["integrate.emit." + q["name"]]
        assert (row["calls"], row["bytes"]) == (q["rows"], q["bytes"])
        # the gather moved each product's rows to three more chips
        assert st["stitch." + q["name"]]["bytes"] == 3 * q["bytes"]
    assert st["readback"]["bytes"] == st["write"]["bytes"] \
        == plan["product_bytes"]
    assert st["coeffs"]["calls"] == 3


def test_the_plan_at_the_real_size(bench):
    import run

    cell = run.load_cell(CELL, rehearse=False)
    assert cell["chips"] == 4
    plan = run.plan_pass(cell, 1 << 62)
    assert plan["blocks"] == 108 and plan["nslots"] == 256
    assert plan["raw_bytes"] == 4 * 108 * 134217728 == 57982058496
    assert [(q["name"], q["nfft"], q["nint"], q["rows"], q["row_bytes"],
             q["tolerance"]) for q in plan["products"]] == [
        ("0000", 1 << 20, 51, 1, 1 << 30, 5e-3),
        ("0001", 8, 128, 55295, 8192, 1e-4),
        ("0002", 1024, 3072, 17, 1 << 20, 4e-3)]
    assert [q["bytes"] for q in plan["products"]] == [
        1073741824, 452976640, 17825792]
    assert all(q["warm_rows"] == q["rows"] for q in plan["products"])
    t = cell["traffic"]
    assert t["name"] == TRAFFIC and t["driver"] == "scan_many"
    assert t["argv"] == [
        "scan", "{root}", "{session}", "{scan}", "-o", "{out}", "--nfft",
        "1048576,8,1024", "--nint", "51,128,3072", "--window-frames", "2"]
    assert t["despike"] is True and t["align_rows"] == 1
    assert [q["path"] for q in t["products"]] == [
        "{out}/band0.rawspec.000%d.fil" % k for k in range(3)]
    assert run.product_paths(cell, "/x/pass0") == [
        "/x/pass0/band0.rawspec.000%d.fil" % k for k in range(3)]
    # one checked channel a bank, each inside a channel of every product
    assert [(q["chan"], "also" in q) for q in t["tones"]] == [
        (5, False), (22, False), (41, False), (60, False)]
    for q in t["tones"]:
        for nfft in (8, 1024):
            at = q["fine_offset"] * nfft / (1 << 20)
            # inside a channel (not on the edge between two) and clear of
            # the DC channel the despike clones over
            assert abs(at - round(at)) < 0.41 and abs(at) > 1, (q, nfft)
    drv = cell["driver"]
    assert drv.WARMUP_CUT is False
    with pytest.raises(ValueError):
        drv.product("/x/pass0")
    cfg = cell["config"]
    assert cfg["geometry"] == {"obsnchan": 64, "nbits": 8, "npol": 2,
                               "block_samples": 524288}
    assert cfg["banks"] == cfg["chips"] == 4 and cfg["mesh"] == [1, 4]
    assert sorted(cfg["reduced"]) == ["banks", "raw_medium", "scan_seconds",
                                      "window_frames"]
    assert "products_per_read" not in cfg["reduced"]
    assert {"table_number", "product_medium", "host_memory"} \
        <= set(cfg["assumed"])
    entry = bench["configs"][-1]
    assert entry["name"] == cfg["name"] == CONFIG
    assert entry["file"] == "benchmark/configs/%s.json" % CONFIG
    assert entry["reduced"] == ["banks", "scan_seconds", "window_frames",
                                "raw_medium"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "rawspec -f 1048576,8,1024 -t 51,128,3072" in entry["source"]
    said = " ".join(cfg["guarantees"])
    for word in ("all three or none", "ONCE", "single command",
                 ".partial", "manifest"):
        assert word in said, word


def test_seven_cells_and_six_configurations(bench):
    assert [w["name"] for w in bench["workloads"]] == [
        "bank.hires", "bank.lowres", "band4.hires", "rawspec.hires51",
        "band4.hires51", "rawspec3.hires51", CELL]
    assert [c["name"] for c in bench["configs"]] == [
        "gbt-bank", "gbt-band4", "gbt-bank-rawspec", "gbt-band4-rawspec",
        "gbt-bank-rawspec3", CONFIG]
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 4)
    assert len(cell["why"]) <= 200 and "Four chips" in cell["why"]
    # three of seven cells take four chips: half of seven, rounded down
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 3 == 7 // 2
    assert bench["run_seconds"] == 35
    # no new end-to-end entry; the name is LAST in the rate's list.  (The
    # rate's bound was 0.15 until the check of PR 42 read `band4.hires`
    # spread by 9.2% and 12.7% in two sets of the same code: PERF.md
    # section 2.)
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("reduce_rate", 0.25), ("first_product_s", 0.06), ("setup_s", 0.25)]
    assert bench["end_to_end"][0]["workloads"] == [
        "bank.lowres", "band4.hires", "rawspec.hires51", "band4.hires51",
        "rawspec3.hires51", CELL]
    assert CELL not in bench["end_to_end"][1]["workloads"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


# -- what older files pinned for five and six cells, for the seven (PR 42) -----

def _readers_and_twins(bench):
    """-> ({(reader, arguments): name}, {twin name: base name})."""
    seen, again = {}, {}
    for m in bench["per_layer"]:
        s = spec(m["name"])
        if "same_as" in s:
            assert "reader" not in s and "args" not in s
            again[m["name"]] = s["same_as"]
            continue
        key = (s["reader"], json.dumps(s.get("args", {}), sort_keys=True))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           s["reader"] + ".py"))
    return seen, again


@pytest.mark.parametrize("pin", [
    "the_other_end_to_end_lists", "no_cell_runs_a_preset",
    "a_file_with_a_reader_is_counted_once", "a_second_name_is_a_twin",
    "every_cell_but_one_reports_the_rate"])
def test_what_older_files_pinned_for_fewer_cells(bench, pin):
    """``test_layer_metrics.py`` pinned these for five cells, PR 39 folded
    them into ``test_rawspec3_cell.py`` for six; a program PR adds a cell
    as files and edits no test, so they failed by their own wording from
    PR 40 on.  Here for the seven, each a case of this test."""
    from test_layer_metrics import FIRST, FOLDED, UNSTEADY

    if pin == "the_other_end_to_end_lists":
        # `bank.lowres` left the list after the check of PR 42 (its first
        # rows come in two modes: `first_product_wait_s`)
        assert bench["end_to_end"][1]["workloads"] == ["bank.hires"]
        assert "workloads" not in bench["end_to_end"][2]
        assert [c["name"] for c in bench["configs"]][:5] == [
            "gbt-bank", "gbt-band4", "gbt-bank-rawspec", "gbt-band4-rawspec",
            "gbt-bank-rawspec3"]
        assert [w["chips"] for w in bench["workloads"]] \
            == [1, 1, 4, 1, 4, 1, 4]
        return
    if pin == "no_cell_runs_a_preset":
        # no cell runs a `--product` preset: blit's can become BL's
        for w in bench["workloads"]:
            with open(os.path.join(BENCH, "traffic",
                                   w["traffic"] + ".json")) as f:
                t = json.load(f)
            assert "--product" not in t["argv"] + t["rehearse"]["argv"]
            assert "reducer" not in t and "reducer" not in t["rehearse"]
        with open(os.path.join(BENCH, "traffic", "hires-19f.json")) as f:
            assert json.load(f)["argv"][-4:] == ["--nfft", "1048576",
                                                 "--nint", "1"]
        return
    seen, again = _readers_and_twins(bench)
    entries = {m["name"]: m for m in bench["per_layer"]}
    if pin == "a_file_with_a_reader_is_counted_once":
        assert sorted(os.listdir(LM)) == sorted(
            m["name"] + ".json" for m in bench["per_layer"])
        # PR 32's 26 and `pass_rate`, PR 34's six, PR 36's ten, PR 40's seven
        assert len(seen) == 27 + 6 + 10 + len(NEW) == 50
        # none of the names PR 32 folded away is back, each went somewhere
        assert not set(FOLDED) & set(seen.values())
        assert set(FOLDED.values()) <= set(seen.values())
    elif pin == "a_second_name_is_a_twin":
        # a second name for a reading exists only where it moves another
        # end-to-end metric, in cells of its own: PR 32's 18, PR 36's five
        assert len(again) == 18 + 5 == 23
        for name, base in again.items():
            assert base in seen.values()
            assert name == base + FIRST
            assert entries[name]["moves"] != entries[base]["moves"]
            assert entries[name]["workloads"] == [UNSTEADY]
            assert UNSTEADY not in entries[base].get("workloads", [])
            for k in ("unit", "layer", "better", "source"):
                assert entries[name][k] == entries[base][k]
    else:
        # every cell but the one whose product disk stalls reports the rate
        assert {w["name"] for w in bench["workloads"]} - set(
            bench["end_to_end"][0]["workloads"]) == {UNSTEADY}


def test_the_cells_metric_names_are_exactly_these(bench):
    import run

    cell = run.load_cell(CELL, rehearse=False)
    assert sorted(m["name"] for m in cell["end_to_end"]) \
        == [RATE, "setup_s"]
    listless = [m["name"] for m in bench["per_layer"]
                if "workloads" not in m and m["moves"] == RATE]
    assert set(EVERY_PASS) <= set(listless)
    assert sorted(m["name"] for m in cell["per_layer"]) \
        == sorted(listless + list(APPENDED) + NEW)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for m in cell["per_layer"]:
        e, s = entries[m["name"]], spec(m["name"])
        assert e["moves"] == RATE
        assert s["name"] == m["name"]
        for k in ("unit", "layer", "better", "source", "moves"):
            assert s[k] == e[k], (m["name"], k)
    # a new name goes at the END of an accepted list, and nothing else moves
    # (but for what a later `benchmark` PR appended after it: `bank.lowres`
    # reports its first rows per layer since the check of PR 42)
    for name, before in APPENDED.items():
        assert entries[name]["workloads"][:len(before) + 1] == before + [CELL]
        assert entries[name]["workloads"][len(before) + 1:] == (
            ["bank.lowres"] if name == "first_product_wait_s" else [])
    # the new entries came in one block at the end, each with a file that
    # resolves to a reader, and list this cell alone
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == NEW
    for name in NEW:
        assert entries[name]["workloads"] == [CELL]
        s = spec(name)
        assert s["reader"] == "band_fanout" and name.startswith("b3_")
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           s["reader"] + ".py"))
        assert entries[name]["layer"] in ("kernels", "collectives")
    assert [entries[n]["unit"] for n in ROOFS] == ["%"] * 3
    assert sorted(os.listdir(LM)) == sorted(
        m["name"] + ".json" for m in bench["per_layer"])
    # no accepted cell gained a reading of what this PR adds
    for w in bench["workloads"][:-1]:
        mine = run.load_cell(w["name"], rehearse=False)
        assert not [m["name"] for m in mine["per_layer"] if m["name"] in NEW]


# -- the new reader -------------------------------------------------------------

RAW = 4 * 108 * 134217728
CHIPS = 4
# band rows: (rows, bytes a row), whole band
ROWS = {"0000": (1, 1 << 30), "0001": (55295, 8192), "0002": (17, 1 << 20)}
NINT = {"0000": 51, "0001": 128, "0002": 3072}


def evidence(per_op_s, windows=26, chips=CHIPS):
    stages = {"dispatch": {"calls": windows, "seconds": 0.7},
              "fanout.share": {"calls": 212, "bytes": 2 * RAW - (1 << 30)}}
    for name, (rows, row) in ROWS.items():
        stages["integrate.emit." + name] = {"calls": rows,
                                            "bytes": rows * row}
        stages["stitch." + name] = {"calls": 1, "bytes": 3 * rows * row}
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    return {"trace": {"per_op_s": per_op_s, "busy_s": 2.7, "window_s": 7.4,
                      "chips": list(range(chips))},
            "stages": stages, "traced_raw_bytes": RAW,
            "device_kind": "TPU v5 lite", "peaks": peaks}


def test_least_bytes_are_one_chips():
    # a quarter of the RAW in, a quarter of the rows out
    assert band_fanout.leg_least_bytes(RAW, 55295 * 8192, 4) \
        == 108 * 134217728 + 55295 * 2048
    chip_row = (1 << 30) // 4
    # 51 frames of that chip's power in, its share of the row out, 26 x
    # (read + write) of its accumulator
    assert band_fanout.fold_least_bytes(1, chip_row, 51, 26) \
        == (51 + 1 + 52) * chip_row
    assert band_fanout.fold_least_bytes(55295, 2048, 128, 26) \
        == (55295 * 129 + 52) * 2048


def test_a_roof_share_never_passes_100():
    """At the roof itself — each program taking, on every chip, exactly
    the seconds ONE chip's least bytes need at 819 GB/s — every share
    reads 100; any real program is slower.  Counted with the whole band's
    bytes against the mean-over-chips seconds it would read 400."""
    fold = sum(band_fanout.fold_least_bytes(
        rows, row // CHIPS, NINT[name], 26)
        for name, (rows, row) in ROWS.items())
    at_roof = {
        "jit_band_stream_0001/fusion.1":
            (RAW + 55295 * 8192) / CHIPS / 819e9,
        "jit_band_stream_0002/fusion.2":
            (RAW + 17 * (1 << 20)) / CHIPS / 819e9,
        "jit_band_carry/fusion.3": fold / 819e9,
        "jit_band_stream/fused1": 0.5}
    ev = evidence(at_roof)
    for name in ROOFS:
        assert band_fanout.read(spec(name)["args"], ev) \
            == pytest.approx(100.0)
    ev = evidence({k: 2 * v for k, v in at_roof.items()})
    for name in ROOFS:
        assert band_fanout.read(spec(name)["args"], ev) \
            == pytest.approx(50.0)
    assert fold == (51 + 1 + 52) * (1 << 28) + (55295 * 129 + 52) * 2048 \
        + (17 * 3073 + 52) * (1 << 18)
    for name, program in (("b3_p0001_busy_s_per_GB", "0001"),
                          ("b3_p0002_busy_s_per_GB", "0002")):
        assert band_fanout.read(spec(name)["args"], ev) == pytest.approx(
            2 * at_roof["jit_band_stream_%s/fusion.%s" % (
                program, program[-1])] / (RAW / 1e9))
    assert band_fanout.read(spec("b3_fold_busy_s_per_GB")["args"], ev) \
        == pytest.approx(2 * fold / 819e9 / (RAW / 1e9))
    # what the three stitches moved: three times the products, per GB
    assert band_fanout.read(spec("b3_stitch_MB_per_GB")["args"], ev) \
        == pytest.approx(3 * (1073741824 + 452976640 + 17825792) / 1e6
                         / (RAW / 1e9))
    # the accepted counter's reader holds on the mesh as it stands
    assert stage_bytes.read(spec("fanout_saved_MB_per_GB")["args"], ev) \
        == pytest.approx(1000 * (2 * RAW - (1 << 30)) / RAW)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent (one product per scan: neither the programs nor the
    counters), a CPU run's missing trace, a stage table without the rows:
    no value, and no exception."""
    parent = {"jit_band_stream/fused1": 0.5, "jit_band_carry/fusion": 0.1}
    for name in NEW:
        args = spec(name)["args"]
        ev = evidence(parent)
        ev["stages"] = {"dispatch": {"calls": 26, "seconds": 0.7},
                        "integrate.emit": {"calls": 1, "bytes": 1 << 30}}
        # (the fold's busy seconds alone need no counter: band4.hires51's
        # own jit_band_carry would read under this name, so the entry
        # lists this cell only)
        if name != "b3_fold_busy_s_per_GB":
            assert band_fanout.read(args, ev) is None, name
        assert band_fanout.read(args, dict(ev, trace=None)) is None, name
        if args["value"] != "busy_s_per_GB":
            assert band_fanout.read(args, dict(ev, stages={})) is None, name
    ours = {"jit_band_stream_0001/f": 0.3, "jit_band_stream_0002/f": 0.3,
            "jit_band_carry/f": 0.1}
    for name in ROOFS:
        # the programs without the counters, the counters without the
        # programs, a trace that names no chip
        assert band_fanout.read(spec(name)["args"],
                                dict(evidence(ours), stages={})) is None
        assert band_fanout.read(spec(name)["args"], evidence({})) is None
        assert band_fanout.read(spec(name)["args"],
                                evidence(ours, chips=0)) is None
