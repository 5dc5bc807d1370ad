"""One file per metric, not one per metric per cell (PR 32).  Until then
an entry listed its cells in two places, so every new cell brought the
accepted metrics again as ``t51_`` / ``b51_`` copies; ``FOLDED`` is where
each went.  Every cell still reports, under the folded name, each reading
it reported at PR 31 (ledger, PR 31: the ``per_layer`` of its lines).

``bank.hires`` reports them as ``<name>.first``: the check of PR 32 found
its ``reduce_rate`` too unsteady for any bound (the rig's product disk), so
there the rate is the per-layer ``pass_rate`` and every per-layer entry of
the cell names its other end-to-end metric, ``first_product_s``.  Such a
file holds ``same_as`` and no reader: one (reader, arguments) pair is
still written once."""

import json
import os

import pytest
from conftest import BENCH, EVERY_PASS, PUMP_WAITS, ROOT

LM = os.path.join(BENCH, "layer_metrics")
# the name until PR 31 -> the one entry that reads the same thing
FOLDED = {
    "t51_read_rate": "read_rate", "b51_read_rate": "read_rate",
    "t51_dispatch_s_per_GB": "dispatch_s_per_GB",
    "t51_roof_share": "hbm_roof_share",
    "t51_readback_s_per_GB": "readback_s_per_GB",
    "t51_write_s_per_GB": "write_s_per_GB",
    "b51_collective_s_per_GB": "collective_s_per_GB",
    "b51_launch_skew_s_per_GB": "launch_skew_s_per_GB",
    "t51_idle_named_share": "idle_named_share",
    "b51_idle_named_share": "idle_named_share",
    "b51_idle_read_s_per_GB": "idle_read_s_per_GB",
    "t51_idle_dispatch_s_per_GB": "idle_dispatch_s_per_GB",
    "b51_idle_put_s_per_GB": "idle_dispatch_s_per_GB",
    "b51_idle_output_s_per_GB": "idle_output_s_per_GB",
    "b51_d2h_MB_per_GB": "d2h_MB_per_GB",
    "t51_h2d_MB_per_GB": "h2d_MB_per_GB", "b51_h2d_MB_per_GB": "h2d_MB_per_GB",
}
# what each cell's traced run reported at PR 31 (ledger, PR 31)
REPORTED_AT_PR31 = {
    "bank.hires": [
        "device_busy_s_per_GB", "hbm_roof_share", "device_idle_share",
        "hbm_peak", "readback_s_per_GB", "write_s_per_GB",
        "host_cpu_s_per_GB", "idle_named_share", "idle_read_s_per_GB",
        "idle_output_s_per_GB"],
    "bank.lowres": [
        "read_rate", "dispatch_s_per_GB", "device_busy_s_per_GB",
        "device_idle_share", "hbm_peak", "host_cpu_s_per_GB",
        "idle_named_share", "idle_read_s_per_GB", "idle_dispatch_s_per_GB",
        "wait_chunk_s_per_GB", "wait_out_slot_s_per_GB"],
    "band4.hires": [
        "read_rate", "device_busy_s_per_GB", "device_idle_share", "hbm_peak",
        "readback_s_per_GB", "write_s_per_GB", "collective_s_per_GB",
        "host_cpu_s_per_GB", "first_product_wait_s", "idle_named_share",
        "idle_read_s_per_GB", "idle_dispatch_s_per_GB",
        "idle_output_s_per_GB", "launch_skew_s_per_GB"],
    "rawspec.hires51": [
        "device_busy_s_per_GB", "device_idle_share", "hbm_peak",
        "host_cpu_s_per_GB", "t51_roof_share", "link_wait_s_per_GB",
        "idle_link_s_per_GB", "carry_busy_s_per_GB", "carry_roof_share",
        "d2h_MB_per_GB", "t51_dispatch_s_per_GB",
        "t51_idle_dispatch_s_per_GB", "t51_idle_named_share",
        "t51_read_rate", "t51_readback_s_per_GB", "t51_write_s_per_GB",
        "t51_h2d_MB_per_GB"],
    "band4.hires51": [
        "device_busy_s_per_GB", "device_idle_share", "hbm_peak",
        "host_cpu_s_per_GB", "b51_collective_s_per_GB",
        "b51_launch_skew_s_per_GB", "b51_read_rate",
        "b51_idle_read_s_per_GB", "b51_idle_put_s_per_GB",
        "b51_idle_output_s_per_GB", "b51_idle_named_share",
        "b51_h2d_MB_per_GB", "b51_d2h_MB_per_GB", "b51_carry_busy_s_per_GB",
        "b51_carry_roof_share"],
}


# the cell whose rate carries no bound, and the suffix of what it reports
UNSTEADY, FIRST = "bank.hires", ".first"


def spec(name):
    with open(os.path.join(LM, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", sorted(REPORTED_AT_PR31))
def test_a_cell_reports_what_it_reported_under_the_folded_names(
        cell, bench_json):
    """Each reading of PR 31 is an entry that lists this cell (or lists
    none, and so holds for all), with its file and its reader; the file
    restates the entry and names no cell: ``BENCHMARK.json`` is the one
    list."""
    entries = {m["name"]: m for m in bench_json["per_layer"]}
    cells = [w["name"] for w in bench_json["workloads"]]
    import run

    mine = {m["name"] for m in run.load_cell(cell, rehearse=False)["per_layer"]}
    moves = "first_product_s" if cell == UNSTEADY else "reduce_rate"
    for old in REPORTED_AT_PR31[cell]:
        name = FOLDED.get(old, old)
        assert old == name or old not in entries, old
        if cell == UNSTEADY:
            assert name not in mine and spec(name + FIRST)["same_as"] == name
            name += FIRST
        e, s = entries[name], spec(name)
        assert name in mine, (cell, name)
        assert e["moves"] == moves
        assert "cells" not in s and s["name"] == name
        for k in ("unit", "layer", "better", "source", "moves"):
            assert s[k] == e[k], (name, k)
        s = spec(s.get("same_as", name))
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           s["reader"] + ".py"))
    # and nothing the cell reports is without a file
    for name in mine:
        assert os.path.exists(os.path.join(LM, name + ".json"))
    assert ("pass_rate" in mine) == (cell == UNSTEADY)


def test_no_two_files_read_the_same_thing(bench_json):
    """A (reader, arguments) pair is one metric: a second file over it is
    the twin this PR removed.  42 entries and files became 26."""
    seen, again = {}, {}
    for m in bench_json["per_layer"]:
        s = spec(m["name"])
        if "same_as" in s:   # no reader, no arguments: the named file's
            assert "reader" not in s and "args" not in s
            again[m["name"]] = s["same_as"]
            continue
        key = (s["reader"], json.dumps(s.get("args", {}), sort_keys=True))
        assert key not in seen, (m["name"], seen[key])
        seen[key] = m["name"]
    assert sorted(os.listdir(LM)) == sorted(
        m["name"] + ".json" for m in bench_json["per_layer"])
    assert len(seen) == 27   # the 26, and `pass_rate`
    assert not set(FOLDED) & set(seen.values())
    assert set(FOLDED.values()) <= set(seen.values())
    # a second name for a reading exists only where it moves another
    # end-to-end metric, in cells of its own
    entries = {m["name"]: m for m in bench_json["per_layer"]}
    cells = [w["name"] for w in bench_json["workloads"]]
    assert len(again) == 18
    for name, base in again.items():
        assert name == base + FIRST and base in seen.values()
        assert entries[name]["moves"] != entries[base]["moves"]
        assert entries[name]["workloads"] == [UNSTEADY]
        assert UNSTEADY not in entries[base].get("workloads", [])
        for k in ("unit", "layer", "better", "source"):
            assert entries[name][k] == entries[base][k]
    assert set(cells) - set(
        bench_json["end_to_end"][0]["workloads"]) == {UNSTEADY}


def test_what_every_pass_can_report_lists_no_cell(bench_json):
    """An entry whose reading any ``blit reduce`` or ``blit scan`` pass
    has (a row of the stage table, a host clock, the device trace) has no
    ``workloads`` key: it holds for every cell that reports ``reduce_rate``,
    the ones a later PR adds too, which a PR that only adds could not
    append to a list.  The pump's own waits list the ``reduce`` cells."""
    listed = {m["name"]: m.get("workloads") for m in bench_json["per_layer"]}
    assert sorted(n for n, cells in listed.items() if cells is None) \
        == sorted(EVERY_PASS + ["device_busy_s_per_GB", "device_idle_share",
                                "hbm_peak", "idle_named_share",
                                "idle_dispatch_s_per_GB"])
    for name in PUMP_WAITS:
        assert listed[name] == ["bank.lowres", "rawspec.hires51"]
        assert listed[name + FIRST] == [UNSTEADY]
    # the output plane's stress cell reads the output plane's backpressure
    assert listed["idle_output_s_per_GB" + FIRST] == [UNSTEADY]


def test_the_files_with_arguments_of_their_own_stay():
    """``readers/band_carry.py`` holds the band's bytes per chip where
    ``readers/carry.py`` holds one chip's: two readers, two metrics."""
    for name, reader in (("carry_busy_s_per_GB", "carry"),
                         ("carry_roof_share", "carry"),
                         ("b51_carry_busy_s_per_GB", "band_carry"),
                         ("b51_carry_roof_share", "band_carry")):
        assert spec(name)["reader"] == reader


def test_the_link_wait_reads_the_links_own_row_and_the_idle_buckets_agree():
    """``link_wait_s_per_GB`` was ``wait_out_slot_s_per_GB``'s copy since
    PR 27; ``wait.link`` is the link's wait.  Idle seconds under it belong
    to the dispatch bucket; ``state`` is a stage no program has."""
    assert spec("link_wait_s_per_GB")["args"] \
        == {"stages": ["wait.link"], "value": "seconds_per_GB"}
    assert spec("wait_out_slot_s_per_GB")["args"]["stages"] \
        == ["wait.out_slot"]
    assert spec("idle_dispatch_s_per_GB")["args"]["ends_in"] \
        == ["dispatch", "feed.put", "wait.link"]
    assert spec("idle_read_s_per_GB")["args"]["ends_in"] \
        == ["ingest", "feed.read", "read"]
    from readers import spans, timeline

    assert "state" not in spans.WAITS_ON["wait.chunk"]
    ev = {"traced_raw_bytes": 2e9,
          "stages": {"wait.link": {"calls": 0, "seconds": 0.0, "bytes": 0,
                                   "byte_free": True},
                     "wait.chunk": {"calls": 3, "seconds": 0.5, "bytes": 0}}}
    # a declared wait that never blocked is a reading; no row is none
    assert timeline.read(spec("link_wait_s_per_GB")["args"], ev) == 0.0
    assert timeline.read(spec("wait_chunk_s_per_GB")["args"], ev) == 0.25
    assert timeline.read(spec("wait_out_slot_s_per_GB")["args"], ev) is None
    assert timeline.read(spec("read_rate")["args"], ev) is None


def test_the_cells_and_configurations_are_the_five_and_four(bench_json):
    assert [w["name"] for w in bench_json["workloads"]] == [
        "bank.hires", "bank.lowres", "band4.hires", "rawspec.hires51",
        "band4.hires51"]
    assert [c["name"] for c in bench_json["configs"]] == [
        "gbt-bank", "gbt-band4", "gbt-bank-rawspec", "gbt-band4-rawspec"]
    assert sum(w["chips"] == 4 for w in bench_json["workloads"]) == 2
    assert bench_json["run_seconds"] == 35
    assert [(m["name"], m["bound"]) for m in bench_json["end_to_end"]] == [
        ("reduce_rate", 0.15), ("first_product_s", 0.06), ("setup_s", 0.25)]
    assert bench_json["end_to_end"][1]["workloads"] == ["bank.hires",
                                                        "bank.lowres"]
    # every cell but the one whose product disk stalls (PERF.md section 2)
    assert bench_json["end_to_end"][0]["workloads"] == [
        "bank.lowres", "band4.hires", "rawspec.hires51", "band4.hires51"]
    assert "workloads" not in bench_json["end_to_end"][2]
    # no cell runs a `--product` preset: blit's can become BL's
    for w in bench_json["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            t = json.load(f)
        assert "--product" not in t["argv"] + t["rehearse"]["argv"]
        assert "reducer" not in t and "reducer" not in t["rehearse"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "hires-19f.json")) as f:
        assert json.load(f)["argv"][-4:] == ["--nfft", "1048576",
                                             "--nint", "1"]
