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
still written once.

What this file pinned as COUNTS for the five cells of PR 32 (the cells and
configurations by name, the readers' and twins' number, which entries list
no cell) is pinned for all the cells there are in
``test_rawspec3_cell.py``, with ``FOLDED`` held against it there (PR 39
folded the two)."""

import json
import os

import pytest
from conftest import BENCH

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
