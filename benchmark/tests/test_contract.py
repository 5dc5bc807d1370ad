"""BENCHMARK.json against the builder's contract, the data files it
names, and the last line's keys."""

import json
import os
import re

import pytest
from conftest import BENCH, ROOT

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes(bench_json):
    assert set(bench_json) == {"command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= bench_json["run_seconds"] <= 51
    assert bench_json["paths"] == ["benchmark"]
    assert bench_json["command"][1].startswith("benchmark/")


def test_names_units_and_lengths(bench_json):
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench_json[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    for m in bench_json["end_to_end"] + bench_json["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for e in bench_json["configs"] + bench_json["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in bench_json["configs"]:
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench_json["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in bench_json["workloads"])
    assert four <= max(1, len(bench_json["workloads"]) // 2)


def test_metrics_meet_the_contract(bench_json):
    e2e = {m["name"]: m for m in bench_json["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.25
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in bench_json["workloads"]}
    for m in bench_json["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        # reported only where the end-to-end metric it should move is: an
        # entry that lists no cell holds in every cell that reports it
        assert set(m.get("workloads", [])) \
            <= set(e2e[m["moves"]].get("workloads", cells)), m["name"]
    for c in cells:   # every cell: setup_s, another end-to-end, a per-layer
        assert sum(c in m.get("workloads", [c]) for m in e2e.values()) >= 2
        assert run.load_cell(c, rehearse=False)["per_layer"]


def test_every_named_thing_is_a_file_of_its_own(bench_json):
    for c in bench_json["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"]
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert doc["guarantees"] and doc["geometry"]["obsnchan"] == 64
    for w in bench_json["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            t = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           t["driver"] + ".py"))
    for m in bench_json["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        for k in ("unit", "layer", "moves", "better", "source"):
            assert spec[k] == m[k], (m["name"], k)
        if "same_as" in spec:   # the file it names holds the reader
            with open(os.path.join(BENCH, "layer_metrics",
                                   spec["same_as"] + ".json")) as f:
                spec = json.load(f)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))


def test_last_line_holds_the_contract_keys_and_no_others():
    """The keys the driver reads, ``breakdown`` where a run was traced, and
    last of all ``compared``: each number that decided ``correct`` beside
    its limit."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    metrics = {"reduce_rate": {"value": 0.123456789, "unit": "GB/s"}}
    compared = {"rel_err.product": [0.0031, 0.012], "wrong_products": [0, 0],
                "compiles_in_window": [0, 0]}
    doc = json.loads(run.result_line(True, 3, 0, metrics, device,
                                     compared=compared))
    assert tuple(doc) == run.RESULT_KEYS + ("compared",)
    assert doc["metrics"]["reduce_rate"]["value"] == 0.123456789
    assert doc["compared"] == compared
    doc = json.loads(run.result_line(False, 3, 1, metrics, device,
                                     {"device_ops": [], "idle_gaps": []},
                                     compared=dict(compared,
                                                   wrong_products=[1, 0])))
    assert tuple(doc) == run.RESULT_KEYS + ("breakdown", "compared")
    assert doc["correct"] is False and doc["failed"] == 1
    assert doc["compared"]["wrong_products"] == [1, 0]


@pytest.mark.parametrize("kind", ["TPU v5 lite"])
def test_peaks_table_has_its_source(kind):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    assert "Google Cloud" in peaks["source"]
    assert peaks["by_device_kind"][kind]["hbm_GBps"] == 819.0
    assert "cpu" not in peaks["by_device_kind"]


def test_the_harness_names_no_format(bench_json):
    """What a product IS is a file of its own (``products/<kind>.py``):
    the harness's own files no longer say what a filterbank file is."""
    for name in ("run.py", "refpool.py", "check.py"):
        with open(os.path.join(BENCH, name)) as f:
            text = f.read()
        for word in (".fil", "nifs", "stokes_i"):
            assert word not in text, (name, word)
    assert "The harness holds no list of cells, traffic mixes, driver " \
        "kinds, product\nkinds or per-layer metrics" in run.__doc__
    # every kind a committed file names is a file of its own
    kinds = {"fil"}
    for w in bench_json["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            kinds |= {p.get("kind", "fil")
                      for p in json.load(f).get("products", [])}
    for kind in kinds | {"hits"}:
        assert os.path.exists(os.path.join(BENCH, "products", kind + ".py"))


@pytest.mark.parametrize("kind", ["fil", "hits"])
def test_a_product_kind_answers_everything_the_harness_asks(kind):
    import importlib

    mod = importlib.import_module("products." + kind)
    for name in ("sized", "nothing", "bytes_at", "rows_under", "frames",
                 "samples_for", "least_bytes", "landed", "guarantees",
                 "sample", "same_product", "reference_tasks", "compute",
                 "against_reference", "limits"):
        assert callable(getattr(mod, name)), (kind, name)
    assert isinstance(mod.RAGGED, bool) and isinstance(mod.ALL_CHANNELS, bool)
