"""What a product IS is a module the harness finds by name (PR 42):
``products/<kind>.py``.  The ``fil`` kind gives ``plan_pass`` the numbers
``test_sizing.py`` pins; a cell with a ``hits`` product, added to a
temporary tree AS FILES ONLY, is found and rehearses ``correct: true``
through today's ``blit search`` on the CPU; a kind is given the slices it
asks for and no others; six mutations of a ``.hits`` table each make the
kind say ``Incorrect`` by the right name; the reference's drift sums are
the program's; a recording without ``drift`` is the bytes it was before."""

import json
import os
import shutil
import zlib

import numpy as np
import pytest
from conftest import LOCAL_DRIVER, lines_of, run_harness, tree_with

import check
import recording
import reference
import run
from products import fil, hits

HERE = os.path.dirname(os.path.abspath(__file__))
GiB = 1 << 30


def toy_hits() -> dict:
    with open(os.path.join(HERE, "local_traffic", "toy-hits.json")) as f:
        return json.load(f)


HITS_CELL = {"name": "bank.search", "config": "gbt-bank",
             "traffic": "toy-hits", "chips": 1,
             "why": "added by a test, as files only: a .hits table is the "
                    "pass's product"}


# -- the `fil` kind's sizing is what `plan_pass` had ------------------------------

@pytest.mark.parametrize("nfft, nint, blocks, rows, row_bytes", [
    (1 << 20, 1, 38, 16, 256 << 20),      # bank.hires: 19 frames less 3
    (1 << 20, 51, 108, 1, 256 << 20),     # the 0000 product
    (8, 128, 108, 55295, 2048),           # 0001
    (1024, 3072, 108, 17, 256 << 10),     # 0002
    (1024, 3072, 37, 6, 256 << 10),       # bank.lowres
])
def test_the_fil_kind_sizes_a_product_as_plan_pass_did(nfft, nint, blocks,
                                                       rows, row_bytes):
    spec = {"name": "p", "nfft": nfft, "nint": nint, "tolerance": 0.01}
    p = fil.sized(spec, blocks * 524288, nslots=64, ntap=4)
    assert list(p) == ["name", "nfft", "nint", "tolerance", "row_bytes",
                       "rows"]                      # the [plan] line's order
    assert (p["rows"], p["row_bytes"]) == (rows, row_bytes)
    assert fil.nothing(p) is None and not fil.RAGGED
    assert fil.bytes_at(p) == rows * row_bytes + fil.FIL_HEADER_ROOM
    assert fil.rows_under(p, fil.bytes_at(p)) == rows
    # as many samples as hold those rows hold them, one frame fewer do not
    need = fil.samples_for(p, rows, 4)
    assert need == (rows * nint + 3) * nfft <= blocks * 524288
    assert fil.sized(spec, need, nslots=64, ntap=4)["rows"] == rows
    assert fil.sized(spec, need - nfft, nslots=64, ntap=4)["rows"] == rows - 1
    assert fil.frames(p, rows) == rows * nint
    assert fil.least_bytes(dict(p, bytes=rows * row_bytes)) == rows * row_bytes
    assert fil.limits(p) == {"rel_err.p": 0.01}


def test_an_empty_fil_product_says_what_it_holds_none_of():
    p = fil.sized({"name": "never", "nfft": 1024, "nint": 19454,
                   "tolerance": 0.01}, 38 * 524288, nslots=64, ntap=4)
    assert p["rows"] == 0
    assert fil.nothing(p) == "no row of product 'never' at nfft 1024, " \
        "nint 19454"


def test_a_cells_kinds_are_resolved_once_beside_its_driver():
    cell = run.load_cell("band4.hires", rehearse=False)
    assert cell["kinds"] == [fil] and "input" not in cell
    assert run.plan_pass(cell, 1 << 62)["raw_bytes"] == 4 * 14 * 134217728
    cell = run.load_cell("rawspec3.hires51", rehearse=False)
    assert cell["kinds"] == [fil, fil, fil]


# -- a ragged kind in the plan ------------------------------------------------------

def hits_cell(blocks=38, rehearse=True, **spec):
    cell = run.load_cell("bank.hires", rehearse=rehearse)
    t = toy_hits()
    t["blocks"] = blocks
    t["products"][0].update(spec)
    cell["traffic"], cell["kinds"] = t, run.product_kinds(t)
    return cell


def test_a_hits_product_is_sized_by_the_most_it_can_hold():
    cell = hits_cell()
    plan = run.plan_pass(cell, 1 << 62)
    (p,) = plan["products"]
    # 16 spectra of the toy recording in windows of 8; 4 coarse channels
    assert (p["kind"], p["rows"], p["warm_rows"]) == ("hits", 2, 1)
    assert p["row_bytes"] == 4 * 32 * hits.HIT_LINE_MOST
    assert plan["product_bytes"] == p["bytes"] == 2 * p["row_bytes"]
    assert hits.RAGGED and hits.ALL_CHANNELS and hits.least_bytes(p) == 0
    assert hits.limits(p) == {"hits_missing.hits": 0,
                              "hits_unexplained.hits": 0,
                              "snr_rel_err.hits": 0.0003}
    # a window that the recording cannot fill refuses, by the kind's words
    with pytest.raises(run.Refused) as e:
        run.plan_pass(hits_cell(window_spectra=32), 1 << 62)
    assert "no window of product 'hits'" in str(e.value) \
        and "window_spectra 32" in str(e.value)


def test_a_ragged_product_never_sizes_the_cut_under_a_file_cap():
    """Beside a hi-res ``.fil`` at the recorder's width the table's most
    bytes are nothing: the ``.fil`` sizes the cut and the table takes the
    windows the same blocks hold."""
    cell = hits_cell(rehearse=False, nfft=1 << 20, window_spectra=4)
    cell["traffic"]["products"].insert(
        0, {"name": "hi", "nfft": 1 << 20, "nint": 1, "tolerance": 0.012})
    cell["traffic"]["align_rows"] = 8
    cell["kinds"] = run.product_kinds(cell["traffic"])
    assert cell["kinds"] == [fil, hits]
    plan = run.plan_pass(cell, 1 << 62)
    assert plan["sized_by"] == "hi" and plan["blocks"] == 38
    assert [p["rows"] for p in plan["products"]] == [16, 4]
    plan = run.plan_pass(cell, 4 * GiB)     # test_sizing's cut: 22 blocks
    assert plan["sized_by"] == "hi" and plan["blocks"] == 22
    assert [p["rows"] for p in plan["products"]] == [8, 2]
    # and alone it is sized by itself and never cut by a cap it fits
    alone = hits_cell(rehearse=False, nfft=1 << 20, window_spectra=4)
    plan = run.plan_pass(alone, 1 * GiB)
    assert plan["sized_by"] == "hits" and plan["blocks"] == 38


# -- cells added AS FILES ONLY --------------------------------------------------------

def test_a_cell_with_a_hits_product_is_found_and_rehearses_correct(tmp_path):
    root = tree_with(tmp_path, workloads=[HITS_CELL],
                     traffic={"toy-hits": toy_hits()},
                     listed_under=("reduce_rate",))
    p, out = run_harness("--workload", "bank.search", "--seed", "4200000003",
                         "--seconds", "0.05", "--trace", "1", "--rehearse",
                         root=root)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is True and doc["failed"] == 0
    (plan,) = lines_of(out, "plan")
    assert plan["products"][0]["kind"] == "hits"
    (ref,) = lines_of(out, "check.reference")
    assert ref["pass_"] == "warmup" and ref["checked_slots"] == [1, 3]
    assert ref["hits_in_checked"] >= 20 and ref["snr_rel_err"] < 1e-5
    # the reference ran for EVERY coarse channel, a child each
    (pool,) = lines_of(out, "reference")
    assert pool["tasks"] == pool["launched"] == 4 and not pool["failed"]
    # every number compared beside its limit, under the kind's names
    said = [ln.split() for ln in p.stderr.splitlines()
            if ln.startswith("compared ")]
    assert [w[1] for w in said] == [
        "hits_missing.hits", "hits_unexplained.hits", "snr_rel_err.hits",
        "wrong_products", "compiles_in_window"]
    assert [w[2] for w in said[:2]] == ["0", "0"] and said[2][4] == "0.0003"
    # a command whose report has no stage table: the traced pass says so
    (traced,) = lines_of(out, "traced")
    assert traced["stages"] == "absent"
    assert doc["metric_names"] == ["host_cpu_s_per_GB"]


def test_each_kind_of_a_pass_is_given_the_slices_it_asks_for(tmp_path):
    """A pass that makes a ``.fil`` AND a ``.hits`` table (the test-local
    driver runs a command a product): the whole band's streams are out for
    the ``hits`` kind's sake, and the ``fil`` kind is given the checked
    channels and no others, as in a cell without a table."""
    t = toy_hits()
    argv = t.pop("argv")
    t.update(name="fil-and-hits", driver="reduce_each")
    t["products"][0].update(
        path="{out}.hits", argv=[w.replace("{out}", "{path}") for w in argv])
    # the table first: a tone's drift counts spectra of the first of the
    # finest products, and the toy tones are sized for `nint` 1
    t["products"].append({
        "name": "fine", "nfft": 1024, "nint": 8, "tolerance": 0.012,
        "path": "{out}.fine.fil",
        "argv": ["reduce", "{raws}", "-o", "{path}", "--nfft", "1024",
                 "--nint", "8"]})   # a row a search window
    cell = dict(HITS_CELL, name="bank.both", traffic="fil-and-hits")
    root = tree_with(tmp_path, workloads=[cell], drivers=[LOCAL_DRIVER],
                     traffic={"fil-and-hits": t},
                     listed_under=("reduce_rate",))
    p, out = run_harness("--workload", "bank.both", "--seed", "4200000007",
                         "--seconds", "0.05", "--trace", "0", "--rehearse",
                         root=root)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert json.loads(out[-1])["correct"] is True
    (plan,) = lines_of(out, "plan")
    assert plan["sized_by"] == "fine"
    assert [q["rows"] for q in plan["products"]] == [2, 2]
    # two checked channels for the filterbank, all four for the table
    (pool,) = lines_of(out, "reference")
    assert sorted(pool["child_s"]) == ["fine/1", "fine/3", "hits/0",
                                       "hits/1", "hits/2", "hits/3"]
    table, fine = lines_of(out, "check.reference")
    assert sorted(fine["rel_err_by_slot"]) == ["1", "3"]
    # the still tone's channel is found where the headers predict it; the
    # channel of the two chirps is held to the reference alone
    assert list(fine["tone_channel_by_slot"]) == ["3"]
    assert table["checked_slots"] == [1, 3] and table["hits_in_checked"] >= 20
    assert [ln.split()[1] for ln in p.stderr.splitlines()
            if ln.startswith("compared ")] == [
        "hits_missing.hits", "hits_unexplained.hits", "snr_rel_err.hits",
        "rel_err.fine", "wrong_products", "compiles_in_window"]


# -- the `hits` kind's comparison, and six ways to break a table ---------------------

@pytest.fixture(scope="module")
def searched(tmp_path_factory):
    """One toy recording through today's ``blit search`` on the CPU, and
    the reference every coarse channel's child would keep."""
    from blit.__main__ import main as blit_main

    d = tmp_path_factory.mktemp("searched")
    cell = hits_cell()
    import importlib
    cell["driver"] = importlib.import_module("drivers.search")
    plan = run.plan_pass(cell, 1 << 62)
    (p,) = plan["products"]
    inputs = run.write_inputs(cell, plan, str(d), 1 << 40, 4200000009,
                              whole_band=True)
    slices = inputs["slices"]
    assert [s["checked"] for s in slices] == [False, True, False, True]
    kept = {slot: hits.compute(next(s for s in slices
                                    if s["slot"] == slot).pop("volt"), args)
            for slot, _, args in hits.reference_tasks(p, slices, ntap=4,
                                                      despike=False)}
    out = str(d / "toy.hits")
    argv = [w for word in cell["traffic"]["argv"]
            for w in (inputs["raws"][0] if word == "{raws}"
                      else [word.format(out=out)])]
    assert blit_main(argv) == 0
    return {"p": p, "slices": slices, "kept": kept, "out": out,
            "rows": p["rows"], "dir": d}


def judge(s, path):
    return hits.against_reference(path, s["p"], s["slices"],
                                  s["kept"].__getitem__, rows=s["rows"],
                                  nslots=4)


def test_todays_search_is_correct_by_the_hits_kind(searched):
    s = searched
    facts = hits.guarantees(s["out"], s["p"], s["rows"], False)
    assert facts["rows"] == 2 and facts["hits"] >= 20 and facts["read_all"]
    said, numbers = judge(s, s["out"])
    assert numbers["hits_missing.hits"] == numbers["hits_unexplained.hits"] \
        == 0
    assert numbers["snr_rel_err.hits"] < 1e-5 < s["p"]["tolerance"]
    assert said["hits_in_checked"] == said["hits"] \
        and said["reference_over_threshold"] >= said["hits"] - 4
    hits.same_product(s["out"], facts, dict(
        facts, sample=hits.sample(s["out"], facts, 1)), 1)


def mutated(s, name, change):
    """A copy of the table (and its sidecar) with ``change(lines)``
    applied to its lines."""
    path = str(s["dir"] / (name + ".hits"))
    shutil.copy(s["out"], path)
    shutil.copy(s["out"] + check.MANIFEST_SUFFIX, path + check.MANIFEST_SUFFIX)
    with open(path) as f:
        lines = f.read().splitlines()
    lines = change(lines)
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


def strong(lines, p):
    """Index of the first hit line clear of the guard band."""
    return next(i for i, ln in enumerate(lines[1:], 1)
                if json.loads(ln)["snr"] > 2 * p["snr"])


@pytest.mark.parametrize("fault, name, says", [
    ("a_hit_removed", "hits_missing.hits", "are not in the table"),
    ("a_hit_added", "hits_unexplained.hits", "have a reference S/N under"),
    ("an_snr_scaled", "snr_rel_err.hits", "rel err"),
])
def test_a_table_that_says_the_wrong_thing_is_incorrect_by_name(
        searched, fault, name, says):
    s, p = searched, searched["p"]

    def change(lines):
        i = strong(lines, p)
        hit = json.loads(lines[i])
        if fault == "a_hit_removed":
            return lines[:i] + lines[i + 1:]
        if fault == "a_hit_added":   # a quiet cell of the same channel
            hit.update(chan=hit["chan"] // 1024 * 1024 + 600, drift_bins=0,
                       snr=p["snr"] * (1 + p["guard"]) + 5)
            return lines + [json.dumps(hit, sort_keys=True)]
        hit["snr"] *= 1 + 2 * p["tolerance"]
        return lines[:i] + [json.dumps(hit, sort_keys=True)] + lines[i + 1:]

    with pytest.raises(check.Incorrect) as e:
        judge(s, mutated(s, fault, change))
    assert says in str(e.value)
    others = {k: v for k, v in e.value.compared.items() if k != name}
    if fault == "an_snr_scaled":
        assert 1.9 * p["tolerance"] < e.value.compared[name] \
            < 2.1 * p["tolerance"]
        assert set(others.values()) == {0}
    else:
        assert e.value.compared[name] == 1
        assert all(v == 0 or v < p["tolerance"] for v in others.values())


@pytest.mark.parametrize("fault, says", [
    ("a_partial_left", ".partial left behind"),
    ("the_manifests_crc_wrong", "crc32"),
    ("search_windows_one_short", "rows"),
])
def test_a_table_that_breaks_a_guarantee_is_incorrect_by_name(
        searched, fault, says):
    s = searched
    path = mutated(s, fault, lambda lines: lines)
    mpath = path + check.MANIFEST_SUFFIX
    with open(mpath) as f:
        doc = json.load(f)
    if fault == "a_partial_left":
        with open(path + ".partial", "w") as f:
            f.write("left behind")
    elif fault == "the_manifests_crc_wrong":
        doc["crc32"] = f"{int(doc['crc32'], 16) ^ 1:08x}"
    else:   # the writer claimed one window fewer than the plan implies
        doc["rows"] -= 1
    with open(mpath, "w") as f:
        json.dump(doc, f)
    with pytest.raises(check.Incorrect) as e:
        hits.guarantees(path, s["p"], s["rows"], False)
    assert says in str(e.value)


def test_a_checked_channel_that_fills_its_top_k_is_refused_by_name(searched):
    s = searched
    with pytest.raises(check.Incorrect) as e:
        hits.against_reference(s["out"], dict(s["p"], top_k=8), s["slices"],
                               s["kept"].__getitem__, rows=s["rows"],
                               nslots=4)
    assert "the cut decided what the table holds" in str(e.value)


# -- the copy is tied to the program ---------------------------------------------------

@pytest.mark.parametrize("nspectra", [8, 16])
def test_the_drift_sums_are_the_programs(nspectra):
    """``reference.drift_sums`` against the program's own oracle (the same
    paths, float64: equal to the bit) and its lax tree (float32 sums)."""
    from blit.ops.pallas_dedoppler import (brute_force_dedoppler,
                                           drift_spectra, tree_path_shift)

    rng = np.random.default_rng(42 + nspectra)
    x = rng.gamma(2.0, 1.0, (nspectra, 300)).astype(np.float32)
    got = reference.drift_sums(x)
    assert got.shape == (2 * nspectra - 1, 300)
    assert np.array_equal(got[nspectra - 1:], brute_force_dedoppler(x))
    assert np.array_equal(got[nspectra - 1::-1][:, ::-1],
                          brute_force_dedoppler(x[:, ::-1]))
    tree = np.asarray(drift_spectra(x, kernel="reference"))
    assert np.abs(tree - got).max() <= 1e-6 * got.max()
    for d in range(nspectra):
        assert [reference.tree_shift(d, t, nspectra)
                for t in range(nspectra)] \
            == [tree_path_shift(d, t, nspectra) for t in range(nspectra)]
    # a straight line is NOT the semantics: from T 16 on some paths part
    lines = sum([reference.tree_shift(d, t, nspectra)
                 for t in range(nspectra)]
                != [round(d * t / (nspectra - 1)) for t in range(nspectra)]
                for d in range(nspectra))
    assert lines == {8: 0, 16: 6}[nspectra]


def test_the_normalisation_and_the_mask_are_the_programs():
    from blit.ops.pallas_dedoppler import drift_rates, snr_normalize

    rng = np.random.default_rng(7)
    dd = rng.gamma(8.0, 1.0, (15, 4096))
    mean, std = reference.snr_rows(dd.sum(axis=1), (dd * dd).sum(axis=1),
                                   4096)
    want = np.asarray(snr_normalize(dd.astype(np.float32)))
    assert np.abs((dd - mean[:, None]) / std[:, None] - want).max() < 1e-4
    assert np.array_equal(reference.drift_mask(8, 3),
                          np.abs(drift_rates(8)) <= 3)
    assert reference.drift_mask(8, None).all() \
        and reference.drift_mask(8, -1).all()


# -- the recording ----------------------------------------------------------------------

def test_a_chirp_is_where_its_drift_says_and_continuous_across_blocks():
    nfft, nsamp = 256, 1024
    whole = np.concatenate([recording.tone_block(
        b, nsamp, nfft, 40, amp=1.0, drift=[3, 2], nint=2)
        for b in range(8)])[:, 0, :].astype(np.float64)
    z = whole[:, 0] + 1j * whole[:, 1]
    assert np.allclose(np.abs(z), 1.0, atol=1e-6)
    # spectrum k of nfft * nint samples: the tone has moved 1.5 channels a
    # spectrum from channel 40
    for k in range(0, 16, 5):
        at = np.argmax(np.abs(np.fft.fft(z[k * 512:k * 512 + nfft])))
        assert abs(at - (40 + 1.5 * (k + 0.25))) <= 1.0, (k, at)
    # the phase is continuous where two blocks meet: its second difference
    # is the constant 2 pi num / (den nfft^2 nint), all the way through
    ph = np.unwrap(np.angle(z))
    second = np.diff(ph, 2)
    assert np.abs(second - 2 * np.pi * 1.5 / (nfft * nfft * 2)).max() < 1e-5
    # and a negative drift mirrors it
    down = recording.tone_block(3, nsamp, nfft, 40, amp=1.0, drift=-2)
    up = recording.tone_block(3, nsamp, nfft, -40, amp=1.0, drift=2)
    assert np.allclose(down[..., 0], up[..., 0], atol=1e-5)
    assert np.allclose(down[..., 1], -up[..., 1], atol=1e-5)


# What the PARENT tree's generator wrote (``git archive`` of PR 41's tree,
# seed 42): every file of the rehearsal's recording, and bank 0's first
# block at the real size.  A recording without `drift`, `amp` or `more` is
# these bytes still.
PINS = {"bank.hires": ("deed7427", "1cd4e209"),
        "bank.lowres": ("649abcda", "f4bd8c0e"),
        "band4.hires": ("12592340", "0056d62a"),
        "rawspec.hires51": ("9887e970", "1cd4e209"),
        "band4.hires51": ("3211049a", "0056d62a"),
        "rawspec3.hires51": ("9887e970", "1cd4e209"),
        "band4.rawspec3": ("af508804", "954bbe08")}


def crc_of(paths) -> str:
    crc = 0
    for path in paths:
        with open(path, "rb") as f:
            while chunk := f.read(1 << 26):
                crc = zlib.crc32(chunk, crc)
    return f"{crc & 0xFFFFFFFF:08x}"


@pytest.mark.parametrize("size", ["rehearsal", "first_block_at_the_real_size"])
@pytest.mark.parametrize("name", sorted(PINS))
def test_a_recording_without_drift_is_the_bytes_it_was(tmp_path, name, size):
    if size == "rehearsal":
        cell = run.load_cell(name, rehearse=True)
        plan = run.plan_pass(cell, 1 << 62)
        want = PINS[name][0]
    else:
        cell = run.load_cell(name, rehearse=False)
        cell["config"]["banks"], plan = 1, {"blocks": 1}
        want = PINS[name][1]
    inputs = run.write_inputs(cell, plan, str(tmp_path), 1 << 40, 42)
    assert crc_of([p for ps in inputs["raws"] for p in ps]) == want
    assert all(s["checked"] for s in inputs["slices"])


def test_every_cell_is_pinned(bench_json):
    assert sorted(PINS) == sorted(w["name"] for w in bench_json["workloads"])
