"""A pass that makes several products (PR 32): the harness sizes, watches,
fsyncs and verifies each.  The cell is added to a temporary tree as files
only; its driver (``tests/local_drivers/reduce_each.py``) runs ``blit
reduce`` once per product on the toy recording.  The controls each break
the SECOND product and must end ``correct: false`` with it named."""

import json

import pytest
from conftest import (LOCAL_DRIVER, lines_of, products_traffic, run_harness,
                      tree_with)

CELL = {"name": "bank.two", "config": "gbt-bank", "traffic": "two-products",
        "chips": 1, "why": "added by a test, as files only: two products a "
        "pass"}
TWO = [("fine", 1024, 1), ("coarse", 64, 8)]


def run_two(tmp_path, trace="0", **more):
    root = tree_with(tmp_path, workloads=[CELL], drivers=[LOCAL_DRIVER],
                     traffic={"two-products":
                              products_traffic("two-products", TWO, **more)})
    p, out = run_harness("--workload", "bank.two", "--seed", "3200000007",
                         "--seconds", "0.05", "--trace", trace, "--rehearse",
                         root=root)
    return p, out


def test_both_products_are_sized_watched_and_verified(tmp_path):
    p, out = run_two(tmp_path, trace="1")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1
    (plan,) = lines_of(out, "plan")
    fine, coarse = plan["products"]
    # 38 blocks of 512 samples: 19 frames of 1024 less the 3-frame filter
    # state; 304 frames of 64 less 3, in eights
    assert (fine["name"], fine["rows"], fine["row_bytes"]) \
        == ("fine", 16, 4 * 1024 * 4)
    assert (coarse["name"], coarse["rows"], coarse["row_bytes"]) \
        == ("coarse", 37, 4 * 64 * 4)
    assert plan["product_bytes"] == fine["bytes"] + coarse["bytes"] \
        == 16 * 16384 + 37 * 1024
    assert plan["raw_bytes"] == 38 * 512 * 4 * 2 * 2   # one read
    assert plan["sized_by"] == "fine"
    (reduced,) = lines_of(out, "reduced")
    assert reduced["rows"] == {"fine": [16, 16], "coarse": [37, 37]}
    # each product against the reference at its own nfft and nint, in the
    # warm-up; every pass against its own verified product
    refs = lines_of(out, "check.reference")
    assert [(r["pass_"], r["product"]) for r in refs] \
        == [("warmup", "fine"), ("warmup", "coarse")]
    assert refs[0]["header"]["nchans"] == 4 * 1024
    assert refs[1]["header"] == {"nchans": 4 * 64, "nifs": 1, "nbits": 32,
                                 "nsamps": 37}
    # the tone, placed on the fine grid, found where the coarse headers
    # predict it
    assert len(refs[1]["tone_channel_by_slot"]) == 1
    assert not lines_of(out, "INCORRECT")
    # the pass's first product is the earlier of the two
    for ps in lines_of(out, "pass") + lines_of(out, "traced"):
        by = ps["first_by_product"]
        assert sorted(by) == ["coarse", "fine"]
        assert ps["first_product_s"] == min(by.values()) == by["fine"]
        assert by["fine"] < by["coarse"] <= ps["wall_s"]
    # both numbers that decided `correct`, each beside its limit
    said = p.stderr.strip().splitlines()[-4:]
    assert [ln.split()[1] for ln in said] == [
        "rel_err.fine", "rel_err.coarse", "wrong_products",
        "compiles_in_window"]
    assert all(float(ln.split()[2]) <= float(ln.split()[4]) for ln in said)


@pytest.mark.parametrize("more, says", [
    ({"fault": {"product": 1, "kind": "alter"}}, "crc32"),
    ({"fault": {"product": 1, "kind": "no_manifest"}}, "no manifest sidecar"),
    ({"fault": {"product": 1, "kind": "partial"}}, ".partial left behind"),
    ({"fault": {"product": 1, "kind": "short"}}, "36 rows, want 37"),
], ids=lambda v: v["fault"]["kind"] if isinstance(v, dict) else None)
def test_a_fault_in_the_second_product_is_not_correct(tmp_path, more, says):
    p, out = run_two(tmp_path, **more)
    assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is False
    assert doc["failed"] == doc["attempted"] >= 1
    wrong = lines_of(out, "INCORRECT")
    assert wrong and all(w["product"] == "coarse" for w in wrong)
    assert all(w["pass_"].startswith("pass") for w in wrong)
    assert says in wrong[0]["problem"], wrong[0]
    (window,) = lines_of(out, "window")
    assert all("product coarse" in said for said in window["problems"])
    assert "compared wrong_products %d limit 0" % len(window["problems"]) \
        in p.stderr


def test_a_misdeclared_nint_is_caught_by_the_reference(tmp_path):
    """The traffic file says the second product integrates 91 spectra; the
    command makes rows of 90.  Either way 301 frames hold 3 rows, so the
    guarantees pass and it is the reference, computed at the declared
    setting, that the product disagrees with — in the warm-up, in every
    pass and in the traced pass, against rows computed ONCE (PR 39: two
    products x two channels are four children, however often they are
    consulted)."""
    t = products_traffic("two-products", [TWO[0], ("coarse", 64, 90)])
    t["products"][1]["nint"] = 91
    root = tree_with(tmp_path, workloads=[CELL], drivers=[LOCAL_DRIVER],
                     traffic={"two-products": t})
    p, out = run_harness("--workload", "bank.two", "--seed", "3200000008",
                         "--seconds", "0.05", "--trace", "1", "--rehearse",
                         root=root)
    assert p.returncode == 1, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is False
    wrong = lines_of(out, "INCORRECT")
    # no pass's second product is ever verified, so each is held against
    # the reference again; the first product is found sound every time
    assert [w["pass_"] for w in wrong][:2] == ["warmup", "pass0"]
    assert wrong[-1]["pass_"] == "traced"
    (ref,) = lines_of(out, "reference")
    assert ref["launched"] == ref["tasks"] == 4 and not ref["failed"]
    assert all(w["product"] == "coarse" and "tsamp" in w["problem"]
               for w in wrong)
    refs = lines_of(out, "check.reference")
    assert len(refs) == len(wrong)
    assert all(r["product"] == "fine" for r in refs)
    assert doc["failed"] == doc["attempted"]
