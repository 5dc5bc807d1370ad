"""The parts reader (ISSUE 36) on the pair PR 24 recorded on the chip
(``bank.lowres.pr24``: the device trace and the spans of one pass), with
synthetic ``part=1`` spans laid inside its ``dispatch`` and ``write``
spans: a program from before the parts has none of its own."""

import json
import os

import pytest
from conftest import BENCH

from readers import parts, spans, xplane
from test_spans import DATA, chrome_spans

PAIR = os.path.join(DATA, "bank.lowres.pr24")
LM = os.path.join(BENCH, "layer_metrics")
READINGS = ["open_s_per_GB", "close_s_per_GB", "coeffs_s_per_GB",
            "put_hold_s_per_GB", "write_digest_s_per_GB", "call_s_per_GB",
            "idle_ends_s_per_GB", "idle_coeffs_s_per_GB",
            "idle_put_hold_s_per_GB", "idle_digest_s_per_GB"]
TWINS = ["write_digest_s_per_GB", "idle_digest_s_per_GB", "coeffs_s_per_GB",
         "put_hold_s_per_GB", "idle_ends_s_per_GB"]
GB = 5.0


def part(name, inside, lo, hi, **attrs):
    """A part over ``[lo, hi]`` (fractions) of the span ``inside``."""
    return {"name": name, "tid": inside["tid"],
            "t0": inside["t0"] + lo * inside["duration_s"],
            "duration_s": (hi - lo) * inside["duration_s"],
            "attrs": {"part": 1, "bytes": 0, **attrs}}


def recorded():
    if not os.path.exists(PAIR + ".xplane.pb"):
        pytest.skip(f"{PAIR}.xplane.pb was not recorded")
    with open(PAIR + ".facts.json") as f:
        return chrome_spans(PAIR + ".blit-spans.json"), json.load(f)


def with_parts(sp, call_at=(0.6, 0.8)):
    """The recorded spans + what ISSUE 36's program would have recorded:
    the bank in the first dispatch, then per dispatch a put and a call
    per channel group (two), and a digest in the middle of every write."""
    by = {n: sorted((s for s in sp if s["name"] == n),
                    key=lambda s: s["t0"]) for n in ("dispatch", "write")}
    out = list(sp)
    out.append(part("coeffs", by["dispatch"][0], 0.0, 0.4, nfft=1024))
    for d in by["dispatch"]:
        out += [part("link.put", d, 0.4, 0.5), part("link.put", d, 0.7, 0.75)]
        lo, hi = call_at
        out += [part(parts.CALL, d, 0.5, 0.55, programs=["jit_channelize"]),
                part(parts.CALL, d, lo, hi, programs=["jit_channelize"])]
    out += [part("write.digest", w, 0.25, 0.75) for w in by["write"]]
    return out


def evidence(sp, facts):
    path = PAIR + ".xplane.pb"
    return {"trace": xplane.reduce_trace(path, facts["window_s"]),
            "trace_path": path, "spans": sp, "traced_raw_bytes": GB * 1e9}


def tail_seconds(causes, tail):
    return sum(s for c, s in causes.items() if c.rsplit(">", 1)[-1] == tail)


def test_the_promoted_attribution_conserves_each_stage(capsys):
    sp, facts = recorded()
    ev = evidence(with_parts(sp), facts)
    got = parts.attribution(ev)
    base = ev["spans_attribution"]["idle_by_cause"]
    after = got["idle_by_cause"]
    # The stages read what they read without the parts ([spans] is the
    # recorded pass's, to the second) ...
    assert sum(base.values()) == pytest.approx(facts["idle_s"], rel=1e-5)
    assert not any(parts.SEP in c for c in base)
    # ... and what [parts] names by a stage and by its parts is that.
    assert got["conservation_ms"] < 1e-6
    for stage, mine in (("dispatch", ["coeffs", "link.put", parts.CALL]),
                        ("write", ["write.digest"])):
        kept = tail_seconds(after, stage) + sum(
            tail_seconds(after, stage + parts.SEP + p) for p in mine)
        assert kept == pytest.approx(tail_seconds(base, stage), abs=1e-9)
    assert sum(after.values()) == pytest.approx(sum(base.values()), abs=1e-9)
    # The bank takes 40 % of the first dispatch, whose idle seconds it
    # shares; a put a tenth and a twentieth of every one.
    idle = got["idle_by_part"]
    assert 0 < idle["coeffs"] < tail_seconds(base, "dispatch")
    assert 0 < idle["link.put"] < tail_seconds(base, "dispatch")
    assert 0 < idle["write.digest"] <= tail_seconds(base, "write")
    assert got["part_spans"] == 1 + 6 * 4 + 6
    # The metrics read it, once.
    read = parts.read({"value": "idle_s_per_GB", "ends_in": ["coeffs"]}, ev)
    assert read == pytest.approx(idle["coeffs"] / GB)
    both = parts.read({"value": "idle_s_per_GB",
                       "ends_in": ["coeffs", "link.put"]}, ev)
    assert both == pytest.approx((idle["coeffs"] + idle["link.put"]) / GB)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[parts] ")]
    assert len(lines) == 1
    shown = json.loads(lines[0][len("[parts] "):])
    assert all(parts.SEP in cause for cause, _ in shown["idle_by_cause"])
    with pytest.raises(ValueError, match="unknown value"):
        parts.read({"value": "named_share"}, ev)
    # A part that outlasts its stage takes idle seconds of that stage to
    # the next one's name: the check says so.
    first = min((s for s in sp if s["name"] == "dispatch"),
                key=lambda s: s["t0"])
    astray = with_parts(sp) + [part("link.put", first, 0.5, 3.0)]
    assert parts.attribution(evidence(astray, facts))[
        "conservation_ms"] > 1.0


def test_no_part_reads_none_not_zero():
    sp, facts = recorded()
    ev = evidence(sp, facts)  # the recorded program: stages, no parts
    for name in ("coeffs", "link.put", "write.digest"):
        assert parts.read({"value": "idle_s_per_GB", "ends_in": [name]},
                          ev) is None
    assert ev["parts_attribution"] is None
    # ... while the stages still read, and a rehearsal has no trace at all.
    assert spans.read({"value": "named_share"}, ev) == pytest.approx(
        facts["idle_named_share"], abs=1e-2)
    assert parts.read({"value": "idle_s_per_GB", "ends_in": ["coeffs"]},
                      {"trace": None, "traced_raw_bytes": 1}) is None
    # A part that no idle instant fell in reads 0.0: it was looked for.
    ev = evidence(with_parts(sp), facts)
    assert parts.read({"value": "idle_s_per_GB", "ends_in": ["open"]},
                      ev) == 0.0


def test_call_skew_pairs_each_run_with_the_call_that_names_it():
    sp, facts = recorded()
    got = parts.attribution(evidence(with_parts(sp), facts))
    # Two programs a dispatch, six dispatches: every run after its call.
    assert (got["call_skew_ms"], got["call_pairs"],
            got["call_unpaired"]) == (0.0, 12, 0)
    # The second call of every dispatch placed 0.2 s late: its program
    # (0.05-0.17 s after the dispatch) ran before it.
    start, _ = spans.clock(PAIR + ".xplane.pb")
    late = [dict(s, t0=s["t0"] + 0.2) if s["name"] == parts.CALL
            and s["duration_s"] > 0.1 * 0.02 else s
            for s in with_parts(sp)]
    got = parts.attribution(evidence(late, facts))
    assert got["call_pairs"] == 12 and got["call_unpaired"] == 0
    assert 20 < got["call_skew_ms"] < 200
    # A call that names a program the chip never ran is left unpaired.
    odd = with_parts(sp) + [dict(part(
        parts.CALL, sp[0], 0, 1, programs=["jit_nowhere"]), t0=start + 0.5)]
    assert parts.attribution(evidence(odd, facts))["call_unpaired"] == 1


def spec(name):
    with open(os.path.join(LM, name + ".json")) as f:
        return json.load(f)


def test_the_new_files_load_and_no_two_read_the_same_thing(bench_json):
    entries = {m["name"]: m for m in bench_json["per_layer"]}
    seen = {}
    for name in sorted(f[:-5] for f in os.listdir(LM)):
        s = spec(name)
        if "same_as" not in s:
            key = (s["reader"], json.dumps(s.get("args", {}),
                                           sort_keys=True))
            assert key not in seen, (name, seen[key])
            seen[key] = name
    for name in READINGS:
        s, e = spec(name), entries[name]
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           s["reader"] + ".py"))
        assert e["moves"] == "reduce_rate"
        for k in ("unit", "layer", "better", "source", "moves"):
            assert s[k] == e[k], (name, k)
        assert ("workloads" in e) == (name == "call_s_per_GB")
    assert entries["call_s_per_GB"]["workloads"] == [
        "bank.lowres", "rawspec.hires51", "rawspec3.hires51"]
    for base in TWINS:
        s, e = spec(base + ".first"), entries[base + ".first"]
        assert s["same_as"] == base and "reader" not in s
        assert e["moves"] == s["moves"] == "first_product_s"
        assert e["workloads"] == ["bank.hires"]
        for k in ("unit", "layer", "better", "source"):
            assert s[k] == e[k] == entries[base][k]
    # ... in one block (PR 40's followed it), in the layers PERF.md
    # section 3 has.
    names = [m["name"] for m in bench_json["per_layer"]]
    at = names.index(READINGS[0])
    assert names[at:at + 15] == READINGS + [b + ".first" for b in TWINS]
    assert {entries[n]["layer"] for n in READINGS} == {
        "whole host path", "pack and H2D", "D2H and write"}
    # The timeline readings read the rows the program records.
    from readers import stage_bytes, timeline

    ev = {"traced_raw_bytes": 2e9, "stages": {
        "open": {"calls": 1, "seconds": 0.5, "bytes": 0, "byte_free": True},
        "link.put": {"calls": 17, "seconds": 0.25, "bytes": 2000000000}}}
    assert timeline.read(spec("open_s_per_GB")["args"], ev) == 0.25
    assert timeline.read(spec("put_hold_s_per_GB")["args"], ev) == 0.125
    assert stage_bytes.read(spec("h2d_MB_per_GB")["args"], ev) == 1000.0
    assert timeline.read(spec("coeffs_s_per_GB")["args"], ev) is None


# What a CPU traced run reports of these readings (the five rows of the
# stage table; the three `idle_*` of the parts reader and `idle_ends` need
# the chip's trace) is pinned with the accepted cells' own traced runs:
# `conftest.EVERY_PASS` and `test_rehearse.py`.
