"""The spans reader on the traces recorded on the chip: the epoch clock
the device trace carries, the chain rule and the launch skew on synthetic
spans laid over the PR 22 traces, and on one real pair recorded by PR 24
(``<cell>.xplane.pb`` + ``<cell>.blit-spans.json`` of the same pass)."""

import glob
import json
import os

import pytest

from readers import spans, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# profile start (Unix ns) and length (s) as recorded in PR 22's traces
CLOCKS = {
    "bank.hires": (1790444881281867434, 13.374),
    "bank.lowres": (1790444953746653616, 1.896),
    "band4.hires": (1790445908052826600, 26.254),
}


def trace_of(cell):
    path = os.path.join(DATA, cell + ".xplane.pb")
    if not os.path.exists(path):
        pytest.skip(f"{path} was not recorded")
    return path


def stage(name, tid, t0, dur, nbytes=0):
    return {"name": name, "tid": tid, "t0": t0, "duration_s": dur,
            "attrs": {"stage": 1, "bytes": nbytes}}


@pytest.mark.parametrize("cell", sorted(CLOCKS))
def test_the_trace_carries_the_epoch_clock(cell):
    start_ns, length = CLOCKS[cell]
    start, stop = spans.clock(trace_of(cell))
    assert start == pytest.approx(start_ns / 1e9, abs=1e-6)
    assert stop - start == pytest.approx(length, abs=1e-3)
    chips = spans.device(trace_of(cell), start)
    assert len(chips) == (4 if cell == "band4.hires" else 1)
    for chip in chips:
        s, e = chip["busy"]
        assert start <= s.min() and e.max() <= stop
        assert all(start <= r[0] < r[1] <= stop for r in chip["runs"])


def test_no_clock_no_attribution(tmp_path, capsys):
    """A trace without the Task Environment stats (here: no trace at all
    that ProfileData can find them in) gives ``None`` and says why."""
    from jax.profiler import ProfileData

    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    assert [p.name for p in ProfileData.from_file(str(empty)).planes] == []
    assert spans.clock(str(empty)) is None
    assert spans.attribute(str(empty), [stage("dispatch", 1, 0.0, 1.0)]) \
        is None
    assert "cannot be put on the epoch clock" in capsys.readouterr().out


def test_no_stage_span_no_attribution(capsys):
    path = trace_of("bank.lowres")
    start, _ = spans.clock(path)
    old = [{"name": "reduce.to_file", "tid": 1, "t0": start + 0.1,
            "duration_s": 1.0}]  # a parent-commit program: no stage=1 span
    assert spans.attribute(path, old) is None
    assert "no stage span" in capsys.readouterr().out


def test_launch_skew_of_the_recorded_band_pass():
    path = trace_of("band4.hires")
    start, _ = spans.clock(path)
    skews = sorted(late - early for early, late in
                   spans.launch_skew(spans.device(path, start)).values())
    # chips 0 and 1 start the first run at 7.3602 s, chip 3 at 15.0203 s;
    # the second at 15.0897 s and 21.8184 s.
    assert skews == pytest.approx([6.7287, 7.6601], abs=1e-3)
    ev = {"trace": {"window_s": 24.9}, "trace_path": path,
          "traced_raw_bytes": 7.516e9}
    got = spans.read({"value": "launch_skew_s_per_GB"}, ev)
    assert got == pytest.approx((6.7287 + 7.6601) / 7.516, rel=1e-3)
    one = dict(ev, trace_path=trace_of("bank.lowres"))
    assert spans.read({"value": "launch_skew_s_per_GB"}, one) is None


def band_spans(start):
    """A serial scan loop (one thread) laid over the recorded band pass:
    window 0 read and fed 0.5-7.3 s, dispatched, window 1 read and fed to
    15.05 s while run 6 waits for chip 3; then the flushes."""
    t = 7
    sp = [stage("read", t, start + 0.5, 6.8),
          stage("feed.read", t, start + 0.5, 5.0),
          stage("feed.put", t, start + 5.5, 1.8),
          stage("dispatch", t, start + 7.3, 0.05),
          stage("read", t, start + 7.35, 7.7),
          stage("feed.read", t, start + 7.35, 5.0),
          stage("feed.put", t, start + 12.35, 2.7),
          stage("dispatch", t, start + 15.05, 0.03),
          stage("device", t, start + 15.08, 0.02),
          stage("readback", t, start + 15.1, 0.8),
          stage("write", t, start + 15.9, 2.4),
          stage("device", t, start + 18.3, 3.6),
          stage("readback", t, start + 21.9, 0.8),
          stage("write", t, start + 22.7, 2.2)]
    return sp + [{"name": "scan.reduce", "tid": t, "t0": start + 0.4,
                  "duration_s": 24.6}]


def test_band_idle_is_split_by_launch_skew():
    path = trace_of("band4.hires")
    start, _ = spans.clock(path)
    got = spans.attribute(path, band_spans(start), window_s=24.9)
    causes = got["idle_by_cause"]
    # Run 6 on chip 0 spans 7.3602-15.0897 s and chip 3 launches at
    # 15.0203 s: until then the idle is launch skew under what the host
    # did, after that it is the program's own.
    assert causes["launch skew>feed.read"] == pytest.approx(
        12.35 - 7.3602, abs=0.15)
    assert causes["launch skew>feed.put"] == pytest.approx(
        15.0203 - 12.35, abs=0.15)
    assert causes["inside jit_band_reduce"] < 0.2
    assert causes["feed.read"] == pytest.approx(5.0, abs=0.02)
    assert got["launch_skew_s"] == {"6": pytest.approx(7.6601, abs=1e-3),
                                    "7": pytest.approx(6.7287, abs=1e-3)}
    # Everything adds up to what xplane calls idle on the first chip.
    red = xplane.reduce_trace(path, 24.9)
    assert got["idle_s"] == pytest.approx(
        sum(red["idle_gaps_s"].values()), rel=1e-3)
    assert got["idle_s"] == pytest.approx(
        24.9 - red["busy_s_by_chip"][0], rel=1e-3)
    # 0.4 s before the root span and what the window has beyond it.
    assert got["beyond_spans_s"] == pytest.approx(24.9 - 24.6, abs=1e-6)
    assert got["unnamed_s"] >= got["beyond_spans_s"]
    # The device waits end after the runs they wait on: no clock skew.
    assert got["clock_skew_ms"] == 0.0 and got["causality_pairs"] == 4


def bank_spans(start):
    """The reduce pump's four threads over the recorded hires pass (two
    dispatches of five programs: 4.10 and 9.34 s)."""
    main, ingest, readback, writer = 1, 2, 3, 4
    return [
        {"name": "reduce.to_file", "tid": main, "t0": start + 0.3,
         "duration_s": 12.6},
        stage("stream", main, start + 0.4, 9.5),
        stage("wait.chunk", main, start + 0.4, 3.6),
        stage("ingest", ingest, start + 0.45, 3.5),
        stage("dispatch", main, start + 4.0, 0.4),
        stage("device", readback, start + 4.4, 0.1),
        stage("readback", readback, start + 4.5, 1.2),
        stage("wait.chunk", main, start + 4.4, 4.8),
        stage("ingest", ingest, start + 4.0, 2.2),
        stage("wait.ingest_slot", ingest, start + 6.2, 1.0),
        stage("dispatch", main, start + 9.2, 0.4),
        stage("wait.out_drain", main, start + 9.6, 1.4),
        stage("device", readback, start + 9.6, 0.1),
        stage("readback", readback, start + 9.7, 1.3),
        stage("wait.sink_flush", main, start + 11.0, 1.8),
        stage("write", writer, start + 10.0, 2.8),
    ]


def test_the_chain_follows_a_wait_to_the_thread_it_waits_on():
    path = trace_of("bank.hires")
    start, _ = spans.clock(path)
    got = spans.attribute(path, bank_spans(start), window_s=12.85)
    causes = got["idle_by_cause"]
    # 0.45-3.95 s: the dispatcher waits for a chunk, the reader reads.
    assert causes["wait.chunk>ingest"] == pytest.approx(3.5 + 1.8, abs=0.01)
    # 6.2-7.2 s: ... and the reader itself waits for a slot the readback
    # thread has not released; nothing is open there then.
    assert causes["wait.chunk>wait.ingest_slot>unnamed"] == \
        pytest.approx(1.0, abs=0.01)
    assert causes["wait.chunk>unnamed"] > 2.0      # 7.2-9.2 s, 0.4-0.45 s
    assert causes["dispatch"] == pytest.approx(0.4 + 0.4 - 0.32, abs=0.05)
    assert causes["wait.out_drain>readback"] == pytest.approx(1.3, abs=0.01)
    assert causes["wait.sink_flush>write"] == pytest.approx(1.8, abs=0.01)
    assert not any(k.startswith("stream") for k in causes)
    assert got["idle_s"] == pytest.approx(
        sum(xplane.reduce_trace(path, 12.85)["idle_gaps_s"].values()),
        rel=1e-3)
    # Programs start after their dispatch (4.0 -> 4.10 s, 9.2 -> 9.34 s).
    assert got["clock_skew_ms"] == 0.0 and got["causality_pairs"] == 4


def test_a_program_ahead_of_its_dispatch_is_clock_skew():
    path = trace_of("bank.hires")
    start, _ = spans.clock(path)
    late = [dict(sp, t0=sp["t0"] + 0.25) for sp in bank_spans(start)]
    got = spans.attribute(path, late, window_s=12.85)
    # the first program ran at 4.102 s, its dispatch now starts at 4.25 s
    assert got["clock_skew_ms"] == pytest.approx(148, abs=2)


def test_the_metrics_read_the_attribution(capsys):
    path = trace_of("bank.hires")
    start, _ = spans.clock(path)
    ev = {"trace": xplane.reduce_trace(path, 12.85), "trace_path": path,
          "spans": bank_spans(start), "traced_raw_bytes": 5.1e9}
    named = spans.read({"value": "named_share"}, ev)
    got = ev["spans_attribution"]
    assert named == pytest.approx(
        100 * (1 - got["unnamed_s"] / got["idle_s"]))
    assert 50 < named < 80
    read = spans.read({"value": "idle_s_per_GB",
                       "ends_in": ["ingest", "state", "feed.read"]}, ev)
    assert read == pytest.approx(5.3 / 5.1, abs=0.01)
    out = spans.read({"value": "idle_s_per_GB",
                      "ends_in": ["readback", "write", "flush"]}, ev)
    assert out == pytest.approx((1.3 + 1.8) / 5.1, abs=0.01)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[spans] ")]
    assert len(lines) == 1    # one attribution, three metrics
    shown = json.loads(lines[0][len("[spans] "):])
    assert shown["idle_s"] == pytest.approx(shown["xplane_idle_s"], rel=1e-3)
    assert shown["idle_by_cause"][0][0] == "wait.chunk>ingest"
    # A CPU rehearsal has no device trace: every metric is left out.
    assert spans.read({"value": "named_share"}, {"trace": None,
                                                 "traced_raw_bytes": 1}) \
        is None


def harness_scratch(tmp_path, cell, product):
    """The harness's layout: the product under its scratch directory and
    the traced pass's profile in ``trace/`` beside it."""
    import shutil

    prof = tmp_path / "trace" / "plugins" / "profile" / "2026_09_27"
    prof.mkdir(parents=True)
    shutil.copy(trace_of(cell), prof / "host.xplane.pb")
    out = tmp_path / product
    out.parent.mkdir(exist_ok=True)
    return str(prof / "host.xplane.pb"), str(out)


@pytest.mark.parametrize("cell, product, root", [
    ("bank.lowres", "traced.rawspec.fil", "reduce.to_file"),
    ("band4.hires", "traced/band0.fil", "scan.reduce"),
])
def test_the_trace_is_found_from_the_pass_own_root_span(
        tmp_path, capsys, cell, product, root):
    """No directory is searched by name or age: the path comes from the
    last root span's ``out``, and a trace whose profile does not hold
    that span (another process's, an earlier run's) is refused."""
    path, out = harness_scratch(tmp_path, cell, product)
    start, stop = spans.clock(path)

    def pass_at(t0, out=out):
        return {"name": root, "tid": 1, "t0": t0, "duration_s": 1.0,
                "attrs": {"out": out}}

    warm = pass_at(start - 30.0, out.replace("traced", "warmup"))
    assert spans.find_trace({}, [warm, pass_at(start + 0.1)]) == path
    assert spans.find_trace({"trace_path": "given"}, []) == "given"
    # the last pass ran after this profile stopped: not its trace
    assert spans.find_trace({}, [warm, pass_at(stop + 5.0)]) is None
    assert "whose profile holds" in capsys.readouterr().out
    # a product somewhere else: nothing beside it, and nothing searched
    assert spans.find_trace({}, [pass_at(
        start + 0.1, str(tmp_path / "other" / "deep" / "x.fil"))]) is None
    # a parent-commit program: no root span names a product
    old = {"name": root, "tid": 1, "t0": start + 0.1, "duration_s": 1.0}
    assert spans.find_trace({}, [old]) is None
    assert "no root span" in capsys.readouterr().out
    ev = {"trace": {"window_s": 1.0}, "traced_raw_bytes": 1e9,
          "spans": [old]}
    assert spans.read({"value": "launch_skew_s_per_GB"}, ev) is None
    assert spans.read({"value": "named_share"}, ev) is None


def chrome_spans(path):
    """``blit-spans.json`` (Chrome trace events, ts in epoch us) back to
    span dicts."""
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    return [{"name": e["name"], "tid": e["tid"], "t0": e["ts"] / 1e6,
             "duration_s": e["dur"] / 1e6,
             "attrs": {k: v for k, v in e["args"].items()
                       if k not in ("trace", "span", "parent")}}
            for e in events if e["ph"] == "X"]


@pytest.mark.parametrize("pair", sorted(
    glob.glob(os.path.join(DATA, "*.blit-spans.json"))) or [None])
def test_a_recorded_pair_of_trace_and_spans(pair):
    """One traced pass of PR 24 on the chip: its device trace and the
    spans of the same pass, as the operator's ``--trace-logdir`` writes
    them."""
    if pair is None:
        pytest.skip("no <cell>.blit-spans.json was recorded")
    with open(pair.replace(".blit-spans.json", ".facts.json")) as f:
        facts = json.load(f)
    got = spans.attribute(pair.replace(".blit-spans.json", ".xplane.pb"),
                          chrome_spans(pair), facts["window_s"])
    assert got["clock_skew_ms"] <= 5.0 and got["causality_pairs"] >= 2
    # as the chip run printed them ([spans] line); the spans went through
    # JSON in microseconds since
    assert got["idle_s"] == pytest.approx(facts["idle_s"], rel=1e-5)
    assert 100 * (1 - got["unnamed_s"] / got["idle_s"]) == pytest.approx(
        facts["idle_named_share"], abs=1e-2)
    assert got["stage_spans"] == facts["stage_spans"]
    top = max(got["idle_by_cause"], key=got["idle_by_cause"].get)
    assert top == facts["top_cause"]
