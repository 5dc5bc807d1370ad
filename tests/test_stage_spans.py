"""Every pump stage and every pump wait is a span (ISSUE 24): the stage
table's seconds, laid on the epoch clock by the process tracer, with the
thread that spent them and the pass's trace id.

No pytest-timeout here, so every test that waits on a thread runs under
:func:`within` — its own deadline, a failure instead of a hung suite."""

import collections
import json
import os
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit import observability  # noqa: E402
from blit.observability import Timeline, Tracer  # noqa: E402
from blit.outplane import AsyncSink, OutputRotation  # noqa: E402
from blit.pipeline import BufferRotation, RawReducer  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

WAITS = ("wait.chunk", "wait.ingest_slot", "wait.out_slot", "wait.out_drain",
         "wait.sink", "wait.sink_flush", "wait.slab")
NFFT, NINT = 64, 2


def within(seconds, fn):
    """Run ``fn`` on a thread of its own; fail if it outlives ``seconds``."""
    box = {}

    def run():
        try:
            box["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    assert not t.is_alive(), f"still running after {seconds} s"
    if "error" in box:
        raise box["error"]
    return box.get("value")


def toy_raw(tmp_path, nblocks=8):
    raw = str(tmp_path / "toy.0000.raw")
    synth_raw(raw, nblocks=nblocks, obsnchan=4, ntime_per_block=4096)
    return raw


def toy_pass(tmp_path, **kw):
    """One ``reduce_to_file`` pass -> (stage table, its spans)."""
    observability.tracer().reset()
    red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=32, **kw)
    within(120, lambda: red.reduce_to_file(toy_raw(tmp_path),
                                           str(tmp_path / "toy.fil")))
    return red.timeline.report(), observability.tracer().span_dicts()


def stage_spans(spans):
    return [s for s in spans if (s.get("attrs") or {}).get("stage") == 1]


def part_spans(spans):
    return [s for s in spans if (s.get("attrs") or {}).get("part") == 1]


class SlowWriter:
    """The slab-writer contract, ``delay`` seconds per append."""

    path = "/fake/slow.fil"

    def __init__(self, delay):
        self.delay, self.nsamps = delay, 0

    def append(self, slab):
        time.sleep(self.delay)
        self.nsamps += slab.shape[0]

    def close(self):
        pass

    def abort(self):
        pass


class TestToyPass:
    def test_one_trace_three_threads_every_stage_parented(self, tmp_path):
        _, spans = toy_pass(tmp_path)
        st = stage_spans(spans)
        assert len({s["trace"] for s in spans}) == 1
        assert len({s["tid"] for s in st}) >= 3
        ids = {s["span"] for s in spans}
        assert all(s["parent"] in ids for s in st)
        assert all(isinstance(s["attrs"]["bytes"], int) for s in st)

    def test_span_durations_sum_to_the_stage_table(self, tmp_path):
        table, spans = toy_pass(tmp_path)
        total = collections.Counter()
        # A row is its stage spans or its part spans (ISSUE 36).
        for s in stage_spans(spans) + part_spans(spans):
            total[s["name"]] += s["duration_s"]
        rows = {k: v for k, v in table.items()
                if isinstance(v, dict) and v.get("seconds", 0) > 0}
        # `stream` wraps the pump and is topped up by hand with the
        # readback and write tails (pipeline.py), so its one span is
        # shorter than its row; every other row is its spans.
        rows.pop("stream")
        assert rows
        for name, row in rows.items():
            assert total[name] == pytest.approx(row["seconds"], rel=0.01,
                                                abs=2e-5), name

    def test_every_wait_row_is_in_the_table(self, tmp_path):
        table, _ = toy_pass(tmp_path)
        for name in WAITS:
            assert table[name]["byte_free"] is True
            assert table[name]["bytes"] == 0

    def test_a_mark_is_one_span_counted_as_many(self):
        # Timeline.mark: a counted instant (ISSUE 26's integrate.carry /
        # integrate.emit) — one zero-length stage span on the tracer's
        # clock, `calls` events in the row, bytes summed once.
        observability.tracer().reset()
        tl = Timeline()
        tl.mark("integrate.emit", nbytes=4096, calls=3)
        tl.mark("integrate.emit", nbytes=1024)
        row = tl.report()["integrate.emit"]
        assert (row["calls"], row["bytes"]) == (4, 5120)
        spans = [s for s in stage_spans(observability.tracer().span_dicts())
                 if s["name"] == "integrate.emit"]
        assert [s["attrs"]["bytes"] for s in spans] == [4096, 1024]
        assert all(s["duration_s"] < 0.01 for s in spans)

    def test_spans_off_same_table_no_span(self, tmp_path, monkeypatch):
        on, _ = toy_pass(tmp_path)
        monkeypatch.setattr(observability, "_TRACER", Tracer(enabled=False))
        off, spans = toy_pass(tmp_path)
        assert spans == []
        assert sorted(off) == sorted(on)
        for name, row in on.items():
            if name in ("gauges", "hists"):
                continue
            assert off[name]["bytes"] == row["bytes"], name
            # How often a wait blocks varies, and what the staging pool
            # lends or allocates depends on what earlier passes left in it.
            if not name.startswith(("wait.", "staging.")):
                assert off[name]["calls"] == row["calls"], name

    def test_blit_reduce_prints_its_stage_table(self, tmp_path, capsys):
        from blit.__main__ import main

        raw = toy_raw(tmp_path)
        rc = within(120, lambda: main(
            ["reduce", raw, "-o", str(tmp_path / "cli.fil"),
             "--nfft", str(NFFT), "--nint", str(NINT)]))
        assert rc == 0
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        for name in WAITS + ("ingest", "dispatch", "device", "write"):
            assert "seconds" in line["stages"][name], name


class TestBackPressure:
    def test_slow_writer_shows_as_wait_sink(self):
        tl = Timeline()
        sink = AsyncSink(SlowWriter(0.05), depth=1, timeline=tl)

        def run():
            for _ in range(4):
                sink.append(np.zeros((1, 1, 4), np.float32))
            sink.close()

        try:
            within(30, run)
        finally:
            sink.abort()
        row = tl.report()["wait.sink"]
        assert row["calls"] >= 1 and row["seconds"] >= 0.05
        assert tl.stages["wait.sink_flush"].seconds > 0

    def test_slow_writer_backs_the_pump_up(self, tmp_path):
        """Through the whole pump the slow write reaches the dispatcher
        either as a full sink queue or (CPU backends, where the readback
        ring recycles slabs the writer still holds) as ``wait.slab``
        behind ``wait.out_slot``."""
        from blit.io.guppi import open_raw

        red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=32)
        raw = open_raw(toy_raw(tmp_path))
        within(120, lambda: red._pump(raw, SlowWriter(0.05)))
        table = red.timeline.report()
        assert table["wait.sink"]["seconds"] \
            + table["wait.slab"]["seconds"] >= 0.05

    def test_slow_reader_shows_as_wait_chunk(self, tmp_path):
        from blit.io.guppi import open_raw

        red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=32)
        raw = open_raw(toy_raw(tmp_path))
        fast = raw.read_block_into

        def slow(*a, **kw):
            time.sleep(0.05)
            return fast(*a, **kw)

        raw.read_block_into = slow
        within(120, lambda: red._pump(raw, SlowWriter(0.0)))
        table = red.timeline.report()
        assert table["wait.chunk"]["seconds"] >= 0.05 * 8 * 0.5
        assert table["wait.sink"]["calls"] == 0

    def test_sink_with_room_never_blocks(self):
        tl = Timeline()
        sink = AsyncSink(SlowWriter(0.0), depth=8, timeline=tl)
        try:
            for _ in range(4):
                sink.append(np.zeros((1, 1, 4), np.float32))
            time.sleep(0.1)  # the writer works the queue off
            within(30, sink.flush)
        finally:
            sink.abort()
        assert tl.stages["wait.sink"].calls == 0
        assert tl.stages["wait.sink"].seconds == 0.0

    def test_consumer_behind_the_producer_never_blocks(self):
        tl = Timeline()

        def fill(rot):
            for i in range(3):
                rot.emit(rot.acquire(), i)

        rot = BufferRotation(4, fill, timeline=tl, name="blit-feed-test")

        def consume():
            got = []
            it = rot.slots()
            slot, payload = next(it)   # may block: the producer just started
            got.append(payload)
            first = tl.stages["wait.chunk"].calls
            time.sleep(0.2)            # the producer runs ahead
            for slot, payload in it:
                got.append(payload)
            return got, first

        got, first = within(30, consume)
        assert got == [0, 1, 2]
        assert tl.stages["wait.chunk"].calls == first
        # The producer never met a full rotation either.
        assert tl.stages["wait.ingest_slot"].calls == 0

    def test_full_rotation_shows_as_wait_ingest_slot(self):
        tl = Timeline()

        def fill(rot):
            for i in range(4):
                slot = rot.acquire()
                if slot is None:
                    return
                rot.emit(slot, i)

        rot = BufferRotation(2, fill, timeline=tl, name="blit-feed-test")

        def consume():
            for slot, _ in rot.slots():
                time.sleep(0.05)
                rot.release(slot)

        within(30, consume)
        assert tl.stages["wait.ingest_slot"].seconds >= 0.04

    def test_out_slot_and_drain_wait_on_the_readback(self):
        import jax.numpy as jnp

        tl = Timeline()
        rot = OutputRotation(depth=1, timeline=tl, name="blit-readback-test")

        def run():
            slabs = []
            for i in range(3):
                slabs += rot.put(jnp.full((4,), float(i)))
            slabs += list(rot.drain())
            return slabs

        try:
            slabs = within(60, run)
        finally:
            rot.close()
        assert len(slabs) == 3
        # depth 1: every put waits for its own fetch.
        assert tl.stages["wait.out_slot"].calls == 3
        assert tl.stages["wait.out_slot"].seconds > 0


class TestSpansAcrossAYield:
    def test_a_generator_span_pops_its_own_entry(self):
        tr = Tracer(enabled=True)

        def gen():
            with tr.span("held"):
                yield 1
                yield 2

        with tr.span("root") as root:
            g = gen()
            next(g)
            with tr.span("consumer") as consumer:
                # `held` is still on this thread's stack, under `consumer`.
                g.close()
                assert tr.context()["span"] == consumer.span_id
                with tr.span("child") as child:
                    pass
            assert tr.context()["span"] == root.span_id
        assert tr.context() is None
        by = {s.name: s for s in tr.spans()}
        assert by["child"].parent_id == by["consumer"].span_id
        assert by["held"].parent_id == by["root"].span_id

    def test_stream_consumer_spans_keep_their_parents(self, tmp_path):
        """`reduce.stream` and the `stream` stage live inside generators
        on the consumer's thread; what the consumer opens between slabs
        must close onto its own parent."""
        observability.tracer().reset()
        red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=32)
        from blit.io.guppi import open_raw

        raw = open_raw(toy_raw(tmp_path))

        def run():
            tr = observability.tracer()
            with tr.span("caller") as caller:
                it = red.stream(raw)
                with tr.span("first"):
                    next(it)  # pushes reduce.stream and `stream` above it
                # `first` took its own entry off, not the generator's.
                held = tr.context()["span"]
                it.close()
                after = tr.context()["span"]
            return caller.span_id, held, after, tr.context()

        caller, held, after, outside = within(120, run)
        by = {s["name"]: s for s in observability.tracer().span_dicts()}
        assert held == by["stream"]["span"]
        assert after == caller
        assert outside is None
        assert by["first"]["parent"] == caller
        assert by["reduce.stream"]["parent"] == by["first"]["span"]

    def test_a_stage_yields_its_span(self):
        """The serving door adds its attrs (``fp``, ``out``) to
        the stage's own span instead of wrapping it in a second one; a
        stage nested in one of its own name still gets its interval."""
        observability.tracer().reset()
        tl = Timeline()
        with tl.stage("serve.reduce", byte_free=True) as sp:
            sp.attrs = dict(sp.attrs, fp="abc")
            with tl.stage("serve.reduce", nbytes=8) as inner:
                assert inner.parent_id == sp.span_id
        got = [s for s in observability.tracer().span_dicts()
               if s["name"] == "serve.reduce"]
        assert len(got) == tl.stages["serve.reduce"].calls == 2
        assert got[1]["attrs"] == {"bytes": 0, "stage": 1, "fp": "abc"}
        assert sum(s["duration_s"] for s in got) == pytest.approx(
            tl.stages["serve.reduce"].seconds)

    @pytest.mark.parametrize("spans_on", [True, False])
    def test_a_stage_is_one_entry_in_the_flight_ring(self, spans_on,
                                                     monkeypatch):
        """With its span recorded a stage is that span's event (carrying
        ``bytes``), not a second ``kind: stage`` entry: an incident dump
        holds as many stages as before.  ``BLIT_SPANS=0`` keeps the old
        end-stamped entry."""
        tr, rec = observability.tracer(), observability.flight_recorder()
        monkeypatch.setattr(tr, "enabled", spans_on)
        rec.clear()
        with Timeline().stage("ingest", nbytes=64):
            pass
        got = [e for e in rec.events() if e["name"] == "ingest"]
        assert [e["kind"] for e in got] == ["span" if spans_on else "stage"]
        assert got[0]["bytes"] == 64


class TestScanWindows:
    def test_each_window_has_a_read_and_a_put_per_bank(self, tmp_path):
        from blit.parallel.scan import reduce_scan_mesh_to_files

        # One band of four contiguous banks: four virtual devices.
        bank_bw = -187.5 / 4
        paths = [[]]
        for k in range(4):
            paths[0].append(str(tmp_path / f"blc0{k}.raw"))
            synth_raw(paths[0][k], nblocks=4, obsnchan=2,
                      ntime_per_block=1024, seed=k, obsbw=bank_bw,
                      obsfreq=8000.0 + (k + 0.5) * bank_bw)
        os.makedirs(tmp_path / "out")
        observability.tracer().reset()
        tl = Timeline()
        within(300, lambda: reduce_scan_mesh_to_files(
            paths, out_dir=str(tmp_path / "out"), nfft=NFFT, nint=NINT,
            window_frames=16, timeline=tl))
        spans = observability.tracer().span_dicts()
        by_id = {s["span"]: s for s in spans}
        roots = [s for s in spans if s["name"] == "scan.reduce"]
        windows = [s for s in spans if s["name"] == "scan.window"]
        assert len(roots) == 1 and len(windows) >= 2
        assert len({s["trace"] for s in spans}) == 1
        assert all(w["parent"] == roots[0]["span"] for w in windows)
        assert len({w["attrs"]["f0"] for w in windows}) == len(windows)
        # The feed thread's `ingest`, one a window (attr `f0`, as the
        # window's own), holds the window's reads; the window's puts stand
        # on the loop's thread, beside `dispatch`.
        ingests = {s["attrs"]["f0"]: s for s in spans
                   if s["name"] == "ingest"}
        assert len(ingests) == len(windows)
        loop = {s["tid"] for s in spans if s["name"] == "dispatch"}
        assert len(loop) == 1
        assert {s["tid"] for s in ingests.values()}.isdisjoint(loop)
        for w in windows:
            read = ingests[w["attrs"]["f0"]]
            assert read["parent"] == roots[0]["span"]
            # A window reads and puts its NEW samples; the stream's head
            # is a read and a put of its own per bank, in its first window.
            n = 8 if w["attrs"]["f0"] == 0 else 4
            assert [s["name"] for s in spans
                    if s["parent"] == read["span"]] == ["feed.read"] * n
            kids = [s for s in spans if s["parent"] == w["span"]
                    and (s.get("attrs") or {}).get("stage") == 1]
            assert [s["name"] for s in kids
                    if s["name"].startswith("feed.p")] == ["feed.put"] * n
            assert {s["tid"] for s in kids} == loop
        # The per-bank stages carry the bank's bytes; `ingest` what it
        # read: every sample of the scan, once.
        table = tl.report()
        assert table["feed.read"]["bytes"] == table["ingest"]["bytes"]
        assert table["feed.put"]["bytes"] == table["ingest"]["bytes"]
        assert table["link.put"]["bytes"] == table["ingest"]["bytes"]
        # The rotation's two waits are rows even where nothing blocked,
        # and the loop waits at most once a window (and once more where
        # it also waited for the stream's end): the windows it did not
        # wait for were read ahead.
        assert table["wait.chunk"]["byte_free"]
        assert table["wait.ingest_slot"]["byte_free"]
        assert table["ingest"]["calls"] == len(windows)
        assert 0 <= table["wait.chunk"]["calls"] <= len(windows) + 1
        assert by_id[windows[0]["parent"]]["name"] == "scan.reduce"


# -- ISSUE 36: a pass's two ends are stages, a stage's insides are parts -------

# The stage=1 span names of the toy pass before ISSUE 36 (the parent's
# tree, `toy_pass` there); `wait.*` come and go with what blocked.
PARENT_STAGE_NAMES = {"device", "dispatch", "flush", "ingest", "link.put",
                      "readback", "state.carry", "state.head", "stream",
                      "write"}
# ... and its `link.put` row: 16 bodies + the stream's head, every sample
# dispatched once.
PARENT_LINK_PUT = {"calls": 17, "bytes": 523264}
PARTS_OF = {"coeffs": "dispatch", "link.put": "dispatch",
            "dispatch.call": "dispatch", "write.digest": "write"}


def inside(inner, outer, slack=1e-3):
    """Does span ``inner`` lie inside ``outer`` (t0 is ``time.time()``,
    the duration ``perf_counter``: a millisecond of slack)?"""
    return (outer["t0"] - slack <= inner["t0"] and
            inner["t0"] + inner["duration_s"]
            <= outer["t0"] + outer["duration_s"] + slack)


def one_pass_shape(spans, root_name, dispatch="dispatch"):
    """What every pass to files must look like -> (root, the dispatching
    thread's stage spans in start order)."""
    assert len({s["trace"] for s in spans}) == 1
    roots = [s for s in spans if s["name"] == root_name]
    assert len(roots) == 1 and roots[0]["parent"] is None
    root = roots[0]
    st = stage_spans(spans)
    assert all(inside(s, root) for s in st), "a stage span outside the root"
    tid = next(s["tid"] for s in st if s["name"] == dispatch)
    assert root["tid"] == tid
    mine = sorted((s for s in st if s["tid"] == tid
                   and s["name"] != "stream"), key=lambda s: s["t0"])
    assert [s["name"] for s in mine].count("open") == 1
    assert [s["name"] for s in mine].count("close") == 1
    first = mine[0]
    last = max(mine, key=lambda s: s["t0"] + s["duration_s"])
    assert first["name"] == "open" and last["name"] == "close"
    # What starts after `close` does lies inside it (an AsyncSink's close
    # passes its flush barrier once more: a `wait.sink_flush`).
    assert all(inside(s, last) for s in mine if s["t0"] > last["t0"])
    by_id = {s["span"]: s for s in spans}
    for end in (first, last):
        up = end  # under the root: its child, or the pump wrapper's
        while up["parent"] is not None:
            up = by_id[up["parent"]]
            assert (up.get("attrs") or {}).get("stage") != 1, up["name"]
        assert up is root
        assert end["attrs"]["bytes"] == 0
    return root, mine


class TestParts:
    @pytest.mark.parametrize("spans_on", [True, False])
    def test_a_part_is_a_row_and_a_span_under_the_open_stage(
            self, spans_on, monkeypatch):
        monkeypatch.setattr(observability, "_TRACER",
                            Tracer(enabled=spans_on))
        tl = Timeline()
        with tl.stage("dispatch", byte_free=True) as stage:
            with tl.part("coeffs", nbytes=64, calls=2) as part:
                time.sleep(0.002)
            with tl.part("coeffs", nbytes=16):
                pass
        row = tl.report()["coeffs"]
        assert (row["calls"], row["bytes"]) == (3, 80)
        assert 0.002 <= row["seconds"] <= tl.stages["dispatch"].seconds
        assert "byte_free" not in row
        spans = observability.tracer().span_dicts()
        if not spans_on:
            assert spans == [] and stage is None and part is None
            return
        got = part_spans(spans)
        assert [s["name"] for s in got] == ["coeffs", "coeffs"]
        assert [s["attrs"] for s in got] == [{"bytes": 64, "part": 1},
                                             {"bytes": 16, "part": 1}]
        assert all(s["parent"] == stage.span_id for s in got)
        assert [s["name"] for s in stage_spans(spans)] == ["dispatch"]
        assert sum(s["duration_s"] for s in got) == pytest.approx(
            tl.stages["coeffs"].seconds)

    @pytest.mark.parametrize("spans_on", [True, False])
    def test_a_part_is_one_entry_in_the_flight_ring(self, spans_on,
                                                    monkeypatch):
        tr, rec = observability.tracer(), observability.flight_recorder()
        monkeypatch.setattr(tr, "enabled", spans_on)
        rec.clear()
        with Timeline().part("write.digest", nbytes=64):
            pass
        got = [e for e in rec.events() if e["name"] == "write.digest"]
        assert [e["kind"] for e in got] == ["span" if spans_on else "stage"]
        assert got[0]["bytes"] == 64

    def test_the_toy_pass_has_its_ends_and_its_parts(self, tmp_path):
        table, spans = toy_pass(tmp_path)
        root, mine = one_pass_shape(spans, "reduce.to_file")
        assert root["attrs"]["out"] == str(tmp_path / "toy.fil")
        # The stage names are the parent's plus the two ends, less the
        # zero-length `link.put` no reader could ever see open.
        names = {s["name"] for s in stage_spans(spans)
                 if not s["name"].startswith("wait.")}
        assert names == (PARENT_STAGE_NAMES | {"open", "close"}) \
            - {"link.put"}
        assert table["open"]["calls"] == table["close"]["calls"] == 1
        assert table["open"]["byte_free"] and table["close"]["byte_free"]
        # Every part lies in a stage span of its enclosing stage's name,
        # on that stage's thread, and takes no more than it.
        by_id = {s["span"]: s for s in spans}
        parts = part_spans(spans)
        assert {s["name"] for s in parts} == set(PARTS_OF)
        for s in parts:
            assert "stage" not in s["attrs"]
            up = by_id[s["parent"]]
            want = "open" if (s["name"] == "write.digest"
                              and up["name"] == "open") else \
                PARTS_OF[s["name"]]
            assert up["name"] == want, (s["name"], up["name"])
            assert up["tid"] == s["tid"] and inside(s, up)
        for part, stage in PARTS_OF.items():
            assert 0 < table[part]["seconds"] <= table[stage]["seconds"]

    def test_link_put_keeps_its_counts_and_gains_seconds(self, tmp_path):
        table, spans = toy_pass(tmp_path)
        row = table["link.put"]
        assert {k: row[k] for k in PARENT_LINK_PUT} == PARENT_LINK_PUT
        assert row["seconds"] > 0
        puts = [s for s in part_spans(spans) if s["name"] == "link.put"]
        assert sum(s["attrs"]["bytes"] for s in puts) == row["bytes"]
        # A put ends before its group's programs are called.
        calls = [s for s in part_spans(spans)
                 if s["name"] == "dispatch.call"]
        assert len(calls) == table["dispatch"]["calls"] == 16
        for put, call in zip(sorted(puts[1:], key=lambda s: s["t0"]),
                             sorted(calls[1:], key=lambda s: s["t0"])):
            assert put["t0"] + put["duration_s"] <= call["t0"] + 1e-3

    def test_coeffs_is_a_miss_only_and_lies_in_dispatch(self, tmp_path):
        table, spans = toy_pass(tmp_path)
        assert (table["coeffs"]["calls"], table["coeffs"]["bytes"]) == (
            1, 4 * NFFT * 4)
        got = [s for s in part_spans(spans) if s["name"] == "coeffs"]
        assert [s["attrs"]["nfft"] for s in got] == [NFFT]

    def test_the_digest_reads_every_byte_of_the_fil(self, tmp_path):
        table, spans = toy_pass(tmp_path)
        from blit.io.sigproc import read_fil_header

        out = str(tmp_path / "toy.fil")
        _, header_bytes = read_fil_header(out)
        row = table["write.digest"]
        # `write`'s bytes (the slabs) and, folded at open, the header.
        assert row["bytes"] - header_bytes == table["write"]["bytes"]
        assert row["bytes"] == os.path.getsize(out)
        assert row["calls"] == table["write"]["calls"] + 1
        sinks = {s["tid"] for s in stage_spans(spans)
                 if s["name"] == "write"}
        folds = [s for s in part_spans(spans)
                 if s["name"] == "write.digest"]
        assert {s["tid"] for s in folds[1:]} == sinks

    def test_three_products_call_every_leg_for_every_group(self, tmp_path):
        # tests/test_reduce_fanout.py's toy: 4 dispatches of one channel
        # group, three legs each, + the two small legs' head steps.
        nsamps = 18 * 1024
        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=4, obsnchan=4, ntime_per_block=nsamps // 4,
                  seed=7, tone_chan=1)
        observability.tracer().reset()
        red = RawReducer(nfft=1024, nint=3, also=((8, 128), (64, 51)),
                         chunk_frames=4)
        outs = [str(tmp_path / f"p{k}.fil") for k in range(3)]
        within(300, lambda: red.reduce_to_files(raw, outs))
        table = red.timeline.report()
        spans = observability.tracer().span_dicts()
        one_pass_shape(spans, "reduce.to_file")
        assert table["dispatch.call"]["calls"] == 4 * 3 + 2
        assert table["dispatch.call"]["calls"] == (
            table["fanout.share"]["calls"] + 4)
        calls = sorted((s for s in part_spans(spans)
                        if s["name"] == "dispatch.call"),
                       key=lambda s: s["t0"])
        legs = ["jit_channelize_stream", "jit_channelize_0001",
                "jit_channelize_0002"]
        assert [s["attrs"]["programs"] for s in calls] == (
            [legs[1:] + legs] + [legs] * 3)
        # A bank per nfft, each built once; a digest per product.
        assert table["coeffs"]["calls"] == 3
        assert table["coeffs"]["bytes"] == 4 * 4 * (1024 + 8 + 64)
        assert table["write.digest"]["bytes"] == sum(
            os.path.getsize(p) for p in outs)

    def test_a_resumed_pass_is_one_trace_with_both_ends(self, tmp_path):
        from blit.pipeline import ReductionCursor

        def reducer():
            return RawReducer(nfft=NFFT, nint=NINT, chunk_frames=32)

        raw = toy_raw(tmp_path)
        out, plain = str(tmp_path / "res.fil"), str(tmp_path / "plain.fil")
        within(120, lambda: reducer().reduce_to_file(raw, plain))
        golden = open(plain, "rb").read()
        for resumed in (False, True):
            if resumed:  # as a crash after 64 rows leaves it
                os.remove(out)
                _cut_resumable(reducer(), raw, out, rows=64)
            observability.tracer().reset()
            red = reducer()
            within(120, lambda: red.reduce_resumable(raw, out))
            root, _ = one_pass_shape(observability.tracer().span_dicts(),
                                     "reduce.resumable")
            assert root["attrs"]["resumed"] is resumed
            assert root["attrs"]["out"] == out
            assert open(out, "rb").read() == golden
            # A resume digests the kept rows again at open, a fresh start
            # the header: either way every byte of the product, once.
            assert red.timeline.report()["write.digest"]["bytes"] == len(
                golden)
        assert ReductionCursor.load(out) is None

    def test_a_mesh_scan_opens_with_the_bank_and_closes_last(
            self, tmp_path):
        from blit.parallel.scan import reduce_scan_mesh_to_files

        bank_bw = -187.5 / 4
        paths = [[]]
        for k in range(4):
            paths[0].append(str(tmp_path / f"blc0{k}.raw"))
            synth_raw(paths[0][k], nblocks=4, obsnchan=2,
                      ntime_per_block=1024, seed=k, obsbw=bank_bw,
                      obsfreq=8000.0 + (k + 0.5) * bank_bw)
        os.makedirs(tmp_path / "out")
        observability.tracer().reset()
        tl = Timeline()
        written = within(300, lambda: reduce_scan_mesh_to_files(
            paths, out_dir=str(tmp_path / "out"), nfft=NFFT, nint=NINT,
            window_frames=16, timeline=tl))
        spans = observability.tracer().span_dicts()
        (out, _), = written.values()
        root, mine = one_pass_shape(spans, "scan.reduce")
        assert root["attrs"]["out"] == out
        table = tl.report()
        by_id = {s["span"]: s for s in spans}
        coeffs, = [s for s in part_spans(spans) if s["name"] == "coeffs"]
        assert by_id[coeffs["parent"]]["name"] == "open"
        assert table["coeffs"]["seconds"] <= table["open"]["seconds"]
        # The scan's puts are parts inside `feed.put`, its digests inside
        # `write` (and the header's inside `open`): the loop's thread does
        # all of them (the feed thread reads, and nothing else).
        ups = collections.Counter(
            by_id[s["parent"]]["name"] for s in part_spans(spans)
            if s["name"] == "link.put")
        assert set(ups) == {"feed.put"}
        assert table["link.put"]["bytes"] == table["ingest"]["bytes"]
        assert table["link.put"]["seconds"] <= table["feed.put"]["seconds"]
        ups = collections.Counter(
            by_id[s["parent"]]["name"] for s in part_spans(spans)
            if s["name"] == "write.digest")
        assert set(ups) == {"open", "write"} and ups["open"] == 1
        assert table["write.digest"]["bytes"] == os.path.getsize(out)
        assert "dispatch.call" not in table  # the scan's dispatch IS its call


def _cut_resumable(red, raw, out, rows):
    """Leave ``out`` as a crash after ``rows`` rows would: run the
    resumable reduction with a writer that dies on the append after."""
    from blit.pipeline import ResumableFilWriter

    real = ResumableFilWriter.append

    def dying(self, slab):
        if self.nsamps >= rows:
            raise OSError("injected: the disk went away")
        real(self, slab)

    ResumableFilWriter.append = dying
    try:
        with pytest.raises(OSError, match="injected"):
            within(120, lambda: red.reduce_resumable(raw, out))
    finally:
        ResumableFilWriter.append = real


class TestProfileTrace:
    def test_writes_the_spans_beside_the_trace(self, tmp_path):
        from blit.observability import profile_trace

        logdir = str(tmp_path / "trace")
        tl = Timeline()
        with observability.span("before"):
            pass
        with profile_trace(logdir):
            with tl.stage("ingest", nbytes=16):
                time.sleep(0.01)
        doc = json.load(open(os.path.join(logdir, "blit-spans.json")))
        events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert [e["name"] for e in events] == ["ingest"]
        assert events[0]["args"]["stage"] == 1
        # Epoch microseconds: the clock the .xplane.pb's Task Environment
        # stamps its start on.
        assert abs(events[0]["ts"] / 1e6 - time.time()) < 60
        found = []
        for root, _, files in os.walk(logdir):
            found += [f for f in files if f.endswith(".xplane.pb")]
        assert found
