"""Streaming ingest plane (ISSUE 7): byte-identity goldens (a stream of
a completed recording == the batch reduction, for .fil/.h5/.hits, under
reordering/duplicate/dropped-chunk faults with masking engaged), the
watermark lateness semantics, the growing-file tailer, the latency
metrics, and the `blit stream` CLI legs."""

import io
import contextlib
import json
import os
import threading
import time

import pytest

from blit import faults, observability
from blit.config import stream_defaults
from blit.faults import FaultRule
from blit.io.guppi import open_raw, write_raw
from blit.observability import StallWatchdog, Timeline
from blit.pipeline import RawReducer
from blit.stream import (
    FileTailSource,
    LiveRawStream,
    QueueSource,
    ReplaySource,
    chunks_of,
    stream_reduce,
    stream_search,
)
from blit.testing import synth_raw

NFFT = 256
NINT = 2
CHUNK_FRAMES = 4


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counters()
    yield
    faults.clear()
    faults.reset_counters()


@pytest.fixture(autouse=True)
def _flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path / "flight"))
    os.makedirs(str(tmp_path / "flight"), exist_ok=True)


def _synth(path, nblocks=4, overlap=NFFT, seed=1, **kw):
    return synth_raw(str(path), nblocks=nblocks, obsnchan=2,
                     ntime_per_block=(8 + 3) * NFFT, overlap=overlap,
                     seed=seed, tone_chan=1, **kw)


def _reducer(**kw):
    kw.setdefault("timeline", Timeline())
    return RawReducer(nfft=NFFT, nint=NINT, chunk_frames=CHUNK_FRAMES,
                      **kw)


def _batch(raw, out):
    _reducer().reduce_to_file(str(raw), str(out))
    with open(out, "rb") as f:
        return f.read()


def _read(path):
    with open(path, "rb") as f:
        return f.read()


class TestByteIdentityGolden:
    """The plane's golden contract: stream ≡ batch, byte for byte."""

    def test_replay_fil_identical_to_batch(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        out = tmp_path / "s.fil"
        hdr = stream_reduce(ReplaySource(str(raw), rate=1e6), str(out),
                            reducer=_reducer())
        assert _read(out) == ref
        # The clean path reports itself clean.
        assert hdr["stream_masked_chunks"] == 0
        assert hdr["stream_late_chunks"] == 0
        assert hdr["stream_dup_chunks"] == 0
        assert hdr["stream_chunks"] == 4

    def test_replay_h5_identical_to_batch(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = tmp_path / "ref.h5"
        _reducer().reduce_to_file(str(raw), str(ref))
        out = tmp_path / "s.h5"
        stream_reduce(ReplaySource(str(raw), rate=1e6), str(out),
                      reducer=_reducer())
        assert _read(out) == _read(ref)

    def test_stream_search_hits_identical_to_batch(self, tmp_path):
        from blit.search import DedopplerReducer

        raw = tmp_path / "r.raw"
        _synth(raw)

        def searcher():
            return DedopplerReducer(
                nfft=NFFT, nint=NINT, chunk_frames=CHUNK_FRAMES,
                window_spectra=8, snr_threshold=2.0, top_k=4,
                timeline=Timeline())

        ref = tmp_path / "ref.hits"
        searcher().search_to_file(str(raw), str(ref))
        out = tmp_path / "s.hits"
        hdr = stream_search(ReplaySource(str(raw), rate=1e6), str(out),
                            searcher=searcher())
        assert _read(out) == _read(ref)
        assert hdr["search_windows"] >= 2
        assert hdr["search_nhits"] > 0

    def test_sync_output_plane_identical(self, tmp_path):
        # The A/B lever holds on the live plane too.
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        out = tmp_path / "s.fil"
        stream_reduce(ReplaySource(str(raw), rate=1e6), str(out),
                      reducer=_reducer(async_output=False))
        assert _read(out) == ref

    def test_reordered_and_duplicated_chunks_repair(self, tmp_path):
        # Late-but-within-budget arrivals reorder; duplicates drop —
        # the product must not notice either.
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        cs = chunks_of(open_raw(str(raw)))
        qs = QueueSource()
        for c in (cs[1], cs[0], cs[2], cs[2], cs[3], cs[0]):
            qs.push(c)
        qs.finish(total=4)
        out = tmp_path / "s.fil"
        hdr = stream_reduce(qs, str(out), reducer=_reducer(),
                            lateness_s=10.0)
        assert _read(out) == ref
        assert hdr["stream_dup_chunks"] == 2
        assert hdr["stream_masked_chunks"] == 0


class TestWatermarkMasking:
    def _zero_masked_ref(self, tmp_path, hdr0, blocks, masked):
        """Batch comparator: the same recording with the masked blocks'
        samples zeroed — exactly what zero-weight masking must yield."""
        zb = [b.copy() for b in blocks]
        for i in masked:
            zb[i][:] = 0
        zraw = tmp_path / "zeroed.raw"
        write_raw(str(zraw), hdr0, zb)
        return _batch(zraw, tmp_path / "zref.fil")

    def test_dropped_chunk_masks_zero_weight(self, tmp_path):
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        ref = self._zero_masked_ref(tmp_path, hdr0, blocks, [2])
        cs = chunks_of(open_raw(str(raw)))
        qs = QueueSource()
        for c in (cs[0], cs[1], cs[3]):  # chunk 2 never arrives
            qs.push(c)
        qs.finish(total=4)
        out = tmp_path / "s.fil"
        hdr = stream_reduce(qs, str(out), reducer=_reducer(),
                            lateness_s=0.1)
        assert _read(out) == ref
        assert hdr["stream_masked_chunks"] == 1
        assert hdr["_masked_chunks"] == [2]
        # Zero-filled samples degrade every output row whose PFB window
        # touches them — and no more.
        assert 0 < hdr["stream_degraded_spectra"] < hdr["nsamps"]
        # The degradation is loud everywhere a healthy run reports:
        # fault counter, flight dump, header.
        assert faults.counters().get("mask.chunk") == 1
        assert hdr["stream_flight_dump"] is not None
        assert os.path.exists(hdr["stream_flight_dump"])
        with open(hdr["stream_flight_dump"]) as f:
            doc = json.load(f)
        assert "masked" in doc["reason"]

    def test_late_chunk_after_mask_is_dropped(self, tmp_path):
        # A straggler past the budget must be counted + dropped, never
        # spliced into already-emitted history.
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        ref = self._zero_masked_ref(tmp_path, hdr0, blocks, [1])
        cs = chunks_of(open_raw(str(raw)))
        qs = QueueSource()
        qs.push(cs[0])
        qs.push(cs[2])  # proof chunk 1 is missing

        def straggler():
            time.sleep(0.5)  # well past the 0.1 s budget
            qs.push(cs[1])
            qs.push(cs[3])
            qs.finish(total=4)

        t = threading.Thread(target=straggler)
        t.start()
        out = tmp_path / "s.fil"
        hdr = stream_reduce(qs, str(out), reducer=_reducer(),
                            lateness_s=0.1)
        t.join()
        assert _read(out) == ref
        assert hdr["stream_masked_chunks"] == 1
        assert hdr["stream_late_chunks"] == 1
        assert hdr["_masked_chunks"] == [1]

    def test_missing_tail_masked_after_eos(self, tmp_path):
        # EOS is evidence too: a gap before a declared total masks once
        # the budget expires, instead of waiting forever.
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        ref = self._zero_masked_ref(tmp_path, hdr0, blocks, [3])
        cs = chunks_of(open_raw(str(raw)))
        qs = QueueSource()
        for c in cs[:3]:
            qs.push(c)
        qs.finish(total=4)  # chunk 3 never comes
        out = tmp_path / "s.fil"
        hdr = stream_reduce(qs, str(out), reducer=_reducer(),
                            lateness_s=0.1)
        assert _read(out) == ref
        assert hdr["_masked_chunks"] == [3]

    def test_injected_drop_and_dup_fault_modes(self, tmp_path):
        # The stream.chunk injection point (faults.py drop/dup modes):
        # a BLIT_FAULTS-style drill masks one chunk and dedups another.
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        ref = self._zero_masked_ref(tmp_path, hdr0, blocks, [1])
        faults.install(
            FaultRule("stream.chunk", "drop", times=1, after=1),
            FaultRule("stream.chunk", "dup", times=1, after=2),
        )
        out = tmp_path / "s.fil"
        hdr = stream_reduce(ReplaySource(str(raw), rate=1e6), str(out),
                            reducer=_reducer(), lateness_s=0.1)
        assert _read(out) == ref
        assert hdr["stream_masked_chunks"] == 1
        assert hdr["stream_dup_chunks"] == 1
        c = faults.counters()
        assert c.get("fault.stream.chunk.drop") == 1
        assert c.get("fault.stream.chunk.dup") == 1

    def test_empty_stream_rejected(self):
        qs = QueueSource()
        qs.finish(total=0)
        with pytest.raises(ValueError, match="empty stream"):
            LiveRawStream(qs, lateness_s=0.1).header(0)


class TestFileTail:
    def _write_slowly(self, src_path, dst_path, done_path, parts=6,
                      dt=0.02):
        data = _read(src_path)
        step = -(-len(data) // parts)

        def run():
            with open(dst_path, "wb") as f:
                for i in range(0, len(data), step):
                    f.write(data[i:i + step])
                    f.flush()
                    time.sleep(dt)
            with open(done_path, "w"):
                pass

        t = threading.Thread(target=run)
        t.start()
        return t

    def test_tail_growing_file_identical_to_batch(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        live = str(tmp_path / "live.0000.raw")
        t = self._write_slowly(str(raw), live,
                               str(tmp_path / "live.done"))
        out = tmp_path / "s.fil"
        hdr = stream_reduce(FileTailSource(live, poll_s=0.005),
                            str(out), reducer=_reducer())
        t.join()
        assert _read(out) == ref
        assert hdr["stream_chunks"] == 4
        assert hdr["stream_masked_chunks"] == 0

    def test_tail_follows_sequence_members(self, tmp_path):
        # The recorder rolls to .0001.raw mid-session; the tailer must
        # follow and the stitched product must match the batch scan.
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        m0 = str(tmp_path / "seq.0000.raw")
        m1 = str(tmp_path / "seq.0001.raw")
        write_raw(m0, hdr0, blocks[:2])
        h1 = dict(hdr0)
        h1["PKTIDX"] = sum(
            b.shape[1] - hdr0.get("OVERLAP", 0) for b in blocks[:2])
        write_raw(m1, h1, blocks[2:])
        ref = tmp_path / "ref.fil"
        _reducer().reduce_to_file([m0, m1], str(ref))

        def recorder():
            time.sleep(0.1)
            with open(str(tmp_path / "seq.done"), "w"):
                pass

        t = threading.Thread(target=recorder)
        t.start()
        out = tmp_path / "s.fil"
        hdr = stream_reduce(FileTailSource(m0, poll_s=0.005), str(out),
                            reducer=_reducer())
        t.join()
        assert _read(out) == _read(ref)
        assert hdr["stream_chunks"] == 4

    def test_idle_timeout_ends_session(self, tmp_path):
        # Recorder dies without a done marker: the tail must end (and
        # the partial product publish) instead of following forever.
        raw = tmp_path / "r.raw"
        _synth(raw)
        live = str(tmp_path / "live.0000.raw")
        with open(str(raw), "rb") as f:
            open(live, "wb").write(f.read())
        out = tmp_path / "s.fil"
        hdr = stream_reduce(
            FileTailSource(live, poll_s=0.01, idle_timeout_s=0.15),
            str(out), reducer=_reducer())
        assert hdr["stream_chunks"] == 4
        assert _read(out) == _batch(raw, tmp_path / "ref.fil")

    def test_half_written_block_not_delivered(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, nblocks=2)
        data = _read(str(raw))
        live = str(tmp_path / "live.0000.raw")
        with open(live, "wb") as f:
            f.write(data[:len(data) - 100])  # final block torn
        src = FileTailSource(live, poll_s=0.005)
        c = src.get(timeout=0.05)
        assert c is not None and c.seq == 0
        assert src.get(timeout=0.05) is None  # block 1 incomplete
        with open(live, "ab") as f:
            f.write(data[len(data) - 100:])
        c = src.get(timeout=0.05)
        assert c is not None and c.seq == 1


class TestLatencyMetrics:
    def test_chunk_to_product_histogram_and_gauges(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        red = _reducer()
        out = tmp_path / "s.fil"
        stream_reduce(ReplaySource(str(raw), rate=1e6), str(out),
                      reducer=red)
        rep = red.timeline.report()
        lat = rep["hists"]["stream.chunk_to_product_s"]
        assert lat["n"] >= 4  # one observation per product append
        assert lat["p99"] >= lat["p50"] >= 0.0
        assert "stream.watermark_lag_s" in rep["gauges"]
        assert rep["stream.chunks"]["calls"] == 4

    def test_default_reducer_records_on_process_timeline(self, tmp_path):
        # The CI telemetry artifact rides the process timeline: entry
        # points that build their own reducer must land stream.* there.
        raw = tmp_path / "r.raw"
        _synth(raw)
        tl = observability.process_timeline()
        before = tl.hists["stream.chunk_to_product_s"].n
        out = tmp_path / "s.fil"
        stream_reduce(ReplaySource(str(raw), rate=1e6), str(out),
                      nfft=NFFT, nint=NINT, chunk_frames=CHUNK_FRAMES)
        assert tl.hists["stream.chunk_to_product_s"].n > before


class TestStallWatchdog:
    def test_unit_semantics(self):
        wd = StallWatchdog(None, "x")
        assert wd.poll_s(0.3) == 0.3
        wd.check("never trips")  # unarmed: no-op
        wd = StallWatchdog(0.2, "x", what="test stall")
        assert wd.poll_s(0.5) == 0.1
        wd._beat -= 1.0
        assert wd.stalled()
        assert not wd.stalled(active=False)
        with pytest.raises(RuntimeError, match="stalled here"):
            wd.check("stalled here")

    def test_wedged_source_trips_feed_watchdog(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        cs = chunks_of(open_raw(str(raw)))
        qs = QueueSource()
        qs.push(cs[0])  # first chunk arrives, then the source wedges
        out = tmp_path / "s.fil"
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="stall"):
            stream_reduce(qs, str(out), reducer=_reducer(),
                          lateness_s=0.05, stall_timeout_s=0.3)
        assert time.monotonic() - t0 < 10

    def test_quiet_source_without_watchdog_is_patient(self, tmp_path):
        # No stall timeout armed (the default): a slow-but-alive
        # recorder must not trip anything.
        raw = tmp_path / "r.raw"
        _synth(raw)
        cs = chunks_of(open_raw(str(raw)))
        qs = QueueSource()

        def trickle():
            for c in cs:
                time.sleep(0.05)
                qs.push(c)
            qs.finish(total=4)

        t = threading.Thread(target=trickle)
        t.start()
        out = tmp_path / "s.fil"
        hdr = stream_reduce(qs, str(out), reducer=_reducer(),
                            lateness_s=5.0)
        t.join()
        assert hdr["stream_masked_chunks"] == 0


class TestStreamConfig:
    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("BLIT_STREAM_LATENESS", "7.5")
        monkeypatch.setenv("BLIT_STREAM_POLL", "0.25")
        monkeypatch.setenv("BLIT_STREAM_IDLE_TIMEOUT", "12")
        monkeypatch.setenv("BLIT_STREAM_STALL_TIMEOUT", "-1")
        d = stream_defaults()
        assert d["lateness_s"] == 7.5
        assert d["poll_s"] == 0.25
        assert d["idle_timeout_s"] == 12.0
        assert d["stall_timeout_s"] is None  # negative = unarmed

    def test_defaults_reach_live_stream_and_tailer(self, monkeypatch,
                                                   tmp_path):
        monkeypatch.setenv("BLIT_STREAM_LATENESS", "3.25")
        monkeypatch.setenv("BLIT_STREAM_IDLE_TIMEOUT", "9")
        live = LiveRawStream(QueueSource())
        assert live.lateness_s == 3.25
        src = FileTailSource(str(tmp_path / "x.0000.raw"))
        assert src.idle_timeout_s == 9.0


class TestWorkersAndCLI:
    def test_workers_stream_raw_replay(self, tmp_path):
        from blit import workers

        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        out = tmp_path / "w.fil"
        hdr = workers.stream_raw(str(raw), str(out), replay_rate=1e6,
                                 nfft=NFFT, nint=NINT,
                                 chunk_frames=CHUNK_FRAMES)
        assert _read(out) == ref
        assert hdr["stream_chunks"] == 4

    def _main(self, argv):
        from blit.__main__ import main

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = main(argv)
        return rc, json.loads(buf.getvalue().strip().splitlines()[-1])

    @pytest.mark.parametrize("hdrkw, rate", [
        ({}, "1000"), ({"tbin": 0.2 / (4 * (8 + 3) * NFFT)}, "8"),
    ], ids=["as-fast-as-read", "paced"])
    def test_cli_stream_smoke(self, tmp_path, hdrkw, rate):
        # The tier-1 CLI smoke (ISSUE 7 satellite): accelerated replay
        # through `blit stream`, latency percentiles in the report.  The
        # paced case is a 0.2 s recording replayed 8x against the clock:
        # no chunk late, none masked, and a latency that is really one.
        raw = tmp_path / "r.raw"
        _synth(raw, **hdrkw)
        out = str(tmp_path / "s.fil")
        rc, rep = self._main([
            "stream", str(raw), "-o", out, "--nfft", str(NFFT),
            "--nint", str(NINT), "--replay-rate", rate,
        ])
        assert rc == 0
        assert rep["output"] == out
        assert rep["masked_chunks"] == 0 and rep["late_chunks"] == 0
        assert rep["degraded_spectra"] == 0
        assert rep["chunk_to_product_p99_s"] >= rep[
            "chunk_to_product_p50_s"] > 0.0
        assert _read(out) == _batch(raw, tmp_path / "ref.fil")

    def test_cli_stream_search_smoke(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        out = str(tmp_path / "s.hits")
        rc, rep = self._main([
            "stream", str(raw), "-o", out, "--nfft", str(NFFT),
            "--search", "--window-spectra", "8", "--snr", "2.0",
            "--replay-rate", "1000",
        ])
        assert rc == 0
        assert rep["windows"] >= 1
        assert os.path.exists(out)
