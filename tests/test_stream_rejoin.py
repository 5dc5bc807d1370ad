"""Live-session rejoin (ISSUE 12): StreamCursor + the resume legs of
``stream_reduce`` / ``stream_search``.

The contract: a consumer that crashes mid-session and restarts with
``resume=True`` re-attaches to the still-recording session and finishes
a product BYTE-IDENTICAL to a never-restarted consumer — including
re-masking seats the pre-crash watermark masked, even when their data
exists on disk by the time the rejoin re-reads the session."""

import os

import pytest

jax = pytest.importorskip("jax")

from blit import faults  # noqa: E402
from blit.io.guppi import open_raw  # noqa: E402
from blit.pipeline import RawReducer  # noqa: E402
from blit.stream import (  # noqa: E402
    QueueSource,
    ReplaySource,
    StreamCursor,
    chunks_of,
    stream_reduce,
    stream_search,
)
from blit.testing import synth_raw  # noqa: E402

NFFT, CF = 32, 4


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counters()
    yield
    faults.clear()
    faults.reset_counters()


def _recording(tmp_path, name="r.raw", nblocks=4, seed=1):
    p = str(tmp_path / name)
    synth_raw(p, nblocks=nblocks, obsnchan=2, ntime_per_block=512,
              seed=seed)
    return p


def _bytes(path):
    with open(path, "rb") as f:
        return f.read()


def _kw():
    return dict(nfft=NFFT, chunk_frames=CF)


class TestStreamCursor:
    def test_save_load_round_trip(self, tmp_path):
        out = str(tmp_path / "x.fil")
        cur = StreamCursor(path="sess.raw", kind="filterbank", nfft=NFFT,
                           frames_done=12, masked_chunks=[1, 3])
        cur.save(out)
        back = StreamCursor.load(out)
        assert back == cur
        assert StreamCursor.path_for(out).endswith(".stream-cursor")

    def test_matches_binds_session_and_knobs(self, tmp_path):
        red = RawReducer(**_kw())
        cur = StreamCursor.fresh(red, "sess.raw", "filterbank")
        assert cur.matches(red, "sess.raw", "filterbank")
        assert not cur.matches(red, "other.raw", "filterbank")
        assert not cur.matches(red, "sess.raw", "hits")
        other = RawReducer(nfft=NFFT * 2, chunk_frames=CF)
        assert not cur.matches(other, "sess.raw", "filterbank")

    def test_hits_claim_ledger(self):
        class _R:
            nfft, ntap, nint = NFFT, 4, 1
            stokes, window, fqav_by, dtype = "I", "hamming", 1, "float32"
            nbits = 32
            window_spectra, top_k = 4, 4
            snr_threshold, max_drift_bins = 2.0, None

        cur = StreamCursor.fresh(_R(), "s.raw", "hits")
        cur.window_claims = [[1, 100, 2], [2, 150, 3]]
        cur.windows_done, cur.byte_offset, cur.hits_done = 2, 150, 3
        assert cur.claim_at(2) == (150, 3)
        assert cur.claim_at(1) == (100, 2)
        assert cur.claim_at(5) is None
        # A trimmed ledger (bounded per-append I/O) resolves only what
        # it still holds — older windows mean a fresh restart, never a
        # wrong offset.
        del cur.window_claims[0]
        assert cur.claim_at(1) is None


class TestFilterbankRejoin:
    def test_crash_and_rejoin_byte_identical_to_batch(self, tmp_path):
        raw = _recording(tmp_path)
        oracle = str(tmp_path / "o.fil")
        RawReducer(**_kw()).reduce_to_file(raw, oracle)
        out = str(tmp_path / "s.fil")
        faults.install_spec("sink.write:fail:after=2")
        with pytest.raises(OSError):
            stream_reduce(ReplaySource(raw, rate=10000), out,
                          resume=True, **_kw())
        faults.clear()
        cur = StreamCursor.load(out)
        assert cur is not None and cur.frames_done > 0
        claimed = cur.frames_done
        hdr = stream_reduce(ReplaySource(raw, rate=10000), out,
                            resume=True, **_kw())
        assert hdr["nsamps"] * 1 >= claimed
        assert _bytes(out) == _bytes(oracle)
        assert StreamCursor.load(out) is None  # completeness marker

    def test_identity_mismatch_restarts_fresh(self, tmp_path):
        raw = _recording(tmp_path)
        out = str(tmp_path / "s.fil")
        # A cursor from a DIFFERENT config must not be spliced into.
        stale = StreamCursor(path=raw, kind="filterbank", nfft=NFFT * 2,
                             frames_done=8)
        stale.save(out)
        with open(out, "wb") as f:
            f.write(b"junk")
        oracle = str(tmp_path / "o.fil")
        RawReducer(**_kw()).reduce_to_file(raw, oracle)
        stream_reduce(ReplaySource(raw, rate=10000), out, resume=True,
                      **_kw())
        assert _bytes(out) == _bytes(oracle)

    def test_claim_past_eof_restarts_fresh(self, tmp_path):
        # The resume_fil_ok guard on the stream path: a cursor claiming
        # more bytes than the product holds would NUL-hole-extend under
        # truncate — must restart fresh instead.
        raw = _recording(tmp_path)
        oracle = str(tmp_path / "o.fil")
        RawReducer(**_kw()).reduce_to_file(raw, oracle)
        out = str(tmp_path / "s.fil")
        faults.install_spec("sink.write:fail:after=2")
        with pytest.raises(OSError):
            stream_reduce(ReplaySource(raw, rate=10000), out,
                          resume=True, **_kw())
        faults.clear()
        size = os.path.getsize(out)
        with open(out, "r+b") as f:
            f.truncate(size - 64)  # eat claimed bytes
        stream_reduce(ReplaySource(raw, rate=10000), out, resume=True,
                      **_kw())
        assert _bytes(out) == _bytes(oracle)

    def test_clean_run_with_resume_leaves_no_sidecar(self, tmp_path):
        raw = _recording(tmp_path)
        out = str(tmp_path / "s.fil")
        oracle = str(tmp_path / "o.fil")
        RawReducer(**_kw()).reduce_to_file(raw, oracle)
        stream_reduce(ReplaySource(raw, rate=10000), out, resume=True,
                      **_kw())
        assert _bytes(out) == _bytes(oracle)
        assert not os.path.exists(StreamCursor.path_for(out))


class TestMaskStateRejoin:
    def _queue(self, raw, seqs, total):
        src = QueueSource(path=raw)
        chunks = chunks_of(open_raw(raw))
        for c in chunks:
            if c.seq in seqs:
                src.push(c)
        src.finish(total)
        return src, len(chunks)

    def test_premasked_seat_stays_masked_when_data_appears(
            self, tmp_path):
        # Run A (never restarted): chunk 1 never arrives — masked.
        # Run B: crash after the mask was claimed, then rejoin against a
        # session where chunk 1's data NOW exists.  The rejoin must
        # re-mask seat 1 (zero weight) and count the data late —
        # producing run A's exact bytes.
        raw = _recording(tmp_path, nblocks=4)
        total = len(chunks_of(open_raw(raw)))
        seqs_missing_1 = {s for s in range(total)} - {1}

        oracle = str(tmp_path / "never_restarted.fil")
        src, _ = self._queue(raw, seqs_missing_1, total)
        hdr_a = stream_reduce(src, oracle, lateness_s=0.01, **_kw())
        assert hdr_a["stream_masked_chunks"] == 1

        out = str(tmp_path / "rejoined.fil")
        src, _ = self._queue(raw, seqs_missing_1, total)
        faults.install_spec("sink.write:fail:after=4")
        with pytest.raises(OSError):
            stream_reduce(src, out, lateness_s=0.01, resume=True,
                          **_kw())
        faults.clear()
        cur = StreamCursor.load(out)
        assert cur is not None
        assert cur.masked_chunks == [1], (
            "the mask must ride the durable claim")

        # The rejoin session has EVERY chunk (the recorder caught up).
        src, _ = self._queue(raw, set(range(total)), total)
        hdr_b = stream_reduce(src, out, lateness_s=5.0, resume=True,
                              **_kw())
        assert hdr_b["stream_masked_chunks"] == 1
        assert hdr_b["stream_late_chunks"] >= 1  # seat-1 data dropped
        assert _bytes(out) == _bytes(oracle)


class TestHitsRejoin:
    def _search_kw(self):
        return dict(nfft=NFFT, window_spectra=4, top_k=4,
                    snr_threshold=2.0, chunk_frames=CF)

    def test_crash_and_rejoin_byte_identical_to_batch(self, tmp_path):
        from blit.search import DedopplerReducer

        raw = _recording(tmp_path, nblocks=4, seed=7)
        oracle = str(tmp_path / "o.hits")
        DedopplerReducer(**self._search_kw()).search_to_file(raw, oracle)
        out = str(tmp_path / "s.hits")
        faults.install_spec("sink.write:fail:after=2")
        with pytest.raises(OSError):
            stream_search(ReplaySource(raw, rate=10000), out,
                          resume=True, **self._search_kw())
        faults.clear()
        cur = StreamCursor.load(out)
        assert cur is not None and cur.windows_done > 0
        hdr = stream_search(ReplaySource(raw, rate=10000), out,
                            resume=True, **self._search_kw())
        assert hdr["search_windows"] > cur.windows_done
        assert _bytes(out) == _bytes(oracle)
        assert StreamCursor.load(out) is None


class TestCLIResume:
    def test_stream_resume_flag_smoke(self, tmp_path, capsys):
        import json

        from blit.__main__ import main

        raw = _recording(tmp_path)
        out = str(tmp_path / "cli.fil")
        oracle = str(tmp_path / "o.fil")
        RawReducer(**_kw()).reduce_to_file(raw, oracle)
        rc = main(["stream", raw, "-o", out, "--nfft", str(NFFT),
                   "--replay-rate", "10000", "--resume"])
        assert rc == 0
        rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rep["nsamps"] is not None
        assert _bytes(out) == _bytes(oracle)
        assert not os.path.exists(StreamCursor.path_for(out))
