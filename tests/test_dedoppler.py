"""Search plane (ISSUE 6): Taylor-tree dedoppler + ``.hits`` products.

Coverage map:

- the drift transform against an O(T·D·F) brute-force oracle summing
  the EXACT tree paths (integer-valued data → float32 sums are exact in
  any association, so the comparison is BYTE equality, not allclose);
- the pallas kernel (interpret mode — the CPU tier-1 path) bitwise
  against the pure-lax reference;
- device-side threshold + per-band top-k packing/decode;
- end-to-end recovery of an injected DRIFTING tone (the
  blit.testing injector) through RAW → spectra → search, both drift
  signs, within one drift step / one channel;
- ``.hits`` writers: atomic publish, sync↔async byte identity,
  window-split resume replay reproducing the uninterrupted bytes;
- ProductService integration (kind="hits"): fingerprints, cache hits,
  dense-array round trip;
- SiteConfig search knobs + BLIT_SEARCH_* env overrides;
- `blit search` CLI smoke (in-process main, like tests/test_cli.py).
"""

import json
import os

import numpy as np
import pytest

from blit.__main__ import main
from blit.io.hits import (
    HitsWriter,
    ResumableHitsWriter,
    WindowHits,
    read_hits,
    write_hits,
)
from blit.observability import Timeline
from blit.ops import pallas_dedoppler as pd
from blit.search import (
    DedopplerReducer,
    Hit,
    SearchCursor,
    hits_from_array,
    hits_to_array,
)
from blit.testing import synth_raw, synth_raw_sequence, tone_drift_for

NFFT = 128
T = 8  # window_spectra for the end-to-end tests


def _synth(path, windows=3, obsnchan=2, ntap=4, drift_bins=0.0,
           tone_chan=None, seed=1, **kw):
    """A recording sized for exactly ``windows`` full search windows
    (plus the PFB tail) with an optional drifting tone."""
    ntime = (T * windows + ntap - 1) * NFFT
    tone_drift = tone_drift_for(NFFT, T, drift_bins)
    return synth_raw(
        str(path), nblocks=2, obsnchan=obsnchan,
        ntime_per_block=-(-ntime // 2), seed=seed, tone_chan=tone_chan,
        tone_drift=tone_drift, **kw,
    )


def _reducer(**kw):
    kw.setdefault("nfft", NFFT)
    kw.setdefault("window_spectra", T)
    kw.setdefault("top_k", 4)
    kw.setdefault("snr_threshold", 2.0)
    kw.setdefault("kernel", "reference")
    return DedopplerReducer(**kw)


class TestTaylorTree:
    def test_golden_against_brute_force_exact(self):
        # Integer-valued float32 data: every partial sum is exact, so
        # tree and brute force agree BYTE-for-byte whatever the
        # association order.
        rng = np.random.default_rng(0)
        for Tw, F in ((4, 37), (16, 96), (32, 64)):
            x = rng.integers(0, 200, size=(Tw, F)).astype(np.float32)
            tree = np.asarray(pd.taylor_tree(x, kernel="reference"))
            brute = pd.brute_force_dedoppler(x).astype(np.float32)
            assert np.array_equal(tree, brute), (Tw, F)

    def test_pallas_kernel_bitwise_matches_reference(self):
        rng = np.random.default_rng(1)
        # Several lane-aligned tiles and a ragged last one.
        x = rng.normal(50.0, 5.0, size=(16, 700)).astype(np.float32)
        ref = np.asarray(pd.taylor_tree(x, kernel="reference"))
        pal = np.asarray(
            pd.taylor_tree(x, kernel="pallas", interpret=True, tile=128))
        assert np.array_equal(ref, pal)

    def test_tree_path_shift_invariants(self):
        # Drift-d path: anchored at 0, monotone, total shift == d at the
        # last sample (the convention hits/frequencies decode under).
        for Tw in (2, 8, 32):
            for d in range(Tw):
                shifts = [pd.tree_path_shift(d, t, Tw) for t in range(Tw)]
                assert shifts[0] == 0
                assert shifts[-1] == d
                assert all(b - a in (0, 1)
                           for a, b in zip(shifts, shifts[1:]))

    def test_drift_spectra_negative_sign(self):
        # A tone walking DOWN the band shows up at negative drift,
        # anchored at its t=0 channel.
        Tw, F = 16, 128
        x = np.zeros((Tw, F), np.float32)
        d, f0 = 5, 80
        for t in range(Tw):
            x[t, f0 - pd.tree_path_shift(d, t, Tw)] = 1.0
        dd = np.asarray(pd.drift_spectra(x, kernel="reference"))
        assert dd.shape == (2 * Tw - 1, F)
        row, col = np.unravel_index(np.argmax(dd), dd.shape)
        assert pd.drift_rates(Tw)[row] == -d
        assert col == f0
        assert dd[row, col] == Tw

    def test_band_edge_paths_read_zeros(self):
        # A path running off the top of the band sums only its in-band
        # samples (the zero padding), never wraps onto low channels.
        Tw, F = 8, 16
        x = np.ones((Tw, F), np.float32)
        tree = np.asarray(pd.taylor_tree(x, kernel="reference"))
        brute = pd.brute_force_dedoppler(x).astype(np.float32)
        assert np.array_equal(tree, brute)
        # Max drift at the last channel: only the t=0 sample is in band.
        assert tree[Tw - 1, F - 1] == 1.0

    def test_window_validation(self):
        x = np.zeros((6, 8), np.float32)  # not a power of two
        with pytest.raises(ValueError):
            pd.taylor_tree(x, kernel="reference")
        with pytest.raises(ValueError):
            pd.dedoppler_hits(np.zeros((4, 10), np.float32),
                              np.float32(0), nbands=3, kernel="reference")


class TestHitExtraction:
    def test_per_band_top_k_and_threshold(self):
        Tw, F, k = 8, 64, 3
        rng = np.random.default_rng(2)
        x = rng.normal(10, 1, size=(Tw, F)).astype(np.float32)
        d, f0 = 3, 10
        for t in range(Tw):
            x[t, f0 + pd.tree_path_shift(d, t, Tw)] += 25.0
        packed = np.asarray(pd.dedoppler_hits(
            x, np.float32(5.0), top_k=k, nbands=2, kernel="reference"))
        assert packed.shape == (2, k, pd.HIT_PACK_COLS)
        snr, power, drift, chan, band = pd.unpack_hits(packed)
        # The tone dominates band 0; sub-threshold cells were sentineled
        # on device and dropped by the decode.
        assert len(snr) >= 1
        assert drift[0] == d and chan[0] == f0 and band[0] == 0
        assert np.all(snr >= 5.0)

    def test_max_drift_mask(self):
        Tw, F = 8, 64
        x = np.zeros((Tw, F), np.float32)
        d, f0 = 6, 20
        for t in range(Tw):
            x[t, f0 + pd.tree_path_shift(d, t, Tw)] = 50.0
        packed = np.asarray(pd.dedoppler_hits(
            x, np.float32(0.0), top_k=4, nbands=1, max_drift_bins=3,
            kernel="reference"))
        _, _, drift, _, _ = pd.unpack_hits(packed)
        assert np.all(np.abs(drift) <= 3)


class TestInjectedToneRecovery:
    """The drifting-tone injector closes the loop: known (f₀, ḟ, SNR)
    in, top hit out, within one drift step and one channel."""

    @pytest.mark.parametrize("drift_bins", [0, 3, -3])
    def test_recovers_injected_drift(self, tmp_path, drift_bins):
        raw = tmp_path / "tone.raw"
        _synth(raw, windows=2, tone_chan=1, drift_bins=drift_bins,
               tone_amp=30.0)
        red = _reducer(snr_threshold=6.0)
        hdr, hits = red.search(str(raw))
        assert hdr["search_windows"] == 2
        assert hits, "injected tone produced no hits"
        top = max(hits, key=lambda h: h.snr)
        assert abs(top.drift_bins - drift_bins) <= 1
        # The tone sits in coarse channel 1 (one band per coarse chan).
        assert top.band == 1
        # Physical decode is self-consistent with the header.
        assert top.freq_mhz == pytest.approx(
            hdr["fch1"] + top.chan * hdr["foff"])
        if drift_bins:
            assert np.sign(top.drift_hz_s) == np.sign(
                drift_bins * hdr["foff"])

    def test_recovers_through_worker_pool(self, tmp_path):
        # The pool path (ISSUE 6 acceptance): the same recovery through
        # workers.search_raw fanned out on a WorkerPool — hit records
        # cross the wire as plain dicts.
        from blit import workers
        from blit.parallel.pool import WorkerPool
        from blit.search.hits import hit_from_record

        raw = tmp_path / "tone.raw"
        _synth(raw, windows=2, tone_chan=1, drift_bins=3, tone_amp=30.0)
        with WorkerPool(["w1"], backend="thread") as pool:
            (res,) = pool.run_on(
                [1], workers.search_raw, [(str(raw),)],
                kwargs=dict(nfft=NFFT, window_spectra=T, top_k=4,
                            snr_threshold=6.0, kernel="reference"),
            )
        hdr, records = res
        hits = [hit_from_record(r) for r in records]
        assert hits, "pool search produced no hits"
        top = max(hits, key=lambda h: h.snr)
        assert abs(top.drift_bins - 3) <= 1 and top.band == 1

    def test_recovery_through_pallas_interpret(self, tmp_path):
        raw = tmp_path / "tone.raw"
        _synth(raw, windows=2, tone_chan=0, drift_bins=2, tone_amp=30.0)
        red = _reducer(kernel="pallas", interpret=True, snr_threshold=6.0)
        _, hits = red.search(str(raw))
        top = max(hits, key=lambda h: h.snr)
        assert abs(top.drift_bins - 2) <= 1 and top.band == 0


class TestHitsIO:
    def _hits(self, n=3):
        return [
            Hit(snr=10.0 + i, power=5.0, drift_bins=i - 1, chan=100 + i,
                band=0, window=0, t_start=0, freq_mhz=8000.5,
                drift_hz_s=0.25 * i)
            for i in range(n)
        ]

    def test_write_read_roundtrip(self, tmp_path):
        path = str(tmp_path / "x.hits")
        hdr = {"nchans": 256, "search_window_spectra": T}
        write_hits(path, hdr, self._hits())
        rh, rhits = read_hits(path)
        assert rh["nchans"] == 256
        assert rhits == self._hits()
        assert not os.path.exists(path + ".partial")

    def test_atomic_publish_and_abort(self, tmp_path):
        path = str(tmp_path / "x.hits")
        w = HitsWriter(path, {"search_window_spectra": T})
        w.append(WindowHits(0, self._hits()))
        # Not yet published: only the .partial exists.
        assert not os.path.exists(path) and os.path.exists(path + ".partial")
        w.abort()
        assert not os.path.exists(path + ".partial")

    def test_resumable_truncates_unclaimed_tail(self, tmp_path):
        path = str(tmp_path / "x.hits")
        hdr = {"search_window_spectra": T}
        cur = SearchCursor("r.raw", NFFT, 4, 1, window_spectra=T)
        w = ResumableHitsWriter(path, hdr, 0, cur)
        w.append(WindowHits(0, self._hits()))
        claimed = os.path.getsize(path)
        # Simulate a crash mid-window-1: bytes past the cursor's claim.
        with open(path, "a") as f:
            f.write("GARBAGE NOT JSON\n")
        w.abort()
        cur2 = SearchCursor.load(path)
        assert cur2 is not None and cur2.windows_done == 1
        w2 = ResumableHitsWriter(path, hdr, cur2.windows_done, cur2)
        assert os.path.getsize(path) == claimed
        w2.close()
        assert not os.path.exists(SearchCursor.path_for(path))

    def test_dense_encoding_roundtrip_large_chan(self):
        # Hi-res channel indices exceed f32's 2^24 integer range; the
        # split encoding must stay exact.
        hdr = {"fch1": 8437.5, "foff": -1e-6, "tsamp": 0.5,
               "search_window_spectra": 16}
        hits = [
            Hit(snr=12.5, power=3.0, drift_bins=-7, chan=(1 << 26) + 12345,
                band=63, window=9, t_start=144,
                freq_mhz=8437.5 + ((1 << 26) + 12345) * -1e-6,
                drift_hz_s=-7 * -1e-6 * 1e6 / (15 * 0.5)),
        ]
        arr = hits_to_array(hits)
        assert arr.shape == (1, 1, 8) and arr.dtype == np.float32
        assert hits_from_array(arr, hdr) == hits


class TestDedopplerReducer:
    def test_sync_async_hits_products_byte_identical(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, windows=3, tone_chan=1)
        out_a = str(tmp_path / "a.hits")
        out_s = str(tmp_path / "s.hits")
        _reducer().search_to_file(str(raw), out_a)
        _reducer(async_output=False).search_to_file(str(raw), out_s)
        with open(out_a, "rb") as fa, open(out_s, "rb") as fs:
            assert fa.read() == fs.read()

    def test_blit_sync_output_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_SYNC_OUTPUT", "1")
        red = _reducer()
        assert red.async_output is False

    def test_resume_replay_reproduces_bytes(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, windows=3, tone_chan=0)
        ref = str(tmp_path / "ref.hits")
        _reducer().search_to_file(str(raw), ref)

        # Simulate an interrupted resumable run: window 0 durable, then
        # crash (abort keeps file + cursor as the resume point).
        out = str(tmp_path / "res.hits")
        red = _reducer()
        from blit.io.guppi import open_raw

        hdr = red.header_for(open_raw(str(raw)))
        stream = red._search_stream(open_raw(str(raw)), hdr)
        first = next(stream)[1]
        stream.close()  # tear the feed down before the resumed run
        from blit.pipeline import ReductionCursor

        size, mtime = ReductionCursor.stat_raw(str(raw))
        cur = SearchCursor(
            str(raw), NFFT, 4, 1, window_spectra=T, top_k=4,
            snr_threshold=2.0, raw_size=size, raw_mtime_ns=mtime)
        w = ResumableHitsWriter(out, hdr, 0, cur)
        w.append(WindowHits(0, first))
        w.abort()

        # The resumed run skips window 0 via the skip-frames replay and
        # finishes the product byte-identical to the uninterrupted one.
        hdr2 = _reducer().search_resumable(str(raw), out)
        assert hdr2["search_windows"] == 3
        with open(ref, "rb") as fr, open(out, "rb") as fo:
            ref_bytes = fr.read()
            assert ref_bytes == fo.read()
        # search_nhits counts EVERY hit line in the finished product,
        # resumed windows included — not just this run's.
        assert hdr2["search_nhits"] == ref_bytes.count(b"\n") - 1
        assert not os.path.exists(SearchCursor.path_for(out))

    def test_kernel_choice_does_not_fork_product_bytes(self, tmp_path):
        # reference and pallas(interpret) are bitwise-identical by
        # construction, so the .hits product — header line included —
        # must not record (or fork on) the kernel choice.
        raw = tmp_path / "r.raw"
        _synth(raw, windows=2, tone_chan=1)
        out_r = str(tmp_path / "ref.hits")
        out_p = str(tmp_path / "pal.hits")
        _reducer(kernel="reference").search_to_file(str(raw), out_r)
        _reducer(kernel="pallas", interpret=True).search_to_file(
            str(raw), out_p)
        with open(out_r, "rb") as fr, open(out_p, "rb") as fp:
            assert fr.read() == fp.read()

    def test_resume_with_overlong_cursor_starts_fresh(self, tmp_path):
        # A cursor claiming more bytes than the file holds must not
        # truncate-EXTEND a NUL hole into the product: fresh start.
        raw = tmp_path / "r.raw"
        _synth(raw, windows=2, tone_chan=0)
        ref = str(tmp_path / "ref.hits")
        _reducer().search_to_file(str(raw), ref)
        out = str(tmp_path / "o.hits")
        _reducer().search_to_file(str(raw), out)
        from blit.pipeline import ReductionCursor

        size, mtime = ReductionCursor.stat_raw(str(raw))
        cur = SearchCursor(
            str(raw), NFFT, 4, 1, window_spectra=T, top_k=4,
            snr_threshold=2.0, windows_done=1,
            byte_offset=os.path.getsize(out) + 999,
            raw_size=size, raw_mtime_ns=mtime)
        cur.save(out)
        hdr = _reducer().search_resumable(str(raw), out)
        assert hdr["search_windows"] == 2
        with open(ref, "rb") as fr, open(out, "rb") as fo:
            assert fr.read() == fo.read()

    def test_resume_identity_mismatch_starts_fresh(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, windows=2, tone_chan=0)
        out = str(tmp_path / "o.hits")
        _reducer().search_resumable(str(raw), out)
        # A different SNR threshold is a different product: a stale
        # cursor must not graft onto it.
        red = _reducer(snr_threshold=3.0)
        cur = SearchCursor.load(out)
        assert cur is None  # completed: sidecar removed
        hdr = red.search_resumable(str(raw), out)
        assert hdr["search_snr_threshold"] == 3.0

    def test_multifile_sequence_and_window_split(self, tmp_path):
        # The same stream split across .NNNN.raw members searches
        # identically to the per-window decomposition: window w covers
        # spectra [wT, (w+1)T) wherever the file boundaries fall.
        paths, _ = synth_raw_sequence(
            str(tmp_path / "seq"), nfiles=2, blocks_per_file=1,
            obsnchan=2, ntime_per_block=(T * 2 + 3) * NFFT // 2 + NFFT,
            seed=3, tone_chan=1)
        hdr, hits = _reducer().search(paths)
        assert hdr["search_windows"] >= 2
        assert all(h.window < hdr["search_windows"] for h in hits)

    def test_search_telemetry(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, windows=2, tone_chan=0)
        from blit import observability

        red = _reducer(async_output=False)
        red.search(str(raw))
        hists = red.timeline.report()["hists"]
        assert "search.tree_s" in hists and hists["search.tree_s"]["n"] == 2
        assert "search.hits_per_window" in hists
        names = [s.name for s in observability.tracer().spans()]
        assert "search.stream" in names and "search.window" in names

    def test_empty_recording_rejected(self, tmp_path):
        p = tmp_path / "empty.raw"
        p.write_bytes(b"")
        with pytest.raises(ValueError):
            _reducer().search(str(p))


class TestSearchCursorDrills:
    """SearchCursor edge cases that landed untested in PR 6 (ISSUE 7
    satellite): the fsync-before-claim crash replay — bytes beyond the
    cursor's claim are truncated and re-reduced identically — and the
    truncate-beyond-EOF boundary, mirroring the ReductionCursor resume
    drills (tests/test_resume_fbh5.py)."""

    def _interrupted(self, tmp_path, claimed_windows=1):
        """A reference product plus an 'interrupted' resumable twin with
        ``claimed_windows`` durably claimed, returning
        ``(raw, ref_path, out_path, per_window_hits)``."""
        from blit.io.guppi import open_raw
        from blit.pipeline import ReductionCursor

        raw = tmp_path / "r.raw"
        _synth(raw, windows=3, tone_chan=0)
        ref = str(tmp_path / "ref.hits")
        _reducer().search_to_file(str(raw), ref)
        out = str(tmp_path / "res.hits")
        red = _reducer()
        hdr = red.header_for(open_raw(str(raw)))
        stream = red._search_stream(open_raw(str(raw)), hdr)
        per_window = []
        for _ in range(3):
            per_window.append(next(stream)[1])
        stream.close()
        size, mtime = ReductionCursor.stat_raw(str(raw))
        cur = SearchCursor(
            str(raw), NFFT, 4, 1, window_spectra=T, top_k=4,
            snr_threshold=2.0, raw_size=size, raw_mtime_ns=mtime)
        w = ResumableHitsWriter(out, hdr, 0, cur)
        for k in range(claimed_windows):
            w.append(WindowHits(k, per_window[k]))
        w.abort()
        return raw, ref, out, per_window

    def test_unclaimed_tail_truncated_and_replayed(self, tmp_path):
        # Crash AFTER window 1's lines hit the file but BEFORE the
        # cursor claimed them (the fsync-before-claim ordering's only
        # legal torn state): resume must truncate the unclaimed tail
        # and replay it, finishing byte-identical.
        raw, ref, out, per_window = self._interrupted(tmp_path)
        with open(out, "a") as f:
            f.write(WindowHits(1, per_window[1]).lines)
        hdr = _reducer().search_resumable(str(raw), out)
        assert hdr["search_windows"] == 3
        with open(ref, "rb") as fr, open(out, "rb") as fo:
            assert fr.read() == fo.read()
        assert not os.path.exists(SearchCursor.path_for(out))

    def test_torn_line_tail_truncated(self, tmp_path):
        # A crash mid-write leaves half a JSON line past the claim:
        # resume truncates it rather than splicing garbage mid-product.
        raw, ref, out, per_window = self._interrupted(tmp_path)
        with open(out, "a") as f:
            f.write(WindowHits(1, per_window[1]).lines[:17])
        hdr = _reducer().search_resumable(str(raw), out)
        assert hdr["search_windows"] == 3
        with open(ref, "rb") as fr, open(out, "rb") as fo:
            assert fr.read() == fo.read()

    def test_cursor_claim_exactly_at_eof_resumes(self, tmp_path):
        # The truncate-beyond-EOF guard is a strict inequality: a claim
        # equal to the file length is the CLEAN crash state and must
        # resume (not start fresh).
        raw, ref, out, _ = self._interrupted(tmp_path)
        cur = SearchCursor.load(out)
        assert cur.byte_offset == os.path.getsize(out)
        assert cur.windows_done == 1
        hdr = _reducer().search_resumable(str(raw), out)
        assert hdr["search_windows"] == 3
        # Resumed, not restarted: window 0 was not re-searched.
        with open(ref, "rb") as fr, open(out, "rb") as fo:
            assert fr.read() == fo.read()

    def test_cursor_one_byte_past_eof_starts_fresh(self, tmp_path):
        # One byte past EOF is already corrupt: POSIX truncate would
        # EXTEND a NUL hole into the product — must start fresh.
        raw, ref, out, _ = self._interrupted(tmp_path)
        cur = SearchCursor.load(out)
        cur.byte_offset = os.path.getsize(out) + 1
        cur.save(out)
        hdr = _reducer().search_resumable(str(raw), out)
        assert hdr["search_windows"] == 3
        with open(ref, "rb") as fr, open(out, "rb") as fo:
            assert fr.read() == fo.read()


class TestServiceHits:
    def test_hits_product_through_service_and_cache(self, tmp_path):
        from blit.serve import ProductRequest, ProductService
        from blit.serve.cache import ProductCache, fingerprint_for

        raw = str(tmp_path / "r.raw")
        _synth(raw, windows=2, tone_chan=1)
        tl = Timeline()
        req = ProductRequest(raw=raw, nfft=NFFT, kind="hits",
                             window_spectra=T, top_k=4, snr_threshold=2.0)
        # Search knobs separate the fingerprint from the filterbank ask
        # over the same bytes.
        fil = ProductRequest(raw=raw, nfft=NFFT)
        assert (fingerprint_for(req.reducer(), raw)
                != fingerprint_for(fil.reducer(), raw))
        with ProductService(
            cache=ProductCache(str(tmp_path / "cache"), timeline=tl),
            timeline=tl,
        ) as svc:
            hdr, data = svc.get(req, timeout=120)
            assert hdr["nchans"] == 8 and hdr["nifs"] == 1
            hits = hits_from_array(data, hdr)
            direct_hdr, direct = DedopplerReducer(
                nfft=NFFT, window_spectra=T, top_k=4, snr_threshold=2.0,
            ).search(raw)
            assert hits == direct
            # Second ask: served from cache, no reduction.
            t2 = svc.submit(req)
            assert t2.source in ("ram", "disk")
            hdr2, data2 = svc.result(t2)
            assert np.array_equal(data, data2)

    def test_request_validation(self):
        from blit.serve import ProductRequest

        with pytest.raises(ValueError):
            ProductRequest(raw="x.raw", top_k=4)  # search knob, no kind
        with pytest.raises(ValueError):
            ProductRequest(raw="x.raw", kind="hits", stokes="IQUV")
        with pytest.raises(ValueError):
            ProductRequest(raw="x.raw", kind="nope")


class TestSearchConfig:
    def test_env_overrides(self, monkeypatch):
        from blit.config import search_defaults

        base = search_defaults()
        monkeypatch.setenv("BLIT_SEARCH_WINDOW", "16")
        monkeypatch.setenv("BLIT_SEARCH_TOP_K", "3")
        monkeypatch.setenv("BLIT_SEARCH_SNR", "7.5")
        monkeypatch.setenv("BLIT_SEARCH_MAX_DRIFT", "5")
        d = search_defaults()
        assert d == {"window_spectra": 16, "top_k": 3,
                     "snr_threshold": 7.5, "max_drift_bins": 5}
        assert base["window_spectra"] == 64  # SiteConfig default

    def test_negative_max_drift_means_unlimited(self, monkeypatch):
        # Headers/cursors encode "no limit" as -1; feeding that back
        # (env, or knobs copied off a product header) must round-trip
        # to unlimited, not mask every drift row into zero hits.
        from blit.config import search_defaults

        monkeypatch.setenv("BLIT_SEARCH_MAX_DRIFT", "-1")
        assert search_defaults()["max_drift_bins"] is None
        red = DedopplerReducer(nfft=NFFT, max_drift_bins=-1)
        assert red.max_drift_bins is None
        assert red.fingerprint_extra()["max_drift_bins"] is None

    def test_reducer_resolves_defaults(self, monkeypatch):
        monkeypatch.setenv("BLIT_SEARCH_WINDOW", "16")
        monkeypatch.setenv("BLIT_SEARCH_SNR", "4.0")
        red = DedopplerReducer(nfft=NFFT)
        assert red.window_spectra == 16
        assert red.snr_threshold == 4.0
        assert red.fingerprint_extra()["window_spectra"] == 16


class TestSearchCLI:
    def test_search_smoke(self, tmp_path, capsys):
        raw = tmp_path / "r.raw"
        _synth(raw, windows=2, tone_chan=1, drift_bins=2, tone_amp=30.0)
        out = str(tmp_path / "o.hits")
        rc = main(["search", str(raw), "-o", out, "--nfft", str(NFFT),
                   "--window-spectra", str(T), "--snr", "6.0",
                   "--top-k", "4", "--kernel", "reference"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["output"] == out and doc["windows"] == 2
        assert doc["platform"] == "cpu"
        assert doc["dedoppler_plan"]["kernel"] == "reference"
        hdr, hits = read_hits(out)
        assert hdr["search_window_spectra"] == T
        assert len(hits) == doc["hits"]
        top = max(hits, key=lambda h: h.snr)
        assert abs(top.drift_bins - 2) <= 1

    def test_search_resume_flag(self, tmp_path, capsys):
        raw = tmp_path / "r.raw"
        _synth(raw, windows=2, tone_chan=0)
        out = str(tmp_path / "o.hits")
        rc = main(["search", str(raw), "-o", out, "--nfft", str(NFFT),
                   "--window-spectra", str(T), "--snr", "2.0", "--resume"])
        assert rc == 0
        assert os.path.exists(out)
        assert not os.path.exists(SearchCursor.path_for(out))
