"""An integration carried across the mesh's windows (ISSUE 30): ``blit
scan --nint`` beyond one window — rawspec's ``-f 1048576 -t 51`` at the
2-frame window four 16 GB chips hold — against the plain whole-file
reference, on four virtual CPU devices.

Each chip folds its own bank's spectra into a partial sum that stays on
the mesh (``parallel/mesh.band_carry``); nothing is gathered, fetched or
written until a row closes."""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit.io.guppi import GuppiRaw  # noqa: E402
from blit.io.sigproc import read_fil_data, read_fil_header  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.ops.channelize import (  # noqa: E402
    STOKES_NIF,
    channelize_np,
    pfb_coeffs,
)
from blit.parallel import mesh as M  # noqa: E402
from blit.parallel import scan as S  # noqa: E402
from blit.parallel.scan import (  # noqa: E402
    reduce_scan_mesh_to_files,
    reduce_scan_pool_to_files,
    scan_window_frames,
)
from blit.pipeline import ReductionCursor  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NFFT, NTAP, NBANK, NCHAN = 32, 4, 4, 2
# Scale-relative max error (max|got - want| / max|want|, compared in
# float64, as benchmark/check.py holds the chip) against channelize_np over
# each bank's whole file, stitched and despiked.  Both sides are float32
# arithmetic that differ in FFT rounding and in the order of the sum (one
# frame at a time on the mesh, numpy's pairwise in the reference):
# tests/test_integrate_carry.py reads 3e-9 to 1.8e-7 for the same pair.  A
# frame in the wrong row, added twice or not at all moves a tone's peak by
# 1/nint >= 2e-2 of itself here, and a bank stitched into the wrong place
# by all of it: 1e-5 is 50 times the worst reading and far under either.
TOL = 1e-5

CASES = [  # (nint, window_frames, whole rows, tail frames dropped)
    (51, 2, 1, 3),
    (51, 8, 2, 7),
    (7, 3, 4, 2),
    (6, 4, 5, 4),
]


def _ids(case):
    return "nint{0}-wf{1}".format(*case)


def make_band(tmp_path, frames, seed=0):
    """One band of four banks, each holding ``frames`` PFB frames and a
    part of one, tiling the band downwards in frequency."""
    total = (frames + NTAP - 1) * NFFT + NFFT // 2
    nblocks = 4
    bank_bw = -187.5 / NBANK
    row = []
    for k in range(NBANK):
        p = str(tmp_path / f"blc0{k}-{frames}-{seed}.raw")
        synth_raw(p, nblocks=nblocks, obsnchan=NCHAN,
                  ntime_per_block=-(-total // nblocks), seed=seed * 8 + k,
                  tone_chan=k % NCHAN, obsbw=bank_bw,
                  obsfreq=8000.0 + (k + 0.5) * bank_bw)
        row.append(p)
    return [row]


def reference(paths, nint, stokes, rows, despike):
    """channelize_np over each bank's whole file, stitched, despiked."""
    banks = []
    for p in paths[0]:
        stream = np.concatenate(
            [blk for _, blk in GuppiRaw(p).iter_blocks(drop_overlap=True)],
            axis=1)
        usable = (rows * nint + NTAP - 1) * NFFT
        banks.append(np.asarray(channelize_np(
            stream[:, :usable], pfb_coeffs(NTAP, NFFT), nfft=NFFT,
            ntap=NTAP, nint=nint, stokes=stokes), np.float64))
    want = np.concatenate(banks, axis=-1)
    if despike:
        want[..., NFFT // 2::NFFT] = want[..., NFFT // 2 - 1::NFFT]
    return want


def rel_err(got, want):
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


def windows_of(total, wf, nint):
    """(windows, windows that end with the integration open)."""
    ends = list(range(wf, total, wf)) + [total]
    return len(ends), sum(e % nint != 0 for e in ends)


def payload(path):
    if path.endswith(".h5"):
        from blit.io.fbh5 import read_fbh5_data

        return np.asarray(read_fbh5_data(path)).tobytes()
    with open(path, "rb") as f:
        return f.read()


def test_one_rule_for_the_window():
    # fold_frames decides for the default; an explicit window below one
    # integration is a measured bound and is kept as given.
    assert scan_window_frames(1 << 20, 51) == 8          # not 51
    assert scan_window_frames(1 << 20, 51, 2) == 2       # the cell
    assert scan_window_frames(1 << 20, 51, 60) == 60     # carried, 2 rows
    assert scan_window_frames(1 << 20, 1, 2) == 2
    assert scan_window_frames(1 << 20, 8) == 8
    assert scan_window_frames(1024, 51) == (8192 // 51) * 51
    assert scan_window_frames(1024, 51, 2) == 2          # the rehearsal
    assert scan_window_frames(1024, 51, 120) == 102      # whole rows
    assert scan_window_frames(64, 2, 5) == 4
    assert scan_window_frames(64, 2, 4) == 4


@pytest.mark.parametrize("despike", [True, False], ids=["despike", "raw"])
@pytest.mark.parametrize("stokes", ["I", "IQUV"])
@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_carried_scan_matches_whole_file(tmp_path, monkeypatch, case, stokes,
                                         despike):
    nint, wf, rows, tail = case
    paths = make_band(tmp_path, rows * nint + tail)
    nif, nchans = STOKES_NIF[stokes], NBANK * NCHAN * NFFT
    kw = dict(nfft=NFFT, nint=nint, stokes=stokes, despike=despike,
              window_frames=wf)
    tl = Timeline()
    out = str(tmp_path / "mesh.fil")
    seen = []
    check = M.ShardedAccumulator._check

    def watched(self, value):
        seen.append(self.rule)
        return check(self, value)

    monkeypatch.setattr(M.ShardedAccumulator, "_check", watched)
    written = reduce_scan_mesh_to_files(paths, out_paths=[out], timeline=tl,
                                        **kw)
    monkeypatch.undo()
    hdr = written[0][1]
    fhdr, got = read_fil_data(out)
    want = reference(paths, nint, stokes, rows, despike)
    # Whole rows only: the tail that fills no integration is dropped.
    assert got.shape == want.shape == (rows, nif, nchans)
    assert hdr["nsamps"] == fhdr["nsamps"] == rows
    assert fhdr["nchans"] == nchans
    tbin = GuppiRaw(paths[0][0]).header(0)["TBIN"]
    assert fhdr["tsamp"] == pytest.approx(tbin * NFFT * nint, rel=1e-12)
    assert rel_err(got, want) < TOL
    if despike:
        np.testing.assert_array_equal(got[..., NFFT // 2::NFFT],
                                      got[..., NFFT // 2 - 1::NFFT])
    # The counters: a window that closes nothing fetches and writes
    # nothing, and the accumulator kept its rule through every fold.
    st = tl.report()
    nwin, open_ = windows_of(rows * nint, wf, nint)
    assert st["ingest"]["calls"] == st["device"]["calls"] == nwin
    assert st["integrate.carry"]["calls"] == open_
    assert st["integrate.carry"]["bytes"] == open_ * nif * nchans * 4
    assert st["integrate.emit"]["calls"] == rows
    assert st["integrate.emit"]["bytes"] == got.nbytes
    assert st["readback"]["calls"] == st["write"]["calls"] == rows < nwin
    assert st["readback"]["bytes"] == got.nbytes
    assert seen.count("integration_acc") == 1 + nwin
    # ... as each bank's filter state kept its own: up from the host once,
    # then the previous window's output (ISSUE 31), every sample of the
    # scan put once.
    assert seen.count("filter_state") == 1 + nwin
    tail = NBANK * NCHAN * (NTAP - 1) * NFFT * 4
    assert (st["state.head"]["calls"], st["state.head"]["bytes"]) \
        == (NBANK, tail)
    assert (st["state.carry"]["calls"], st["state.carry"]["bytes"]) \
        == (NBANK * (nwin - 1), tail * (nwin - 1))
    fed = NBANK * NCHAN * (rows * nint + NTAP - 1) * NFFT * 4
    assert st["link.put"]["bytes"] == st["ingest"]["bytes"] == fed
    assert st["link.put"]["calls"] == NBANK * (nwin + 1)
    # The same RAW bytes give the same product bytes ...
    again = str(tmp_path / "again.fil")
    reduce_scan_mesh_to_files(paths, out_paths=[again], **kw)
    assert payload(again) == payload(out)
    assert not os.path.exists(again + ".partial")
    # ... and the pool oracle's (per-bank RawReducer at chunk_frames = the
    # window, host stitch and despike).
    pool = str(tmp_path / "pool.fil")
    reduce_scan_pool_to_files(paths, out_paths=[pool], **kw)
    assert payload(pool) == payload(out)


@pytest.mark.parametrize("stokes", ["I", "IQUV"])
@pytest.mark.parametrize("nint,wf", [(2, 4), (4, 4), (1, 3), (2, 5)])
def test_integration_inside_a_window_takes_the_old_path(tmp_path, nint, wf,
                                                        stokes):
    # nint | window: no accumulator, no integrate.* row, every window
    # gathers and writes, and the bytes are those of band_reduce at that
    # nint, window by window (the path every existing product takes).
    import jax.numpy as jnp

    frames = 13
    paths = make_band(tmp_path, frames, seed=2)
    tl = Timeline()
    out = str(tmp_path / "mesh.fil")
    reduce_scan_mesh_to_files(paths, out_paths=[out], nfft=NFFT, nint=nint,
                              stokes=stokes, window_frames=wf, timeline=tl)
    st = tl.report()
    assert "integrate.carry" not in st and "integrate.emit" not in st
    eff = scan_window_frames(NFFT, nint, wf)
    assert eff % nint == 0
    total = frames // nint * nint
    assert st["readback"]["calls"] == st["ingest"]["calls"] \
        == -(-total // eff)
    mesh = M.make_mesh(1, NBANK)
    streams = [np.concatenate(
        [blk for _, blk in GuppiRaw(p).iter_blocks(drop_overlap=True)],
        axis=1) for p in paths[0]]
    h = jnp.asarray(pfb_coeffs(NTAP, NFFT))
    want = []
    for f0 in range(0, total, eff):
        n = min(eff, total - f0)
        volt = np.stack([s[:, f0 * NFFT:(f0 + n + NTAP - 1) * NFFT]
                         for s in streams])[None]
        want.append(np.asarray(M.band_reduce(
            M.shard_voltages(volt, mesh), h, mesh=mesh, nfft=NFFT,
            ntap=NTAP, nint=nint, stokes=stokes, stitch=True,
            despike_nfpc=NFFT))[0])
    assert read_fil_data(out)[1].tobytes() == np.concatenate(want).tobytes()


def test_a_second_pass_allocates_no_staging_slab(tmp_path, monkeypatch):
    # The cell's shape of scan: full windows and a ragged last one (51 =
    # 25 x 2 + 1).  Every window stages through slabs of the full window's
    # shape, so from the second reduction on the pool hands back memory
    # that is already faulted: nothing allocated, nothing dropped.
    from blit import hostmem

    monkeypatch.setattr(hostmem, "_POOL", hostmem.SlabPool())  # an empty one
    paths = make_band(tmp_path, 51 + 3, seed=4)
    tables = []
    for tag in ("a", "b", "c"):
        tl = Timeline()
        reduce_scan_mesh_to_files(
            paths, out_paths=[str(tmp_path / f"{tag}.fil")], nfft=NFFT,
            nint=51, window_frames=2, timeline=tl)
        tables.append(tl.report())
    # Three windows alive (on the chips, being put, being read a window
    # ahead), and the stream's head beside the first.
    assert 2 * NBANK < tables[0]["staging.alloc"]["calls"] <= 4 * NBANK
    for st in tables[1:]:
        assert st["staging.alloc"]["calls"] == 0
        assert st["staging.drop"]["calls"] == 0
        assert st["staging.reuse"]["calls"] == (26 + 1) * NBANK
    assert hostmem.slab_pool().stats()["lent_bytes"] == 0
    assert payload(str(tmp_path / "a.fil")) == payload(str(tmp_path / "c.fil"))


def test_the_cells_own_grid():
    # band4.hires51: 51 frames in 2-frame windows are 25 windows that
    # leave the integration open and a one-frame window that closes the
    # row; the link carries the 3-frame head and every window's new
    # frames, 54 of the recording's 54 (until ISSUE 31 each window re-sent
    # its 3-frame prologue: (25 * 5 + 4) / 54 = 2.389 of it).
    assert windows_of(51, 2, 51) == (26, 25)
    assert scan_window_frames(1 << 20, 51, 2) == 2
    assert (NTAP - 1) + 25 * 2 + 1 == 54
    assert round(1000 * (25 * 5 + 4) / 54) == 2389


class TestResumeInsideAnIntegration:
    """``--resume`` keeps whole rows only: a run killed between two rows or
    inside one resumes at the last whole row (no longer a window boundary)
    and ends with the uninterrupted run's bytes."""

    NINT, WF, ROWS, TAIL = 7, 3, 5, 2

    def _run(self, paths, out, **kw):
        tl = Timeline()
        written = reduce_scan_mesh_to_files(
            paths, out_paths=[out], nfft=NFFT, nint=self.NINT,
            window_frames=self.WF, resume=True, timeline=tl, **kw)
        return written, tl.report()

    @pytest.mark.parametrize("ext,comp", [(".fil", None), (".h5", None),
                                          (".h5", "bitshuffle")])
    @pytest.mark.parametrize("where", ["between_rows", "inside_a_row"])
    def test_resumed_bytes_equal_uninterrupted(self, tmp_path, monkeypatch,
                                               ext, comp, where):
        paths = make_band(tmp_path, self.ROWS * self.NINT + self.TAIL,
                          seed=3)
        ref = str(tmp_path / ("ref" + ext))
        self._run(paths, ref, compression=comp)
        out = str(tmp_path / ("res" + ext))
        real, windows = S._read_window, []

        def dying(raws, local, nchan, npol, start, ntime, *a, **k):
            # A window is fed its NEW samples: those of its first frame
            # start NTAP - 1 frames into that frame's filter window.
            windows.append(start // NFFT - (NTAP - 1))
            # Window 5 starts at frame 15 (row 2 closed at 14: killed
            # between two rows, its flush still pending); window 6 at
            # frame 18, inside row 2..3's integration.
            if len(windows) == (6 if where == "between_rows" else 7):
                raise RuntimeError("killed")
            return real(raws, local, nchan, npol, start, ntime, *a, **k)

        monkeypatch.setattr(S, "_read_window", dying)
        with pytest.raises(RuntimeError, match="killed"):
            self._run(paths, out, compression=comp)
        monkeypatch.undo()
        cur = ReductionCursor.load(out)
        # Whole rows only; and the claim is no window boundary.
        assert cur is not None and cur.frames_done % self.NINT == 0
        assert 0 < cur.frames_done < self.ROWS * self.NINT
        assert cur.frames_done % self.WF != 0
        fed_before = len(windows) - 1
        assert windows[:fed_before] == list(range(0, fed_before * self.WF,
                                                  self.WF))
        written, st = self._run(paths, out, compression=comp)
        assert written[0][1]["nsamps"] == self.ROWS
        # A resumed stream starts with a head of its own, read at the
        # claimed row, and carries its filter state from there.
        left = self.ROWS * self.NINT - cur.frames_done
        assert st["state.head"]["calls"] == NBANK
        assert st["state.carry"]["calls"] \
            == NBANK * (-(-left // self.WF) - 1)
        assert st["link.put"]["bytes"] == st["ingest"]["bytes"] \
            == NBANK * NCHAN * (left + NTAP - 1) * NFFT * 4
        # Resumed at the claimed row, not restarted ...
        assert st["integrate.emit"]["calls"] \
            == self.ROWS - cur.frames_done // self.NINT
        # ... and still the uninterrupted run's bytes.
        assert payload(out) == payload(ref)
        assert not os.path.exists(ReductionCursor.path_for(out))

    def test_bitshuffle_chunks_hold_one_row_when_carried(self, tmp_path,
                                                         caplog):
        # A window holds no whole row (window_frames // nint = 0): the
        # bitshuffle product is chunked one row at a time — said once, as
        # a warning — and any whole row is a resume point.
        import logging

        assert S._bitshuffle_window_chunk_rows(16, 0) == 1
        paths = make_band(tmp_path, 2 * self.NINT)
        out = str(tmp_path / "one.h5")
        with caplog.at_level(logging.WARNING, logger="blit.scan"):
            self._run(paths, out, compression="bitshuffle")
        assert any("chunk rows are 1" in r.message for r in caplog.records)
        import h5py

        with h5py.File(out, "r") as f:
            assert f["data"].chunks[0] == 1
            assert f["data"].shape[0] == 2


def test_sharded_plane_refuses_by_name(tmp_path):
    # --sharded shares no window program with the default loop: it
    # refuses an integration it would have to carry, naming the loop that
    # does — never a window rounded up to nint.
    from blit.parallel.sharded import reduce_scan_sharded_to_files

    paths = make_band(tmp_path, 16)
    with pytest.raises(ValueError, match="default mesh loop"):
        reduce_scan_sharded_to_files(
            paths, out_paths=[str(tmp_path / "s.fil")], nfft=NFFT, nint=7,
            window_frames=3)
    # Where the integration fits the window it runs as before.
    out = str(tmp_path / "s.fil")
    reduce_scan_sharded_to_files(paths, out_paths=[out], nfft=NFFT, nint=2,
                                 window_frames=4)
    mesh = str(tmp_path / "m.fil")
    reduce_scan_mesh_to_files(paths, out_paths=[mesh], nfft=NFFT, nint=2,
                              window_frames=4)
    assert payload(out) == payload(mesh)


def test_header_of_a_carried_product(tmp_path):
    paths = make_band(tmp_path, 60)
    out = str(tmp_path / "b.fil")
    reduce_scan_mesh_to_files(paths, out_paths=[out], nfft=NFFT, nint=51,
                              window_frames=2)
    hdr, _ = read_fil_header(out)
    assert hdr["nchans"] == NBANK * NCHAN * NFFT and hdr["nifs"] == 1
    assert abs(hdr["foff"]) * hdr["nchans"] == pytest.approx(187.5)


# -- the band's filter state stays on the chips (ISSUE 31) -------------------
#
# A window reads and puts its NEW frames only, as sample words; each bank's
# filter state is the previous window's donated output
# (``parallel/mesh.band_stream``).  The stitched windows (``nint`` divides
# the window) and the carried ones take the one feed.

STREAM_CASES = [  # (nint, window_frames, frames in the file, last window)
    (51, 2, 54, 1),   # band4.hires51's grid: a 1-frame last window,
    (7, 3, 30, 1),    # ... its body shorter than the 3-frame state
    (7, 5, 30, 3),    # a ragged last window, carried
    (1, 2, 7, 1),     # band4.hires's path: every window stitches
    (1, 3, 8, 2),     # ... and a ragged last one
    (4, 4, 14, 4),    # whole windows of one row each
]


@pytest.mark.parametrize("ext", [".fil", ".h5"])
@pytest.mark.parametrize("case", STREAM_CASES, ids=_ids)
def test_every_sample_goes_up_once(tmp_path, case, ext):
    nint, wf, frames, last = case
    paths = make_band(tmp_path, frames, seed=5)
    rows = frames // nint
    total = rows * nint
    nwin = -(-total // wf)
    assert total - (nwin - 1) * wf == last
    kw = dict(nfft=NFFT, nint=nint, window_frames=wf)
    tl = Timeline()
    out = str(tmp_path / ("mesh" + ext))
    reduce_scan_mesh_to_files(paths, out_paths=[out], timeline=tl, **kw)
    # The pool oracle's bytes (per-bank RawReducer, host stitch) ...
    pool = str(tmp_path / ("pool" + ext))
    reduce_scan_pool_to_files(paths, out_paths=[pool], **kw)
    assert payload(out) == payload(pool)
    # ... and the whole-file reference's spectra.
    got = np.frombuffer(payload(out)[-rows * NBANK * NCHAN * NFFT * 4:],
                        np.float32).reshape(rows, 1, -1)
    assert rel_err(got, reference(paths, nint, "I", rows, True)) < TOL
    st = tl.report()
    tail = NBANK * NCHAN * (NTAP - 1) * NFFT * 4
    fed = NBANK * NCHAN * (total + NTAP - 1) * NFFT * 4
    assert st["ingest"]["calls"] == nwin
    assert st["link.put"]["bytes"] == st["ingest"]["bytes"] == fed
    assert st["feed.read"]["bytes"] == st["feed.put"]["bytes"] == fed
    assert st["link.put"]["calls"] == NBANK * (nwin + 1)
    assert (st["state.head"]["calls"], st["state.head"]["bytes"]) \
        == (NBANK, tail)
    if nwin > 1:
        assert (st["state.carry"]["calls"], st["state.carry"]["bytes"]) \
            == (NBANK * (nwin - 1), tail * (nwin - 1))
    else:
        assert "state.carry" not in st


@pytest.mark.parametrize("case", STREAM_CASES[:4], ids=_ids)
def test_the_filter_state_is_held_once(tmp_path, monkeypatch, case):
    # The tail a window hands to its program is deleted by it (donated:
    # the next tail takes its place), and what comes back is laid out by
    # the ``filter_state`` rule, one bank a chip.
    nint, wf, frames, _ = case
    paths = make_band(tmp_path, frames, seed=6)
    real, tails = M.band_stream, []

    def watched(tail, body, coeffs, **kw):
        out, nxt = real(tail, body, coeffs, **kw)
        mesh = kw["mesh"]
        assert tail.is_deleted() and not body.is_deleted()
        assert nxt.shape == (1, NBANK, NCHAN, (NTAP - 1) * NFFT)
        assert nxt.dtype == body.dtype == np.int32  # words
        assert nxt.sharding.is_equivalent_to(
            M.sharding_for(mesh, "filter_state"), nxt.ndim)
        assert {s.device for s in nxt.addressable_shards} \
            == set(mesh.devices.flat)
        tails.append((tail, nxt))
        return out, nxt

    monkeypatch.setattr(M, "band_stream", watched)
    reduce_scan_mesh_to_files(paths, out_paths=[str(tmp_path / "m.fil")],
                              nfft=NFFT, nint=nint, window_frames=wf)
    assert len(tails) == -(-(frames // nint * nint) // wf) > 1
    for w in range(1, len(tails)):
        # A window's state IS the last window's second output, and is
        # gone once its program has it.
        assert tails[w][0] is tails[w - 1][1]
        assert tails[w - 1][1].is_deleted()
    assert not tails[-1][1].is_deleted()


@pytest.mark.parametrize("stitch", [True, False], ids=["stitched", "sharded"])
@pytest.mark.parametrize("frames", [1, 2, 3, 5])
def test_band_stream_is_band_reduce_of_the_gross_block(frames, stitch):
    # One program per window either way; the stream's reads the gross
    # block as (state on the chip, new samples as words).  A body shorter
    # than the state keeps part of the old tail.
    import jax.numpy as jnp

    from blit.ops.channelize import sample_words

    mesh = M.make_mesh(1, NBANK)
    rng = np.random.default_rng(frames)
    ntail = (NTAP - 1) * NFFT
    gross = rng.integers(-40, 40, (1, NBANK, NCHAN, ntail + frames * NFFT,
                                   2, 2), np.int8)
    h = jnp.asarray(pfb_coeffs(NTAP, NFFT))
    kw = dict(mesh=mesh, nfft=NFFT, ntap=NTAP, nint=1, stitch=stitch,
              despike_nfpc=NFFT)
    want = np.asarray(M.band_reduce(M.shard_voltages(gross, mesh), h, **kw))
    words = np.stack([sample_words(gross[0, k]) for k in range(NBANK)])[None]
    tail = M.shard_voltages(np.ascontiguousarray(words[..., :ntail]), mesh)
    body = M.shard_voltages(np.ascontiguousarray(words[..., ntail:]), mesh)
    out, nxt = M.band_stream(tail, body, h, **kw)
    assert np.asarray(out).tobytes() == want.tobytes()
    np.testing.assert_array_equal(np.asarray(nxt), words[..., -ntail:])
    assert tail.is_deleted()


def test_the_budget_lets_go_of_a_head_before_it_is_donated(monkeypatch):
    # A stream's heads are transfers of their own on the link budget, and
    # a handle the budget holds must never be donated (its ``is_ready()``
    # would race the deletion): by the time ``_put_window`` hands the
    # tail over, the heads have landed and the budget holds none of them.
    from blit import device

    mesh = M.make_mesh(1, NBANK)
    link = device.HostLink()
    monkeypatch.setattr(device, "_HOST_LINK", link)
    monkeypatch.setattr(device, "host_link_bytes", lambda: 1 << 40)
    rng = np.random.default_rng(0)
    streams = {(0, k): rng.integers(-9, 9, (NCHAN, 5 * NFFT, 2, 2), np.int8)
               for k in range(NBANK)}

    def gapless(raw, n, skip=0, out=None):
        return np.ascontiguousarray(raw[:, skip:skip + n])

    monkeypatch.setattr(S, "_gapless", gapless)
    tl = Timeline()
    tail, body = S._put_window(
        *S._read_window(streams, sorted(streams), NCHAN, 2, 3 * NFFT,
                        2 * NFFT, tl, head_ntime=3 * NFFT), mesh, tl)
    held = [a for handle, _ in link._puts
            for a in jax.tree_util.tree_leaves(handle)]
    mine = {id(s.data) for s in tail.addressable_shards}
    assert not any(id(a) in mine for a in held)
    assert tail.sharding.is_equivalent_to(
        M.sharding_for(mesh, "filter_state"), tail.ndim)
    from blit.ops.channelize import sample_words

    for k in range(NBANK):
        words = sample_words(streams[(0, k)])
        np.testing.assert_array_equal(np.asarray(tail)[0, k],
                                      words[:, :3 * NFFT])
        np.testing.assert_array_equal(np.asarray(body)[0, k],
                                      words[:, 3 * NFFT:])
    st = tl.report()
    assert st["feed.put"]["calls"] == st["link.put"]["calls"] == 2 * NBANK
    assert st["link.put"]["bytes"] == st["feed.read"]["bytes"] \
        == sum(v.nbytes for v in streams.values())


def test_a_stitched_scan_allocates_nothing_the_second_time(tmp_path,
                                                           monkeypatch):
    # band4.hires's shape of scan (nint 1, 2-frame windows, a 1-frame
    # last): head and body slabs are back in the pool when a scan ends.
    from blit import hostmem

    monkeypatch.setattr(hostmem, "_POOL", hostmem.SlabPool())
    paths = make_band(tmp_path, 7, seed=7)
    tables = []
    for tag in ("a", "b"):
        tl = Timeline()
        reduce_scan_mesh_to_files(
            paths, out_paths=[str(tmp_path / f"{tag}.fil")], nfft=NFFT,
            nint=1, window_frames=2, timeline=tl)
        tables.append(tl.report())
    assert 2 * NBANK < tables[0]["staging.alloc"]["calls"] <= 4 * NBANK
    assert tables[1]["staging.alloc"]["calls"] == 0
    assert tables[1]["staging.drop"]["calls"] == 0
    assert tables[1]["staging.reuse"]["calls"] == (4 + 1) * NBANK
    assert hostmem.slab_pool().stats()["lent_bytes"] == 0
    assert payload(str(tmp_path / "a.fil")) == payload(str(tmp_path / "b.fil"))


# -- ISSUE 45: the read runs a window ahead, on a thread of its own ------------
#
# The feed thread reads window N+1 into pooled slabs while the loop puts
# and dispatches window N and window N-1 runs on the chips.  It touches no
# device: the puts, the program calls, the fetches and the appends are the
# loop's, in the serial feed's order, so the bytes cannot depend on how
# far ahead the read is.

def feed_threads():
    import threading

    return [t for t in threading.enumerate()
            if t.name.startswith("blit-feed") and t.is_alive()]


def left_behind(tmp_path):
    return sorted(f for f in os.listdir(tmp_path)
                  if f.startswith(("out", "band")))


KINDS = {  # what `blit scan` is asked for
    "carried-ragged": dict(nint=7, window_frames=3),
    "stitched-max_frames": dict(nint=1, window_frames=2, max_frames=9),
    "three-products": dict(nint=3, also=((8, 16), (16, 5)),
                           window_frames=2),
}


def scan_to(tmp_path, paths, tag, kw, tl=None):
    """One scan -> the bytes of every product it wrote."""
    kw = dict(kw, nfft=NFFT, timeline=tl)
    if "also" in kw:
        out = tmp_path / f"out-{tag}"
        out.mkdir()
        written = reduce_scan_mesh_to_files(paths, out_dir=str(out), **kw)
        return [payload(p) for p, _ in written[0]]
    out = str(tmp_path / f"out-{tag}.fil")
    reduce_scan_mesh_to_files(paths, out_paths=[out], **kw)
    return [payload(out)]


@pytest.mark.parametrize("pace", ["lockstep", "late"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_bytes_do_not_depend_on_how_far_ahead_the_read_is(
        tmp_path, monkeypatch, kind, pace):
    import sys
    import time

    paths = make_band(tmp_path, 30, seed=8)
    free = scan_to(tmp_path, paths, "free", KINDS[kind])
    real, tl = S._read_window, Timeline()

    def paced(*a, **k):
        if pace == "late":  # the loop waits for its windows
            time.sleep(0.01)
        else:
            # The serial feed's order: window k was read once window k-2
            # had been waited out and written (its `device` wait is the
            # k-1-th).  This `ingest` is still open: k of them are closed.
            k_th, until = tl.stages["ingest"].calls, time.time() + 60
            while tl.stages["device"].calls < k_th - 1:
                assert time.time() < until, "the loop never got there"
                time.sleep(0.001)
        return real(*a, **k)

    monkeypatch.setattr(S, "_read_window", paced)
    # (The two threads change places as often as the interpreter can.)
    every = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        assert scan_to(tmp_path, paths, pace, KINDS[kind], tl) == free
    finally:
        sys.setswitchinterval(every)
    st = tl.report()
    nwin = st["ingest"]["calls"]
    assert nwin == st["dispatch"]["calls"] > 3
    # A window is read ahead or waited for (and the stream's end may be
    # too).
    assert 0 <= st["wait.chunk"]["calls"] <= nwin + 1
    assert st["ingest"]["bytes"] == st["link.put"]["bytes"]
    assert not feed_threads()


@pytest.mark.parametrize("fault", ["short", "raises"])
@pytest.mark.parametrize("kind", ["carried-ragged", "three-products"])
def test_a_failed_read_is_the_loops_exception(tmp_path, monkeypatch, kind,
                                              fault):
    # Window 3's first read comes short, or raises, on the feed thread:
    # the loop sees the serial feed's exception when it asks for that
    # window, with windows 0-2 dispatched; nothing is left behind.
    paths = make_band(tmp_path, 30, seed=9)
    real, calls = S._gapless, []

    def gapless(raw, n, **kw):
        calls.append(n)
        if len(calls) == 4 * NBANK + 1:  # heads, then three windows' bodies
            if fault == "raises":
                raise OSError("the disk is gone")
            return real(raw, n - 1, **kw)
        return real(raw, n, **kw)

    monkeypatch.setattr(S, "_gapless", gapless)
    tl = Timeline()
    error, said = {"short": (ValueError, "incompatible"),
                   "raises": (OSError, "the disk is gone")}[fault]
    with pytest.raises(error, match=said):
        scan_to(tmp_path, paths, "x", KINDS[kind], tl)
    assert tl.stages["dispatch"].calls == 3
    assert tl.stages["ingest"].calls == 4
    assert not feed_threads()
    assert left_behind(tmp_path) in ([], ["out-x"])
    if kind == "three-products":
        assert os.listdir(tmp_path / "out-x") == []


def test_an_exception_in_the_loop_stops_the_feed(tmp_path, monkeypatch):
    paths = make_band(tmp_path, 30, seed=10)
    real, puts = S._put_window, []

    def dying(*a, **k):
        puts.append(1)
        if len(puts) == 3:
            raise RuntimeError("killed")
        return real(*a, **k)

    monkeypatch.setattr(S, "_put_window", dying)
    tl = Timeline()
    with pytest.raises(RuntimeError, match="killed"):
        scan_to(tmp_path, paths, "x", dict(nint=1, window_frames=2), tl)
    # Fifteen windows; the feed was a window ahead of the third, no more
    # (a slot is free only when a window's programs have been waited out).
    assert 3 <= tl.stages["ingest"].calls <= 5
    assert tl.stages["dispatch"].calls == 2
    assert not feed_threads()
    assert left_behind(tmp_path) == []


@pytest.mark.parametrize("dies", ["the read", "the put"])
def test_a_run_that_died_leaves_nothing_for_a_cyclic_gc(tmp_path,
                                                        monkeypatch, dies):
    # The writers, the readers and the staged slabs of a run that died go
    # with its exception, by reference count: kept in a cycle (the
    # rotation's frame held the exception it raised) they lived until
    # some thread's cyclic GC — a resumed run's feed thread, which then
    # closed the dead .h5 writer's handles inside libhdf5 beside the
    # loop's chunk write.
    import gc
    import weakref

    from blit.io import fbh5

    paths = make_band(tmp_path, 30, seed=11)
    name = "_read_window" if dies == "the read" else "_put_window"
    real, calls, writers = getattr(S, name), [], []

    def dying(*a, **k):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("killed")
        return real(*a, **k)

    init = fbh5.ResumableFBH5Writer.__init__

    def seen(self, *a, **k):
        init(self, *a, **k)
        writers.append(weakref.ref(self))

    monkeypatch.setattr(S, name, dying)
    monkeypatch.setattr(fbh5.ResumableFBH5Writer, "__init__", seen)
    gc.collect()
    gc.disable()
    try:
        with pytest.raises(RuntimeError, match="killed"):
            reduce_scan_mesh_to_files(
                paths, out_paths=[str(tmp_path / "out.h5")], nfft=NFFT,
                nint=1, window_frames=2, resume=True,
                compression="bitshuffle")
        assert writers and [w() for w in writers] == [None] * len(writers)
    finally:
        gc.enable()
    assert not feed_threads()
