"""An integration carried across dispatches (ISSUE 26): ``nint`` that the
chunk cannot hold or does not divide — rawspec's ``-f 1048576 -t 51`` at
the 8-frame dispatch the chip wants — against the whole-file reference.

The straddle is driven with an explicit ``chunk_frames`` (kept as given);
the boundary then moves through the chunk grid (51 = 6 x 8 + 3)."""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit import faults  # noqa: E402
from blit.io.guppi import GuppiRaw  # noqa: E402
from blit.io.sigproc import read_fil_data  # noqa: E402
from blit.ops.channelize import (  # noqa: E402
    STOKES_NIF,
    channelize,
    channelize_np,
    integrate_carry,
    pfb_coeffs,
    usable_frames,
)
from blit.pipeline import RawReducer, ReductionCursor  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NTAP = 4
# Scale-relative max error (max|got - want| / max|want|, as
# benchmark/check.py and tests/test_channelize.py hold MXU-grade stages)
# against channelize_np over the whole file, compared in float64.  Both
# sides are float32 arithmetic and differ in FFT rounding and in the order
# of the sum (one frame at a time here, numpy's pairwise there): the cases
# below read 3e-9 to 1.8e-7 on the CPU.  The tone sits in every frame, so a
# frame in the wrong row, added twice or not at all moves the peak by
# 1/nint >= 3e-4 of itself: 1e-5 is 50 times the worst reading and 30
# times under the smallest fault.
TOL = 1e-5

CASES = [  # (nfft, nint, chunk_frames, whole rows, tail frames dropped)
    (32, 51, 8, 2, 13),
    (32, 51, 17, 2, 5),
    (32, 51, 64, 3, 40),
    (32, 6, 8, 5, 4),
    (32, 7, 3, 4, 2),
    (8, 3072, 8192, 3, 700),
]


def _ids(case):
    return "nint{1}-cf{2}".format(*case)


def _recording(tmp_path, nfft, frames, seed=0, nblocks=4, obsnchan=2):
    """A RAW file holding exactly ``frames`` PFB frames (+ a part of one)."""
    total = (frames + NTAP - 1) * nfft + nfft // 2
    per = -(-total // nblocks)
    p = str(tmp_path / f"r{nfft}-{frames}.raw")
    synth_raw(p, nblocks=nblocks, obsnchan=obsnchan, ntime_per_block=per,
              seed=seed, tone_chan=1)
    return p


def _reference(raw_path, nfft, nint, stokes, rows):
    raw = GuppiRaw(raw_path)
    stream = np.concatenate(
        [blk for _, blk in raw.iter_blocks(drop_overlap=True)], axis=1)
    usable = (rows * nint + NTAP - 1) * nfft
    return channelize_np(stream[:, :usable], pfb_coeffs(NTAP, nfft),
                         nfft=nfft, ntap=NTAP, nint=nint, stokes=stokes)


def _rel_err(got, want):
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


def _expected_counts(frames_in_file, nint, cf):
    """(dispatches that end with an integration open, rows closed, frames
    dispatched), by walking the chunk grid the way the producer lays it."""
    full, rest = divmod(frames_in_file, cf)
    chunks = [cf] * full
    flush = (sum(chunks) + rest) // nint * nint - sum(chunks)
    if flush > 0:
        chunks.append(flush)
    done = open_ = 0
    for c in chunks:
        done += c
        open_ += done % nint != 0
    return open_, done // nint, done


@pytest.mark.parametrize("async_output", [False, True],
                         ids=["sync", "async"])
@pytest.mark.parametrize("stokes", ["I", "IQUV"])
@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_carried_integration_matches_whole_file(tmp_path, case, stokes,
                                                async_output):
    nfft, nint, cf, rows, tail = case
    frames = rows * nint + tail
    raw = _recording(tmp_path, nfft, frames)
    red = RawReducer(nfft=nfft, nint=nint, chunk_frames=cf, stokes=stokes,
                     async_output=async_output)
    assert red.chunk_frames == cf and red._carries
    hdr, got = red.reduce(raw)
    want = _reference(raw, nfft, nint, stokes, rows)
    # Whole rows only: the tail that fills no integration is dropped.
    assert got.shape == want.shape == (rows, STOKES_NIF[stokes], 2 * nfft)
    assert hdr["nsamps"] == rows
    tbin = GuppiRaw(raw).header(0)["TBIN"]
    assert hdr["tsamp"] == pytest.approx(tbin * nfft * nint, rel=1e-12)
    assert _rel_err(got, want) < TOL
    st = red.timeline.report()
    open_, closed, dispatched = _expected_counts(frames, nint, cf)
    assert closed == rows
    assert st["integrate.emit"]["calls"] == rows
    assert st["integrate.emit"]["bytes"] == got.nbytes
    assert st["integrate.carry"]["calls"] == open_
    assert st["integrate.carry"]["bytes"] == open_ * got[0].nbytes
    # Frames of closed rows only: whole chunks past the last row's end
    # were dispatched (an integration left open) and dropped.
    assert red.stats.output_frames == rows * nint <= dispatched
    # The same RAW bytes give the same product bytes — through the file
    # writer too (the pump, the sink and the manifest see rows only).
    out = str(tmp_path / "again.fil")
    red2 = RawReducer(nfft=nfft, nint=nint, chunk_frames=cf, stokes=stokes,
                      async_output=async_output)
    fhdr = red2.reduce_to_file(raw, out)
    assert fhdr["nsamps"] == rows
    assert read_fil_data(out)[1].tobytes() == got.tobytes()
    assert not os.path.exists(out + ".partial")


def test_rawspec_hires_geometry_counts():
    # The cell's own grid (ISSUE 26 step 7): 51 frames in 8-frame chunks
    # are six dispatches that leave the integration open and a 3-frame
    # flush that closes the one row — which the old flush rule (frames
    # rounded down to nint inside the flush chunk) would have dropped.
    assert _expected_counts(51, 51, 8) == (6, 1, 51)
    nfft = 1 << 20
    # The flush chunk's 3 frames, counted with the filter state the chip
    # holds (the rule counts samples as it always did; ISSUE 29).
    held = (3 + NTAP - 1) * nfft
    assert usable_frames(held, nfft, NTAP, 51) == 0
    assert usable_frames(held, nfft, NTAP, 51, open_frames=48) == 3
    assert usable_frames(held, nfft, NTAP, 51, open_frames=47) == 0
    assert usable_frames(0, nfft, NTAP, 51, open_frames=48) == 0


def test_integration_inside_a_chunk_takes_the_old_path(tmp_path):
    # nint | chunk_frames: no accumulator, no integrate.* row, and the
    # bytes are those of one in-program integration per chunk (the path
    # every existing product takes).
    nfft, nint, cf = 32, 4, 8
    raw = _recording(tmp_path, nfft, 3 * cf)
    red = RawReducer(nfft=nfft, nint=nint, chunk_frames=cf)
    assert not red._carries
    _, got = red.reduce(raw)
    st = red.timeline.report()
    assert "integrate.carry" not in st and "integrate.emit" not in st
    g = GuppiRaw(raw)
    stream = np.concatenate(
        [blk for _, blk in g.iter_blocks(drop_overlap=True)], axis=1)
    h = jax.numpy.asarray(pfb_coeffs(NTAP, nfft))
    want = [np.asarray(channelize(
        stream[:, k * cf * nfft:(k * cf + cf + NTAP - 1) * nfft], h,
        nfft=nfft, ntap=NTAP, nint=nint, stokes="I", fft_method="auto"))
        for k in range(3)]
    assert got.tobytes() == np.concatenate(want).tobytes()
    # And a carried reduction of the same file agrees to rounding.
    _, carried = RawReducer(nfft=nfft, nint=nint,
                            chunk_frames=cf - 1).reduce(raw)
    assert carried.shape == got.shape
    assert _rel_err(carried, got) < 1e-5


def test_channel_groups_are_laid_out_once_per_stream(tmp_path, monkeypatch):
    # On the chip a chunk goes up in channel groups sized from the
    # device's memory, and the 3-frame flush chunk would fit twice the
    # channels of an 8-frame one (my chip run, PR 26: 32 against 64): the
    # accumulators keep the first chunk's grouping to the stream's end.
    nfft, nint, cf, rows, tail = 32, 11, 4, 3, 2
    raw = _recording(tmp_path, nfft, rows * nint + tail, obsnchan=4)
    asked = []

    def sized_by_shape(self, shape):
        full = shape[1] == cf * nfft  # a chunk is its new frames only
        asked.append(2 if full else 4)
        return asked[-1]

    monkeypatch.setattr(RawReducer, "_channel_block", sized_by_shape)
    red = RawReducer(nfft=nfft, nint=nint, chunk_frames=cf)
    _, got = red.reduce(raw)
    assert asked == [2]  # asked once, for the stream's first chunk
    # ... and the filter state is laid out with the accumulators: two
    # groups, the head up once, every later tail left on the chip.
    st = red.timeline.report()
    dispatches = -(-(rows * nint) // cf)
    assert st["state.head"]["calls"] == 2
    assert st["state.carry"]["calls"] == 2 * (dispatches - 1)
    monkeypatch.undo()
    _, whole = RawReducer(nfft=nfft, nint=nint, chunk_frames=cf).reduce(raw)
    assert got.shape == (rows, 1, 4 * nfft)
    assert _rel_err(got, whole) < 1e-6
    assert _rel_err(got, _reference(raw, nfft, nint, "I", rows)) < TOL


def test_a_row_does_not_depend_on_the_dispatch_grid():
    # integrate_carry adds frame by frame from zero: feeding one
    # integration as 8+8+1, as 3+14 or at once gives the same bits.
    rng = np.random.default_rng(5)
    power = rng.standard_normal((17, 2, 96)).astype(np.float32) ** 2

    def run(cuts):
        acc, at, rows = np.zeros((2, 96), np.float32), 0, None
        for a, b in zip((0,) + cuts, cuts + (17,)):
            rows, acc = integrate_carry(power[a:b], acc, np.int32(at),
                                        nint=17)
            at += b - a
        assert not np.asarray(acc).any()  # closed: a fresh start
        return np.asarray(rows[0])

    whole = run(())
    assert whole.tobytes() == run((8, 16)).tobytes() == run((3,)).tobytes()
    seq = np.zeros((2, 96), np.float32)
    for f in power:
        seq = seq + f
    assert whole.tobytes() == seq.tobytes()


class TestResumeInsideAnIntegration:
    """``--resume`` keeps whole rows only; a run killed between two rows or
    in the middle of one resumes at the last whole row and ends with the
    uninterrupted run's bytes (frames are added in the order of their
    place in the integration, wherever the chunk grid falls after the
    restart)."""

    NFFT, NINT, CF, ROWS, TAIL = 32, 11, 4, 5, 3

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        faults.clear()
        faults.reset_counters()
        yield
        faults.clear()
        faults.reset_counters()

    def _kw(self):
        return dict(nfft=self.NFFT, nint=self.NINT, chunk_frames=self.CF)

    def _payload(self, path):
        if path.endswith(".h5"):
            from blit.io.fbh5 import read_fbh5_data

            return np.asarray(read_fbh5_data(path)).tobytes()
        with open(path, "rb") as f:
            return f.read()

    @pytest.mark.parametrize("ext", [".fil", ".h5"])
    @pytest.mark.parametrize("where", ["between_rows", "inside_a_row"])
    def test_resumed_bytes_equal_uninterrupted(self, tmp_path, monkeypatch,
                                               ext, where):
        raw = _recording(tmp_path, self.NFFT,
                         self.ROWS * self.NINT + self.TAIL, seed=3)
        ref = str(tmp_path / ("ref" + ext))
        RawReducer(**self._kw()).reduce_resumable(raw, ref)
        out = str(tmp_path / ("res" + ext))
        crash = RawReducer(**self._kw())
        if where == "between_rows":
            # The writer dies taking the third row: two are durable.
            faults.install_spec("sink.write:fail:after=2")
            with pytest.raises(OSError):
                crash.reduce_resumable(raw, out)
            faults.clear()
        else:
            # The dispatcher dies on chunk 8: frames 28..31, an
            # integration open (row 2 closed at frame 22, row 3 needs 33).
            real, seen = RawReducer._dispatch, []

            def dying(self_, chunk, carry=None):
                seen.append(carry.filled)
                if len(seen) == 8:
                    raise RuntimeError("killed inside an integration")
                return real(self_, chunk, carry)

            monkeypatch.setattr(RawReducer, "_dispatch", dying)
            with pytest.raises(RuntimeError, match="inside an integration"):
                crash.reduce_resumable(raw, out)
            monkeypatch.undo()
            assert seen[-1] == 28 % self.NINT != 0
        cur = ReductionCursor.load(out)
        assert cur is not None and cur.frames_done % self.NINT == 0
        assert 0 < cur.frames_done < self.ROWS * self.NINT
        red = RawReducer(**self._kw())
        hdr = red.reduce_resumable(raw, out)
        assert hdr["nsamps"] == self.ROWS
        # Resumed at the claimed row, not restarted ...
        assert red.stats.output_frames \
            == self.ROWS * self.NINT - cur.frames_done
        # ... onto a chunk grid that has moved (the claim is no multiple
        # of the chunk), and still the uninterrupted run's bytes.
        assert cur.frames_done % self.CF != 0 or where == "between_rows"
        assert self._payload(out) == self._payload(ref)
        assert not os.path.exists(ReductionCursor.path_for(out))
