"""ReductionCursor torn-write crash drills (ISSUE 12 satellite).

The ``.fil`` resume path's crash states, mirroring the PR 7
SearchCursor drills (tests/test_dedoppler.py TestSearchCursorDrills):
the fsync-before-claim ordering's only legal torn state (durable rows
beyond the claim), a torn partial row, a claim exactly at EOF (the
clean crash — must RESUME), and a claim past EOF (crash-corrupted —
POSIX truncate would NUL-hole-extend; must restart fresh, the
``resume_fil_ok`` guard).  Every drill finishes byte-identical to an
uninterrupted reduction — the supervisor's resume contract is now
pinned on BOTH cursor types."""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit import faults  # noqa: E402
from blit.pipeline import (  # noqa: E402
    RawReducer,
    ReductionCursor,
    resume_fil_ok,
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


def _kw():
    return dict(nfft=NFFT, chunk_frames=CF)


def _bytes(path):
    with open(path, "rb") as f:
        return f.read()


class TestReductionCursorDrills:
    def _interrupted(self, tmp_path):
        """A reference product plus an 'interrupted' resumable twin:
        crash (injected sink failure) after two durable appends,
        returning ``(raw, ref_path, out_path, row_bytes)``."""
        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=4, obsnchan=2, ntime_per_block=512,
                  seed=2)
        ref = str(tmp_path / "ref.fil")
        RawReducer(**_kw()).reduce_to_file(raw, ref)
        out = str(tmp_path / "res.fil")
        faults.install_spec("sink.write:fail:after=2")
        with pytest.raises(OSError):
            RawReducer(**_kw()).reduce_resumable(raw, out)
        faults.clear()
        cur = ReductionCursor.load(out)
        assert cur is not None and cur.frames_done > 0
        from blit.io.guppi import open_raw

        hdr = RawReducer(**_kw()).header_for(open_raw(raw))
        row_bytes = hdr["nchans"] * hdr["nifs"] * 4
        return raw, ref, out, row_bytes

    def test_unclaimed_tail_truncated_and_replayed(self, tmp_path):
        # Durable rows past the claim (the crash window between fsync
        # and cursor save): resume truncates and re-reduces them,
        # finishing byte-identical.
        raw, ref, out, row_bytes = self._interrupted(tmp_path)
        with open(out, "ab") as f:
            f.write(np.full(row_bytes // 4, 7.0, np.float32).tobytes())
        RawReducer(**_kw()).reduce_resumable(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert not os.path.exists(ReductionCursor.path_for(out))

    def test_torn_row_tail_truncated(self, tmp_path):
        # A crash mid-write leaves HALF a row past the claim: resume
        # truncates it rather than splicing garbage mid-product.
        raw, ref, out, row_bytes = self._interrupted(tmp_path)
        with open(out, "ab") as f:
            f.write(b"\x01" * (row_bytes // 2))
        RawReducer(**_kw()).reduce_resumable(raw, out)
        assert _bytes(out) == _bytes(ref)

    def test_claim_exactly_at_eof_resumes(self, tmp_path):
        # The clean crash state: claim == file length must RESUME (the
        # guard is a strict can-the-file-hold-the-claim check), not
        # restart — pinned by watching how many frames re-reduce.
        raw, ref, out, _ = self._interrupted(tmp_path)
        claimed = ReductionCursor.load(out).frames_done
        red = RawReducer(**_kw())
        red.reduce_resumable(raw, out)
        assert _bytes(out) == _bytes(ref)
        # Resumed, not restarted: this run produced only the remainder.
        assert red.stats.output_frames > 0
        ref_frames = RawReducer(**_kw()).reduce(raw)[1].shape[0]
        assert red.stats.output_frames == ref_frames - claimed

    def test_claim_past_eof_starts_fresh(self, tmp_path):
        # One row short of the claim is already corrupt: truncate would
        # EXTEND a NUL hole into the product — must start fresh (the
        # new resume_fil_ok guard) and still finish byte-identical.
        raw, ref, out, row_bytes = self._interrupted(tmp_path)
        size = os.path.getsize(out)
        with open(out, "r+b") as f:
            f.truncate(size - row_bytes)
        red = RawReducer(**_kw())
        red.reduce_resumable(raw, out)
        assert _bytes(out) == _bytes(ref)
        ref_frames = RawReducer(**_kw()).reduce(raw)[1].shape[0]
        # Fresh start: EVERY frame was re-reduced.
        assert red.stats.output_frames == ref_frames


class TestResumeFilOk:
    def test_holds_claim(self, tmp_path):
        from blit.io.sigproc import write_fil

        p = str(tmp_path / "x.fil")
        hdr = {"nchans": 4, "nifs": 1, "nbits": 32, "tsamp": 1.0,
               "fch1": 1000.0, "foff": -0.1}
        write_fil(p, hdr, np.zeros((3, 1, 4), np.float32))
        assert resume_fil_ok(p, 1, 4, 3)
        assert not resume_fil_ok(p, 1, 4, 4)
        assert not resume_fil_ok(str(tmp_path / "missing.fil"), 1, 4, 0)

    def test_unparseable_header_fails_closed(self, tmp_path):
        p = str(tmp_path / "junk.fil")
        with open(p, "wb") as f:
            f.write(b"not a sigproc header")
        assert not resume_fil_ok(p, 1, 4, 0)
