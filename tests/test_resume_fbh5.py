"""Crash-resumable FBH5 products (VERDICT r4 missing item 2): BL's native
product format (src/gbtworkerfunctions.jl:141-155) must survive a crash the
way ``.fil`` products do — cursor sidecar, resize-truncate to the last
durable slab, decoded payload identical to an uninterrupted run."""

import contextlib
import os

import numpy as np
import pytest

pytest.importorskip("jax")

from blit import faults  # noqa: E402
from blit.faults import FaultRule  # noqa: E402
from blit.io.fbh5 import ResumableFBH5Writer, read_fbh5_data  # noqa: E402
from blit.pipeline import RawReducer, ReductionCursor  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

HDR = {"fch1": 8000.0, "foff": -0.1, "tsamp": 1.0, "nbits": 32,
       "source_name": "SYNTH"}


def make_red():
    return RawReducer(nfft=64, nint=2, chunk_frames=4)


@pytest.fixture
def raw(tmp_path):
    p = str(tmp_path / "x.raw")
    synth_raw(p, nblocks=4, obsnchan=2, ntime_per_block=1024, tone_chan=1)
    return p


class Boom(Exception):
    pass


@contextlib.contextmanager
def crash_after(n_slabs):
    """Crash the product path after exactly ``n_slabs`` slab appends
    landed, via the write-behind sink's fault-injection point (ISSUE 4:
    the async output plane moved the append onto a writer thread, so the
    realistic crash seam is ``sink.write`` — the failure is recorded
    writer-side and re-raises clean on the consumer thread)."""
    faults.install(FaultRule(point="sink.write", mode="fail",
                             after=n_slabs, times=-1, exc=Boom))
    try:
        yield
    finally:
        faults.clear()
        faults.reset_counters()


def test_cursor_sidecar_paths_in_lockstep():
    # blit.io.fbh5 dodges a pipeline dependency by duplicating the
    # sidecar naming rule; this pin keeps the two in lockstep.
    from blit.io.fbh5 import _cursor_path

    assert _cursor_path("/x/y.h5") == ReductionCursor.path_for("/x/y.h5")


def test_cursor_matches_is_member_order_insensitive(tmp_path):
    # Regression (ISSUE 3 satellite): a multi-file scan sequence is the
    # same recording whatever order a glob listed its members in —
    # open_raw sorts members before reading — so a cursor recorded under
    # one ordering must match a resume (and a cache fingerprint) under
    # another.  Before the fix, matches() compared the path/stat lists
    # positionally and any reordering forced a spurious fresh start.
    paths = []
    for i in range(3):
        p = str(tmp_path / f"x.{i:04d}.raw")
        synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=256, seed=i)
        paths.append(p)
    red = make_red()
    size, mtime_ns = ReductionCursor.stat_raw(paths)
    cur = ReductionCursor(paths, red.nfft, red.ntap, red.nint, red.stokes,
                          window=red.window, raw_size=size,
                          raw_mtime_ns=mtime_ns)
    assert cur.matches(red, paths)
    assert cur.matches(red, list(reversed(paths)))
    assert cur.matches(red, [paths[1], paths[2], paths[0]])
    # Still a real identity check: a different member set must NOT match.
    assert not cur.matches(red, paths[:2])
    other = str(tmp_path / "x.0003.raw")
    synth_raw(other, nblocks=1, obsnchan=2, ntime_per_block=256, seed=9)
    assert not cur.matches(red, [paths[0], paths[1], other])


class TestWriterDurability:
    """ResumableFBH5Writer's own contract, driven directly."""

    def test_plain_checkpoints_every_append(self, tmp_path):
        p = str(tmp_path / "x.h5")
        cur = ReductionCursor(p, 64, 4, 2, "I")
        w = ResumableFBH5Writer(p, HDR, 2, 16, 0, 2, cur)
        data = np.random.default_rng(0).standard_normal(
            (10, 2, 16)).astype(np.float32)
        w.append(data[:6])
        assert cur.frames_done == 12  # 6 rows * nint, claimed immediately
        assert ReductionCursor.load(p).frames_done == 12
        w.append(data[6:])
        w.close()
        np.testing.assert_array_equal(read_fbh5_data(p), data)
        assert not os.path.exists(ReductionCursor.path_for(p))

    def test_bitshuffle_claims_only_flushed_chunks(self, tmp_path):
        pytest.importorskip("blit.io.bshuf").available() or pytest.skip(
            "native codec unbuilt")
        p = str(tmp_path / "x.h5")
        cur = ReductionCursor(p, 64, 4, 2, "I")
        w = ResumableFBH5Writer(p, HDR, 2, 16, 0, 2, cur,
                                compression="bitshuffle",
                                chunks=(4, 2, 16))
        data = np.random.default_rng(1).standard_normal(
            (11, 2, 16)).astype(np.float32)
        w.append(data[:6])  # one full chunk (4) + 2 buffered
        assert cur.frames_done == 4 * 2  # chunk-aligned claim only
        w.append(data[6:9])  # 5 buffered -> one more chunk, 1 buffered
        assert cur.frames_done == 8 * 2
        # A crash here loses only the buffered row; the claim is durable.
        w.abort()
        cur2 = ReductionCursor.load(p)
        assert cur2.frames_done == 16
        # Resume from the claim and finish.
        w2 = ResumableFBH5Writer(p, HDR, 2, 16, 8, 2, cur2,
                                 compression="bitshuffle",
                                 chunks=(4, 2, 16))
        w2.append(data[8:])
        w2.close()
        np.testing.assert_array_equal(read_fbh5_data(p), data)

    def test_resume_truncates_unclaimed_tail(self, tmp_path):
        p = str(tmp_path / "x.h5")
        cur = ReductionCursor(p, 64, 4, 2, "I")
        w = ResumableFBH5Writer(p, HDR, 1, 8, 0, 2, cur)
        a = np.arange(6 * 8, dtype=np.float32).reshape(6, 1, 8)
        w.append(a)
        w.abort()
        # Tamper: pretend the last 2 rows were never claimed (crash between
        # data landing and cursor save is the other direction and is
        # covered by the fsync-before-cursor ordering).
        cur2 = ReductionCursor.load(p)
        start = (cur2.frames_done // 2) - 2
        w2 = ResumableFBH5Writer(p, HDR, 1, 8, start, 2, cur2)
        assert w2.nsamps == 4
        b = 100 + np.arange(2 * 8, dtype=np.float32).reshape(2, 1, 8)
        w2.append(b)
        w2.close()
        got = read_fbh5_data(p)
        np.testing.assert_array_equal(got[:4], a[:4])
        np.testing.assert_array_equal(got[4:], b)

    def test_bitshuffle_refuses_misaligned_restart(self, tmp_path):
        pytest.importorskip("blit.io.bshuf").available() or pytest.skip(
            "native codec unbuilt")
        p = str(tmp_path / "x.h5")
        cur = ReductionCursor(p, 64, 4, 2, "I")
        with pytest.raises(ValueError, match="aligned"):
            ResumableFBH5Writer(p, HDR, 2, 16, 3, 2, cur,
                                compression="bitshuffle", chunks=(4, 2, 16))

    def test_resume_refuses_filter_mismatch(self, tmp_path):
        pytest.importorskip("blit.io.bshuf").available() or pytest.skip(
            "native codec unbuilt")
        p = str(tmp_path / "x.h5")
        cur = ReductionCursor(p, 64, 4, 2, "I")
        w = ResumableFBH5Writer(p, HDR, 2, 16, 0, 2, cur, chunks=(4, 2, 16))
        w.append(np.zeros((4, 2, 16), np.float32))
        w.abort()
        # Writing bitshuffle payloads through a plain pipeline would store
        # undecodable chunks; the writer must refuse, not corrupt.
        with pytest.raises(ValueError, match="filter"):
            ResumableFBH5Writer(p, HDR, 2, 16, 4, 2,
                                ReductionCursor.load(p),
                                compression="bitshuffle", chunks=(4, 2, 16))


class TestReduceResumableH5:
    @pytest.mark.parametrize("compression", [None, "bitshuffle"])
    def test_fresh_run_equals_plain_reduction(self, tmp_path, raw,
                                              compression):
        out = str(tmp_path / "x.h5")
        hdr = make_red().reduce_resumable(raw, out, compression=compression)
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)
        assert hdr["nsamps"] == want.shape[0]
        assert not os.path.exists(ReductionCursor.path_for(out))

    @pytest.mark.parametrize("compression", [None, "bitshuffle"])
    def test_interrupted_run_resumes_identically(self, tmp_path, raw,
                                                 compression):
        out = str(tmp_path / "x.h5")
        # chunks sized so each slab (chunk_frames=4 / nint=2 = 2 rows)
        # flushes a whole bitshuffle chunk — the claim is then non-zero
        # after one slab for both codecs.
        chunks = (2, 1, 128)
        with crash_after(1), pytest.raises(Boom):
            make_red().reduce_resumable(raw, out, compression=compression,
                                        chunks=chunks)
        cur = ReductionCursor.load(out)
        assert cur is not None and cur.frames_done == 4  # one slab landed
        assert cur.compression == (compression or "none")

        make_red().reduce_resumable(raw, out, compression=compression,
                                    chunks=chunks)
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)
        assert not os.path.exists(ReductionCursor.path_for(out))

    def test_bitshuffle_default_chunks_resume_restarts_clean(self, tmp_path,
                                                             raw):
        # With the default 16-row chunks a 2-row slab never completes a
        # chunk before the crash: the claim is legitimately 0 and the
        # resume is a clean fresh start, not a corrupt splice.
        out = str(tmp_path / "x.h5")
        with crash_after(1), pytest.raises(Boom):
            make_red().reduce_resumable(raw, out, compression="bitshuffle")
        assert ReductionCursor.load(out).frames_done == 0
        make_red().reduce_resumable(raw, out, compression="bitshuffle")
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)

    def test_compression_flip_restarts_fresh(self, tmp_path, raw):
        out = str(tmp_path / "x.h5")
        with crash_after(1), pytest.raises(Boom):
            make_red().reduce_resumable(raw, out)
        # Same config, different codec: identity mismatch -> fresh start
        # (NOT the writer's filter-mismatch refusal, and NOT corruption).
        make_red().reduce_resumable(raw, out, compression="bitshuffle")
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)

    def test_chunks_flip_restarts_fresh(self, tmp_path, raw):
        # chunks= is part of the resume identity for the same reason as
        # compression: the dataset's chunk grid is fixed at creation, so
        # a mismatch must restart fresh — not die on the writer's
        # chunk-mismatch refusal.
        out = str(tmp_path / "x.h5")
        with crash_after(1), pytest.raises(Boom):
            make_red().reduce_resumable(raw, out, chunks=(2, 1, 128))
        make_red().reduce_resumable(raw, out)  # default chunks
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)

    def test_tampered_raw_restarts_fresh(self, tmp_path, raw):
        out = str(tmp_path / "x.h5")
        with crash_after(1), pytest.raises(Boom):
            make_red().reduce_resumable(raw, out)
        # Replace the recording with a DIFFERENT valid one (new mtime and
        # payload): the cursor's input identity no longer matches, so the
        # resume must restart fresh and reduce the new bytes.
        synth_raw(raw, nblocks=4, obsnchan=2, ntime_per_block=1024,
                  tone_chan=0, seed=7)
        make_red().reduce_resumable(raw, out)
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)


class TestCorruptTargetFallback:
    """ADVICE r5 medium: libhdf5 metadata updates between checkpoints are
    not crash-atomic — a SIGKILL can leave a target the resume path cannot
    open while the cursor sidecar still parses.  The resume must fall back
    to a fresh start (identity-mismatch behavior), never raise."""

    def test_probe_rejects_garbage_and_accepts_good(self, tmp_path):
        from blit.io.fbh5 import resume_target_ok
        from blit.io.fbh5 import write_fbh5

        good = str(tmp_path / "good.h5")
        data = np.random.default_rng(0).standard_normal(
            (6, 1, 8)).astype(np.float32)
        write_fbh5(good, HDR, data)
        assert resume_target_ok(good, 1, 8, 6)
        assert not resume_target_ok(good, 1, 8, 7)  # claims > rows
        assert not resume_target_ok(good, 2, 8, 4)  # wrong geometry
        bad = str(tmp_path / "bad.h5")
        with open(bad, "wb") as f:
            f.write(b"\x00not hdf5 at all" * 64)
        assert not resume_target_ok(bad, 1, 8, 1)
        assert not resume_target_ok(str(tmp_path / "absent.h5"), 1, 8, 1)

    def test_corrupt_target_restarts_fresh(self, tmp_path, raw, caplog):
        import logging

        out = str(tmp_path / "x.h5")
        with crash_after(1), pytest.raises(Boom):
            make_red().reduce_resumable(raw, out)
        cur = ReductionCursor.load(out)
        assert cur is not None and cur.frames_done > 0
        # Smash the HDF5 superblock — the file no longer opens, but the
        # cursor (own tmp-rename+fsync discipline) still parses.
        with open(out, "r+b") as f:
            f.write(b"\xde\xad\xbe\xef" * 128)
        with caplog.at_level(logging.WARNING, logger="blit.pipeline"):
            make_red().reduce_resumable(raw, out)
        assert "starting fresh" in caplog.text
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)
        assert not os.path.exists(ReductionCursor.path_for(out))


class TestSigkillResume:
    def test_sigkill_mid_reduction_resumes_identically(self, tmp_path):
        # The real crash, not an injected exception: a subprocess running
        # the bitshuffle .h5 reduction is SIGKILLed once its cursor
        # claims progress (no cleanup, no atexit — the durability
        # ordering alone must leave a resumable prefix).  The resumed
        # product must equal an uninterrupted run bit-for-bit (decoded).
        import json
        import signal
        import subprocess
        import sys
        import time

        pytest.importorskip("blit.io.bshuf").available() or pytest.skip(
            "native codec unbuilt")
        raw = str(tmp_path / "x.raw")
        synth_raw(raw, nblocks=6, obsnchan=2, ntime_per_block=2048,
                  tone_chan=1)
        out = str(tmp_path / "x.h5")
        # chunk_frames=2: ~90 fsync'd cursor updates per run — a wide
        # window for the 2 ms poll to land the kill mid-run.
        child = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "from blit.pipeline import RawReducer\n"
            "RawReducer(nfft=64, nint=2, chunk_frames=2).reduce_resumable("
            f"{raw!r}, {out!r}, compression='bitshuffle', "
            "chunks=(1, 1, 128))\n"
        )
        env = {**os.environ, "PYTHONPATH": ""}
        p = subprocess.Popen([sys.executable, "-c", child], env=env,
                             stderr=subprocess.PIPE, text=True)
        deadline = time.time() + 120
        killed = False
        cursor = ReductionCursor.path_for(out)
        while time.time() < deadline and p.poll() is None:
            try:
                if json.load(open(cursor))["frames_done"] > 0:
                    p.send_signal(signal.SIGKILL)
                    killed = True
                    break
            except (OSError, ValueError):
                pass
            time.sleep(0.002)
        if p.poll() is None and not killed:
            p.kill()  # deadline expired with a hung child: don't leak it
        _, err = p.communicate(timeout=60)
        if not killed:
            # Startup crash vs genuinely-too-fast must be distinguishable.
            pytest.fail(
                f"child was not killed mid-run (rc={p.returncode}); "
                f"stderr:\n{(err or '')[-2000:]}"
            )
        assert os.path.exists(out) and os.path.exists(cursor)
        make_red().reduce_resumable(raw, out, compression="bitshuffle",
                                    chunks=(1, 1, 128))
        _, want = make_red().reduce(raw)
        np.testing.assert_array_equal(read_fbh5_data(out), want)
        assert not os.path.exists(cursor)


class TestCLI:
    def test_reduce_resume_h5_bitshuffle(self, tmp_path, raw, capsys):
        import json

        from blit.__main__ import main

        out = str(tmp_path / "x.h5")
        rc = main(["reduce", raw, "-o", out, "--nfft", "64", "--nint", "2",
                   "--compression", "bitshuffle", "--resume"])
        assert rc == 0
        stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        _, want = make_red().reduce(raw)
        assert stats["nsamps"] == want.shape[0]
        np.testing.assert_array_equal(read_fbh5_data(out), want)
