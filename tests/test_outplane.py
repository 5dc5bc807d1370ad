"""The asynchronous output plane (ISSUE 4): overlapped device→host
readback (OutputRotation), write-behind product sinks (AsyncSink), the
shared fold bookkeeping (FoldInFlight) — and the contract that matters
above all: products through the async plane are BYTE-IDENTICAL to the
synchronous path's, crash/resume semantics included."""

import os
import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit import faults  # noqa: E402
from blit.faults import FaultRule, RetryPolicy  # noqa: E402
from blit.io.fbh5 import read_fbh5_data, read_fbh5_header  # noqa: E402
from blit.io.sigproc import read_fil_data  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.outplane import AsyncSink, FoldInFlight, OutputRotation  # noqa: E402
from blit.pipeline import RawReducer, ReductionCursor  # noqa: E402
from blit.testing import synth_raw  # noqa: E402


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    faults.reset_counters()
    faults.set_io_policy(RetryPolicy(attempts=3, base_s=0.0, jitter=0.0))
    yield
    faults.clear()
    faults.reset_counters()
    faults.set_io_policy(None)


def no_plane_threads():
    """No output-plane thread may outlive its driver."""
    names = ("blit-readback", "blit-sink", "blit-bf-readback")
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        alive = [t.name for t in threading.enumerate()
                 if t.name in names and t.is_alive()]
        if not alive:
            return True
        time.sleep(0.02)
    return False


# -- OutputRotation ---------------------------------------------------------


class TestOutputRotation:
    def test_order_and_values_preserved(self):
        tl = Timeline()
        rot = OutputRotation(depth=2, timeline=tl)
        try:
            got = []
            for i in range(7):
                out = jnp.full((4, 3), float(i))
                got.extend(rot.put(out, nbytes=out.nbytes))
            for slab in rot.drain():
                got.append(slab)
            assert len(got) == 7
            for i, slab in enumerate(got):
                np.testing.assert_array_equal(
                    slab.data, np.full((4, 3), float(i), np.float32))
                slab.release()
        finally:
            rot.close()
        assert tl.stages["readback"].calls == 7
        assert tl.stages["readback"].bytes == 7 * 4 * 3 * 4
        assert tl.stages["device"].bytes == 7 * 4 * 3 * 4

    def test_ring_mode_reuses_bounded_slabs(self):
        rot = OutputRotation(depth=2, reuse=True)
        try:
            seen_ids = set()
            for i in range(10):
                out = jnp.full((8,), float(i))
                for slab in rot.put(out):
                    seen_ids.add(id(slab.data))
                    np.testing.assert_array_equal(
                        slab.data, np.full((8,), held_val(slab)))
                    slab.release()
            for slab in rot.drain():
                seen_ids.add(id(slab.data))
                slab.release()
            # At most depth+1 distinct resident slab buffers ever existed
            # (CPU fetches alias the jax buffer, so the recycling ring is
            # the path exercised here).
            assert len(seen_ids) <= 3
        finally:
            rot.close()

    def test_late_release_retires_slab_to_staging_pool(self):
        # A slab still held by a consumer when close() sweeps the ring
        # (the AsyncSink write-behind tail pattern) must retire to the
        # process staging pool on release — not feed the GC and make the
        # next stream re-pay allocation + first-touch faults.
        from blit import hostmem

        pool = hostmem.slab_pool()
        rot = OutputRotation(depth=2, reuse=True)
        held = []
        try:
            for slab in rot.put(jnp.full((4099,), 7.0)):
                held.append(slab)
            for slab in rot.drain():
                held.append(slab)
        finally:
            rot.close()
        assert held  # the ring path ran (CPU fetch copies into a slab)
        before = pool.stats()["free_bytes"]
        for slab in held:
            slab.release()
        assert pool.stats()["free_bytes"] >= before + 4099 * 4

    def test_on_consumed_fires_before_emission(self):
        events = []
        rot = OutputRotation(depth=1)
        try:
            out = jnp.zeros((4,))
            # depth=1: put blocks until the readback completes, so the
            # finished slab comes back from put() itself.
            done = rot.put(out, on_consumed=lambda: events.append("consumed"))
            for slab in done:
                events.append("slab")
                slab.release()
            for slab in rot.drain():
                events.append("slab")
                slab.release()
        finally:
            rot.close()
        assert events == ["consumed", "slab"]

    def test_readback_error_reraises_in_consumer(self):
        rot = OutputRotation(depth=1)

        class Dead:
            def block_until_ready(self):
                raise RuntimeError("device fell over")

        try:
            with pytest.raises(RuntimeError, match="device fell over"):
                rot.put(Dead())
                list(rot.drain())
        finally:
            rot.close()
        assert no_plane_threads()

    def test_close_is_idempotent_and_joins(self):
        rot = OutputRotation(depth=1)
        rot.put(jnp.zeros((2,)))
        list(rot.drain())
        rot.close()
        rot.close()
        assert no_plane_threads()


def held_val(slab):
    return float(slab.data.flat[0])


# -- AsyncSink --------------------------------------------------------------


class _ListWriter:
    """Recording writer with the slab-writer contract."""

    def __init__(self):
        self.slabs = []
        self.closed = False
        self.aborted = False
        self.flushes = 0
        self.path = "/fake/list.fil"

    def append(self, slab):
        self.slabs.append(np.array(slab, copy=True))

    def flush(self):
        self.flushes += 1

    def close(self):
        self.closed = True

    def abort(self):
        self.aborted = True

    @property
    def nsamps(self):
        return sum(s.shape[0] for s in self.slabs)


class TestAsyncSink:
    def test_writes_in_order_and_finalizes(self):
        tl = Timeline()
        w = _ListWriter()
        sink = AsyncSink(w, depth=2, timeline=tl)
        for i in range(6):
            sink.append(np.full((2, 1, 4), float(i), np.float32))
        sink.close()
        assert w.closed and not w.aborted
        assert len(w.slabs) == 6
        for i, s in enumerate(w.slabs):
            np.testing.assert_array_equal(s, np.full((2, 1, 4), float(i)))
        assert tl.stages["write"].calls == 6
        assert tl.stages["write"].bytes == 6 * 2 * 4 * 4
        assert sink.nsamps == 12
        assert no_plane_threads()

    def test_flush_is_a_barrier(self):
        w = _ListWriter()
        sink = AsyncSink(w, depth=4)
        for i in range(3):
            sink.append(np.zeros((1, 1, 4), np.float32))
        sink.flush()
        assert len(w.slabs) == 3  # every prior append applied
        assert w.flushes == 1     # writer's own flush hook ran
        sink.close()
        assert no_plane_threads()

    def test_release_fires_after_write(self):
        w = _ListWriter()
        released = []
        sink = AsyncSink(w, depth=2)
        sink.append(np.zeros((1, 1, 4), np.float32),
                    release=lambda: released.append(len(w.slabs)))
        sink.flush()
        # The release saw the write already applied (FIFO on one thread).
        assert released == [1]
        sink.close()

    def test_writer_stall_watchdog(self):
        class Wedged(_ListWriter):
            def append(self, slab):
                time.sleep(3600)

        # Distinct thread name: the wedged daemon is abandoned (sleeping),
        # and must not trip later tests' no_plane_threads() sweeps.
        sink = AsyncSink(Wedged(), depth=1, stall_timeout_s=0.3,
                         name="blit-sink-wedged")
        sink.append(np.zeros((1, 1, 4), np.float32))
        with pytest.raises(RuntimeError, match="stall"):
            # Queue full behind the wedged append -> watchdog, not a hang.
            for _ in range(10):
                sink.append(np.zeros((1, 1, 4), np.float32))
        # Bounded teardown: the wedged daemon is abandoned, not joined.
        t0 = time.monotonic()
        sink.abort(join_timeout_s=0.2)
        assert time.monotonic() - t0 < 5.0


# -- FoldInFlight -----------------------------------------------------------


class _FakeWin:
    def __init__(self, log, i):
        self.log, self.i = log, i

    def release(self):
        self.log.append(self.i)


class TestFoldInFlight:
    def test_lag_release_order(self):
        tl = Timeline()
        fl = FoldInFlight(tl, depth=1)
        log = []
        for i in range(4):
            fl.make_room()
            fl.admit(_FakeWin(log, i), jnp.zeros((2,)))
        assert log == [0, 1, 2]  # lag-1: last window still admitted
        fl.drain(synced=True)
        assert log == [0, 1, 2, 3]
        # synced drain did not run a device wait for the tail
        assert tl.stages["device"].calls == 3


# -- async-vs-sync equivalence (ISSUE 4 satellite) --------------------------


def _synth(tmp_path, **kw):
    p = str(tmp_path / "x.raw")
    kw.setdefault("nblocks", 3)
    kw.setdefault("obsnchan", 2)
    kw.setdefault("ntime_per_block", 1024)
    kw.setdefault("tone_chan", 1)
    synth_raw(p, **kw)
    return p


class TestAsyncSyncEquivalence:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("fqav_by", [1, 4])
    def test_fil_products_byte_identical(self, tmp_path, dtype, fqav_by):
        raw = _synth(tmp_path)
        kw = dict(nfft=64, nint=2, chunk_frames=4, dtype=dtype,
                  fqav_by=fqav_by)
        out_a = str(tmp_path / "a.fil")
        out_s = str(tmp_path / "s.fil")
        RawReducer(**kw).reduce_to_file(raw, out_a)
        RawReducer(**kw, async_output=False).reduce_to_file(raw, out_s)
        with open(out_a, "rb") as fa, open(out_s, "rb") as fs:
            assert fa.read() == fs.read()  # whole file, header included
        assert no_plane_threads()

    @pytest.mark.parametrize("fqav_by", [1, 4])
    def test_h5_products_identical(self, tmp_path, fqav_by):
        raw = _synth(tmp_path)
        kw = dict(nfft=64, nint=2, chunk_frames=4, fqav_by=fqav_by)
        out_a = str(tmp_path / "a.h5")
        out_s = str(tmp_path / "s.h5")
        ha = RawReducer(**kw).reduce_to_file(raw, out_a)
        hs = RawReducer(**kw, async_output=False).reduce_to_file(raw, out_s)
        np.testing.assert_array_equal(read_fbh5_data(out_a),
                                      read_fbh5_data(out_s))
        assert read_fbh5_header(out_a) == read_fbh5_header(out_s)
        assert ha["nsamps"] == hs["nsamps"]

    def test_stream_slabs_identical(self, tmp_path):
        raw = _synth(tmp_path)
        kw = dict(nfft=64, nint=2, chunk_frames=4)
        _, da = RawReducer(**kw).reduce(raw)
        _, ds = RawReducer(**kw, async_output=False).reduce(raw)
        np.testing.assert_array_equal(da, ds)

    def test_skip_frames_replay_identical(self, tmp_path):
        # The resume path's exact-replay contract through the new plane.
        raw = _synth(tmp_path)
        from blit.io.guppi import GuppiRaw

        kw = dict(nfft=64, nint=2, chunk_frames=4)
        full = np.concatenate(
            list(RawReducer(**kw).stream(GuppiRaw(raw))), axis=0)
        tail_a = np.concatenate(
            list(RawReducer(**kw).stream(GuppiRaw(raw), skip_frames=8)),
            axis=0)
        tail_s = np.concatenate(
            list(RawReducer(**kw, async_output=False).stream(
                GuppiRaw(raw), skip_frames=8)), axis=0)
        np.testing.assert_array_equal(tail_a, tail_s)
        np.testing.assert_array_equal(tail_a, full[8 // 2:])

    def test_resume_mid_file_through_async_plane(self, tmp_path):
        # Crash the write-behind sink mid-product, resume, compare with
        # an uninterrupted synchronous run: decoded payloads identical.
        raw = _synth(tmp_path, nblocks=4)
        kw = dict(nfft=64, nint=2, chunk_frames=4)
        out = str(tmp_path / "r.fil")
        faults.install(FaultRule(point="sink.write", mode="fail", after=2,
                                 times=-1))
        try:
            with pytest.raises(faults.InjectedFault):
                RawReducer(**kw).reduce_resumable(raw, out)
        finally:
            faults.clear()
        cur = ReductionCursor.load(out)
        assert cur is not None and cur.frames_done == 8  # two slabs landed
        RawReducer(**kw).reduce_resumable(raw, out)
        _, got = read_fil_data(out)
        want_out = str(tmp_path / "w.fil")
        RawReducer(**kw, async_output=False).reduce_to_file(raw, want_out)
        _, want = read_fil_data(want_out)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert not os.path.exists(ReductionCursor.path_for(out))
        assert no_plane_threads()

    def test_env_kill_switch(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_SYNC_OUTPUT", "1")
        assert RawReducer(nfft=64).async_output is False


class TestMaskedStreamThroughPlane:
    def test_masked_antenna_stream_matches_zero_weight(self, tmp_path):
        # on_antenna_error="mask" windows ride the same OutputRotation:
        # the degraded stream's slabs must equal a clean stream whose
        # failed antenna is zero-weighted from the failing window on.
        from blit.parallel.antenna import AntennaStream
        from blit.parallel.beamform import beamform_stream, delay_weights_planar
        from blit.parallel.mesh import make_mesh

        nant, nsamp = 4, 512
        paths = []
        for a in range(nant):
            p = str(tmp_path / f"ant{a}.raw")
            synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=nsamp // 2,
                      seed=a)
            paths.append(p)
        mesh = make_mesh(1, 4)
        w = delay_weights_planar(
            jnp.zeros((2, nant)), jnp.asarray([1e9, 2e9]))

        def powers(ps, **feed_kw):
            feed = AntennaStream(ps, mesh=mesh, window_samples=128,
                                 max_samples=nsamp, **feed_kw)
            slabs = list(beamform_stream(feed, w, mesh=mesh, nint=64))
            return np.concatenate(slabs, axis=2), feed

        # Fail antenna 2's reads from its second window on.
        faults.install(FaultRule(point="guppi.read", mode="fail", after=2,
                                 times=-1, match="ant2"))
        faults.set_io_policy(RetryPolicy(attempts=1))
        try:
            got, feed = powers(paths, on_antenna_error="mask")
        finally:
            faults.clear()
        assert feed.masked_antennas == {2}
        assert feed.timeline.stages["antenna.masked"].calls >= 1
        assert got.shape[2] == nsamp // 64
        # Clean slabs for the unmasked prefix; finite everywhere after.
        clean, _ = powers(paths)
        np.testing.assert_array_equal(got[..., :2, :], clean[..., :2, :])
        assert np.isfinite(got).all()
        assert not np.array_equal(got[..., 2:, :], clean[..., 2:, :])
        assert no_plane_threads()


# -- the ingest rig's byte accounting (ISSUE 4 satellite) -------------------


class TestRigAccounting:
    def test_timeline_reset_preserves_stage_identity(self):
        tl = Timeline()
        with tl.stage("stream", nbytes=10):
            pass
        tl.gauge("depth", 3.0)
        held = tl.stages["stream"]  # a concurrent thread's captured ref
        tl.reset()
        assert tl.stages["stream"] is held  # identity preserved...
        assert held.bytes == 0 and held.seconds == 0.0  # ...and zeroed
        assert tl.gauges["depth"].n == 0
        held.bytes += 7  # late update from the holder
        assert tl.stages["stream"].bytes == 7  # ...lands in the report
        # clear() is exactly the footgun reset() exists to avoid:
        tl.stages.clear()
        held.bytes += 5
        assert tl.stages["stream"].bytes == 0  # orphaned — the r05 bug

    def test_rig_sequence_keeps_stream_bytes(self, tmp_path):
        # BENCH_r05 reported "stream": {"s": 350.3, "bytes": 0} — the rig
        # lost the stream-stage byte counter across its warmup/clear/
        # drain sequence (seed-era _chunks never counted them; clear()
        # would orphan them today).  Pin the exact rig sequence from
        # bench.py::_run_ingest: warmup chunk passes, Timeline.reset(),
        # timed drain — the dominant stage must carry its bytes.
        from blit.io.guppi import GuppiRaw

        raw = _synth(tmp_path)
        red = RawReducer(nfft=64, nint=1, chunk_frames=4)
        g = GuppiRaw(raw)
        for _ in range(2):
            for c in red._chunks(g):
                c.release()
        red.timeline.reset()
        red.drain(g)
        st = red.timeline.stages
        assert st["stream"].bytes == st["device"].bytes > 0
        for name, s in st.items():
            if s.seconds > 0:
                assert s.bytes > 0 or s.byte_free, name


# -- overlap gauge + product-path stage table -------------------------------


class TestOverlapObservability:
    def test_product_run_times_readback_and_write(self, tmp_path):
        raw = _synth(tmp_path)
        red = RawReducer(nfft=64, nint=2, chunk_frames=4)
        red.reduce_to_file(raw, str(tmp_path / "p.fil"))
        st = red.timeline.stages
        assert st["readback"].calls > 0 and st["readback"].bytes > 0
        assert st["write"].calls > 0 and st["write"].bytes > 0
        assert st["write"].bytes == st["readback"].bytes
        assert st["dispatch"].byte_free
        # The gauge landed (value is rig-dependent; presence is the pin).
        assert "overlap.stream" in red.timeline.gauges
        rep = red.timeline.report()
        assert rep["gauges"]["overlap.stream"]["n"] == 1

    def test_overlap_efficiency_math(self):
        tl = Timeline()
        tl.stages["stream"].seconds = 2.0
        tl.stages["device"].seconds = 1.0
        tl.stages["readback"].seconds = 2.0
        tl.stages["write"].seconds = 1.0
        assert tl.overlap_efficiency() == pytest.approx(2.0)
        assert tl.gauges["overlap.stream"].last == pytest.approx(2.0)
        assert Timeline().overlap_efficiency() == 0.0


class TestReduceCLI:
    """`blit reduce` as a user types it, with the output plane on (the
    default) and off (``BLIT_SYNC_OUTPUT=1``)."""

    @pytest.fixture(scope="class")
    def runs(self, tmp_path_factory):
        import contextlib
        import io
        import json

        from blit.__main__ import main

        td = tmp_path_factory.mktemp("reduce_cli")
        raw = _synth(td)
        out = {}
        with pytest.MonkeyPatch.context() as mp:
            for sync in (False, True):
                if sync:
                    mp.setenv("BLIT_SYNC_OUTPUT", "1")
                else:
                    mp.delenv("BLIT_SYNC_OUTPUT", raising=False)
                fil = str(td / f"sync{int(sync)}.fil")
                buf = io.StringIO()
                with contextlib.redirect_stdout(buf):
                    rc = main(["reduce", raw, "-o", fil, "--nfft", "128"])
                rep = json.loads(buf.getvalue().strip().splitlines()[-1])
                with open(fil, "rb") as f:
                    out[sync] = (rc, rep, f.read())
        return out

    @pytest.mark.parametrize("sync", [False, True],
                             ids=["async", "BLIT_SYNC_OUTPUT"])
    def test_one_product_and_its_stage_table(self, runs, sync):
        rc, rep, product = runs[sync]
        assert rc == 0
        assert product == runs[not sync][2]
        payload = rep["nsamps"] * rep["nifs"] * rep["nchans"] * 4
        assert 0 < payload < len(product)
        st = rep["stages"]
        if not sync:
            # The output plane's rows: every product byte read back
            # from the device and every one written.
            assert {"dispatch", "readback", "write"} <= set(st)
            assert st["write"]["bytes"] == st["readback"]["bytes"] == payload
        else:
            # The switch was honoured: the oracle ran without the plane.
            assert "readback" not in st and "write" not in st
        assert no_plane_threads()
