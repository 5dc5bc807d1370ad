"""End-to-end RAW → filterbank pipeline tests (blit/pipeline.py): streaming
chunking vs whole-file golden reduction, overlap handling, product output."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit.io.guppi import GuppiRaw  # noqa: E402
from blit.io.sigproc import read_fil_data  # noqa: E402
from blit.ops.channelize import channelize_np, pfb_coeffs  # noqa: E402
from blit.pipeline import RawReducer, reducer_for_product  # noqa: E402
from blit.testing import synth_raw  # noqa: E402


def whole_file_reference(raw_path, nfft, ntap, nint, stokes="I"):
    """Golden: concatenate the overlap-trimmed stream and reduce in one shot
    with the NumPy reference implementation."""
    raw = GuppiRaw(raw_path)
    stream = np.concatenate(
        [blk for _, blk in raw.iter_blocks(drop_overlap=True)], axis=1
    )
    frames = stream.shape[1] // nfft - ntap + 1
    frames = (frames // nint) * nint
    usable = (frames + ntap - 1) * nfft
    h = pfb_coeffs(ntap, nfft)
    return channelize_np(
        stream[:, :usable], h, nfft=nfft, ntap=ntap, nint=nint, stokes=stokes
    )


class TestStreaming:
    @pytest.mark.parametrize("overlap", [0, 64])
    def test_streaming_matches_whole_file(self, tmp_path, overlap):
        # Chunked streaming with PFB state carry must equal the one-shot
        # reduction of the gap-free stream — block/chunk boundaries invisible.
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=4, obsnchan=4, ntime_per_block=1024 + overlap,
                  overlap=overlap, tone_chan=2)
        red = RawReducer(nfft=128, nint=2, chunk_frames=4)
        hdr, data = red.reduce(p)
        want = whole_file_reference(p, nfft=128, ntap=4, nint=2)
        assert data.shape == want.shape
        np.testing.assert_allclose(data, want, rtol=1e-4, atol=0.5)
        rel = np.abs(data - want).max() / want.max()
        assert rel < 1e-4

    @pytest.mark.parametrize("overlap", [0, 64])
    def test_drain_checksum_matches_stream(self, tmp_path, overlap):
        # The device-sink path must reduce exactly the frames the host-sink
        # path yields (same chunker underneath).
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=4, obsnchan=4, ntime_per_block=1024 + overlap,
                  overlap=overlap, tone_chan=1)
        red = RawReducer(nfft=128, nint=2, chunk_frames=4)
        slabs = list(red.stream(GuppiRaw(p)))
        want = sum(float(s.sum()) for s in slabs)
        red2 = RawReducer(nfft=128, nint=2, chunk_frames=4)
        got = red2.drain(GuppiRaw(p))
        assert red2.stats.output_frames == red.stats.output_frames
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_chunk_frames_rounds_to_nint(self):
        # The contract since ISSUE 26: an explicit chunk_frames is kept as
        # given (nint need not divide it — the integration is then carried
        # across dispatches); the DEFAULT still fits whole integrations
        # wherever the per-dispatch sample budget (2^23) holds one ...
        red = RawReducer(nfft=64, nint=6, chunk_frames=8)
        assert red.chunk_frames == 8 and red._carries
        for nfft, nint in [(64, 6), (1024, 3072), (1 << 20, 1),
                           (1 << 20, 8), (8, 128)]:
            red = RawReducer(nfft=nfft, nint=nint)
            assert red.chunk_frames % nint == 0 and not red._carries
        # ... and is the budget's own where it cannot (rawspec's
        # -f 1048576 -t 51: 8 frames a dispatch, never a 54-frame chunk).
        red = RawReducer(nfft=1 << 20, nint=51)
        assert red.chunk_frames == 8 and red._carries
        with pytest.raises(ValueError, match="chunk_frames"):
            RawReducer(nfft=64, nint=2, chunk_frames=0)

    @pytest.mark.parametrize("depth", [2, 3, 4])
    def test_prefetch_depth_invariant(self, tmp_path, depth):
        # The rotation depth changes pipelining only — never the product.
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=4, obsnchan=2, ntime_per_block=1024,
                  overlap=32, tone_chan=1)
        base = RawReducer(nfft=64, nint=2, chunk_frames=4, prefetch_depth=2)
        _, want = base.reduce(p)
        red = RawReducer(nfft=64, nint=2, chunk_frames=4,
                         prefetch_depth=depth)
        _, got = red.reduce(p)
        np.testing.assert_array_equal(got, want)
        drained = RawReducer(nfft=64, nint=2, chunk_frames=4,
                             prefetch_depth=depth).drain(GuppiRaw(p))
        np.testing.assert_allclose(drained, float(want.sum()), rtol=1e-5)

    def test_abandoned_stream_stops_producer(self, tmp_path):
        # Breaking out of a stream must not leak a blocked ingest thread.
        import threading

        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=8, obsnchan=2, ntime_per_block=1024)
        red = RawReducer(nfft=64, nint=1, chunk_frames=2)
        it = red.stream(GuppiRaw(p))
        next(it)
        it.close()  # abandon mid-stream
        for _ in range(50):
            if not any(t.name == "blit-ingest" and t.is_alive()
                       for t in threading.enumerate()):
                break
            import time

            time.sleep(0.05)
        assert not any(t.name == "blit-ingest" and t.is_alive()
                       for t in threading.enumerate())

    def test_stats_track_input_bytes(self, tmp_path):
        p = str(tmp_path / "x.raw")
        _, blocks = synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=512)
        red = RawReducer(nfft=64, nint=1)
        red.reduce(p)
        assert red.stats.input_bytes == sum(b.nbytes for b in blocks)
        assert red.stats.wall_seconds > 0
        assert red.stats.gbps > 0

    def test_every_timed_stage_carries_bytes(self, tmp_path):
        # VERDICT r5 weak #3: the dominant stage of the streaming leg
        # reported zero bytes (BENCH_r05 stream.s=350, bytes=0), so the
        # stage table couldn't be sanity-summed against end-to-end GB/s.
        # Invariant, pinned for every reducer stage: nonzero seconds ⇒
        # nonzero bytes, unless the stage is explicitly byte-free.
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=3, obsnchan=2, ntime_per_block=1024)
        red = RawReducer(nfft=64, nint=2, chunk_frames=4)
        red.reduce(p)
        assert red.timeline.stages["stream"].bytes > 0
        for name, st in red.timeline.stages.items():
            if st.seconds > 0:
                assert st.bytes > 0 or st.byte_free, (
                    f"stage {name!r} spent {st.seconds}s moving 0 bytes "
                    "without declaring byte_free"
                )

    def test_stream_stage_counts_every_chunk_byte_once(self, tmp_path):
        # The stream stage moves every byte it hands downstream: each
        # chunk's new samples and, with the first, the stream's head (the
        # filter state of frame 0) — no sample twice (ISSUE 29: a chunk
        # no longer re-sends the previous chunk's tail).
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=1024)
        red = RawReducer(nfft=64, nint=1, chunk_frames=4)
        sent, heads, shapes = 0, [], set()
        for c in red._chunks(GuppiRaw(p)):
            sent += c.nbytes
            heads.append(c.head)
            shapes.add(c.view.shape)
            assert c.view.shape[1] == c.frames * 64
            c.release()
        assert red.timeline.stages["stream"].bytes == sent > 0
        # 2048 samples: a 3-frame head, 7 chunks of 4 frames, one frame
        # left for the flush chunk.
        assert heads[0].shape == (2, 3 * 64, 2, 2)
        assert all(h is None for h in heads[1:])
        assert shapes == {(2, 4 * 64, 2, 2), (2, 64, 2, 2)}
        assert sent == red.timeline.stages["ingest"].bytes \
            == 2 * 2048 * 2 * 2
        assert "state" not in red.timeline.report()


class TestProducts:
    def test_reduce_to_fil_roundtrip(self, tmp_path):
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=1024, tone_chan=1)
        out = str(tmp_path / "x.rawspec.0002.fil")
        red = RawReducer(nfft=64, nint=4, stokes="I")
        hdr = red.reduce_to_file(p, out)
        rhdr, data = read_fil_data(out)
        assert rhdr["nchans"] == 2 * 64
        assert rhdr["nifs"] == 1
        assert data.shape[0] == hdr["nsamps"]
        # The injected tone (chan 1, freq 0.25) must dominate its fine channel.
        spec = np.asarray(data).sum(axis=0)[0]
        assert spec.argmax() == 64 + 32 + 16  # coarse 1, fftshift(0.25*64)=48

    def test_reduce_to_fbh5_roundtrip(self, tmp_path):
        h5py = pytest.importorskip("h5py")  # noqa: F841
        from blit.io.fbh5 import read_fbh5_data, read_fbh5_header

        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=1024)
        out = str(tmp_path / "x.rawspec.0002.h5")
        red = RawReducer(nfft=64, nint=4)
        red.reduce_to_file(p, out)
        hdr = read_fbh5_header(out)
        data = read_fbh5_data(out)
        assert hdr["nchans"] == 128 and data.ndim == 3

    def test_header_frequency_axis(self, tmp_path):
        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=1, obsnchan=4, ntime_per_block=512, obsbw=-187.5)
        red = RawReducer(nfft=64, nint=1)
        hdr, _ = red.reduce(p)
        assert hdr["foff"] == pytest.approx(-187.5 / 4 / 64)
        freqs = hdr["fch1"] + hdr["foff"] * np.arange(hdr["nchans"])
        assert freqs.mean() == pytest.approx(8437.5, abs=abs(hdr["foff"]))

    def test_product_presets(self):
        red = reducer_for_product("0001")
        assert (red.nfft, red.nint) == (8, 128)


class TestEdgeCases:
    def test_empty_raw_file_raises(self, tmp_path):
        p = tmp_path / "empty.raw"
        p.write_bytes(b"")
        with pytest.raises(ValueError, match="empty"):
            RawReducer(nfft=64).reduce(str(p))

    def test_hires_default_chunk_is_hbm_sized(self):
        red = RawReducer(nfft=1 << 20, nint=1)
        assert red.chunk_frames <= 8  # budget-scaled, not the small-nfft 64
        red2 = RawReducer(nfft=1024, nint=1)
        assert red2.chunk_frames == 64
