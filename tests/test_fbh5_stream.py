"""Streaming FBH5 writes (VERDICT r3 item 5): slab-by-slab, time-resizable
``.h5`` products at bounded host memory, identical payload to the
in-memory writer, with ``.partial`` atomicity — BL's native product
format (src/gbtworkerfunctions.jl:141-155) without materializing it."""

import os

import h5py
import numpy as np
import pytest

from blit.io.fbh5 import (
    FBH5Writer,
    read_fbh5_data,
    read_fbh5_header,
    write_fbh5,
)

HDR = {"fch1": 8000.0, "foff": -0.1, "tsamp": 1.0, "nbits": 32,
       "source_name": "SYNTH"}


def make_data(nsamps=37, nifs=2, nchans=64, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((nsamps, nifs, nchans)).astype(np.float32)


def stream_write(path, data, slab_sizes, **kw):
    with FBH5Writer(path, HDR, nifs=data.shape[1], nchans=data.shape[2],
                    **kw) as w:
        pos = 0
        for k in slab_sizes:
            w.append(data[pos:pos + k])
            pos += k
        assert pos == data.shape[0]
    return w


class TestStreamedPayload:
    @pytest.mark.parametrize("compression", [None, "gzip", "bitshuffle"])
    def test_matches_in_memory_write(self, tmp_path, compression):
        data = make_data()
        mem = str(tmp_path / "mem.h5")
        st = str(tmp_path / "stream.h5")
        chunks = (8, data.shape[1], data.shape[2])
        write_fbh5(mem, HDR, data, compression=compression, chunks=chunks)
        # Ragged slabs that straddle chunk boundaries both ways.
        stream_write(st, data, [5, 11, 1, 13, 7], compression=compression,
                     chunks=chunks)
        np.testing.assert_array_equal(read_fbh5_data(st), data)
        hm, hs = read_fbh5_header(mem), read_fbh5_header(st)
        assert hm == hs  # includes nsamps and data_size

    def test_bitshuffle_chunks_byte_identical(self, tmp_path):
        # The streamed file's ENCODED chunks equal the in-memory writer's:
        # same codec, same padding convention, chunk for chunk.
        data = make_data(nsamps=20, nchans=100)
        mem = str(tmp_path / "mem.h5")
        st = str(tmp_path / "stream.h5")
        chunks = (8, 2, 100)
        write_fbh5(mem, HDR, data, compression="bitshuffle", chunks=chunks)
        stream_write(st, data, [3, 9, 8], compression="bitshuffle",
                     chunks=chunks)
        with h5py.File(mem) as a, h5py.File(st) as b:
            for t0 in range(0, 20, 8):
                pa = a["data"].id.read_direct_chunk((t0, 0, 0))[1]
                pb = b["data"].id.read_direct_chunk((t0, 0, 0))[1]
                assert pa == pb

    def test_single_append_whole_product(self, tmp_path):
        data = make_data(nsamps=16)
        p = str(tmp_path / "x.h5")
        stream_write(p, data, [16], compression="bitshuffle")
        np.testing.assert_array_equal(read_fbh5_data(p), data)

    def test_empty_product(self, tmp_path):
        p = str(tmp_path / "x.h5")
        stream_write(p, make_data(nsamps=0), [], compression="bitshuffle")
        assert read_fbh5_header(p)["nsamps"] == 0


class TestBoundedMemory:
    def test_buffer_never_exceeds_one_chunk_row(self, tmp_path):
        # The streaming writer's residency bound: one chunk row of pending
        # spectra, however the appends arrive.
        data = make_data(nsamps=100)
        p = str(tmp_path / "x.h5")
        w = FBH5Writer(p, HDR, nifs=2, nchans=64, compression="bitshuffle",
                       chunks=(16, 2, 64))
        try:
            pos = 0
            for k in (1, 33, 2, 50, 14):
                w.append(data[pos:pos + k])
                pos += k
                assert w._buffered < 16  # full rows always flushed
                assert w._buf.shape == (16, 2, 64)
        finally:
            w.close()
        np.testing.assert_array_equal(read_fbh5_data(p), data)


class TestAtomicity:
    def test_crash_leaves_no_product(self, tmp_path):
        p = str(tmp_path / "x.h5")
        with pytest.raises(RuntimeError, match="boom"):
            with FBH5Writer(p, HDR, nifs=2, nchans=64) as w:
                w.append(make_data(nsamps=4))
                raise RuntimeError("boom")
        assert not os.path.exists(p)
        assert not os.path.exists(p + ".partial")

    def test_partial_invisible_until_close(self, tmp_path):
        p = str(tmp_path / "x.h5")
        w = FBH5Writer(p, HDR, nifs=2, nchans=64)
        try:
            w.append(make_data(nsamps=4))
            assert not os.path.exists(p)
            assert os.path.exists(p + ".partial")
        finally:
            w.close()
        assert os.path.exists(p) and not os.path.exists(p + ".partial")

    def test_bad_slab_shape_rejected(self, tmp_path):
        p = str(tmp_path / "x.h5")
        with pytest.raises(ValueError, match="slab shape"):
            with FBH5Writer(p, HDR, nifs=2, nchans=64) as w:
                w.append(np.zeros((4, 2, 32), np.float32))
        assert not os.path.exists(p + ".partial")


class TestReducerH5Streaming:
    def test_reduce_to_file_h5_matches_reduce(self, tmp_path):
        jax = pytest.importorskip("jax")
        from blit.pipeline import RawReducer
        from blit.testing import synth_raw

        raw = str(tmp_path / "x.raw")
        synth_raw(raw, nblocks=3, obsnchan=2, ntime_per_block=512)
        red = RawReducer(nfft=64, nint=2)
        hdr_mem, data = red.reduce(raw)
        out = str(tmp_path / "x.h5")
        hdr = red.reduce_to_file(raw, out)
        np.testing.assert_array_equal(read_fbh5_data(out), data)
        assert hdr["nsamps"] == data.shape[0] == read_fbh5_header(out)["nsamps"]

    def test_reduce_to_file_h5_bitshuffle(self, tmp_path):
        jax = pytest.importorskip("jax")
        from blit.pipeline import RawReducer
        from blit.testing import synth_raw

        raw = str(tmp_path / "x.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512)
        red = RawReducer(nfft=32)
        _, data = red.reduce(raw)
        out = str(tmp_path / "x.h5")
        red.reduce_to_file(raw, out, compression="bitshuffle")
        np.testing.assert_array_equal(read_fbh5_data(out), data)

    def test_fil_rejects_compression(self, tmp_path):
        jax = pytest.importorskip("jax")
        from blit.pipeline import RawReducer
        from blit.testing import synth_raw

        raw = str(tmp_path / "x.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=256)
        with pytest.raises(ValueError, match="uncompressed"):
            RawReducer(nfft=32).reduce_to_file(
                raw, str(tmp_path / "x.fil"), compression="gzip"
            )


class TestConstructionGuards:
    def test_bitshuffle_rejects_channel_split_chunks(self, tmp_path):
        # The streaming encoder writes one chunk per time row; channel-split
        # chunks would silently drop data, so construction refuses them.
        p = str(tmp_path / "x.h5")
        with pytest.raises(ValueError, match="whole-spectrum"):
            FBH5Writer(p, HDR, nifs=2, nchans=1024,
                       compression="bitshuffle", chunks=(16, 2, 512))
        assert not os.path.exists(p + ".partial")

    def test_unknown_compression_rejected(self, tmp_path):
        p = str(tmp_path / "x.h5")
        with pytest.raises(ValueError, match="unknown compression"):
            FBH5Writer(p, HDR, nifs=2, nchans=64, compression="lzma")

    def test_plain_writer_skips_chunk_buffer(self, tmp_path):
        # Only the bitshuffle path needs the pending chunk-row buffer; a
        # plain/gzip writer of a wide product must not allocate it.
        p = str(tmp_path / "x.h5")
        with FBH5Writer(p, HDR, nifs=1, nchans=1 << 20) as w:
            assert w._buf is None
            w.append(np.zeros((1, 1, 1 << 20), np.float32))


class TestChunkClamp:
    """HDF5 refuses chunks of 4 GiB or more; defaults must clamp (ADVICE
    r4: the hi-res preset's unclamped 16-row default was 16 GiB and made
    the flagship .h5 product unwritable via the public APIs)."""

    def test_default_chunks_clamped_under_limit(self):
        from blit.io.fbh5 import H5_CHUNK_LIMIT, default_chunks

        # hi-res bank product: 64 coarse channels x 2^20 fine = 256 MiB/row.
        c = default_chunks(1, 64 << 20, 4)
        assert c == (15, 1, 64 << 20)
        assert c[0] * c[1] * c[2] * 4 <= H5_CHUNK_LIMIT
        # IQUV hi-res: 1 GiB rows -> 3.
        assert default_chunks(4, 64 << 20, 4)[0] == 3
        # Small products keep BL's conventional 16 rows.
        assert default_chunks(4, 64, 4) == (16, 4, 64)

    def test_default_chunks_splits_channels_past_limit(self):
        from blit.io.fbh5 import H5_CHUNK_LIMIT, default_chunks

        # Full-band IQUV mesh product: one spectrum is 8 GiB.
        rows, nifs, cchunk = default_chunks(4, 512 << 20, 4)
        assert rows == 1 and nifs == 4 and cchunk < 512 << 20
        assert rows * nifs * cchunk * 4 <= H5_CHUNK_LIMIT
        with pytest.raises(ValueError, match="whole-spectrum"):
            default_chunks(4, 512 << 20, 4, whole_spectrum=True)

    def test_hires_writer_opens_with_default_chunks(self, tmp_path):
        # The ADVICE repro: writer open at the hi-res shape must succeed.
        p = str(tmp_path / "hires.h5")
        w = FBH5Writer(p, HDR, nifs=1, nchans=64 << 20)
        try:
            assert w.chunks[0] * w.chunks[1] * w.chunks[2] * 4 < 2**32
        finally:
            w.abort()


class TestDirectChunksBesideAnotherThread:
    """h5py's ``read_direct_chunk`` / ``write_direct_chunk`` enter libhdf5
    without h5py's lock (3.14): beside ANY h5py call of another thread —
    a property list closed by a cyclic GC that ran on a feed thread is
    enough — libhdf5's state is torn (a quarter of the writes failed).
    blit's two helpers hold the lock themselves."""

    @pytest.mark.parametrize("what", ["write", "read"])
    def test_the_pair_holds_h5pys_lock(self, tmp_path, what):
        import sys
        import threading

        from blit.io import fbh5

        stop = threading.Event()
        every = sys.getswitchinterval()

        def churn():  # what finalizers of dead writers do, all the time
            while not stop.is_set():
                del [h5py.h5p.create(h5py.h5p.DATASET_XFER)
                     for _ in range(20)][:]

        payload = np.zeros(256, np.float32).tobytes()
        with h5py.File(tmp_path / "x.h5", "w") as h5:
            ds = h5.create_dataset("data", shape=(300, 1, 256),
                                   chunks=(1, 1, 256), dtype="f4")
            for n in range(300):
                fbh5._write_chunk(ds, (n, 0, 0), payload)
            beside = threading.Thread(target=churn)
            beside.start()
            # (The two threads change places as often as they can.)
            sys.setswitchinterval(1e-5)
            try:
                for n in range(3000):
                    if what == "write":
                        fbh5._write_chunk(ds, (n % 300, 0, 0), payload)
                    else:
                        assert fbh5._read_chunk(ds, (n % 300, 0, 0)) \
                            == payload
            finally:
                sys.setswitchinterval(every)
                stop.set()
                beside.join()
