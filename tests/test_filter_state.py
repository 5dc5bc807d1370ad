"""The PFB's filter state stays on the device between dispatches (ISSUE 29).

A stream's chunk is its NEW frames only; each channel group's program
takes ``(tail, body)`` and hands the next tail on
(:func:`blit.ops.channelize.channelize_stream`).  Only the stream's head
crosses the host link as filter state.  Pinned here, on the CPU:

- the streamed product is byte-equal to ONE dispatch of the whole file,
  whatever the chunk size (1 and 2 frames are shorter than the filter
  state at ``ntap`` 4), folded or carried integration, with or without a
  flush chunk, through ``stream`` (sync and async), the pump and ``drain``;
- the counters: ``link.put`` bytes are the samples dispatched, each once;
  ``state.head`` one per group per stream, ``state.carry`` one per group
  per later dispatch; no ``state`` row;
- a ``--resume`` inside an integration takes its head from the file again;
- rotation buffers hold ``chunk_frames * nfft`` samples, and a second
  reduction of the same shape allocates nothing, head slab included.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit import device, hostmem  # noqa: E402
from blit.io.guppi import GuppiRaw  # noqa: E402
from blit.io.sigproc import read_fil_data  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.ops.channelize import (  # noqa: E402
    channelize,
    channelize_np,
    channelize_stream,
    channels_per_dispatch,
    integrate_carry,
    pfb_coeffs,
    sample_words,
)
from blit.pipeline import RawReducer, ReductionCursor  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NFFT, NTAP, NCHAN = 32, 4, 2
STATE = (NTAP - 1) * NFFT
SAMPLE = NCHAN * 2 * 2  # bytes of one time sample, all channels

# (chunk_frames, nint, frames in the file): every chunk size against a
# folded integration (nint | chunk_frames) and a carried one (nint 5
# divides none of them), ending on the chunk grid ("even") and past it
# ("flush": a shorter last chunk, up to the last frame that closes a
# row).  One-frame chunks cannot leave a flush chunk: their second case
# ends in frames that close no row instead (dispatched, then dropped).
GRID = {
    "cf1-folded-even": (1, 1, 7), "cf1-folded-part": (1, 1, 9),
    "cf1-carried-even": (1, 5, 10), "cf1-carried-part": (1, 5, 13),
    "cf2-folded-even": (2, 1, 8), "cf2-folded-flush": (2, 1, 9),
    "cf2-carried-even": (2, 5, 10), "cf2-carried-flush": (2, 5, 15),
    "cf3-folded-even": (3, 1, 9), "cf3-folded-flush": (3, 1, 11),
    "cf3-carried-even": (3, 5, 15), "cf3-carried-flush": (3, 5, 20),
    "cf8-folded-even": (8, 4, 24), "cf8-folded-flush": (8, 4, 30),
    "cf8-carried-even": (8, 5, 40), "cf8-carried-flush": (8, 5, 45),
}
CASES = [pytest.param(*v, id=k) for k, v in GRID.items()]


def chunk_grid(frames, cf, nint):
    """The frames of each dispatch, as the producer lays them out."""
    full, rest = divmod(frames, cf)
    chunks = [cf] * full
    flush = (full * cf + rest) // nint * nint - full * cf
    if rest and flush > 0:
        chunks.append(flush)
    return chunks


def recording(tmp_path, frames, seed=0, name="r.raw"):
    """A RAW file of exactly ``frames`` PFB frames and half a frame more."""
    total = (frames + NTAP - 1) * NFFT + NFFT // 2
    per = -(-total // 3)
    p = str(tmp_path / name)
    synth_raw(p, nblocks=3, obsnchan=NCHAN, ntime_per_block=per, seed=seed,
              tone_chan=1)
    return p


def one_dispatch(raw_path, nint):
    """The whole file through ONE program, no tail, no chunk: the gross
    block as :func:`channelize` takes it, every whole integration."""
    raw = GuppiRaw(raw_path)
    v = np.concatenate(
        [blk for _, blk in raw.iter_blocks(drop_overlap=True)], axis=1)
    frames = v.shape[1] // NFFT - NTAP + 1
    rows = frames // nint
    v = v[:, :(rows * nint + NTAP - 1) * NFFT]
    h = jnp.asarray(pfb_coeffs(NTAP, NFFT))
    kw = dict(nfft=NFFT, ntap=NTAP, stokes="I", fft_method="auto")
    return v, h, kw, rows


def reference(raw_path, nint, carried):
    v, h, kw, rows = one_dispatch(raw_path, nint)
    if not carried:
        return np.asarray(channelize(v, h, nint=nint, **kw))
    power = channelize(v, h, nint=1, **kw)
    acc = jnp.zeros(power.shape[1:], jnp.float32)
    out, _ = integrate_carry(power, acc, np.int32(0), nint=nint)
    return np.asarray(out[:rows])


def reducer(cf, nint, **kw):
    return RawReducer(nfft=NFFT, nint=nint, chunk_frames=cf, **kw)


@pytest.fixture
def two_groups(monkeypatch):
    """As on the chip: a chunk goes up in channel groups (here of one)."""
    monkeypatch.setattr(RawReducer, "_channel_block", lambda *a: 1)
    return NCHAN


def check_counters(table, frames, cf, nint, groups):
    chunks = chunk_grid(frames, cf, nint)
    sent = (NTAP - 1 + sum(chunks)) * NFFT * SAMPLE
    assert table["link.put"]["bytes"] == sent  # no frame twice
    assert table["link.put"]["calls"] == groups * (len(chunks) + 1)
    assert table["state.head"]["calls"] == groups
    assert table["state.head"]["bytes"] == STATE * SAMPLE
    carried = groups * (len(chunks) - 1)
    assert table.get("state.carry", {"calls": 0})["calls"] == carried
    if carried:
        assert table["state.carry"]["bytes"] \
            == (len(chunks) - 1) * STATE * SAMPLE
    assert "state" not in table


class TestStreamedEqualsOneDispatch:
    @pytest.mark.parametrize("path", ["stream-sync", "stream-async",
                                      "pump"])
    @pytest.mark.parametrize("cf,nint,frames", CASES)
    def test_product_bytes(self, tmp_path, two_groups, cf, nint, frames,
                           path):
        raw = recording(tmp_path, frames)
        red = reducer(cf, nint, async_output=path != "stream-sync")
        assert red._carries == (cf % nint != 0)
        want = reference(raw, nint, red._carries)
        assert want.shape[0] == frames // nint > 0
        if path == "pump":
            out = str(tmp_path / "p.fil")
            hdr = red.reduce_to_file(raw, out)
            got = np.asarray(read_fil_data(out)[1])
            assert hdr["nsamps"] == want.shape[0]
        else:
            got = np.concatenate(list(red.stream(GuppiRaw(raw))))
        assert got.shape == want.shape
        assert got.tobytes() == want.tobytes()
        assert red.stats.output_frames == frames // nint * nint
        check_counters(red.timeline.report(), frames, cf, nint, two_groups)

    @pytest.mark.parametrize("cf,nint,frames", CASES)
    def test_drain(self, tmp_path, two_groups, cf, nint, frames):
        raw = recording(tmp_path, frames)
        red = reducer(cf, nint)
        want = reference(raw, nint, red._carries)
        got = red.drain(GuppiRaw(raw))
        assert got == pytest.approx(float(want.sum(dtype=np.float64)),
                                    rel=1e-5)
        assert red.stats.output_frames == frames // nint * nint
        table = red.timeline.report()
        check_counters(table, frames, cf, nint, two_groups)
        assert table["stream"]["bytes"] == table["device"]["bytes"] \
            == table["link.put"]["bytes"]

    def test_one_group_where_the_device_reports_no_limit(self, tmp_path):
        raw = recording(tmp_path, 20)
        red = reducer(8, 5)
        got = np.concatenate(list(red.stream(GuppiRaw(raw))))
        assert got.tobytes() == reference(raw, 5, True).tobytes()
        check_counters(red.timeline.report(), 20, 8, 5, groups=1)

    def test_a_file_shorter_than_the_filter_state_yields_nothing(
            self, tmp_path):
        p = str(tmp_path / "short.raw")
        synth_raw(p, nblocks=1, obsnchan=NCHAN, ntime_per_block=STATE - 8)
        red = reducer(4, 1)
        assert list(red.stream(GuppiRaw(p))) == []
        table = red.timeline.report()
        assert "link.put" not in table and "state.head" not in table
        assert table["ingest"]["bytes"] == (STATE - 8) * SAMPLE


class TestTheProgram:
    @pytest.mark.parametrize("frames", [1, 2, 3, 8])
    def test_next_tail_is_the_end_of_the_concatenation(self, frames):
        rng = np.random.default_rng(frames)
        v = rng.integers(-40, 40, (NCHAN, (NTAP - 1 + frames) * NFFT, 2, 2),
                         dtype=np.int8)
        h = jnp.asarray(pfb_coeffs(NTAP, NFFT))
        tail = jnp.asarray(sample_words(v[:, :STATE]))
        out, nxt = channelize_stream(tail, sample_words(v[:, STATE:]), h,
                                     nfft=NFFT, ntap=NTAP)
        assert tail.is_deleted()  # donated: the next tail took its place
        # One int32 word per dual-pol sample, as it crossed the link.
        assert nxt.shape == (NCHAN, STATE) and nxt.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(nxt),
                                      sample_words(v[:, -STATE:]))
        want = channelize(v, h, nfft=NFFT, ntap=NTAP)
        assert np.asarray(out).tobytes() == np.asarray(want).tobytes()

    @pytest.mark.parametrize("npol", [2, 1])
    @pytest.mark.parametrize("nint", [1, 2])
    @pytest.mark.parametrize("nfft", [8, 128, 1024])
    @pytest.mark.parametrize("nchan", [1, 2, 8, 16, 24])
    def test_a_step_is_the_whole_block(self, nchan, nfft, nint, npol):
        # The stream's program hands the XLA path the WORDS it joined; the
        # product's bits are those of the whole block as int8, at every
        # channel count on either side of a slab of eight.
        rng = np.random.default_rng(nchan * nfft + nint)
        state = (NTAP - 1) * nfft
        v = rng.integers(-128, 128, (nchan, state + 4 * nfft, npol, 2),
                         dtype=np.int8)
        h = jnp.asarray(pfb_coeffs(NTAP, nfft))
        # The matmul DFT, as on the chip (the CPU's FFT library does not
        # give the same bits twice at 1024 points).
        kw = dict(nfft=nfft, ntap=NTAP, nint=nint, fft_method="matmul")
        out, nxt = channelize_stream(
            jnp.asarray(sample_words(v[:, :state])),
            sample_words(v[:, state:]), h, **kw)
        np.testing.assert_array_equal(np.asarray(nxt),
                                      sample_words(v[:, -state:]))
        want = channelize(v, h, **kw)
        assert np.asarray(out).tobytes() == np.asarray(want).tobytes()
        ref = channelize_np(v, np.asarray(h), nfft=nfft, ntap=NTAP,
                            nint=nint)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4,
                                   atol=2e-2)

    def test_a_head_from_the_host_and_a_tail_from_the_chip_share_a_program(
            self):
        # Same shapes and dtypes: the warm-up pass compiles everything.
        h = jnp.asarray(pfb_coeffs(NTAP, NFFT))
        body = sample_words(np.zeros((NCHAN, 4 * NFFT, 2, 2), np.int8))
        head = sample_words(np.ones((NCHAN, STATE, 2, 2), np.int8))
        kw = dict(nfft=NFFT, ntap=NTAP, nint=2)
        _, nxt = channelize_stream(jax.device_put(head), body, h, **kw)
        before = channelize_stream._cache_size()
        _, nxt = channelize_stream(nxt, body, h, **kw)
        channelize_stream(nxt, body, h, **kw)
        assert channelize_stream._cache_size() == before

    def test_the_probe_leaves_the_filter_state_to_the_caller(self):
        # The compiler's account of the program that runs, per channel,
        # less the tail: every group's is resident between dispatches and
        # counted once, by RawReducer._channel_block.  Plus the next
        # group's new samples, which go up while this program runs.
        shape = (8, 8 * NFFT, 2, 2)
        kw = dict(nfft=NFFT, ntap=NTAP)
        m = channelize_stream.lower(
            jax.ShapeDtypeStruct((8, STATE), jnp.int32),
            jax.ShapeDtypeStruct(shape[:2], jnp.int32),
            jax.ShapeDtypeStruct((NTAP, NFFT), jnp.float32), **kw,
        ).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                 + m.output_size_in_bytes - m.alias_size_in_bytes)
        per_chan = -(-(total - 8 * STATE * 4 + 8 * shape[1] * 4) // 8)
        assert channels_per_dispatch(shape, 4 * per_chan, **kw) == 4
        assert channels_per_dispatch(shape, 4 * per_chan - 1, **kw) == 2
        with pytest.raises(MemoryError, match="device memory"):
            channels_per_dispatch(shape, per_chan - 1, **kw)

    def test_the_reducer_counts_every_groups_tail_as_resident(
            self, monkeypatch):
        import blit.pipeline as P

        asked = []
        monkeypatch.setattr(P, "hbm_bytes_limit", lambda: 1 << 30)
        monkeypatch.setattr(
            P, "channels_per_dispatch",
            lambda shape, budget, **kw: asked.append((shape, budget)) or 1)
        red = reducer(8, 1)
        shape = (NCHAN, 8 * NFFT, 2, 2)
        red._channel_block(shape)
        row = NCHAN * NFFT * 4
        products = (max(2, red.out_depth) + 1) * 8 * row
        assert asked == [(shape, int(0.9 * (1 << 30)) - products
                          - NCHAN * STATE * 4)]


class TestLinkPut:
    def test_each_array_put_is_counted(self):
        tl = Timeline()
        link = device.HostLink()
        a = np.zeros((2, 8), np.int8)
        b = np.ones((2, 24), np.int8)
        got = link.put(a, timeline=tl)
        np.testing.assert_array_equal(np.asarray(got), a)
        seen = link.put((a, b), timeline=tl,
                        then=lambda up: [x.shape for x in up])
        assert seen == [a.shape, b.shape]
        row = tl.report()["link.put"]
        assert (row["calls"], row["bytes"]) == (3, 2 * a.nbytes + b.nbytes)

    def test_without_a_timeline_nothing_is_marked(self):
        link = device.HostLink()
        out = link.put(np.arange(4, dtype=np.int8))
        assert np.asarray(out).tolist() == [0, 1, 2, 3]

    def test_a_failed_program_lets_go_of_its_bytes(self, monkeypatch):
        monkeypatch.setattr(device, "host_link_bytes", lambda: 1 << 20)
        link = device.HostLink()

        def boom(_):
            raise RuntimeError("program failed")

        with pytest.raises(RuntimeError, match="program failed"):
            link.put((np.zeros(64, np.int8), np.zeros(64, np.int8)),
                     then=boom)
        assert link.inflight_bytes() == 0


class TestResumeTakesItsHeadFromTheFile:
    CF, NINT, ROWS, TAIL = 4, 11, 5, 3

    @pytest.mark.parametrize("die_at", [3, 8])
    def test_resumed_inside_an_integration(self, tmp_path, monkeypatch,
                                           two_groups, die_at):
        frames = self.ROWS * self.NINT + self.TAIL
        raw = recording(tmp_path, frames, seed=3)
        ref = str(tmp_path / "ref.fil")
        reducer(self.CF, self.NINT).reduce_resumable(raw, ref)
        want = reference(raw, self.NINT, True)
        assert np.asarray(read_fil_data(ref)[1]).tobytes() == want.tobytes()

        out = str(tmp_path / "res.fil")
        real, seen = RawReducer._dispatch, []

        def dying(self_, chunk, st):
            seen.append(st.filled)
            if len(seen) == die_at:
                raise RuntimeError("killed inside an integration")
            return real(self_, chunk, st)

        monkeypatch.setattr(RawReducer, "_dispatch", dying)
        crash = reducer(self.CF, self.NINT)
        with pytest.raises(RuntimeError, match="inside an integration"):
            crash.reduce_resumable(raw, out)
        monkeypatch.setattr(RawReducer, "_dispatch", real)
        assert seen[-1] != 0  # an integration was open
        cur = ReductionCursor.load(out)
        done = cur.frames_done if cur is not None else 0
        assert done % self.NINT == 0 and done < self.ROWS * self.NINT

        red = reducer(self.CF, self.NINT)
        hdr = red.reduce_resumable(raw, out)
        assert hdr["nsamps"] == self.ROWS
        with open(out, "rb") as f, open(ref, "rb") as g:
            assert f.read() == g.read()
        # The resumed stream starts at frame `done` with a head of its
        # own, read from the file: the filter state of that frame.
        table = red.timeline.report()
        check_counters(table, frames - done, self.CF, self.NINT, two_groups)
        assert red.stats.output_frames == self.ROWS * self.NINT - done


class TestStaging:
    @pytest.fixture
    def fresh_pool(self):
        hostmem._reset_pool()
        yield hostmem.slab_pool()
        hostmem._reset_pool()

    @pytest.mark.parametrize("cf,nint", [(4, 2), (8, 5)])
    def test_buffers_hold_new_samples_and_come_back(self, tmp_path,
                                                    fresh_pool, cf, nint):
        raw = recording(tmp_path, 40)
        tables = []
        for _ in range(2):
            red = reducer(cf, nint)
            red.reduce(raw)
            tables.append(red.timeline.report())
            assert red._head_slab is None and red._buf_cache == []
        shapes = sorted(shape for shape, _ in fresh_pool._free)
        assert shapes == [(NCHAN, STATE, 2, 2), (NCHAN, cf * NFFT, 2, 2)]
        first, second = tables
        nbufs = first["staging.alloc"]["calls"]
        assert nbufs == max(2, red.prefetch_depth) + 1 + 1  # + the head
        assert first["staging.reuse"]["calls"] == 0
        assert second["staging.alloc"]["calls"] == 0
        assert second["staging.reuse"]["calls"] == nbufs
        assert fresh_pool.stats()["lent_bytes"] == 0

    def test_a_reducer_keeps_its_head_slab_between_streams(self, tmp_path,
                                                           fresh_pool):
        raw = recording(tmp_path, 12)
        red = reducer(4, 1)
        g = GuppiRaw(raw)
        for _ in range(2):
            for c in red._chunks(g):
                c.release()
        assert red._head_slab is not None
        assert red.timeline.report()["staging.alloc"]["calls"] \
            == max(2, red.prefetch_depth) + 1


class TestSampleWords:
    @pytest.mark.parametrize("npol,word", [(2, np.int32), (1, np.int16)])
    def test_one_word_per_sample_and_no_copy(self, npol, word):
        rng = np.random.default_rng(npol)
        v = rng.integers(-128, 128, (3, 40, npol, 2), dtype=np.int8)
        w = sample_words(v)
        assert w.shape == (3, 40) and w.dtype == word
        assert np.shares_memory(w, v) and w.flags.c_contiguous
        # A chunk's flush view (rows strided) is still a view, rows apart.
        part = sample_words(v[:, :24])
        assert part.shape == (3, 24) and np.shares_memory(part, v)
        np.testing.assert_array_equal(part, w[:, :24])
        # Little-endian: byte 0 is the first polarization's real part.
        np.testing.assert_array_equal(
            w.view(np.int8).reshape(v.shape), v)


class TestWordsAreTheOnlyFormInside:
    """Every front reads words (ISSUE 46): int8 handed to ``channelize``
    becomes words by a bitcast, a stream's ``(tail, body)`` reach a
    Pallas front as they are (``fused1``: an operand each, a group's 8
    channels to a block, a strided load a channel), and each gives the
    bits of the one gross block.  Interpret mode, 8 channels (one whole
    group) and 3 (none)."""

    NFFT = 8192  # (128, 64): the least ``fused1`` takes

    @staticmethod
    def _voltages(nchan, frames, nfft, seed):
        rng = np.random.default_rng(seed)
        return rng.integers(-128, 128, (nchan, (NTAP - 1 + frames) * nfft,
                                        2, 2), dtype=np.int8)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("pfb_kernel", ["fused1", "pallas"])
    @pytest.mark.parametrize("nchan", [8, 3])
    def test_int8_and_its_words_give_the_same_bits(self, nchan, pfb_kernel,
                                                   dtype):
        nfft = self.NFFT
        v = self._voltages(nchan, 2, nfft, seed=nchan)
        h = jnp.asarray(pfb_coeffs(NTAP, nfft))
        kw = dict(nfft=nfft, ntap=NTAP, fft_method="matmul",
                  pfb_kernel=pfb_kernel, dtype=dtype)
        from blit.ops.channelize import last_kernel_plan

        a = np.asarray(channelize(jnp.asarray(v), h, **kw))
        assert last_kernel_plan()["pfb_kernel"] == pfb_kernel
        b = np.asarray(channelize(jnp.asarray(sample_words(v)), h, **kw))
        assert a.tobytes() == b.tobytes()
        # ... and they are the samples' spectra, not only each other's.
        want = channelize_np(v, np.asarray(h), nfft=nfft, ntap=NTAP)
        scale = np.abs(want).max()
        np.testing.assert_allclose(
            a / scale, want / scale,
            atol=2e-2 if dtype == "bfloat16" else 1e-4)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("pfb_kernel", ["fused1", "pallas"])
    @pytest.mark.parametrize("frames", [4, 2], ids=["body", "short-body"])
    def test_a_stream_step_is_the_gross_block(self, frames, pfb_kernel,
                                              dtype):
        # ``frames`` 2 < ntap - 1: the next tail keeps part of the old.
        nfft, nchan = self.NFFT, 8
        state = (NTAP - 1) * nfft
        w = sample_words(self._voltages(nchan, frames, nfft, seed=frames))
        h = jnp.asarray(pfb_coeffs(NTAP, nfft))
        kw = dict(nfft=nfft, ntap=NTAP, fft_method="matmul",
                  pfb_kernel=pfb_kernel, dtype=dtype)
        want = np.asarray(channelize(jnp.asarray(w), h, **kw))
        got, tail = channelize_stream(
            jnp.asarray(w[:, :state]), jnp.asarray(w[:, state:]), h, **kw)
        assert np.asarray(got).tobytes() == want.tobytes()
        np.testing.assert_array_equal(np.asarray(tail), w[:, -state:])
        # The same runs handed to ``channelize`` as a tuple.
        runs = np.asarray(channelize(
            (jnp.asarray(w[:, :state]), jnp.asarray(w[:, state:])), h, **kw))
        assert runs.tobytes() == want.tobytes()

    def test_runs_that_are_not_whole_blocks_are_refused(self):
        h = jnp.asarray(pfb_coeffs(NTAP, 64))
        with pytest.raises(ValueError, match="whole blocks"):
            channelize((jnp.zeros((2, 3 * 64), jnp.int32),
                        jnp.zeros((2, 2 * 64 + 1), jnp.int32)), h, nfft=64)
