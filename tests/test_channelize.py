"""Golden-value tests for the RAW → filterbank reduction core
(blit/ops/channelize.py) against NumPy references, per SURVEY.md §4."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402


@pytest.fixture(autouse=True)
def nan_guard():
    """SURVEY.md §5 sanitizer plan: every golden run in this module executes
    under jax_debug_nans, so a NaN produced anywhere in the reduction
    (relevant with reduced-precision MXU paths) fails loudly here rather
    than silently polluting products."""
    jax.config.update("jax_debug_nans", True)
    yield
    jax.config.update("jax_debug_nans", False)


from blit.ops import channelize as ch  # noqa: E402


def channelize_blocked(voltages, coeffs, tails, *, channel_block,
                       put=None, **kw):
    """:func:`channelize_fanout` for ONE product integrated inside its
    program: ``tails`` is each group's filter state (``split_tails`` of the
    stream's head for its first dispatch, device words after that).
    Returns ``(product, tails)``."""
    leg = ch.StreamLeg(coeffs, nint=kw.pop("nint", 1), carried=False, **kw)
    head = None
    if isinstance(tails[0], np.ndarray):  # the stream's head, still host
        head = tails[0] if len(tails) == 1 else np.concatenate(tails)
    else:
        leg.tails = list(tails)
    (rows,), _ = ch.channelize_fanout(
        voltages, [leg], [voltages.shape[1] // leg.nfft],
        channel_block=channel_block, head=head,
        **({} if put is None else {"put": put}))
    return rows[0], leg.tails


def make_voltages(nchan=4, ntime=8 * 256, npol=2, seed=0, tone=None, nfft=256):
    rng = np.random.default_rng(seed)
    v = rng.integers(-32, 32, size=(nchan, ntime, npol, 2), dtype=np.int8)
    if tone is not None:
        chan, fine = tone
        t = np.arange(ntime)
        # complex tone at fine-channel offset `fine` (fftshifted index)
        f = (fine - nfft // 2) / nfft
        z = 30 * np.exp(2j * np.pi * f * t)
        v[chan, :, :, 0] += z.real.astype(np.int8)[:, None]
        v[chan, :, :, 1] += z.imag.astype(np.int8)[:, None]
    return v


class TestFFT:
    def test_four_step_matches_direct(self):
        rng = np.random.default_rng(1)
        z = (rng.standard_normal((3, 1024)) + 1j * rng.standard_normal((3, 1024))).astype(
            np.complex64
        )
        a = ch.fft(jnp.asarray(z), method="four_step")
        b = np.fft.fft(z)
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-4, atol=2e-3)

    def test_four_step_large_pow2(self):
        rng = np.random.default_rng(2)
        n = 1 << 16
        z = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex64)
        a = np.asarray(ch.fft(jnp.asarray(z), method="four_step"))
        b = np.fft.fft(z)
        assert np.max(np.abs(a - b)) / np.max(np.abs(b)) < 1e-4

    def test_four_step_non_pow2(self):
        rng = np.random.default_rng(3)
        n = 12 * 25
        z = (rng.standard_normal((2, n)) + 1j * rng.standard_normal((2, n))).astype(
            np.complex64
        )
        a = np.asarray(ch.fft(jnp.asarray(z), method="four_step"))
        np.testing.assert_allclose(a, np.fft.fft(z), rtol=1e-3, atol=1e-3)

    def test_factors(self):
        assert ch._four_step_factors(1 << 20) == (1 << 10, 1 << 10)
        n1, n2 = ch._four_step_factors(300)
        assert n1 * n2 == 300


class TestPFB:
    def test_coeffs_shape_and_dc_gain(self):
        h = ch.pfb_coeffs(4, 64)
        assert h.shape == (4, 64)
        assert abs(h.sum() - 1.0) < 1e-6

    def test_frontend_frame_count(self):
        x = jnp.ones((2, 8 * 32))
        h = jnp.asarray(ch.pfb_coeffs(4, 32))
        y = ch.pfb_frontend(x, h)
        assert y.shape == (2, 5, 32)

    def test_rect_window_single_tap_is_framing(self):
        # ntap=1 rect window = plain framing (scaled by 1/nfft via DC norm).
        x = np.arange(64, dtype=np.float32)
        h = ch.pfb_coeffs(1, 16, window="rect")
        y = np.asarray(ch.pfb_frontend(jnp.asarray(x), jnp.asarray(h)))
        np.testing.assert_allclose(y, x.reshape(4, 16) * h[0], rtol=1e-6)


# Options of the XLA path, each to give what the reference gives and the
# same bits from either form of input (ISSUE 37).
WORDS_OPTIONS = {
    "plain": {},
    "channel_block": dict(channel_block=8),
    "channel_block_small": dict(channel_block=2),
    "fqav": dict(fqav_by=4),
    "matmul": dict(fft_method="matmul"),
    "matmul_channel_block": dict(fft_method="matmul", channel_block=8),
    "bf16": dict(fft_method="matmul", dtype="bfloat16"),
    "twisted": dict(fft_method="matmul", dft_order="twisted"),
    "four_step": dict(fft_method="four_step"),
    "highest": dict(fft_method="matmul", precision="highest"),
}


class TestChannelize:
    @pytest.mark.parametrize("nchan", [3, 16])
    @pytest.mark.parametrize("stokes",
                             ["I", "XX", "YY", "XXYY", "full", "IQUV"])
    def test_matches_numpy_reference(self, stokes, nchan):
        nfft, ntap, nint = 64, 4, 2
        v = make_voltages(nchan=nchan, ntime=(ntap - 1 + 2 * nint) * nfft)
        h = ch.pfb_coeffs(ntap, nfft)
        got = np.asarray(
            ch.channelize(
                jnp.asarray(v), jnp.asarray(h), nfft=nfft, ntap=ntap, nint=nint,
                stokes=stokes,
            )
        )
        want = ch.channelize_np(v, h, nfft=nfft, ntap=ntap, nint=nint, stokes=stokes)
        assert got.shape == want.shape == (2, ch.STOKES_NIF[stokes],
                                           nchan * nfft)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)

    @pytest.mark.parametrize("npol", [2, 1])
    @pytest.mark.parametrize("nint", [1, 3])
    @pytest.mark.parametrize("nfft", [8, 128, 1024])
    @pytest.mark.parametrize("nchan", [1, 2, 8, 16, 24])
    def test_words_where_they_lie(self, nchan, nfft, nint, npol):
        # The XLA path filters the words as rows (a block's channels one
        # after the other, eight to a slab where eight divide more): the
        # reference's product at every channel count on either side of a
        # slab, and the same bits whether the samples come as int8 or as
        # the words they are.
        ntap = 4
        v = make_voltages(nchan=nchan, ntime=(ntap - 1 + 2 * nint) * nfft,
                          npol=npol, seed=nchan + nfft)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        # The matmul DFT, as on the chip (the CPU's FFT library does not
        # give the same bits twice at 1024 points).
        kw = dict(nfft=nfft, ntap=ntap, nint=nint)
        got = np.asarray(ch.channelize(jnp.asarray(v), h,
                                       fft_method="matmul", **kw))
        want = ch.channelize_np(v, np.asarray(h), **kw)
        kw["fft_method"] = "matmul"
        assert got.shape == want.shape == (2, 1, nchan * nfft)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-2)
        words = ch.sample_words(v)
        assert words.dtype == (np.int32 if npol == 2 else np.int16)
        same = np.asarray(ch.channelize(jnp.asarray(words), h, **kw))
        assert same.tobytes() == got.tobytes()
        assert ch.last_kernel_plan()["pfb_kernel"] == "xla"

    @pytest.mark.parametrize("option", sorted(WORDS_OPTIONS))
    def test_words_and_int8_give_the_same_bits(self, option):
        nfft, ntap, nint = 128, 4, 2
        kw = dict(nfft=nfft, ntap=ntap, nint=nint, stokes="IQUV",
                  **WORDS_OPTIONS[option])
        v = make_voltages(nchan=16, ntime=(ntap - 1 + 3 * nint) * nfft,
                          seed=11)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        got = np.asarray(ch.channelize(jnp.asarray(v), h, **kw))
        words = np.asarray(ch.channelize(jnp.asarray(ch.sample_words(v)),
                                         h, **kw))
        assert words.tobytes() == got.tobytes()
        want = ch.channelize_np(v, np.asarray(h), nfft=nfft, ntap=ntap,
                                nint=nint, stokes="IQUV")
        by = kw.get("fqav_by", 1)
        if by > 1:
            from blit.ops.fqav import fqav

            want = fqav(want, by)
        assert got.shape == want.shape
        scale = np.abs(want).max()
        np.testing.assert_allclose(
            got / scale, want / scale,
            atol=2e-2 if option == "bf16" else 1e-5)

    def test_a_pallas_front_reads_words_too(self):
        # Either form of input reaches either front end: words are the
        # only form inside the program, int8 becomes them by a bitcast.
        nfft, ntap = 128, 4
        v = make_voltages(nchan=2, ntime=(ntap - 1 + 2) * nfft, seed=3)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        kw = dict(nfft=nfft, ntap=ntap, pfb_kernel="pallas")
        got = np.asarray(ch.channelize(jnp.asarray(v), h, **kw))
        assert ch.last_kernel_plan()["pfb_kernel"] == "pallas"
        words = np.asarray(ch.channelize(jnp.asarray(ch.sample_words(v)),
                                         h, **kw))
        assert words.tobytes() == got.tobytes()
        xla = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft,
                                       ntap=ntap))
        np.testing.assert_allclose(got, xla, rtol=1e-4, atol=1e-2)

    def test_a_ragged_block_of_words_is_refused(self):
        h = jnp.asarray(ch.pfb_coeffs(4, 64))
        with pytest.raises(ValueError, match="whole blocks"):
            ch.channelize(jnp.zeros((2, 4 * 64 + 3), jnp.int32), h, nfft=64)
        with pytest.raises(ValueError, match="whole blocks"):
            ch.channelize(jnp.zeros((2, 3 * 64, 2, 2), jnp.int8), h, nfft=64)
        with pytest.raises(ValueError, match="does not divide nframes"):
            ch.channelize(jnp.zeros((2, 6 * 64), jnp.int32), h, nfft=64,
                          nint=2)

    def test_fqav_epilogue_matches_host_fqav(self):
        # On-device frequency averaging == host fqav of the full product
        # (the reduce-before-the-wire lever moved into the jitted kernel).
        from blit.ops.fqav import fqav

        nfft, ntap, nint, by = 64, 4, 1, 8
        v = make_voltages(nchan=2, ntime=(ntap - 1 + 3) * nfft)
        h = ch.pfb_coeffs(ntap, nfft)
        got = np.asarray(
            ch.channelize(
                jnp.asarray(v), jnp.asarray(h), nfft=nfft, ntap=ntap,
                nint=nint, fqav_by=by,
            )
        )
        full = np.asarray(
            ch.channelize(
                jnp.asarray(v), jnp.asarray(h), nfft=nfft, ntap=ntap, nint=nint
            )
        )
        assert got.shape == (3, 1, 2 * nfft // by)
        np.testing.assert_allclose(got, fqav(full, by), rtol=1e-5, atol=1e-2)

    def test_fqav_epilogue_through_reducer(self, tmp_path):
        # RawReducer(fqav_by=): product + header shrink together.
        from blit.ops.fqav import fqav
        from blit.pipeline import RawReducer
        from blit.testing import synth_raw

        p = str(tmp_path / "x.raw")
        synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=1024, tone_chan=1)
        hdr, data = RawReducer(nfft=64, nint=2, fqav_by=4).reduce(p)
        fhdr, full = RawReducer(nfft=64, nint=2).reduce(p)
        assert hdr["nchans"] == fhdr["nchans"] // 4 == data.shape[-1]
        assert hdr["foff"] == pytest.approx(fhdr["foff"] * 4)
        assert hdr["nfpc"] == 64 // 4
        np.testing.assert_allclose(data, fqav(full, 4), rtol=1e-5, atol=1e-2)

    @pytest.mark.parametrize("frames", [1, 2, 3, 8])
    @pytest.mark.parametrize("channel_block", [2, 8])
    def test_channelize_blocked_matches_flat(self, channel_block, frames):
        # Host-looped channel blocking of (filter state, new samples) ==
        # the flat single dispatch of their concatenation, and the tails
        # it hands on are the concatenation's last ntap-1 frames (a body
        # shorter than the filter state keeps part of the old tail).
        nfft, ntap = 64, 4
        state = (ntap - 1) * nfft
        v = make_voltages(nchan=8, ntime=(ntap - 1 + frames) * nfft)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        flat = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft,
                                        ntap=ntap))
        head, body = v[:, :state], v[:, state:]
        puts = []

        def put(host, then):
            puts.append(jax.tree_util.tree_map(np.shape, host))
            return then(host)

        out, tails = channelize_blocked(
            body, h, ch.split_tails(head, channel_block),
            channel_block=channel_block, put=put, nfft=nfft, ntap=ntap)
        np.testing.assert_array_equal(np.asarray(out), flat)
        groups = 8 // channel_block
        assert len(tails) == groups
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(t) for t in tails]),
            ch.sample_words(v[:, -state:]))
        # A head in host memory goes up through `put`, group by group,
        # with its group's samples (both as one word per sample); a tail
        # from the device does not.
        assert puts == [((channel_block, state),
                         (channel_block, frames * nfft))] * groups
        puts.clear()
        again, _ = channelize_blocked(
            body, h, [jnp.asarray(ch.sample_words(t)) for t in
                      ch.split_tails(head, channel_block)],
            channel_block=channel_block, put=put, nfft=nfft, ntap=ntap)
        np.testing.assert_array_equal(np.asarray(again), flat)
        assert puts == [(channel_block, frames * nfft)] * groups

    def test_channelize_blocked_refuses_a_ragged_block(self):
        v = make_voltages(nchan=8, ntime=6 * 64)
        h = jnp.asarray(ch.pfb_coeffs(4, 64))
        with pytest.raises(ValueError, match="divide nchan"):
            channelize_blocked(v[:, 192:], h, [v[:, :192]],
                                  channel_block=3, nfft=64, ntap=4)

    def test_fqav_must_divide_nfft(self, tmp_path):
        # Averaging groups must not straddle coarse-channel boundaries.
        from blit.pipeline import RawReducer

        with pytest.raises(ValueError, match="divide nfft"):
            RawReducer(nfft=64, fqav_by=48)
        v = make_voltages(nchan=3, ntime=4 * 64)  # 3*64 divisible by 48
        h = ch.pfb_coeffs(4, 64)
        with pytest.raises(ValueError, match="divide nfft"):
            ch.channelize(jnp.asarray(v), jnp.asarray(h), nfft=64, fqav_by=48)

    def test_tone_lands_in_right_fine_channel(self):
        nfft = 128
        v = make_voltages(nchan=2, ntime=8 * nfft, tone=(1, 96), nfft=nfft, seed=5)
        h = ch.pfb_coeffs(4, nfft)
        out = np.asarray(
            ch.channelize(jnp.asarray(v), jnp.asarray(h), nfft=nfft, nint=5)
        )
        spectrum = out[0, 0]
        # global fine index = coarse*nfft + fine
        assert spectrum.argmax() == 1 * nfft + 96

    def test_dc_tone_lands_at_despike_index(self):
        # A DC offset concentrates at fftshifted index nfft//2 — the exact
        # fine channel blit.ops.despike repairs (src/gbt.jl:101-111 parity).
        nfft = 64
        v = np.zeros((1, 8 * nfft, 2, 2), dtype=np.int8)
        v[..., 0] = 20
        h = ch.pfb_coeffs(4, nfft)
        out = np.asarray(
            ch.channelize(jnp.asarray(v), jnp.asarray(h), nfft=nfft, nint=5)
        )
        assert out[0, 0].argmax() == nfft // 2

    def test_four_step_equals_direct_end_to_end(self):
        nfft = 1024
        v = make_voltages(nchan=1, ntime=5 * nfft)
        h = ch.pfb_coeffs(4, nfft)
        a = np.asarray(
            ch.channelize(
                jnp.asarray(v), jnp.asarray(h), nfft=nfft, nint=2, fft_method="direct"
            )
        )
        b = np.asarray(
            ch.channelize(
                jnp.asarray(v), jnp.asarray(h), nfft=nfft, nint=2,
                fft_method="four_step",
            )
        )
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=10.0)
        rel = np.abs(a - b).max() / np.abs(a).max()
        assert rel < 1e-4

    def test_bfloat16_stage_dtype_close_to_golden(self):
        # dtype="bfloat16" halves the DFT intermediates' HBM (the
        # frames-per-dispatch lever, DESIGN.md §8); detected powers stay
        # within bf16-grade accuracy of the f64 NumPy golden.
        nfft, ntap, nint = 256, 4, 2
        v = make_voltages(
            ntime=(ntap - 1 + 2 * nint) * nfft, nfft=nfft, tone=(1, 70)
        )
        h = ch.pfb_coeffs(ntap, nfft)
        want = ch.channelize_np(v, h, nfft=nfft, ntap=ntap, nint=nint)
        got = np.asarray(ch.channelize(
            jnp.asarray(v), jnp.asarray(h), nfft=nfft, ntap=ntap, nint=nint,
            fft_method="matmul", dtype="bfloat16",
        ))
        assert got.dtype == np.float32  # detect/integrate accumulate in f32
        scale = want.max()
        np.testing.assert_allclose(got / scale, want / scale, atol=2e-2)
        # The tone must land in the same fine channel at full amplitude.
        assert got[0, 0].argmax() == want[0, 0].argmax()
        np.testing.assert_allclose(
            got[0, 0].max(), want[0, 0].max(), rtol=1e-2
        )

    def test_single_pol(self):
        v = make_voltages(nchan=2, ntime=5 * 32, npol=1)
        h = ch.pfb_coeffs(4, 32)
        out = np.asarray(ch.channelize(jnp.asarray(v), jnp.asarray(h), nfft=32))
        assert out.shape == (2, 1, 64)
        with pytest.raises(ValueError):
            ch.detect_stokes(jnp.zeros((1, 1, 2, 4), dtype=jnp.complex64), "IQUV")


class TestOutputHeader:
    RAW = {
        "OBSNCHAN": 64,
        "OBSFREQ": 1500.0,
        "OBSBW": -187.5,
        "TBIN": 64 / 187.5e6,
        "SRC_NAME": "J1234+56",
        "STT_IMJD": 59000,
        "STT_SMJD": 43200,
        "STT_OFFS": 0.0,
    }

    def test_header_fields(self):
        hdr = ch.output_header(self.RAW, nfft=1024, nint=8, stokes="full")
        assert hdr["nchans"] == 64 * 1024
        assert hdr["nifs"] == 4
        assert hdr["nfpc"] == 1024
        assert hdr["foff"] == pytest.approx(-187.5 / 64 / 1024)
        assert hdr["tsamp"] == pytest.approx(64 / 187.5e6 * 1024 * 8)
        assert hdr["tstart"] == pytest.approx(59000.5)

    def test_band_edges(self):
        # The nchans fine channels must span exactly OBSBW centered on OBSFREQ.
        nfft = 256
        hdr = ch.output_header(self.RAW, nfft=nfft, nint=1)
        freqs = hdr["fch1"] + hdr["foff"] * np.arange(hdr["nchans"])
        assert freqs.mean() == pytest.approx(1500.0, abs=abs(hdr["foff"]))
        span = abs(freqs[-1] - freqs[0]) + abs(hdr["foff"])
        assert span == pytest.approx(187.5)


class TestKernelPlan:
    def test_last_kernel_plan_records_trace_resolution(self):
        # ADVICE r3: 'auto' dispatch must be attributable.  On CPU the
        # auto path resolves to XLA kernels; the record reflects the most
        # recent TRACE (unique shape to force one).
        from blit.ops.channelize import (
            channelize, last_kernel_plan, pfb_coeffs,
        )

        rng = np.random.default_rng(0)
        v = rng.integers(-8, 8, (3, 7 * 16, 2, 2), dtype=np.int8)
        channelize(
            jnp.asarray(v), jnp.asarray(pfb_coeffs(4, 16)), nfft=16,
        ).block_until_ready()
        plan = last_kernel_plan()
        assert plan["pfb_kernel"] == "xla"
        assert plan["detect_kernel"] == "xla"
        assert plan["dft_order"] == "natural"
        assert plan["dtype"] == "float32"
