"""Collective science products on the virtual 8-device mesh: coherent
multibeam beamforming (blit/parallel/beamform.py) and the FX correlator
(blit/parallel/correlator.py), golden-tested against NumPy references."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit.ops.channelize import pfb_coeffs  # noqa: E402
from blit.parallel import beamform as B  # noqa: E402
from blit.parallel import correlator as C  # noqa: E402
from blit.parallel.mesh import make_mesh  # noqa: E402


def make_antenna_voltages(nant=8, nchan=4, ntime=64, npol=2, seed=0):
    rng = np.random.default_rng(seed)
    shape = (nant, nchan, ntime, npol)
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


class TestDelayWeights:
    def test_phasors(self):
        delays = jnp.asarray([[0.0, 1e-9], [1e-9, 0.0]])  # (2 beams, 2 ants)
        freqs = jnp.asarray([1.0e9, 1.5e9])
        w = B.delay_weights(delays, freqs)
        assert w.shape == (2, 2, 2)
        np.testing.assert_allclose(np.asarray(w[0, 0]), [1, 1], atol=1e-6)
        # exp(-2pi i * 1e9 * 1e-9) = exp(-2pi i) = 1
        np.testing.assert_allclose(np.asarray(w[0, 1, 0]), 1.0, atol=1e-5)
        # exp(-2pi i * 1.5) = -1
        np.testing.assert_allclose(np.asarray(w[0, 1, 1]), -1.0, atol=1e-5)

    def test_amplitude_taper(self):
        w = B.delay_weights(
            jnp.zeros((1, 3)), jnp.ones(2) * 1e9, amplitudes=jnp.asarray([1.0, 0.5, 0.0])
        )
        np.testing.assert_allclose(np.abs(np.asarray(w[0, :, 0])), [1, 0.5, 0])


class TestBeamform:
    @pytest.mark.parametrize("detect,nint", [(True, 4), (True, 1), (False, 1)])
    def test_matches_numpy(self, detect, nint):
        nant, nbeam = 8, 5
        v = make_antenna_voltages(nant=nant)
        rng = np.random.default_rng(1)
        w = (rng.standard_normal((nbeam, nant, 4))
             + 1j * rng.standard_normal((nbeam, nant, 4))).astype(np.complex64)
        m = make_mesh(1, 8)
        vs = jax.device_put(v, B.antenna_sharding(m))
        ws = jax.device_put(w, B.weight_sharding(m))
        got = np.asarray(
            B.beamform(vs, ws, mesh=m, nint=nint, detect=detect)
        )
        want = B.beamform_np(v, w, nint=nint, detect=detect)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)

    def test_steering_recovers_point_source(self):
        # A plane wave delayed per antenna: the matched beam collects nant^2
        # power, a mismatched beam collects ~nant.
        nant, nchan, ntime = 8, 2, 32
        freqs = np.array([1.0e9, 1.1e9])
        delays = np.linspace(0, 3e-9, nant)
        t = np.arange(ntime)
        v = np.zeros((nant, nchan, ntime, 1), np.complex64)
        for a in range(nant):
            for c in range(nchan):
                # source signal with per-antenna geometric phase
                v[a, c, :, 0] = np.exp(2j * np.pi * (0.05 * t + freqs[c] * delays[a]))
        w_match = B.delay_weights(jnp.asarray(delays)[None, :], jnp.asarray(freqs))
        w_zero = B.delay_weights(jnp.zeros((1, nant)), jnp.asarray(freqs))
        m = make_mesh(1, 8)
        vs = jax.device_put(v, B.antenna_sharding(m))
        p_match = np.asarray(B.beamform(
            vs, jax.device_put(w_match, B.weight_sharding(m)), mesh=m,
            nint=ntime)).sum()
        p_zero = np.asarray(B.beamform(
            vs, jax.device_put(w_zero, B.weight_sharding(m)), mesh=m,
            nint=ntime)).sum()
        assert p_match > 5 * p_zero
        np.testing.assert_allclose(
            p_match, nant**2 * nchan * ntime, rtol=1e-3
        )


class TestBeamformBf16:
    def test_bf16_resident_matches_f32(self):
        # bf16-resident planes (load_antennas_mesh(dtype="bfloat16")) run
        # the contraction + psum in bf16 (measured +26% on the chip,
        # DESIGN.md §9 r5).  8-bit voltages are exact in bf16; rounding
        # comes from the weight phasors and the bf16 partial sums —
        # ~1e-2 max rel err on detected power.
        nant, nbeam, nchan, ntime = 8, 5, 4, 64
        rng = np.random.default_rng(7)
        v8 = rng.integers(-40, 41, (2, nant, nchan, ntime, 2)).astype(
            np.float32
        )
        wr, wi = B.delay_weights_planar(
            jnp.asarray(rng.uniform(0, 1e-9, (nbeam, nant))),
            jnp.asarray(np.linspace(1e9, 1.1e9, nchan)),
        )
        m = make_mesh(1, 8)
        wp = jax.device_put((np.asarray(wr), np.asarray(wi)),
                            B.weight_sharding(m))
        vp32 = jax.device_put((v8[0], v8[1]), B.antenna_sharding(m))
        vp16 = jax.device_put(
            (v8[0].astype(jnp.bfloat16), v8[1].astype(jnp.bfloat16)),
            B.antenna_sharding(m),
        )
        p32 = np.asarray(B.beamform(vp32, wp, mesh=m, nint=4))
        p16 = np.asarray(B.beamform(vp16, wp, mesh=m, nint=4))
        assert p16.dtype == np.float32  # detection always comes back f32
        np.testing.assert_allclose(p16, p32, rtol=3e-2,
                                   atol=3e-2 * np.abs(p32).max())

    def test_loader_bf16_residency(self, tmp_path):
        from blit.parallel.antenna import load_antennas_mesh
        from blit.testing import synth_raw

        paths = []
        for a in range(8):
            p = str(tmp_path / f"a{a}.raw")
            synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=64, seed=a)
            paths.append(p)
        m = make_mesh(1, 8)
        _, (vr, vi) = load_antennas_mesh(paths, mesh=m, dtype="bfloat16")
        assert vr.dtype == jnp.bfloat16 and vi.dtype == jnp.bfloat16
        # Lossless: the bf16 planes decode to the same int8-origin values.
        _, (fr, fi) = load_antennas_mesh(paths, mesh=m)
        np.testing.assert_array_equal(
            np.asarray(vr).astype(np.float32), np.asarray(fr)
        )
        with pytest.raises(ValueError, match="dtype"):
            load_antennas_mesh(paths, mesh=m, dtype="float16")


class TestBeamformPlanar:
    """The TPU-native planar (re, im) input path."""

    def test_planar_matches_complex_path(self):
        nant, nbeam = 8, 3
        v = make_antenna_voltages(nant=nant)
        rng = np.random.default_rng(7)
        w = (rng.standard_normal((nbeam, nant, 4))
             + 1j * rng.standard_normal((nbeam, nant, 4))).astype(np.complex64)
        m = make_mesh(1, 8)
        vp = jax.device_put(
            (v.real.copy(), v.imag.copy()), B.antenna_sharding(m)
        )
        wp = jax.device_put(
            (w.real.copy(), w.imag.copy()), B.weight_sharding(m)
        )
        got = np.asarray(B.beamform(vp, wp, mesh=m, nint=4))
        want = B.beamform_np(v, w, nint=4)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)

    def test_planar_voltages_out(self):
        v = make_antenna_voltages(nant=8, seed=9)
        rng = np.random.default_rng(10)
        w = (rng.standard_normal((2, 8, 4))
             + 1j * rng.standard_normal((2, 8, 4))).astype(np.complex64)
        m = make_mesh(1, 8)
        vp = jax.device_put((v.real.copy(), v.imag.copy()), B.antenna_sharding(m))
        wp = jax.device_put((w.real.copy(), w.imag.copy()), B.weight_sharding(m))
        br, bi = B.beamform(vp, wp, mesh=m, detect=False)
        want = B.beamform_np(v, w, detect=False)
        np.testing.assert_allclose(np.asarray(br), want.real, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(bi), want.imag, rtol=1e-4, atol=1e-3)

    def test_delay_weights_planar_matches_numpy(self):
        delays = np.array([[0.0, 1e-9, 2e-9]])
        freqs = np.array([1.0e9, 1.5e9])
        amp = np.array([1.0, 0.5, 2.0])
        wr, wi = B.delay_weights_planar(
            jnp.asarray(delays), jnp.asarray(freqs), amplitudes=jnp.asarray(amp)
        )
        # Independent reference: the complex phasor computed in NumPy.
        want = np.exp(-2j * np.pi * delays[..., None] * freqs[None, None, :])
        want = want * amp[None, :, None]
        # f32 phase accumulation at multiples of 2pi costs ~1e-6 absolute.
        np.testing.assert_allclose(np.asarray(wr), want.real, atol=1e-5)
        np.testing.assert_allclose(np.asarray(wi), want.imag, atol=1e-5)


class TestCorrelator:
    @pytest.mark.parametrize("nband,nbank", [(1, 8), (2, 4), (4, 2)])
    def test_matches_numpy(self, nband, nbank):
        nfft, ntap = 16, 4
        nant, nchan = 3, 8
        ntime = nband * 8 * nfft  # 8 blocks per band segment
        v = make_antenna_voltages(nant=nant, nchan=nchan, ntime=ntime, seed=3)
        h = pfb_coeffs(ntap, nfft)
        m = make_mesh(nband, nbank)
        vs = jax.device_put(v, C.correlator_sharding(m))
        got = np.asarray(
            C.correlate(vs, jnp.asarray(h), mesh=m, nfft=nfft, ntap=ntap)
        )
        want = C.correlate_np(v, h, nfft=nfft, ntap=ntap, nsegments=nband)
        assert got.shape == (nant, nant, nchan, nfft, 2, 2)
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)

    def test_hermitian_and_autos_real(self):
        nfft = 8
        v = make_antenna_voltages(nant=2, nchan=8, ntime=8 * nfft, seed=4)
        h = pfb_coeffs(4, nfft)
        m = make_mesh(1, 8)
        vis = np.asarray(C.correlate(
            jax.device_put(v, C.correlator_sharding(m)), jnp.asarray(h),
            mesh=m, nfft=nfft))
        # V[a,b,...,p,q] = conj(V[b,a,...,q,p])
        np.testing.assert_allclose(
            vis, np.conj(np.transpose(vis, (1, 0, 2, 3, 5, 4))), rtol=1e-5,
            atol=1e-4,
        )
        autos = vis[np.arange(2), np.arange(2)][..., [0, 1], [0, 1]]
        assert np.abs(autos.imag).max() < 1e-3
        assert autos.real.min() >= 0

    def test_planar_matches_complex_path(self):
        nfft, ntap = 16, 4
        nant, nchan = 3, 8
        nband, nbank = 2, 4
        ntime = nband * 8 * nfft
        v = make_antenna_voltages(nant=nant, nchan=nchan, ntime=ntime, seed=11)
        h = pfb_coeffs(ntap, nfft)
        m = make_mesh(nband, nbank)
        vp = jax.device_put(
            (v.real.copy(), v.imag.copy()), C.correlator_sharding(m)
        )
        visr, visi = C.correlate(vp, jnp.asarray(h), mesh=m, nfft=nfft, ntap=ntap)
        want = C.correlate_np(v, h, nfft=nfft, ntap=ntap, nsegments=nband)
        np.testing.assert_allclose(np.asarray(visr), want.real, rtol=1e-3, atol=1e-2)
        np.testing.assert_allclose(np.asarray(visi), want.imag, rtol=1e-3, atol=1e-2)

    @pytest.mark.parametrize("nband,nbank", [(1, 8), (2, 4)])
    def test_packed_layout_matches_standard(self, nband, nbank):
        # vis_layout="packed" is the TPU-fast layout (pallas X-engine at
        # MXU-sized nap; packed einsums elsewhere — this CPU mesh takes
        # the einsum fallback).  Same numbers, axes (c,f,a,p,b,q).
        nfft, ntap = 16, 4
        nant, nchan = 3, 8
        ntime = nband * 8 * nfft
        v = make_antenna_voltages(nant=nant, nchan=nchan, ntime=ntime,
                                  seed=13)
        h = pfb_coeffs(ntap, nfft)
        m = make_mesh(nband, nbank)
        vs = jax.device_put(v, C.correlator_sharding(m))
        std = np.asarray(
            C.correlate(vs, jnp.asarray(h), mesh=m, nfft=nfft, ntap=ntap)
        )
        packed = np.asarray(C.correlate(
            vs, jnp.asarray(h), mesh=m, nfft=nfft, ntap=ntap,
            vis_layout="packed",
        ))
        assert packed.shape == (nchan, nfft, nant, 2, nant, 2)
        np.testing.assert_allclose(
            packed, std.transpose(2, 3, 0, 4, 1, 5), rtol=1e-5, atol=1e-5
        )

    @pytest.mark.parametrize("vis_layout", ["standard", "packed"])
    def test_bf16_resident_matches_f32(self, vis_layout):
        # bf16-resident voltages run the bf16-staged path (bf16 FIR +
        # bf16 spectra, f32 accumulation — measured +25% at nant=64,
        # DESIGN.md §9 r5).  On this CPU mesh the f32 reference computes
        # exact f32 (no MXU truncation), so the tolerance covers the
        # bf16 rounding the chip applies to BOTH paths anyway.
        nfft, ntap = 16, 4
        nant, nchan = 3, 8
        ntime = 8 * nfft
        rng = np.random.default_rng(23)
        v8 = rng.integers(-40, 41, (2, nant, nchan, ntime, 2)).astype(
            np.float32
        )
        h = pfb_coeffs(ntap, nfft)
        m = make_mesh(1, 8)
        vp32 = jax.device_put((v8[0], v8[1]), C.correlator_sharding(m))
        vp16 = jax.device_put(
            (v8[0].astype(jnp.bfloat16), v8[1].astype(jnp.bfloat16)),
            C.correlator_sharding(m),
        )
        kw = dict(mesh=m, nfft=nfft, ntap=ntap, vis_layout=vis_layout)
        r32, i32 = C.correlate(vp32, jnp.asarray(h), **kw)
        r16, i16 = C.correlate(vp16, jnp.asarray(h), **kw)
        assert r16.dtype == jnp.float32  # visibilities accumulate f32
        scale = float(np.abs(np.asarray(r32)).max())
        np.testing.assert_allclose(np.asarray(r16), np.asarray(r32),
                                   rtol=2e-2, atol=2e-2 * scale)
        np.testing.assert_allclose(np.asarray(i16), np.asarray(i32),
                                   rtol=2e-2, atol=2e-2 * scale)

    def test_loader_bf16_residency(self, tmp_path):
        from blit.parallel.antenna import load_correlator_mesh
        from blit.testing import synth_raw

        paths = []
        for a in range(3):
            p = str(tmp_path / f"c{a}.raw")
            synth_raw(p, nblocks=2, obsnchan=4, ntime_per_block=512, seed=a)
            paths.append(p)
        m = make_mesh(2, 4)
        _, (vr, vi) = load_correlator_mesh(paths, mesh=m, nfft=64,
                                           dtype="bfloat16")
        assert vr.dtype == jnp.bfloat16 and vi.dtype == jnp.bfloat16
        _, (fr, fi) = load_correlator_mesh(paths, mesh=m, nfft=64)
        np.testing.assert_array_equal(
            np.asarray(vr).astype(np.float32), np.asarray(fr)
        )

    def test_bad_vis_layout_rejected(self):
        m = make_mesh(1, 8)
        v = make_antenna_voltages(nant=2, nchan=8, ntime=8 * 16, seed=1)
        with pytest.raises(ValueError, match="vis_layout"):
            C.correlate(
                jax.device_put(v, C.correlator_sharding(m)),
                jnp.asarray(pfb_coeffs(4, 16)), mesh=m, nfft=16,
                vis_layout="fast",
            )

    def test_correlated_signal_shows_fringe(self):
        # Identical signal in two antennas → cross-power == auto-power.
        nfft = 16
        rng = np.random.default_rng(5)
        base = (rng.standard_normal(8 * nfft) +
                1j * rng.standard_normal(8 * nfft)).astype(np.complex64)
        v = np.zeros((2, 8, 8 * nfft, 1), np.complex64)
        v[0, 0, :, 0] = base
        v[1, 0, :, 0] = base
        h = pfb_coeffs(4, nfft)
        m = make_mesh(1, 8)
        vis = np.asarray(C.correlate(
            jax.device_put(v, C.correlator_sharding(m)), jnp.asarray(h),
            mesh=m, nfft=nfft))
        np.testing.assert_allclose(
            np.abs(vis[0, 1, 0, :, 0, 0]), vis[0, 0, 0, :, 0, 0].real, rtol=1e-4
        )
