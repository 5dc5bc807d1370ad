"""Pallas DFT-stage kernel tests — interpreter mode on CPU (the real-TPU
path is exercised by chip_smoke.py / the driver's compile checks)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit.ops import dft as D  # noqa: E402
from blit.ops import pallas_dft as P  # noqa: E402


def planar(shape, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal(shape).astype(np.float32)),
            jnp.asarray(rng.standard_normal(shape).astype(np.float32)))


class TestStageKernel:
    @pytest.mark.parametrize("with_twiddle", [False, True])
    def test_matches_reference(self, with_twiddle):
        n, m, b = 16, 256, 3
        xr, xi = planar((b, n, m))
        wr, wi = (jnp.asarray(a) for a in D.dft_matrices(n))
        tr = ti = None
        if with_twiddle:
            tr, ti = (jnp.asarray(a) for a in D.twiddles(n, m))
        got = P.dft_stage(xr, xi, wr, wi, tr, ti, interpret=True)
        want = P.stage_reference(xr, xi, wr, wi, tr, ti)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=1e-3)

    def test_tiling_indivisible_m_falls_back(self):
        n, m = 8, 96  # m not divisible by the default tile
        xr, xi = planar((2, n, m), seed=1)
        wr, wi = (jnp.asarray(a) for a in D.dft_matrices(n))
        got = P.dft_stage(xr, xi, wr, wi, interpret=True)
        want = P.stage_reference(xr, xi, wr, wi)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=1e-4, atol=1e-3)

    def test_multi_batch_dims(self):
        n, m = 8, 128
        xr, xi = planar((2, 3, n, m), seed=2)
        wr, wi = (jnp.asarray(a) for a in D.dft_matrices(n))
        got = P.dft_stage(xr, xi, wr, wi, interpret=True)
        assert got[0].shape == (2, 3, n, m)
        want = P.stage_reference(xr, xi, wr, wi)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-4, atol=1e-3)


class TestLastKernel:
    def test_matches_direct_dft(self):
        n, b = 64, 512
        xr, xi = planar((b, n), seed=3)
        wr, wi = (jnp.asarray(a) for a in D.dft_matrices(n))
        got = P.dft_last(xr, xi, wr, wi, interpret=True)
        z = np.fft.fft(np.asarray(xr) + 1j * np.asarray(xi))
        np.testing.assert_allclose(np.asarray(got[0]), z.real, rtol=1e-3,
                                   atol=1e-2)
        np.testing.assert_allclose(np.asarray(got[1]), z.imag, rtol=1e-3,
                                   atol=1e-2)

    def test_row_tiling_fallback(self):
        n = 32
        xr, xi = planar((100, n), seed=4)  # 100 not divisible by 256
        wr, wi = (jnp.asarray(a) for a in D.dft_matrices(n))
        got = P.dft_last(xr, xi, wr, wi, interpret=True)
        z = np.fft.fft(np.asarray(xr) + 1j * np.asarray(xi))
        np.testing.assert_allclose(np.asarray(got[0]), z.real, rtol=1e-3,
                                   atol=1e-2)


class TestDftIntegration:
    def test_auto_is_off_on_cpu(self):
        # CPU backend must not route through pallas (no interpret flag there).
        xr, xi = planar((2, 1 << 13), seed=5)
        yr, yi = D.dft(xr, xi)  # would crash if pallas were chosen
        wr, wi = D.dft_np(np.asarray(xr), np.asarray(xi))
        scale = np.abs(wr + 1j * wi).max()
        assert np.abs(np.asarray(yr) - wr).max() / scale < 1e-3


class TestDftTail2:
    @pytest.mark.parametrize("f2,f3,tile_b", [(8, 4, 4), (16, 8, 2), (8, 8, 3)])
    def test_matches_two_factor_dft(self, f2, f3, tile_b):
        # dft_tail2 == a natural-order (f2, f3)-factored DFT of each row
        # (the tail of a 3-factor transform after its stage 1).
        m = f2 * f3
        xr, xi = planar((2, 3, m), seed=6)
        got_r, got_i = P.dft_tail2(jnp.asarray(xr), jnp.asarray(xi), f2, f3,
                                   tile_b=tile_b, interpret=True)
        want_r, want_i = D.dft(jnp.asarray(xr), jnp.asarray(xi),
                               factors=(f2, f3),
                               precision=jax.lax.Precision.HIGHEST)
        np.testing.assert_allclose(np.asarray(got_r), np.asarray(want_r),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(got_i), np.asarray(want_i),
                                   rtol=1e-4, atol=1e-3)

    def test_channelize_guard(self):
        # tail_kernel='pallas' needs fused1 + exactly 3 factors.
        from blit.ops.channelize import channelize, pfb_coeffs

        v = jnp.zeros((1, 7 * 8192, 2, 2), jnp.int8)
        h = jnp.asarray(pfb_coeffs(4, 8192))
        with pytest.raises(ValueError, match="tail_kernel"):
            channelize(v, h, nfft=8192, fft_method="matmul",
                       pfb_kernel="fused1", tail_kernel="pallas")

    def test_vmem_gate_and_conflict(self):
        from blit.ops.channelize import channelize, pfb_coeffs
        from blit.ops.pallas_dft import tail2_fits

        assert tail2_fits(48 * 2 * 8 * 128, 128, 64, "bfloat16")  # prod
        assert not tail2_fits(1, 2048, 4096)  # huge panels, even tile_b=1
        v = jnp.zeros((1, 7 * 8192, 2, 2), jnp.int8)
        h = jnp.asarray(pfb_coeffs(4, 8192))
        # The explicit pallas+pallas pair (the fused tail+detect) is
        # ineligible at a 2-factor nfft.
        with pytest.raises(ValueError, match="fused tail"):
            channelize(v, h, nfft=8192, fft_method="matmul",
                       pfb_kernel="fused1", detect_kernel="pallas",
                       tail_kernel="pallas")
