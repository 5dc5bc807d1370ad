"""Fused detect+untwist kernel (blit/ops/pallas_detect.py), interpret mode."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit.ops import channelize as ch  # noqa: E402
from blit.ops import dft as D  # noqa: E402
from blit.ops.pallas_detect import (  # noqa: E402
    detect_untwist_i,
    tail2_detect,
    tail2_detect_i,
)


class TestDetectUntwist:
    # (8, 32, 4) with tile_mid=16 spans mid=32 over TWO grid tiles — the
    # j index-map path the production 2^20 shape (mid=128, 8 tiles) uses;
    # tile_mid=2 forces 16 tiles over the same shape.
    @pytest.mark.parametrize("factors,tile_mid", [
        ((8, 4), 16), ((8, 4, 4), 16), ((16,), 16),
        ((8, 32, 4), 16), ((8, 32, 4), 2),
    ])
    def test_matches_untwist_then_detect(self, factors, tile_mid):
        rng = np.random.default_rng(0)
        n = int(np.prod(factors))
        nchan, npol, nframes = 2, 2, 3
        sr = rng.standard_normal((nchan, npol, nframes, n)).astype(np.float32)
        si = rng.standard_normal((nchan, npol, nframes, n)).astype(np.float32)
        got = np.asarray(detect_untwist_i(
            jnp.asarray(sr), jnp.asarray(si), factors, tile_mid=tile_mid,
            interpret=True))
        nat_r = np.asarray(D.untwist(jnp.asarray(sr), factors))
        nat_i = np.asarray(D.untwist(jnp.asarray(si), factors))
        want = (nat_r**2 + nat_i**2).sum(axis=1)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)

    def test_channelize_fused_detect_matches(self):
        rng = np.random.default_rng(4)
        nfft, ntap = 8192, 4
        v = rng.integers(-40, 40, (2, 7 * nfft, 2, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        a = np.asarray(ch.channelize(
            jnp.asarray(v), h, nfft=nfft, nint=2, fft_method="matmul",
            pfb_kernel="fused1", detect_kernel="pallas"))
        b = np.asarray(ch.channelize(
            jnp.asarray(v), h, nfft=nfft, nint=2, fft_method="matmul",
            pfb_kernel="xla"))
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-2 * np.abs(b).max())

    def test_vmem_gate(self):
        from blit.ops import pallas_detect as pd

        assert pd.fits((128, 128, 64))  # the hi-res production shape
        assert pd.fits((128, 128, 1024))  # 2^24: fits by shrinking tile_mid
        # f1 and flast are untiled, so a square 1M split cannot fit.
        assert not pd.fits((1024, 1024))
        sr = jnp.zeros((1, 2, 1, 1024 * 1024), jnp.bfloat16)
        with pytest.raises(ValueError, match="VMEM"):
            detect_untwist_i(sr, sr, (1024, 1024), interpret=True)

    def test_guards(self):
        v = jnp.zeros((1, 7 * 8192, 2, 2), jnp.int8)
        h = jnp.asarray(ch.pfb_coeffs(4, 8192))
        with pytest.raises(ValueError, match="detect_kernel"):
            ch.channelize(v, h, nfft=8192, fft_method="matmul",
                          pfb_kernel="xla", detect_kernel="pallas")
        with pytest.raises(ValueError, match="detect_kernel"):
            ch.channelize(v, h, nfft=8192, fft_method="matmul",
                          pfb_kernel="fused1", stokes="IQUV",
                          detect_kernel="pallas")


class TestTail2Detect:
    """Fully-fused tail+detect (tail2_detect_i): DFT levels 2+3, inner
    untwist, Stokes-I detection and the product transpose in one pass."""

    # (16, 8, 8) with tile_f1=8 spans f1=16 over TWO grid tiles — the j
    # index-map path the production (128, 128, 64) shape uses.  (Tiles
    # must be 8-divisible or full-f1: mosaic's sublane constraint, which
    # interpret mode does not enforce but the fit gate must.)
    @pytest.mark.parametrize("factors,tile_f1", [
        ((8, 32, 4), 16), ((8, 4, 4), 16), ((16, 8, 8), 8),
    ])
    def test_matches_tail_then_detect(self, factors, tile_f1):
        rng = np.random.default_rng(0)
        f1, f2, f3 = factors
        m = f2 * f3
        nchan, npol, nframes = 2, 2, 3
        ur = rng.standard_normal((nchan, npol, nframes, f1, m))
        ui = rng.standard_normal((nchan, npol, nframes, f1, m))
        ur = ur.astype(np.float32)
        ui = ui.astype(np.float32)
        got = np.asarray(tail2_detect_i(
            jnp.asarray(ur), jnp.asarray(ui), f2, f3, tile_f1=tile_f1,
            interpret=True))
        sr, si = D.dft_tail(jnp.asarray(ur), jnp.asarray(ui), factors)
        want = np.asarray((sr**2 + si**2).sum(axis=1))  # (chan, frame, n)
        want = want.transpose(1, 0, 2)  # frame-major product layout
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-4 * np.abs(want).max())

    @pytest.mark.parametrize("stokes", ["XX", "YY", "XXYY", "full", "IQUV"])
    def test_all_products_match_detect(self, stokes):
        from blit.ops.channelize import detect_stokes_planar

        rng = np.random.default_rng(2)
        f1, f2, f3 = 8, 32, 4
        m = f2 * f3
        nchan, npol, nframes = 2, 2, 3
        ur = rng.standard_normal((nchan, npol, nframes, f1, m))
        ui = rng.standard_normal((nchan, npol, nframes, f1, m))
        ur = ur.astype(np.float32)
        ui = ui.astype(np.float32)
        got = np.asarray(tail2_detect(
            jnp.asarray(ur), jnp.asarray(ui), f2, f3, stokes=stokes,
            interpret=True))
        sr, si = D.dft_tail(jnp.asarray(ur), jnp.asarray(ui), (f1, f2, f3))
        # dft_tail emits (nchan, npol, nframes, n) — detect's expected
        # (..., npol, nframes, n) layout — giving (nchan, nif, nframes, n).
        want = np.asarray(detect_stokes_planar(sr, si, stokes))
        want = want.transpose(2, 1, 0, 3)  # (nframes, nif, nchan, n)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-4 * np.abs(want).max())

    @pytest.mark.parametrize("npol,stokes", [(2, "full"), (1, "I"),
                                             (2, "I")])
    @pytest.mark.parametrize("factors", [(16, 4, 128), (8, 2, 256),
                                         (16, 8, 8)],
                             ids=["lanes128", "lanes256", "lanes8"])
    def test_the_panels_are_read_where_the_front_wrote_them(
            self, factors, npol, stokes):
        # ISSUE 46: the block is the stage-1 rows as they lie — 8 of them
        # interleaved, 128 lanes a row where f3 has them (one strided
        # load a panel; two where f3 is 256) — not an (f2, f3) re-tiling.
        from blit.ops.channelize import detect_stokes_planar
        from blit.ops import pallas_detect as pd

        f1, f2, f3 = factors
        assert pd._td_panels(f3, min(f1, 16)) == (
            (8, 128) if f3 % 128 == 0 else (1, f3))
        rng = np.random.default_rng(f3 + npol)
        shape = (2, npol, 2, f1, f2 * f3)
        ur = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        ui = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
        got = np.asarray(tail2_detect(ur, ui, f2, f3, stokes=stokes,
                                      interpret=True))
        sr, si = D.dft_tail(ur, ui, factors)
        want = np.asarray(detect_stokes_planar(sr, si, stokes))
        want = want.transpose(2, 1, 0, 3)  # (nframes, nif, nchan, n)
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-4 * np.abs(want).max())

    def test_single_pol_guard(self):
        ur = jnp.zeros((1, 1, 1, 8, 128), jnp.float32)
        with pytest.raises(ValueError, match="2 pols"):
            tail2_detect(ur, ur, 32, 4, stokes="IQUV", interpret=True)

    def test_bfloat16_input(self):
        rng = np.random.default_rng(1)
        f1, f2, f3 = 8, 32, 4
        ur = rng.standard_normal((1, 2, 2, f1, f2 * f3)).astype(np.float32)
        ui = rng.standard_normal((1, 2, 2, f1, f2 * f3)).astype(np.float32)
        ub_r = jnp.asarray(ur).astype(jnp.bfloat16)
        ub_i = jnp.asarray(ui).astype(jnp.bfloat16)
        got = np.asarray(tail2_detect_i(ub_r, ub_i, f2, f3, interpret=True))
        sr, si = D.dft_tail(jnp.asarray(ur), jnp.asarray(ui), (f1, f2, f3))
        want = np.asarray((sr**2 + si**2).sum(axis=1)).transpose(1, 0, 2)
        # bf16 inputs: ~3 decimal digits.
        np.testing.assert_allclose(got, want, rtol=0.05,
                                   atol=0.05 * np.abs(want).max())

    def test_channelize_fused_tail_detect_matches(self):
        # The only default_factors 3-factor sizes are >= 2^20; keep the
        # batch tiny so interpret mode stays fast.
        rng = np.random.default_rng(4)
        nfft, ntap = 1 << 20, 4
        v = rng.integers(-40, 40, (1, (ntap + 1) * nfft, 2, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        a = np.asarray(ch.channelize(
            jnp.asarray(v), h, nfft=nfft, nint=2, fft_method="matmul",
            pfb_kernel="fused1", tail_kernel="pallas",
            detect_kernel="pallas"))
        b = np.asarray(ch.channelize(
            jnp.asarray(v), h, nfft=nfft, nint=2, fft_method="matmul",
            pfb_kernel="xla"))
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-2 * np.abs(b).max())

    def test_channelize_fused_iquv_matches(self):
        # Full-Stokes product through the fused path ("auto" now resolves
        # to tail2_detect for every detect_stokes_planar product).
        rng = np.random.default_rng(6)
        nfft, ntap = 1 << 20, 4
        v = rng.integers(-40, 40, (1, (ntap + 1) * nfft, 2, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        kw = dict(nfft=nfft, stokes="IQUV", fft_method="matmul")
        a = np.asarray(ch.channelize(
            jnp.asarray(v), h, pfb_kernel="fused1", tail_kernel="pallas",
            detect_kernel="pallas", **kw))
        b = np.asarray(ch.channelize(jnp.asarray(v), h, pfb_kernel="xla",
                                     **kw))
        assert a.shape == b.shape and a.shape[1] == 4
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-2 * np.abs(b).max())

    def test_channelize_fused_tail_detect_channel_block(self):
        # The blocked-mode assembly (lax.map + moveaxis + channel-major
        # flatten) must keep coarse channels in order.
        rng = np.random.default_rng(5)
        nfft, ntap = 1 << 20, 4
        v = rng.integers(-40, 40, (2, (ntap + 1) * nfft, 2, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        kw = dict(nfft=nfft, fft_method="matmul", pfb_kernel="fused1",
                  tail_kernel="pallas", detect_kernel="pallas")
        a = np.asarray(ch.channelize(
            jnp.asarray(v), h, channel_block=1, **kw))
        b = np.asarray(ch.channelize(jnp.asarray(v), h, **kw))
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * np.abs(b).max())

    def test_vmem_gate(self):
        from blit.ops import pallas_detect as pd

        # The hi-res production shape, bf16 and f32.
        assert pd.tail2_detect_fits((128, 128, 64), esize=2)
        assert pd.tail2_detect_fits((128, 128, 64), esize=4)
        assert not pd.tail2_detect_fits((128, 2048), esize=2)  # 2 factors
        assert not pd.tail2_detect_fits((1, 2048, 4096), esize=2)
        ur = jnp.zeros((1, 2, 1, 1, 2048 * 4096), jnp.bfloat16)
        with pytest.raises(ValueError, match="VMEM"):
            tail2_detect_i(ur, ur, 2048, 4096, interpret=True)

    def test_guards(self):
        v = jnp.zeros((1, 7 * 8192, 2, 2), jnp.int8)
        h = jnp.asarray(ch.pfb_coeffs(4, 8192))
        # 8192 → two factors: the combined path is ineligible.
        with pytest.raises(ValueError, match="fused tail"):
            ch.channelize(v, h, nfft=8192, fft_method="matmul",
                          pfb_kernel="fused1", tail_kernel="pallas",
                          detect_kernel="pallas")
