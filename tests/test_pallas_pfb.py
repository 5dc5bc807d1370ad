"""Fused dequant+PFB pallas kernel (blit/ops/pallas_pfb.py) vs the jnp
path — interpreter mode on CPU, same harness pattern as test_pallas_dft."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit.ops import channelize as ch  # noqa: E402
from blit.ops.pallas_pfb import pfb_dequant  # noqa: E402


def jnp_reference(v, coeffs, work_dtype):
    re, im = ch.dequantize(jnp.asarray(v), dtype=work_dtype)
    re = jnp.moveaxis(re, -1, 1)
    im = jnp.moveaxis(im, -1, 1)
    h = jnp.asarray(coeffs).astype(work_dtype)
    return ch.pfb_frontend(re, h), ch.pfb_frontend(im, h)


class TestPfbDequant:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_matches_jnp_path(self, dtype):
        rng = np.random.default_rng(0)
        nchan, nfft, ntap, nblk = 3, 256, 4, 6
        v = rng.integers(-128, 128, (nchan, nblk * nfft, 2, 2), np.int8)
        coeffs = ch.pfb_coeffs(ntap, nfft)
        fr, fi = pfb_dequant(jnp.asarray(ch.sample_words(v)),
                             jnp.asarray(coeffs),
                             dtype=dtype, interpret=True)
        wr, wi = jnp_reference(
            v, coeffs, jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
        )
        assert fr.shape == wr.shape == (nchan, 2, nblk - ntap + 1, nfft)
        assert fr.dtype == jnp.dtype(dtype)
        # pallas accumulates taps in f32 (more accurate than the bf16 jnp
        # accumulation) — compare at bf16 grain.
        tol = 3e-2 if dtype == "bfloat16" else 1e-6
        scale = max(np.abs(np.asarray(wr, np.float32)).max(), 1.0)
        for a, b in zip((fr, fi), (wr, wi)):
            err = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
            assert err.max() / scale < tol

    def test_full_byte_range_sign_extension(self):
        # Every int8 value decodes exactly (the in-kernel byte unpack).
        v = np.arange(-128, 128, dtype=np.int8)
        v = np.tile(v, 8)  # 2048 samples
        block = np.stack([v, -v - 1], axis=-1)  # re, im
        block = np.stack([block, block[::-1]], axis=-2)  # 2 pols
        block = block[None]  # (1, 2048, 2, 2)
        coeffs = np.zeros((4, 256), np.float32)
        coeffs[0] = 1.0  # tap-0 passthrough: frames = raw blocks
        fr, fi = pfb_dequant(jnp.asarray(ch.sample_words(block)),
                             jnp.asarray(coeffs), interpret=True)
        want = block.reshape(1, 8, 256, 2, 2).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(fr)[0, 0], want[0, :5, :, 0, 0])
        np.testing.assert_array_equal(
            np.asarray(fi)[0, 1], want[0, :5, :, 1, 1])

    def test_channelize_pallas_pfb_matches_xla(self):
        rng = np.random.default_rng(2)
        nfft, ntap = 128, 4
        v = rng.integers(-40, 40, (2, 7 * nfft, 2, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        a = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft, nint=2,
                                     stokes="XXYY", pfb_kernel="pallas"))
        b = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft, nint=2,
                                     stokes="XXYY"))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-2)

    def test_single_pol_explicit_rejected_auto_falls_back(self):
        rng = np.random.default_rng(3)
        nfft = 64
        v = rng.integers(-40, 40, (2, 5 * nfft, 1, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(4, nfft))
        # Explicit opt-in that cannot run must error, not silently degrade.
        with pytest.raises(ValueError, match="npol=2"):
            ch.channelize(jnp.asarray(v), h, nfft=nfft, pfb_kernel="pallas")
        # "auto" quietly takes the XLA path for unsupported shapes.
        a = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft))
        b = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft,
                                     pfb_kernel="xla"))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-4)

    def test_bad_kernel_name_rejected(self):
        v = jnp.zeros((1, 256, 2, 2), jnp.int8)
        h = jnp.asarray(ch.pfb_coeffs(4, 64))
        with pytest.raises(ValueError, match="pfb_kernel"):
            ch.channelize(v, h, nfft=64, pfb_kernel="cuda")

    def test_fused1_matches_xla_end_to_end(self):
        # dequant+PFB+stage1 fused: whole channelize parity on a
        # multi-factor nfft (8192 -> factors (128, 64)).
        rng = np.random.default_rng(5)
        nfft, ntap = 8192, 4
        v = rng.integers(-40, 40, (2, 6 * nfft, 2, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(ntap, nfft))
        a = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft,
                                     stokes="IQUV", fft_method="matmul",
                                     pfb_kernel="fused1"))
        b = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft,
                                     stokes="IQUV", fft_method="matmul",
                                     pfb_kernel="xla"))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-2 * np.abs(b).max())

    def test_fused1_guards(self):
        v = jnp.zeros((1, 6 * 256, 2, 2), jnp.int8)
        h = jnp.asarray(ch.pfb_coeffs(4, 256))
        with pytest.raises(ValueError, match="multi-factor"):
            ch.channelize(v, h, nfft=256, fft_method="matmul",
                          pfb_kernel="fused1")
        v2 = jnp.zeros((1, 6 * 8192, 2, 2), jnp.int8)
        h2 = jnp.asarray(ch.pfb_coeffs(4, 8192))
        with pytest.raises(ValueError, match="twisted"):
            ch.channelize(v2, h2, nfft=8192, fft_method="matmul",
                          pfb_kernel="fused1", dft_order="twisted")

    def test_vmem_gate(self):
        from blit.ops import pallas_pfb as pp

        # Bench shape fits; the '0002' preset's 2048-frame chunks do not.
        assert pp.fits(1 << 20, 11, 4, "bfloat16")
        assert not pp.fits(1 << 10, 2051, 4, "float32")
        # And pfb_dequant refuses outright rather than failing in mosaic.
        v = jnp.zeros((1, 2051 * 1024), jnp.int32)
        h = jnp.asarray(ch.pfb_coeffs(4, 1024))
        with pytest.raises(ValueError, match="VMEM"):
            pp.pfb_dequant(v, h, interpret=True)
