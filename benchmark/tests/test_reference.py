"""The plain reference against the program's own golden model, at a small
size: two independent writings of the same mathematics must agree."""

import numpy as np

import reference


def test_stokes_i_matches_channelize_np():
    from blit.ops.channelize import channelize_np, pfb_coeffs

    rng = np.random.default_rng(7)
    nfft, nchan = 256, 3
    v = rng.integers(-40, 40, (nchan, 19 * nfft, 2, 2), dtype=np.int8)
    for nint in (1, 4):
        want = channelize_np(v, pfb_coeffs(4, nfft), nfft=nfft, nint=nint)
        for c in range(nchan):
            got = reference.stokes_i(v[c], nfft=nfft, nint=nint)
            ref = want[:, 0, c * nfft:(c + 1) * nfft]
            assert got.shape == ref.shape
            # channelize_np filters in float32: 1e-5 of the largest value
            assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()


def test_despike_copies_the_lower_neighbour():
    rng = np.random.default_rng(8)
    v = rng.integers(-40, 40, (8 * 64, 2, 2), dtype=np.int8)
    plain = reference.stokes_i(v, nfft=64)
    fixed = reference.stokes_i(v, nfft=64, despike=True)
    assert np.array_equal(fixed[:, 32], plain[:, 31])
    assert np.array_equal(np.delete(fixed, 32, 1), np.delete(plain, 32, 1))


def test_product_header_matches_output_header():
    import recording
    from blit.ops.channelize import output_header

    g = {"obsnchan": 64, "nbits": 8, "npol": 2, "block_samples": 1 << 19}
    for obsbw in (187.5, -187.5):
        rh = recording.raw_header(g, obsfreq=8437.5, obsbw=obsbw)
        assert rh["BLOCSIZE"] == 134217728
        want = output_header(rh, nfft=1 << 20, nint=1)
        got = reference.product_header(rh, nfft=1 << 20, nint=1)
        for k, v in got.items():
            assert abs(v - want[k]) <= 1e-12 * abs(want[k])


def test_least_bytes_is_int8_in_plus_f32_out():
    assert reference.least_bytes(5_100_273_664, 4 << 30) \
        == 5_100_273_664 + (4 << 30)
