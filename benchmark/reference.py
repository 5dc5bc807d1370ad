"""The plain reference: what a product must hold, computed the slow way.

PFB (4-tap Hamming-windowed sinc, unit DC gain) -> FFT -> fftshift ->
|X|^2 + |Y|^2 -> integrate ``nint`` spectra, in NumPy float64, one coarse
channel at a time.  A copy in spirit of ``blit.ops.channelize.channelize_np``
and deliberately not an import of it: the program may change its own golden
model, this one only a benchmark PR may touch.  ``benchmark/tests`` pins the
two against each other at a small size.
"""

from __future__ import annotations

import numpy as np


def pfb_coeffs(ntap: int, nfft: int) -> np.ndarray:
    """``(ntap, nfft)`` windowed-sinc prototype, main lobe one fine channel
    wide, normalised to unit sum (the rawspec / CASPER design)."""
    n = np.arange(ntap * nfft, dtype=np.float64)
    h = np.sinc(n / nfft - ntap / 2.0) * np.hamming(ntap * nfft)
    return (h / h.sum()).reshape(ntap, nfft)


def stokes_i(volt: np.ndarray, *, nfft: int, ntap: int = 4, nint: int = 1,
             despike: bool = False) -> np.ndarray:
    """One coarse channel's Stokes-I product rows ``(nspectra, nfft)``
    float64 from its int8 stream ``(ntime, npol, 2)``.  ``despike`` copies
    the fine channel below the coarse channel's centre over the centre
    (what ``blit scan`` does to the DC spike unless told not to).

    Frames are taken a few at a time (about 2^21 samples, a divisor of
    ``nint``) only so that the work stays in cache; the arithmetic is the
    textbook's."""
    h = pfb_coeffs(ntap, nfft)
    nblk = volt.shape[0] // nfft
    nspectra = (nblk - ntap + 1) // nint
    step = max(d for d in range(1, nint + 1)
               if nint % d == 0 and d * nfft <= max(nfft, 1 << 21)) \
        if nint > 1 else max(1, (1 << 21) // nfft)
    out = np.zeros((nspectra, nfft))
    for f0 in range(0, nspectra * nint, step):
        n = min(step, nspectra * nint - f0)
        z = volt[f0 * nfft:(f0 + n + ntap - 1) * nfft].astype(np.float64)
        z = (z[..., 0] + 1j * z[..., 1]).reshape(n + ntap - 1, nfft, -1)
        power = np.zeros((n, nfft))
        for pol in range(z.shape[2]):
            frames = sum(h[k] * z[k:k + n, :, pol] for k in range(ntap))
            spec = np.fft.fftshift(np.fft.fft(frames, axis=-1), axes=-1)
            power += spec.real ** 2 + spec.imag ** 2
        if nint == 1:
            out[f0:f0 + n] = power
        else:
            out[f0 // nint] += power.sum(axis=0)
    if despike:
        out[:, nfft // 2] = out[:, nfft // 2 - 1]
    return out


def product_header(raw_hdr: dict, *, nfft: int, nint: int) -> dict:
    """fch1 / foff / tsamp a product of this RAW header must carry: coarse
    channel c's centre is OBSFREQ - OBSBW/2 + (c + 1/2) CHAN_BW and fine
    index f sits (f - nfft/2) fine widths from it."""
    chan_bw = raw_hdr["OBSBW"] / raw_hdr["OBSNCHAN"]
    foff = chan_bw / nfft
    c0 = raw_hdr["OBSFREQ"] - raw_hdr["OBSBW"] / 2 + chan_bw / 2
    return {"fch1": c0 - (nfft / 2) * foff, "foff": foff,
            "tsamp": raw_hdr["TBIN"] * nfft * nint}


def least_bytes(raw_bytes: int, product_bytes: int) -> int:
    """The fewest bytes the device's memory must move for one pass: every
    int8 sample in once, every float32 product value out once.  The roof a
    kernel's busy time is held against (``hbm_roof_share``)."""
    return raw_bytes + product_bytes
