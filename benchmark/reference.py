"""The plain reference: what a product must hold, computed the slow way.

PFB (4-tap Hamming-windowed sinc, unit DC gain) -> FFT -> fftshift ->
|X|^2 + |Y|^2 -> integrate ``nint`` spectra, in NumPy float64, one coarse
channel at a time.  A copy in spirit of ``blit.ops.channelize.channelize_np``
and deliberately not an import of it: the program may change its own golden
model, this one only a benchmark PR may touch.  ``benchmark/tests`` pins the
two against each other at a small size.

Since PR 42 also the drift search's sums, for the product kind ``hits``:
``drift_sums`` (one path at a time), ``snr_rows`` (the per-drift-row
normalisation) and ``drift_mask``; a copy in spirit of
``blit.ops.pallas_dedoppler`` and no import of it, pinned against its
``brute_force_dedoppler`` and its lax tree by ``benchmark/tests``.
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


# -- the drift search (product kind ``hits``) ------------------------------------

def tree_shift(d: int, t: int, nspectra: int) -> int:
    """Fine channels by which the drift-``d`` path of a window of
    ``nspectra`` spectra (a power of two) has moved at spectrum ``t``.

    The product's DEFINITION pins this recursion
    (``blit/ops/pallas_dedoppler.py``, its docstring and
    ``tree_path_shift``): each half of the window inherits drift ``d >> 1``
    and the second half starts ``(d + 1) >> 1`` channels up.  A straight
    line, ``round(d t / (T - 1))``, is NOT the semantics: the tree's paths
    are staircases that meet the line at the window's two ends; up to
    T 8 they are the line's rounding, at T 16 six of the sixteen drifts'
    paths part from it by a channel somewhere in between, and more do as
    T grows.  turboSETI's Taylor tree sums the same staircases.  A search
    whose sums ran along the rounded lines would read other S/N for those
    drifts and find other hits near the threshold, so the comparison
    holds the program to the paths its product states."""
    if nspectra == 1:
        return 0
    half = nspectra // 2
    if t < half:
        return tree_shift(d >> 1, t, half)
    return ((d + 1) >> 1) + tree_shift(d >> 1, t - half, half)


def drift_sums(x: np.ndarray) -> np.ndarray:
    """``(T, F)`` power, ``T`` a power of two -> ``(2T - 1, F)`` float64
    drift sums, row ``i`` the drift ``i - (T - 1)`` channels a window:
    ``out[d, f] = sum_t x[t, f + shift(d, t)]``, negative drifts the same
    over the flipped frequency axis, a path that leaves the array reads
    zeros there.  Written out ONE PATH AT A TIME, O(T D F): no partial sum
    is shared between two drifts, so nothing of a tree's staging (its
    order of addition, its buffers, its padding) is copied."""
    x = np.asarray(x, np.float64)
    nspectra, nchan = x.shape
    out = np.zeros((2 * nspectra - 1, nchan))
    for d in range(nspectra):
        up, down = out[nspectra - 1 + d], out[nspectra - 1 - d]
        for t in range(nspectra):
            s = tree_shift(d, t, nspectra)
            if s >= nchan:
                continue
            up[:nchan - s] += x[t, s:]
            if d:   # drift 0 is one row, not two
                down[s:] += x[t, :nchan - s]
    return out


def drift_mask(nspectra: int, max_drift_bins) -> np.ndarray:
    """Which rows of ``drift_sums`` a search limited to ``max_drift_bins``
    (None or negative: no limit) looks at."""
    drifts = np.arange(-(nspectra - 1), nspectra)
    if max_drift_bins is None or max_drift_bins < 0:
        return np.ones(len(drifts), bool)
    return np.abs(drifts) <= max_drift_bins


def snr_rows(total: np.ndarray, total_sq: np.ndarray, ncells: int):
    """The per-drift-row normalisation's two numbers from a row's sum and
    sum of squares over its ``ncells`` cells (ALL the band's fine channels:
    the search normalises a drift row over the whole frequency axis):
    ``(mean, standard deviation)``, the population's, as ``(x - mean) /
    std`` takes them."""
    mean = total / ncells
    return mean, np.sqrt(np.maximum(total_sq / ncells - mean * mean, 0.0))
