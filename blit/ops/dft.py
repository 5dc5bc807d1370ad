"""Planar (real/imag) DFT on the MXU: FFT as matmuls.

The TPU-native FFT path.  Two facts drive this design:

1. The fused Pallas kernels (blit/ops/pallas_*.py) work on real-valued
   tiles, so the compute path is real-valued end to end.  (The v5e under
   jax 0.9 / libtpu 0.0.34 does run complex64 ``device_put`` and
   ``jnp.fft.fft`` — chip_smoke.py prints it — but nothing here uses
   them; the planar path stands on its own merits.)
2. The MXU wants big batched matmuls.  A DFT *is* a matmul (``y = W x``), and
   the four-step factorization N = N1·N2 turns an arbitrarily large FFT into
   two batched ≤4K-point DFT matmuls plus one elementwise twiddle — for the
   1M-point hi-res product that is two 1024×1024 matrices applied to large
   batches: peak MXU shape (SURVEY.md §7 "hard parts", pallas_guide.md MXU
   notes).

"Planar" complex convention used across blit's TPU path: a complex array is
a ``(re, im)`` pair of equal-shape real arrays.  4 real matmuls implement one
complex matmul; XLA fuses the adds.

All matrices/twiddles are precomputed NumPy constants — they are jit-time
constants, transferred to HBM once and reused every step.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from blit.device import pallas_interpret

# Largest DFT applied as a single matmul; larger sizes four-step-decompose.
# 4096² f32 matrices are 64 MB each — HBM-comfortable, VMEM-tileable.
DIRECT_DFT_MAX = 4096

Planar = Tuple[jax.Array, jax.Array]

# A planar entry point's input: one complex array (CPU/GPU convenience) or a
# planar (re, im) pair (the TPU-native form).
ComplexOrPlanar = Union[jax.Array, Tuple[jax.Array, jax.Array]]


def as_planar(x) -> Tuple[jax.Array, jax.Array, bool]:
    """Normalize a complex array or a planar pair to ``(re, im,
    was_complex)``.

    The shared input-dispatch for every planar entry point (beamform,
    correlator, …): planar ``(re, im)`` pairs — the TPU-native form — pass
    through; complex arrays split (CPU/GPU convenience; the dispatch is
    trace-time static since it keys on python type / dtype); real arrays get
    a zero imaginary plane.
    """
    if isinstance(x, (tuple, list)):
        xr, xi = x
        return jnp.asarray(xr), jnp.asarray(xi), False
    x = jnp.asarray(x)
    if jnp.iscomplexobj(x):
        return jnp.real(x), jnp.imag(x), True
    return x, jnp.zeros_like(x), False


@functools.lru_cache(maxsize=32)
def dft_matrices(n: int, dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """(Wr, Wi): real and imaginary parts of the n-point DFT matrix
    ``W[k, j] = exp(-2πi k j / n)`` (symmetric, so it applies to either
    side of a matmul without transposition)."""
    k = np.arange(n).reshape(n, 1).astype(np.float64)
    j = np.arange(n).reshape(1, n).astype(np.float64)
    ang = -2.0 * np.pi * ((k * j) % n) / n
    return np.cos(ang).astype(dtype), np.sin(ang).astype(dtype)


@functools.lru_cache(maxsize=32)
def twiddles(n1: int, n2: int, dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """(Tr, Ti): four-step twiddle factors ``exp(-2πi k1 j2 / (n1 n2))``
    shaped (n1, n2) — k1 indexes stage-1 output rows, j2 stage-2 columns."""
    n = n1 * n2
    k1 = np.arange(n1).reshape(n1, 1).astype(np.float64)
    j2 = np.arange(n2).reshape(1, n2).astype(np.float64)
    ang = -2.0 * np.pi * ((k1 * j2) % n) / n
    return np.cos(ang).astype(dtype), np.sin(ang).astype(dtype)


def default_factors(n: int) -> Tuple[int, ...]:
    """Factorization policy for the multi-level decomposition.

    The DFT-matmul cost is ``N · Σ factors`` complex MACs, so small factors
    win FLOPs — but the MXU is a 128×128 systolic array, so factors below
    128 waste it.  Policy: peel factors of 128 while the remainder stays
    >= 128, yielding e.g. 2^20 → (128, 128, 64) (sum 320, 6.4× fewer FLOPs
    than the square 1024×1024 split).  Non-power-of-two sizes fall back to
    as-square-as-possible two-factor splits.
    """
    if n <= DIRECT_DFT_MAX:
        return (n,)
    if n & (n - 1) == 0:
        factors = []
        while n > DIRECT_DFT_MAX:
            f = min(128, n)
            factors.append(f)
            n //= f
        factors.append(n)
        return tuple(factors)
    n1 = int(math.isqrt(n))
    while n % n1:
        n1 -= 1
    if n1 == 1 or max(n1, n // n1) > DIRECT_DFT_MAX:
        raise NotImplementedError(
            f"dft: no supported factorization for n={n}"
        )
    return (n1, n // n1)


def _cmatmul_last(
    xr: jax.Array, xi: jax.Array, wr: jax.Array, wi: jax.Array, precision
) -> Planar:
    """Complex DFT along the LAST axis via 4 real matmuls:
    ``y[..., k] = Σ_j x[..., j]·W[k, j]`` — with symmetric W this is
    ``x @ W``."""
    rr = jnp.matmul(xr, wr, precision=precision)
    ri = jnp.matmul(xr, wi, precision=precision)
    ir = jnp.matmul(xi, wr, precision=precision)
    ii = jnp.matmul(xi, wi, precision=precision)
    return rr - ii, ri + ir


# Largest DFT matrix held whole in VMEM by the pallas kernels (n x n f32
# twice = 8 MB at 1024; above that the jnp path tiles through XLA instead).
_PALLAS_MAX_N = 1024


def untwist(x: jax.Array, factors: Tuple[int, ...]) -> jax.Array:
    """Restore natural frequency order after ``dft(..., order="twisted")``.

    The twisted-flat layout enumerates the per-level digit axes
    ``(k1, k2, ..., klast)`` row-major, while the true frequency index is
    ``k = k1 + f1*k2 + f1*f2*k3 + ...`` — so the untwist is one reshape /
    reverse-axes transpose / reshape, a single materialized pass.
    """
    if len(factors) == 1:
        return x
    batch = x.shape[:-1]
    nb = len(batch)
    y = x.reshape(batch + tuple(factors))
    perm = tuple(range(nb)) + tuple(reversed(range(nb, nb + len(factors))))
    return jnp.transpose(y, perm).reshape(batch + (int(np.prod(factors)),))


def dft(
    xr: jax.Array,
    xi: jax.Array,
    *,
    precision=None,
    dtype: str = "float32",
    factors: Optional[Tuple[int, ...]] = None,
    use_pallas: bool = False,
    order: str = "natural",
) -> Planar:
    """Planar DFT along the last axis.

    Sizes <= DIRECT_DFT_MAX use one matmul; larger sizes recurse on the
    Cooley-Tukey split n = n1 · rest — an n1-point DFT matmul down the
    columns, a twiddle multiply, and a recursive DFT along the rows.  With
    :func:`default_factors` the 1M-point case runs as three matmul stages
    (128, 128, 64).  Matches ``np.fft.fft`` (golden-tested).

    ``precision``: a ``jax.lax.Precision`` for the matmuls — ``HIGHEST``
    forces full-f32 MXU passes; None uses the backend default (bf16-grade
    multiplies on TPU, exact on CPU).
    ``factors``: override the factorization (each factor <= DIRECT_DFT_MAX,
    product == n); None → :func:`default_factors`.
    ``use_pallas``: run the stages as fused pallas kernels
    (blit/ops/pallas_dft.py) — one VMEM-resident pass per stage.  Measured
    on a v5e (160× 1M-point, batched): XLA einsum path 95 ms/call, pallas
    108 ms/call — XLA's own fusion already wins at these shapes, so the
    default is the XLA path; the kernels remain available (and correct on
    hardware, sum-checked) as the tuning surface for future tile-size work.
    ``order``: ``"natural"`` emits true frequency order; ``"twisted"``
    skips the per-level untwist transposes — the two materialized
    HBM passes of the multi-level path — and emits the digit-permuted
    layout that :func:`untwist` restores.  Order-oblivious consumers
    (elementwise power detection) read the twisted spectra directly and
    untwist once on their smaller output (the channelize fast path).
    """
    n = xr.shape[-1]
    if factors is None:
        factors = default_factors(n)
    if int(np.prod(factors)) != n:
        raise ValueError(f"dft: factors {factors} do not multiply to {n}")
    if order not in ("natural", "twisted"):
        raise ValueError(f"order must be 'natural' or 'twisted', got {order!r}")
    if use_pallas and dtype != "float32":
        # The kernels hardcode f32 tiles/accumulators (pallas_dft.py).
        raise ValueError("use_pallas supports dtype='float32' only")
    interpret = use_pallas and pallas_interpret(jax.default_backend())
    return _dft_rec(xr, xi, factors, precision, dtype, use_pallas, interpret,
                    order == "twisted")


def _dft_rec(
    xr: jax.Array, xi: jax.Array, factors: Tuple[int, ...], precision, dtype,
    use_pallas: bool = False, interpret: bool = False, twisted: bool = False,
) -> Planar:
    n = xr.shape[-1]
    if len(factors) == 1:
        if n > DIRECT_DFT_MAX:
            raise NotImplementedError(f"dft: single factor {n} too large")
        wr, wi = dft_matrices(n, dtype)
        if use_pallas and n <= _PALLAS_MAX_N:
            from blit.ops.pallas_dft import dft_last

            return dft_last(xr, xi, jnp.asarray(wr), jnp.asarray(wi),
                            interpret=interpret)
        return _cmatmul_last(xr, xi, jnp.asarray(wr), jnp.asarray(wi), precision)
    n1 = factors[0]
    n2 = n // n1
    batch = xr.shape[:-1]
    # x[j] with j = n2*j1 + j2 → rows j1, cols j2.
    xr_ = xr.reshape(batch + (n1, n2))
    xi_ = xi.reshape(batch + (n1, n2))
    # Stage 1: n1-point DFTs down the columns, then the twiddle
    # W_n^{k1·j2}: y[..., k1, j2] = tw · Σ_j1 W1[k1, j1] x[..., j1, j2].
    w1r, w1i = (jnp.asarray(a) for a in dft_matrices(n1, dtype))
    tr, ti = (jnp.asarray(a) for a in twiddles(n1, n2, dtype))
    if use_pallas and n1 <= _PALLAS_MAX_N:
        from blit.ops.pallas_dft import dft_stage

        ur, ui = dft_stage(xr_, xi_, w1r, w1i, tr, ti, interpret=interpret)
    else:
        ar = jnp.einsum("kj,...jm->...km", w1r, xr_, precision=precision)
        ai = jnp.einsum("kj,...jm->...km", w1i, xr_, precision=precision)
        br = jnp.einsum("kj,...jm->...km", w1r, xi_, precision=precision)
        bi = jnp.einsum("kj,...jm->...km", w1i, xi_, precision=precision)
        sr, si = ar - bi, ai + br
        ur = sr * tr - si * ti
        ui = sr * ti + si * tr
    # Recurse: n2-point DFTs along the rows (last axis).
    vr, vi = _dft_rec(ur, ui, factors[1:], precision, dtype, use_pallas,
                      interpret, twisted)
    if twisted:
        # Keep the (k1, <twisted n2>) layout: flatten row-major; the digit
        # axes accumulate as (k1 of every level..., last k) — exactly what
        # :func:`untwist` reverses.  No transpose pass at any level.
        vr = vr.reshape(batch + (n,))
        vi = vi.reshape(batch + (n,))
        return vr, vi
    # Output index k = k1 + n1*k2: transpose (k1, k2) → (k2, k1) then flatten.
    vr = jnp.swapaxes(vr, -1, -2).reshape(batch + (n,))
    vi = jnp.swapaxes(vi, -1, -2).reshape(batch + (n,))
    return vr, vi


def dft_tail(
    ur: jax.Array,
    ui: jax.Array,
    factors: Tuple[int, ...],
    *,
    precision=None,
    dtype: str = "float32",
    order: str = "natural",
) -> Planar:
    """Finish a DFT whose first stage (n1-point matmul + twiddle) was
    computed externally — e.g. by the fused dequant+PFB+stage-1 pallas
    kernel (blit/ops/pallas_pfb.pfb_dft1): run the remaining ``factors[1:]``
    along the last axis and assemble natural frequency order.

    ``ur, ui``: ``(..., n1, m)`` stage-1 outputs (twiddle already applied).
    Returns ``(..., n1*m)`` spectra — natural order, or the digit-permuted
    layout of :func:`untwist` with ``order="twisted"`` (for order-oblivious
    consumers like the fused detect kernel; keeps the twisted-flat layout
    contract in this module).
    """
    n1, m = ur.shape[-2], ur.shape[-1]
    if factors[0] != n1 or int(np.prod(factors[1:])) != m:
        raise ValueError(f"dft_tail: factors {factors} mismatch ({n1}, {m})")
    if order not in ("natural", "twisted"):
        raise ValueError(f"order must be 'natural' or 'twisted', got {order!r}")
    batch = ur.shape[:-2]
    if order == "twisted":
        vr, vi = _dft_rec(ur, ui, factors[1:], precision, dtype, twisted=True)
        return (vr.reshape(batch + (n1 * m,)),
                vi.reshape(batch + (n1 * m,)))
    vr, vi = _dft_rec(ur, ui, factors[1:], precision, dtype)
    vr = jnp.swapaxes(vr, -1, -2).reshape(batch + (n1 * m,))
    vi = jnp.swapaxes(vi, -1, -2).reshape(batch + (n1 * m,))
    return vr, vi


def dft_np(xr: np.ndarray, xi: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """NumPy golden reference (tests)."""
    z = np.fft.fft(xr + 1j * xi)
    return z.real, z.imag
