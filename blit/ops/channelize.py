"""GUPPI RAW → high-resolution filterbank reduction: the TPU compute core.

This is the per-``BLP<band><bank>`` worker reduction the reference delegates
to ``rawspec`` on CUDA nodes (SURVEY.md §0: products ``*_<scan>.rawspec.NNNN``;
BASELINE.json config 2).  The rebuild is pure JAX — everything here is
jittable with static shapes, so XLA fuses dequantization, the polyphase
frontend, Stokes detection and integration around the FFT:

    int8 voltages (nchan_coarse, ntime, npol, 2)
      → dequant (float32 complex)
      → 4-tap polyphase filter bank frontend (windowed-sinc FIR)
      → nfft-point FFT per coarse channel  (four-step for the 1M-pt case)
      → fftshift (DC lands at fine index nfft//2 — exactly where the
        reference's despike expects it, src/gbt.jl:101-111)
      → Stokes detect (I / XXYY / full-pol / IQUV)
      → time integrate by ``nint``
      → (ntime_out, nif, nchan_coarse*nfft) float32 filterbank slab

TPU notes (pallas_guide.md; SURVEY.md §7 "hard parts"):

- The 1M-point FFT exceeds VMEM as a monolith.  ``fft`` therefore factors
  N = N1·N2 and runs two batched small FFTs plus a twiddle multiply (the
  classic four-step decomposition) — each stage is a contiguous batch of
  ≤8K-point FFTs that XLA tiles comfortably; the twiddle and transpose fuse.
- All control flow is static; ``jax.lax`` only.  No data-dependent shapes.
- The FIR stage runs on separate real/imag float32 planes, keeping it
  real-valued VPU/MXU work; the FFT recombines via ``lax.complex``.
- Every path reads the samples as the int32 words they are
  (:func:`sample_words`), where they lie.  The XLA path filters 8 coarse
  channels on the sublanes, a block's ``nfft`` points on the lanes, the
  blocks down a major axis (``channelize``'s ``words_core``).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import math
import threading
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.extend
import jax.numpy as jnp

from blit.device import TPU_BACKEND, pallas_interpret
from blit.ops import dft as dftmod
from blit.ops.fqav import fqav as _fqav

STOKES_NIF = {"I": 1, "XX": 1, "YY": 1, "XXYY": 2, "full": 4, "IQUV": 4}

# Largest FFT run as a single jnp.fft call; above this, four-step decompose.
_DIRECT_FFT_MAX = 8192



def usable_frames(nsamps: int, nfft: int, ntap: int, nint: int,
                  open_frames: int = 0) -> int:
    """Whole PFB frames a gap-free span of ``nsamps`` samples yields, cut
    so that the stream ends on a whole integration — THE frame-accounting
    invariant shared by the streaming flush (blit/pipeline.py) and the mesh
    scan loader (blit/parallel/scan.py).  ``open_frames`` counts the frames
    an integration carried into the span already holds
    (:func:`integrate_carry`): they close with the span's first
    ``nint - open_frames``."""
    frames = nsamps // nfft - ntap + 1
    if frames <= 0:
        return 0
    return max(0, (open_frames + frames) // nint * nint - open_frames)


def pfb_coeffs(ntap: int, nfft: int, window: str = "hamming") -> np.ndarray:
    """Windowed-sinc prototype filter for the polyphase frontend, shaped
    ``(ntap, nfft)`` and normalized to unit DC gain per fine channel.

    Matches the standard rawspec/CASPER design: ``sinc(x)·w(n)`` over
    ``ntap*nfft`` taps with the sinc main lobe spanning one fine channel.
    """
    n = np.arange(ntap * nfft, dtype=np.float64)
    x = n / nfft - ntap / 2.0
    sinc = np.sinc(x)
    if window == "hamming":
        win = np.hamming(ntap * nfft)
    elif window == "hanning":
        win = np.hanning(ntap * nfft)
    elif window == "rect":
        win = np.ones(ntap * nfft)
    else:
        raise ValueError(f"unknown window {window!r}")
    h = sinc * win
    h /= h.sum()  # unit DC gain: a constant input yields 1.0 in the DC bin pre-FFT-scaling
    return h.reshape(ntap, nfft).astype(np.float32)


class _BankStore:
    """The process's coefficient banks on the device: a small LRU behind
    :func:`coeff_bank`, its one user.  A bank is a pure function of its
    key, so keeping one is never wrong; ``SIZE`` bounds what a worker that
    sweeps ``nfft`` leaves in HBM (rawspec's three products need three)."""

    SIZE = 8

    def __init__(self):
        self._lock = threading.Lock()  # WorkerPool threads, serve/
        self._banks: "collections.OrderedDict[tuple, jax.Array]" = (
            collections.OrderedDict())

    def get(self, ntap: int, nfft: int, window: str) -> Tuple[jax.Array, bool]:
        """``(bank, hit)``: the bank kept for these, else a new one, kept
        from now on.  The key is what a bank is a function of and the
        device a new array lands on, so two backends or two
        ``jax.default_device`` scopes never share one.  The build runs
        under the lock: threads that ask for one bank at once get one
        build and one array.  A kept bank whose backend was cleared
        (``is_deleted``) is built again."""
        key = (ntap, nfft, window, jax.extend.backend.get_default_device())
        with self._lock:
            bank = self._banks.get(key)
            hit = bank is not None and not bank.is_deleted()
            if not hit:
                bank = self._banks[key] = jnp.asarray(
                    pfb_coeffs(ntap, nfft, window))
            self._banks.move_to_end(key)
            while len(self._banks) > self.SIZE:
                self._banks.popitem(last=False)
            return bank, hit


_BANKS = _BankStore()


def coeff_bank(ntap: int, nfft: int, window: str, timeline) -> jax.Array:
    """The ``(ntap, nfft)`` coefficient bank on the device: :func:`pfb_coeffs`
    built and shipped ONCE A PROCESS (half a second of host arithmetic at
    2^20, with the chip waiting) and the same array from then on.

    The process owns the bank (:class:`_BankStore`); every reduction's
    programs read it and none may DONATE it (``donate_argnames`` are the
    filter state's ``tail`` only): a donated bank would be deleted under
    every later pass.

    The lookup, hit or miss, is the part ``coeffs`` of ``timeline``
    (``calls`` = banks asked for, ``bytes`` = theirs; attr ``nfft``) inside
    whatever stage asked; ``coeffs.hit`` counts the lookups that found
    their bank (0 in a process's first pass)."""
    with timeline.part("coeffs", ntap * nfft * 4) as sp:
        if sp is not None:
            sp.attrs["nfft"] = nfft
        bank, hit = _BANKS.get(ntap, nfft, window)
    timeline.count("coeffs.hit", int(hit))
    return bank


def dequantize(voltages: jax.Array, dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """int8 GUPPI voltages ``(..., 2)`` (re, im) → real/imag float pair.

    Returns separate real and imaginary parts rather than a complex dtype so
    the FIR stage runs real-valued on the VPU/MXU; the FFT stage recombines.
    """
    v = voltages.astype(dtype)
    return v[..., 0], v[..., 1]


def pfb_frontend(
    x: jax.Array,
    coeffs: jax.Array,
) -> jax.Array:
    """Polyphase FIR: frame ``x`` (..., ntime) into windows of ``nfft`` and
    produce tap-weighted frame sums ``(..., nframes, nfft)`` where
    ``nframes = ntime//nfft - ntap + 1``.

    ``ntime`` must be a multiple of ``nfft``.  Works on real or complex
    inputs (applied separately to re/im keeps everything real).
    """
    ntap, nfft = coeffs.shape
    ntime = x.shape[-1]
    if ntime % nfft:
        raise ValueError(f"pfb_frontend: ntime={ntime} not a multiple of nfft={nfft}")
    nblk = ntime // nfft
    nframes = nblk - ntap + 1
    if nframes < 1:
        raise ValueError(f"pfb_frontend: need >= {ntap} blocks of {nfft}, got {nblk}")
    blocks = x.reshape(x.shape[:-1] + (nblk, nfft))
    # ntap is tiny (4): unrolled shifted-slice sum; XLA fuses this into one
    # vectorized pass, no gather needed.
    acc = coeffs[0] * blocks[..., 0:nframes, :]
    for k in range(1, ntap):
        acc = acc + coeffs[k] * blocks[..., k : k + nframes, :]
    return acc


def _four_step_factors(n: int) -> Tuple[int, int]:
    """Split n = n1*n2 with n1, n2 as close as possible (prefer powers of 2)."""
    if n & (n - 1) == 0:  # power of two
        p = n.bit_length() - 1
        n1 = 1 << (p // 2)
        return n1, n // n1
    n1 = int(math.isqrt(n))
    while n % n1:
        n1 -= 1
    return n1, n // n1


def resolve_fft_method(method: str, n: int) -> str:
    """Resolve ``"auto"`` to a concrete FFT strategy for the current backend.

    On the TPU the path is the planar matmul DFT (:mod:`blit.ops.dft`):
    the MXU does the work and the fused Pallas kernels build on it.  On
    CPU/GPU, native complex FFTs win: direct for small N, four-step above.
    """
    if method != "auto":
        return method
    if jax.default_backend() == TPU_BACKEND:
        return "matmul"
    return "direct" if n <= _DIRECT_FFT_MAX else "four_step"


def fft_planar(
    fr: jax.Array,
    fi: jax.Array,
    *,
    method: str = "auto",
    precision=None,
    dtype: str = "float32",
    order: str = "natural",
) -> Tuple[jax.Array, jax.Array]:
    """Planar (re, im) FFT along the last axis — the dispatch point between
    the complex-dtype XLA paths and the TPU matmul-DFT path.

    ``dtype``: working dtype of the matmul-DFT stages ("float32" |
    "bfloat16").  bf16 halves the HBM held by the inter-stage intermediates
    — the lever that lets more frames fit per dispatch (DESIGN.md §3) — at
    a measured spectral accuracy cost comparable to the MXU's default
    bf16-grade multiplies (DESIGN.md §1).  Complex-FFT backends ignore it.

    ``order="twisted"`` (matmul path only) skips the DFT's per-level
    untwist transposes and emits the digit-permuted spectrum that
    :func:`blit.ops.dft.untwist` restores — for order-oblivious consumers
    (power detection) that can untwist their smaller output instead.
    Complex-FFT methods always emit natural order.
    """
    method = resolve_fft_method(method, fr.shape[-1])
    if method == "matmul":
        if dtype != "float32":
            fr = fr.astype(dtype)
            fi = fi.astype(dtype)
        return dftmod.dft(fr, fi, precision=precision, dtype=dtype,
                          order=order)
    # Complex-FFT backends (CPU/GPU) reject bf16 planes: upcast those —
    # the bf16-staged collective paths stay correct off-TPU, they just
    # lose the traffic saving the TPU matmul path keeps.  (Only bf16:
    # f64 planes must keep flowing into a complex128 FFT.)
    if fr.dtype == jnp.bfloat16:
        fr = fr.astype(jnp.float32)
        fi = fi.astype(jnp.float32)
    z = fft(jax.lax.complex(fr, fi), method=method)
    return jnp.real(z), jnp.imag(z)


def fft(z: jax.Array, *, method: str = "auto") -> jax.Array:
    """Complex FFT along the last axis (CPU/GPU paths).

    ``method``:
      - ``"direct"``: one ``jnp.fft.fft`` call.
      - ``"four_step"``: N = N1·N2 decomposition — two batched small FFTs +
        twiddle multiply + transpose.  This keeps every sub-FFT's working set
        VMEM-sized and its batch MXU/VPU-friendly; required for the 1M-point
        hi-res product (SURVEY.md §7 "hard parts").
      - ``"auto"``: direct for N <= 8192, four-step above.
    """
    n = z.shape[-1]
    if method == "auto":
        method = "direct" if n <= _DIRECT_FFT_MAX else "four_step"
    if method == "direct":
        return jnp.fft.fft(z)
    if method != "four_step":
        raise ValueError(f"unknown fft method {method!r}")
    n1, n2 = _four_step_factors(n)
    if n1 == 1:
        return jnp.fft.fft(z)
    # x[n] with n = N2*j1 + j2  →  view (n1, n2): rows index j1.
    x = z.reshape(z.shape[:-1] + (n1, n2))
    # Stage 1: length-N1 FFTs down the columns (axis -2).
    a = jnp.fft.fft(x, axis=-2)
    # Twiddle W_N^{j2*k1}: shape (n1, n2) (k1 rows, j2 cols).
    k1 = np.arange(n1).reshape(n1, 1)
    j2 = np.arange(n2).reshape(1, n2)
    tw = np.exp(-2j * np.pi * (k1 * j2) / n).astype(np.complex64)
    a = a * jnp.asarray(tw)
    # Stage 2: length-N2 FFTs along the rows; X[k1 + N1*k2] = b[k1, k2].
    b = jnp.fft.fft(a, axis=-1)
    return jnp.swapaxes(b, -1, -2).reshape(z.shape)


def _stokes_products(x: Tuple[jax.Array, jax.Array],
                     y: Optional[Tuple[jax.Array, jax.Array]],
                     stokes: str) -> list:
    """The ``nif`` detection products of one spectrum, a plane each:
    ``x`` and ``y`` are the two polarizations' planar ``(re, im)``, any
    shape (everything here is elementwise); ``y`` is ``None`` at one
    polarization, which only supports total power."""
    xr, xi = x
    if y is None:
        if stokes not in ("I", "XX"):
            raise ValueError(f"stokes={stokes!r} needs 2 pols, got 1")
        return [xr**2 + xi**2]
    yr, yi = y
    xx = xr**2 + xi**2
    yy = yr**2 + yi**2
    if stokes == "I":
        return [xx + yy]
    if stokes == "XX":
        return [xx]
    if stokes == "YY":
        return [yy]
    if stokes == "XXYY":
        return [xx, yy]
    # X·conj(Y):
    xy_re = xr * yr + xi * yi
    xy_im = xi * yr - xr * yi
    if stokes == "full":
        return [xx, yy, xy_re, xy_im]
    if stokes == "IQUV":
        return [xx + yy, xx - yy, 2 * xy_re, -2 * xy_im]
    raise ValueError(f"unknown stokes {stokes!r}")


def detect_stokes_planar(
    sr: jax.Array, si: jax.Array, stokes: str
) -> jax.Array:
    """Detect planar spectra (re, im), each (..., npol, nframes, nfft) →
    power products (..., nif, nframes, nfft) float32.

    Products (rawspec conventions, SURVEY.md §0):
      - ``"I"``:    |X|² + |Y|²                       (nif=1)
      - ``"XX"``/``"YY"``: single-pol power           (nif=1)
      - ``"XXYY"``: [|X|², |Y|²]                      (nif=2)
      - ``"full"``: [|X|², |Y|², Re(XY*), Im(XY*)]    (nif=4)
      - ``"IQUV"``: Stokes parameters                 (nif=4)
    Single-pol input only supports total power.
    """
    pols = [(sr[..., p, :, :], si[..., p, :, :])
            for p in range(sr.shape[-3])]
    prods = _stokes_products(pols[0], pols[1] if len(pols) > 1 else None,
                             stokes)
    if len(prods) == 1:
        return prods[0][..., None, :, :]
    return jnp.stack(prods, axis=-3)


def detect_stokes(spec: jax.Array, stokes: str) -> jax.Array:
    """Complex-dtype convenience wrapper over :func:`detect_stokes_planar`
    (CPU/GPU callers; the TPU path stays planar throughout)."""
    return detect_stokes_planar(jnp.real(spec), jnp.imag(spec), stokes)


def integrate(power: jax.Array, nint: int) -> jax.Array:
    """Sum groups of ``nint`` consecutive frames (axis -2)."""
    if nint <= 1:
        return power
    nframes = power.shape[-2]
    if nframes % nint:
        raise ValueError(f"integrate: nint={nint} does not divide nframes={nframes}")
    shape = power.shape[:-2] + (nframes // nint, nint, power.shape[-1])
    return power.reshape(shape).sum(axis=-2)


# Rows of a vector register's (8, 128) tile: the coarse channels the XLA
# path and :func:`channelize_lanes` lay side by side on the sublanes.
_SUBLANES = 8

# Kernel resolution of the most recent channelize trace (see the
# assignment inside channelize; read via last_kernel_plan()).
_LAST_PLAN: dict = {}


def last_kernel_plan() -> dict:
    """The kernel plan the most recent :func:`channelize` TRACE resolved
    ('auto' dispatch made concrete: which pallas fusions ran).  Empty until
    a trace happens; a jit cache hit does not refresh it."""
    return dict(_LAST_PLAN)


# The keyword arguments that select a program (:func:`channelize`'s, and
# :func:`channelize_stream`'s, which passes them through).
_CHANNELIZE_STATIC = (
    "nfft", "ntap", "nint", "stokes", "fft_method", "precision",
    "channel_block", "dtype", "fqav_by", "dft_order", "pfb_kernel",
    "detect_kernel", "tail_kernel",
)


def _resolve_pfb_kernel(pfb_kernel: str, *, nfft: int, nblk: int, ntap: int,
                        npol: int, resolved: str, twisted: bool,
                        dtype: str) -> str:
    """:func:`channelize`'s ``pfb_kernel`` made concrete for a block of
    ``nblk`` blocks of ``nfft`` samples: ``"xla"`` | ``"pallas"`` |
    ``"fused1"`` (``resolved``: the FFT method, ``twisted``: its order)."""
    if pfb_kernel not in ("auto", "xla", "pallas", "fused1"):
        raise ValueError(f"bad pfb_kernel {pfb_kernel!r}")
    backend = jax.default_backend()
    pol_ok = npol == 2
    if pfb_kernel == "auto":
        from blit.ops import pallas_pfb

        # Prefer the fullest fusion that compiles natively AND fits the
        # VMEM budget: fused1 (dequant+PFB+DFT stage 1; interleaved A/B
        # 8.3-8.7 vs 6.4 GB/s) → pallas (dequant+PFB) → xla.  Large-
        # nframes chunks (e.g. the '0002' preset) exceed any fine tile
        # and take the XLA path.
        pfb_kernel = "xla"
        if backend == TPU_BACKEND and pol_ok:
            # default_factors only inside the matmul guard: the FFT paths
            # accept nfft values it cannot factor.
            factors = (
                dftmod.default_factors(nfft) if resolved == "matmul" else ()
            )
            if (
                len(factors) >= 2
                and not twisted  # fused1 ignores dft_order='twisted'
                and pallas_pfb.fused1_fits(
                    nfft, nblk, ntap, factors[0], dtype
                )
            ):
                pfb_kernel = "fused1"
            elif pallas_pfb.fits(nfft, nblk, ntap, dtype):
                pfb_kernel = "pallas"
    elif pfb_kernel in ("pallas", "fused1"):
        if not pol_ok:
            raise ValueError(
                f"pfb_kernel={pfb_kernel!r} needs npol=2 complex int8"
            )
        pallas_interpret(backend)  # raises off TPU/CPU
        if pfb_kernel == "fused1":
            if resolved != "matmul":
                raise ValueError(
                    "pfb_kernel='fused1' fuses the matmul-DFT's first "
                    "stage; it needs fft_method='matmul'"
                )
            if len(dftmod.default_factors(nfft)) < 2:
                raise ValueError(
                    "pfb_kernel='fused1' needs a multi-factor nfft "
                    f"(> {dftmod.DIRECT_DFT_MAX})"
                )
            if twisted:
                raise ValueError(
                    "pfb_kernel='fused1' emits natural order; it does not "
                    "combine with dft_order='twisted'"
                )
    return pfb_kernel


@functools.partial(jax.jit, static_argnames=_CHANNELIZE_STATIC)
def channelize(
    voltages: jax.Array,
    coeffs: jax.Array,
    *,
    nfft: int,
    ntap: int = 4,
    nint: int = 1,
    stokes: str = "I",
    fft_method: str = "auto",
    precision: Optional[str] = None,
    channel_block: int = 0,
    dtype: str = "float32",
    fqav_by: int = 1,
    dft_order: str = "auto",
    pfb_kernel: str = "auto",
    detect_kernel: str = "auto",
    tail_kernel: str = "auto",
) -> jax.Array:
    """The full single-chip reduction: int8 voltage block → filterbank slab.

    Args:
      voltages: int8 ``(nchan_coarse, ntime, npol, 2)`` (GuppiRaw.read_block
        layout, blit/io/guppi.py) with ``ntime`` a multiple of ``nfft`` and
        ``ntime//nfft >= ntap + nint - 1`` — or the same memory as
        :func:`sample_words`, ``(nchan_coarse, ntime)`` with one int32
        (int16 at one polarization) per time sample — or a tuple of such
        runs of words, each whole blocks of ``nfft``, consecutive in time
        (a stream's filter state and its new samples).  Every form gives
        the same bits: words are the only form inside the program (the
        XLA path and both Pallas fronts read them where they lie; int8
        becomes words by a bitcast, :func:`_samples_as_words`), and a
        tuple's runs are put end to end only where a front needs ONE run
        (``fused1`` takes them as they are, an operand each).
      coeffs: ``(ntap, nfft)`` PFB prototype from :func:`pfb_coeffs`.
      nfft: fine channels per coarse channel (the rawspec product size; 2**20
        for the hi-res product).
      nint: spectra integrated per output sample.
      stokes: detection product (see :func:`detect_stokes_planar`).
      fft_method: "auto" | "direct" | "four_step" | "matmul" (see
        :func:`resolve_fft_method`; "auto" picks "matmul" on TPU).
      precision: matmul precision for the "matmul" path — None (backend
        default; bf16-grade multiplies on the MXU) or "highest" (full f32,
        ~3x the MXU passes).
      channel_block: if > 0 and < nchan, process coarse channels in groups
        of this size via ``lax.map`` *inside* one device program — large
        per-dispatch work (amortizing dispatch latency) at bounded peak HBM
        (the hi-res 1M-point intermediates are what overflow otherwise).
      dtype: working dtype from dequantization through the FFT stages
        ("float32" | "bfloat16").  bfloat16 halves the HBM every
        intermediate occupies — the f32 dequant/PFB planes were the peak
        residents — fitting ~2x the frames per dispatch; int8 voltages
        carry exactly bf16's 8 mantissa bits, and the detected powers
        still accumulate in float32 (the MXU truncates matmul products to
        bf16 grade by default anyway).  Measured accuracy: DESIGN.md §8.
      fqav_by: on-device frequency-averaging epilogue — sum every
        ``fqav_by`` consecutive fine channels (reference ``fqav`` default-f
        semantics, src/gbtworkerfunctions.jl:16-20) before anything leaves
        the chip, shrinking the product (and any host readback) by that
        factor.  Callers must map the channel axis with
        :func:`blit.ops.fqav.fqav_range`.

    Where things lie in the XLA path (DESIGN.md §3; the nested
    ``words_core``): 8 coarse channels on the sublanes, a block's ``nfft``
    points on the lanes, the blocks down a major axis — words tiled (8
    channels x 128 samples) ARE rows ``(nchan/8, nblk*8, nfft)`` tiled
    over their last two axes, so nothing moves before the filter, a tap
    is a run of whole tiles, the integration sums a major axis and the
    product is put together by moving major axes only.

    Returns:
      float32 ``(ntime_out, nif, nchan_coarse*nfft)`` in blit's canonical
      ``(time, pol, chan)`` layout — channel fastest, fine channels fftshifted
      within each coarse channel so the DC artifact sits at fine index
      ``nfft//2`` (despike parity, blit/ops/despike.py).
    """
    if isinstance(voltages, (tuple, list)):  # runs of words, end to end
        parts = tuple(voltages)
    elif voltages.ndim == 2:  # sample_words: one word per time sample
        parts = (voltages,)
    else:
        if voltages.shape[-1] != 2:
            raise ValueError(
                f"channelize: (re, im) pairs, got {voltages.shape[-1]}")
        parts = (_samples_as_words(voltages),)
    nchan = parts[0].shape[0]
    ntime = sum(p.shape[1] for p in parts)
    npol = parts[0].dtype.itemsize // 2
    if any(p.shape[1] % nfft for p in parts):
        raise ValueError(
            f"channelize: runs of {[p.shape[1] for p in parts]} samples "
            f"are not whole blocks of {nfft}")
    if precision == "highest":
        prec = jax.lax.Precision.HIGHEST
    elif precision is None:
        prec = None
    else:
        raise ValueError(f"precision must be None or 'highest', got {precision!r}")
    if nfft % 2:
        raise ValueError("channelize: nfft must be even")
    # Fold the fftshift into the window via the shift theorem: multiplying
    # the DFT input by (-1)^j rolls the spectrum by nfft/2, so the shifted
    # coefficients make the FFT emit fftshifted order directly — two fewer
    # full-array HBM passes.  (Frame sample index ≡ j mod 2 because nfft is
    # even, so the sign pattern is tap-independent.)
    sign = jnp.asarray(
        np.where(np.arange(nfft) % 2 == 0, 1.0, -1.0).astype(np.float32)
    )
    shifted_coeffs = coeffs * sign[None, :]

    if dtype not in ("float32", "bfloat16"):
        raise ValueError(f"dtype must be float32 or bfloat16, got {dtype!r}")
    if fqav_by > 1 and nfft % fqav_by:
        # nchan*nfft divisibility alone would let averaging groups straddle
        # coarse-channel boundaries, corrupting nfpc-keyed consumers.
        raise ValueError(f"fqav_by={fqav_by} does not divide nfft={nfft}")

    # bf16 mode applies from dequantization on: the int8 voltages carry 8
    # significant bits, exactly bf16's mantissa, so the dequant planes and
    # the 4-tap PFB lose nothing material in half-width — and the f32
    # dequant/PFB intermediates were the peak-HBM residents that capped
    # frames-per-dispatch (the gross (ntap-1+frames)/frames factor makes
    # them BIGGER than the DFT intermediates).  Accuracy is pinned by
    # tests/test_channelize.py::test_bfloat16_stage_dtype_close_to_golden.
    work_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    wcoeffs = shifted_coeffs.astype(work_dtype)

    # dft_order: "twisted" runs the matmul DFT in digit-permuted order
    # (skipping its per-level transposes; detection is elementwise so the
    # permutation rides through free) and untwists ONCE on the detected
    # power.  Analytically that saves one full pass of traffic — but the
    # interleaved A/B on the chip measured it ~20% SLOWER (4.08 vs
    # 5.06 GB/s at the bf16 bench config): the reversed multi-axis power
    # transpose lowers worse than the two spectra swapaxes XLA fuses.
    # "auto" therefore = "natural"; the twisted path stays as a verified-
    # correct tuning knob (see DESIGN.md §9).
    if dft_order not in ("auto", "twisted", "natural"):
        raise ValueError(f"bad dft_order {dft_order!r}")
    resolved = resolve_fft_method(fft_method, nfft)
    twisted = resolved == "matmul" and dft_order == "twisted"

    # pfb_kernel: "pallas" fuses dequant + FIR into one VMEM-resident pass
    # (blit/ops/pallas_pfb.py — the fix for the roofline's dominant stage,
    # DESIGN.md §9): the int8 voltages are read once and the gross
    # dequantized planes never exist in HBM.  Interleaved A/B on the chip:
    # pallas 5.9-6.3 vs xla 4.86 GB/s end-to-end at the bf16 bench config,
    # so "auto" = pallas on the TPU and the jnp path elsewhere
    # (interpret-mode pallas is for the CPU tests only).  The kernel needs
    # npol=2 int8 input; other shapes fall back.
    backend = jax.default_backend()
    pfb_kernel = _resolve_pfb_kernel(
        pfb_kernel, nfft=nfft, nblk=ntime // nfft, ntap=ntap, npol=npol,
        resolved=resolved, twisted=twisted, dtype=dtype)
    use_pallas_pfb = pfb_kernel == "pallas"
    use_fused1 = pfb_kernel == "fused1"
    interp = (use_pallas_pfb or use_fused1) and pallas_interpret(backend)

    # Tail/detect kernel resolution.  Three pallas surfaces cover the
    # pipeline after the fused1 front (each measured on the chip,
    # DESIGN.md §9):
    #
    # - COMBINED tail+detect (blit/ops/pallas_detect.tail2_detect,
    #   ``use_td``): DFT levels 2+3, the inner untwist, the detection
    #   product (any detect_stokes_planar product — the pol pair is
    #   block-resident), and (up to one XLA lane swap) the product
    #   transpose in ONE pass — the bf16 tail spectra never exist in HBM.
    #   Interleaved A/B at the production config: 15.1-16.7 vs
    #   9.9-11.0 GB/s (+48%) — "auto" prefers it whenever eligible.
    # - tail-only (blit/ops/pallas_dft.dft_tail2, ``use_pallas_tail``):
    #   levels 2+3 + inner untwist, XLA detect.  A/B: +15% over the XLA
    #   tail — the fallback when the combined kernel's output planes
    #   exceed VMEM.
    # - detect-only (blit/ops/pallas_detect.detect_untwist_i,
    #   ``use_pallas_detect``): twisted XLA tail, fused detect+untwist.
    #   A/B: parity — a verified-correct opt-in tuning surface.
    if detect_kernel not in ("auto", "xla", "pallas"):
        raise ValueError(f"bad detect_kernel {detect_kernel!r}")
    if tail_kernel not in ("auto", "xla", "pallas"):
        raise ValueError(f"bad tail_kernel {tail_kernel!r}")
    detect_eligible = td_eligible = tail_eligible = False
    if use_fused1:
        from blit.ops import pallas_detect
        from blit.ops.pallas_dft import tail2_fits

        _kw = dict(
            npol=npol,
            esize=2 if dtype == "bfloat16" else 4,
        )
        _factors = dftmod.default_factors(nfft)
        # detect_untwist_i is Stokes-I only; tail2_detect covers every
        # detect_stokes_planar product (the pol pair is block-resident).
        detect_eligible = stokes == "I" and pallas_detect.fits(
            _factors, **_kw)
        td_eligible = pallas_detect.tail2_detect_fits(
            _factors[:1] + tuple(sorted(_factors[1:])), stokes=stokes,
            **_kw)
        _nframes = ntime // nfft - ntap + 1
        tail_eligible = (
            len(_factors) == 3
            and tail2_fits(
                nchan * npol * _nframes * _factors[0],
                _factors[1], _factors[2], dtype,
            )
        )

    use_td = (
        td_eligible and detect_kernel != "xla" and tail_kernel != "xla"
    )
    if detect_kernel == "pallas" and tail_kernel == "pallas" and not use_td:
        raise ValueError(
            "tail_kernel='pallas' with detect_kernel='pallas' (the fused "
            "tail+detect) needs pfb_kernel='fused1', a known stokes "
            "product, exactly 3 DFT factors, and the nif output planes "
            "inside the VMEM budget"
        )
    use_pallas_detect = (
        not use_td and detect_kernel == "pallas" and detect_eligible
    )
    if detect_kernel == "pallas" and not (use_td or use_pallas_detect):
        raise ValueError(
            "detect_kernel='pallas' (without tail_kernel='pallas') needs "
            "pfb_kernel='fused1', stokes='I', <= 3 DFT factors, and "
            "factor sizes inside the VMEM budget"
        )
    use_pallas_tail = (
        not use_td and not use_pallas_detect
        and tail_kernel != "xla" and tail_eligible
    )
    if tail_kernel == "pallas" and not (use_td or use_pallas_tail):
        raise ValueError(
            "tail_kernel='pallas' needs pfb_kernel='fused1', exactly 3 "
            "DFT factors, and panel sizes inside the VMEM budget"
        )

    # Record what "auto" resolved to — 'auto' silently upgraded to the
    # fused kernels in round 3, so output diffs against older runs must be
    # attributable (ADVICE r3).  Trace-time only: a jit cache hit does not
    # re-run this body, so the record describes the most recent TRACE
    # (`blit reduce` prints it as `kernel_plan`).
    _LAST_PLAN.clear()
    _LAST_PLAN.update(
        fft_method=resolved,
        pfb_kernel=pfb_kernel,
        tail_kernel=("tail2_detect" if use_td
                     else "dft_tail2" if use_pallas_tail else "xla"),
        detect_kernel=("tail2_detect" if use_td
                       else "detect_untwist_i" if use_pallas_detect
                       else "xla"),
        dft_order="twisted" if twisted else "natural",
        dtype=dtype,
    )

    def core(v):
        if use_fused1:
            # dequant + PFB + DFT stage 1 in one pallas pass; the frame
            # planes never hit HBM.  Remaining factors + natural-order
            # assembly via dft_tail, then detect as usual.
            from blit.ops.pallas_pfb import pfb_dft1

            factors = dftmod.default_factors(nfft)
            n1 = factors[0]
            w1r, w1i = (jnp.asarray(a)
                        for a in dftmod.dft_matrices(n1, "float32"))
            t1r, t1i = (jnp.asarray(a)
                        for a in dftmod.twiddles(n1, nfft // n1, "float32"))
            ur, ui = pfb_dft1(
                v, shifted_coeffs, w1r, w1i, t1r, t1i, dtype=dtype,
                interpret=interp,
            )
            if use_td:
                from blit.ops.pallas_detect import tail2_detect

                # Whole remaining pipeline — tail levels, untwist, detect,
                # product transpose — in one pass; power arrives frame-
                # major in the product layout.
                # The remaining levels with the LARGER factor last: the
                # stage-1 rows are read as (f2, f3) panels, f3 on the
                # lanes, and 2^20 = 128 x (64 x 128) fills them where
                # 128 x (128 x 64) half-fills them and makes XLA re-tile
                # both planes in between (PERF.md section 6, PR 46).
                f2, f3 = sorted(factors[1:])
                power = tail2_detect(
                    ur, ui, f2, f3, stokes=stokes, interpret=interp,
                )  # (nframes, nif, cb, nfft)
                if nint > 1:
                    if power.shape[0] % nint:
                        raise ValueError(
                            f"integrate: nint={nint} does not divide "
                            f"nframes={power.shape[0]}"
                        )
                    power = power.reshape(
                        (power.shape[0] // nint, nint) + power.shape[1:]
                    ).sum(axis=1)
                return power  # (ntime_out, nif, cb, nfft)
            if use_pallas_detect:
                from blit.ops.pallas_detect import detect_untwist_i

                # Remaining factors in twisted order (no transposes);
                # the detect kernel untwists while it detects.
                vr, vi = dftmod.dft_tail(
                    ur, ui, factors, precision=prec, dtype=dtype,
                    order="twisted",
                )
                power = detect_untwist_i(vr, vi, factors, interpret=interp)
                # (cb, frames, nfft) → (cb, nif=1, t, nfft)
                return integrate(power, nint)[:, None]
            if use_pallas_tail:
                from blit.ops.pallas_dft import dft_tail2

                # Fused levels 2+3 (+ inner untwist) → natural-m panels;
                # only the level-0 untwist remains.
                vr, vi = dft_tail2(
                    ur, ui, factors[1], factors[2], dtype=dtype,
                    interpret=interp,
                )
                bshape = ur.shape[:3]
                sr = jnp.swapaxes(vr, -1, -2).reshape(bshape + (nfft,))
                si = jnp.swapaxes(vi, -1, -2).reshape(bshape + (nfft,))
            else:
                sr, si = dftmod.dft_tail(
                    ur, ui, factors, precision=prec, dtype=dtype
                )
            if sr.dtype != jnp.float32:
                sr, si = sr.astype(jnp.float32), si.astype(jnp.float32)
            power = detect_stokes_planar(sr, si, stokes)
            return integrate(power, nint)
        if not use_pallas_pfb:
            return words_core(jnp.concatenate(v, axis=1))
        from blit.ops.pallas_pfb import pfb_dequant

        fr, fi = pfb_dequant(
            v, shifted_coeffs, dtype=dtype, interpret=interp,
        )  # (cb, npol, nframes, nfft)
        sr, si = fft_planar(
            fr, fi, method=fft_method, precision=prec, dtype=dtype,
            order="twisted" if twisted else "natural",
        )
        if sr.dtype != jnp.float32:
            # Detect + integrate accumulate in f32 (the cast fuses into the
            # detect kernel; only the DFT intermediates stay half-width).
            sr, si = sr.astype(jnp.float32), si.astype(jnp.float32)
        power = detect_stokes_planar(sr, si, stokes)  # (cb, nif, frames, nfft)
        power = integrate(power, nint)  # (cb, nif, ntime_out, nfft)
        if twisted:
            power = dftmod.untwist(power, dftmod.default_factors(nfft))
        return power

    def words_core(words):
        """The XLA path: ``(cb, T)`` words → power ``(cg, ntime_out, nif,
        c, nfft)``, channel ``g * c + j`` at ``[g, :, :, j]``.

        What the filter slices and what the integration sums lie on a
        MAJOR axis; the two axes a vector register tiles hold what every
        operation is elementwise over — ``c`` coarse channels (8, a
        register's sublanes, where 8 divides a larger ``cb``; else all of
        them) and the ``nfft`` points of a block.  The words arrive tiled
        (8 channels x 128 samples), in memory ``(cb/8, T/128; 8, 128)``;
        with ``T = nblk * nfft`` and ``nfft`` a multiple of 128 that is,
        letter for letter, the memory of ROWS ``(cg, nblk * c, nfft)``
        tiled over their last two axes — a block's ``c`` channels one
        after the other, block after block — so no sample moves before
        the filter reads it, tap ``k`` of every frame is the rows from
        ``k * c`` on (whole tiles, at any ``k``), and the float32 planes
        of all ``nblk`` blocks are never written: each tap widens its own
        slice of the words.  Blocks and channels are ONE axis on purpose:
        given ``(cg, nblk, c, nfft)`` the v5e's compiler puts the blocks
        back on the sublanes (a copy of the words and a filter of
        misaligned sublane slices); an axis it cannot split it cannot
        re-tile.  (Frames on the sublanes, as ``(cb, npol, nblk, nfft)``
        has them, cost two re-tiling passes over the float32 samples
        besides: 69 % of the chip's seconds at ``nfft`` 1024, PERF.md
        section 6, PR 37.)"""
        cb, nsamp = words.shape
        c = _SUBLANES if cb > _SUBLANES and cb % _SUBLANES == 0 else cb
        cg = cb // c
        nblk = nsamp // nfft
        if nsamp % nfft or nblk < ntap:
            raise ValueError(
                f"channelize: {nsamp} samples are not >= {ntap} whole "
                f"blocks of {nfft}")
        nframes = nblk - ntap + 1
        if nframes % nint:
            raise ValueError(
                f"integrate: nint={nint} does not divide nframes={nframes}")
        rows = jnp.transpose(words.reshape(cg, c, nblk, nfft),
                             (0, 2, 1, 3)).reshape(cg, nblk * c, nfft)
        bits = 8 * words.dtype.itemsize

        def plane(byte):
            """Byte ``byte`` of every word (0: the first polarization's
            real part), sign-extended, through the filter: ``(cg,
            nframes * c, nfft)``."""
            def tap(k):
                x = jax.lax.shift_right_arithmetic(
                    jax.lax.shift_left(
                        rows[:, k * c:(k + nframes) * c],
                        jnp.asarray(bits - 8 - 8 * byte, words.dtype)),
                    jnp.asarray(bits - 8, words.dtype))
                return wcoeffs[k] * x.astype(work_dtype)

            acc = tap(0)
            for k in range(1, ntap):
                acc = acc + tap(k)
            return acc

        # A transform per polarization: stacked into one the four matmuls'
        # outputs of both are alive at once (temporaries 4.5 GiB against
        # 3.0 at bank.lowres's shape, the compiler's account for a v5e).
        pols = []
        for pol in range(npol):
            sr, si = fft_planar(
                plane(2 * pol), plane(2 * pol + 1), method=fft_method,
                precision=prec, dtype=dtype,
                order="twisted" if twisted else "natural",
            )
            # Detect + integrate accumulate in f32 (only the DFT
            # intermediates stay half-width).
            pols.append((sr.astype(jnp.float32), si.astype(jnp.float32)))
        power = jnp.stack([
            p.reshape(cg, nframes // nint, nint, c, nfft).sum(axis=2)
            if nint > 1 else p.reshape(cg, nframes, c, nfft)
            for p in _stokes_products(
                pols[0], pols[1] if npol > 1 else None, stokes)], axis=2)
        if twisted:
            power = dftmod.untwist(power, dftmod.default_factors(nfft))
        return power  # (cg, ntime_out, nif, c, nfft)

    use_words = pfb_kernel == "xla"
    if channel_block and channel_block < nchan:
        if nchan % channel_block:
            raise ValueError(
                f"channel_block={channel_block} does not divide nchan={nchan}"
            )
        groups = tuple(
            p.reshape(nchan // channel_block, channel_block, p.shape[1])
            for p in parts)
        power = jax.lax.map(core, groups)
        if use_td:
            # (g, t, nif, cb, nfft): channel-major assembly — one
            # transpose of the (already detected) power, the blocked
            # mode's price.
            power = jnp.moveaxis(power, 0, 2)  # (t, nif, g, cb, nfft)
        else:  # the groups' leading axes (channels, or slabs of them)
            power = power.reshape((-1,) + power.shape[2:])
    else:
        power = core(parts)
    if use_words:
        # (cg, t, nif, c, nfft) → (t, nif, cg, c, nfft): major axes only.
        power = jnp.transpose(power, (1, 2, 0, 3, 4))
    if use_td or use_words:
        # The product layout but for the channel axes, (t, nif, ...,
        # nfft): flatten them into place.
        out = power.reshape(power.shape[0], power.shape[1], nchan * nfft)
    else:
        # → (ntime_out, nif, nchan*nfft), channel fastest.
        out = jnp.transpose(power, (2, 1, 0, 3))
        out = out.reshape(out.shape[0], out.shape[1], nchan * nfft)
    if fqav_by > 1:
        out = _fqav(out, fqav_by)
    return out


def _word_dtype(npol: int, ncomp: int = 2) -> np.dtype:
    """The signed integer as wide as one time sample (``npol`` x (re, im)
    int8): int32 at two polarizations, int16 at one."""
    return np.dtype(f"i{npol * ncomp}")


def sample_words(voltages: np.ndarray) -> np.ndarray:
    """Host int8 voltages ``(nchan, ntime, npol, 2)`` as ``(nchan, ntime)``
    machine words, one per time sample (int32 at two polarizations, int16
    at one): a VIEW of the same memory, the form a stream's samples cross
    the host link in (:func:`channelize_stream`).

    Why a view and not the array itself: the TPU runtime re-tiles what it
    is handed on the host, and an int8 array whose minor dimensions are
    ``(2, 2)`` costs it far more than the words it is made of — on a v5e
    a 32-channel x 8-frame hi-res group (1.07 GB, ``ntime`` 2^23) goes up
    in 0.53 s and 4.3 cpu-s as int8 ``(32, 2^23, 2, 2)``, in 0.10 s and
    0.17 cpu-s as int32 ``(32, 2^23)``; the 11-frame group the reducer
    sent until PR 29 in 0.29 s against 0.15 (PERF.md section 6, PR 29)."""
    nchan, ntime, npol, ncomp = voltages.shape
    return voltages.reshape(nchan, ntime, npol * ncomp).view(
        _word_dtype(npol, ncomp))[..., 0]


def _samples_as_words(voltages: jax.Array) -> jax.Array:
    """:func:`sample_words` in a program: int8 ``(nchan, ntime, npol, 2)``
    → ``(nchan, ntime)`` words (a bitcast: byte 0 of a word is the first
    polarization's real part, as on the host)."""
    nchan, ntime, npol, ncomp = voltages.shape
    return jax.lax.bitcast_convert_type(
        voltages.reshape(nchan, ntime, npol * ncomp),
        _word_dtype(npol, ncomp))


def stream_step(
    tail: jax.Array, body: jax.Array, coeffs: jax.Array, **kw
) -> Tuple[jax.Array, jax.Array]:
    """One chip's step of a STREAM, to be traced into a program that
    donates ``tail``: ``concat(tail, body)`` reduced exactly as
    :func:`channelize` (kwargs ``kw``) reduces that gross block, and the
    filter state the next step of the same channels starts from.

    Both are :func:`sample_words` of voltages: ``tail`` ``(nchan,
    (ntap-1)*nfft)`` is the last ``ntap - 1`` frames' worth of samples
    before ``body`` ``(nchan, frames*nfft)``, the stream's new samples.
    Returns ``(product, next_tail)``: ``next_tail`` is the last
    ``(ntap-1)*nfft`` words of the concatenation (a body shorter than the
    filter state keeps part of the old tail by the same line).  The ONE
    body of the reducer's :func:`channelize_stream` and of the mesh's
    per-chip :func:`blit.parallel.mesh.band_stream`."""
    return _gross_step((tail, body), coeffs, **kw)


def head_step(words: jax.Array, coeffs: jax.Array, **kw
              ) -> Tuple[jax.Array, jax.Array]:
    """One chip's FIRST step of a leg whose filter state is shorter than
    the stream's head: ``words`` ``(nchan, head)`` holds the leg's
    ``(ntap-1)*nfft`` words of state and, after them, samples that are
    its data.  Returns ``(product, next_tail)`` as :func:`stream_step`
    does.  The body of :func:`leg_programs`' ``head`` and of the mesh's
    per-chip :func:`blit.parallel.mesh.band_programs`."""
    return _gross_step((words,), coeffs, **kw)


def _span(parts: Sequence[jax.Array], start: int, stop: int
          ) -> Tuple[jax.Array, ...]:
    """Words ``[start, stop)`` of ``parts`` put end to end, as the runs
    they lie in: slices of the parts, nothing joined."""
    out, at = [], 0
    for p in parts:
        lo, hi = max(start - at, 0), min(stop - at, p.shape[1])
        if lo < hi:
            out.append(p if (lo, hi) == (0, p.shape[1]) else p[:, lo:hi])
        at += p.shape[1]
    return tuple(out)


def _gross_step(parts: Tuple[jax.Array, ...], coeffs: jax.Array, *,
                frames: Optional[int] = None, lanes: int = 0, **kw):
    """``parts`` end to end, the words of a filter state and the samples
    after it, reduced to their first ``frames`` frames (all they hold, by
    default) and the filter state the frame after them starts from.
    Nothing here joins them: :func:`channelize` takes the runs as they
    are, and so does the small-``nfft`` path (``lanes`` > 0:
    :func:`channelize_lanes`, blocks of that many words; it joins the
    parts itself, a few channels at a time)."""
    nfft, state = kw["nfft"], (kw.get("ntap", 4) - 1) * kw["nfft"]
    if frames is None:
        frames = (sum(p.shape[1] for p in parts) - state) // nfft
    used = frames * nfft
    if lanes:
        power = channelize_lanes(
            parts, coeffs, nfft=nfft, ntap=kw.get("ntap", 4), block=lanes,
            frames=frames, stokes=kw.get("stokes", "I"))
    else:
        power = channelize(_span(parts, 0, used + state), coeffs, **kw)
    return power, jnp.concatenate(_span(parts, used, used + state), axis=1)


_STREAM_STATIC = _CHANNELIZE_STATIC + ("frames", "lanes")


@functools.lru_cache(maxsize=None)
def leg_programs(name: str):
    """``(step, head)``, the two programs of one leg of a stream, under
    ``jit_<name>`` in a device trace (the trace names programs: a leg
    whose seconds are to be read apart has a name of its own).

    ``step(tail, body, coeffs, *, frames=None, lanes=0, **kw)`` is one
    dispatch: :func:`stream_step` of the first ``frames`` frames (all, by
    default) of ``concat(tail, body)``, with ``tail`` DONATED — the next
    tail takes its place in device memory, so a group's filter state is
    held once, and a device array passed as ``tail`` is deleted by the
    call.  ``head(words, coeffs, **same)`` is a stream's first: ``words``
    holds the leg's filter state and, after it, samples the stream's head
    has beyond it (a leg whose ``nfft`` is not the largest: the head is
    the largest's); it is not donated, the other legs read it too."""

    def step(tail, body, coeffs, **kw):
        return stream_step(tail, body, coeffs, **kw)

    def head(words, coeffs, **kw):
        return head_step(words, coeffs, **kw)

    for fn in (step, head):
        fn.__name__ = fn.__qualname__ = name
    return (jax.jit(step, static_argnames=_STREAM_STATIC,
                    donate_argnames=("tail",)),
            jax.jit(head, static_argnames=_STREAM_STATIC))


# One dispatch of a STREAM (:func:`stream_step` as a program of its own):
# the product of ``concat(tail, body)`` and the next tail, device-resident
# data the next dispatch consumes like :func:`integrate_carry`'s
# accumulator — a stream's filter state crosses the host link once, as its
# head.  ``tail`` is DONATED (:func:`leg_programs`).
channelize_stream = leg_programs("channelize_stream")[0]


def _fft_halves(xs: list) -> Tuple[list, list]:
    """The last butterfly stage of :func:`_fft_planes`, not yet added up:
    ``(e, t)``, ``len(xs) // 2`` planar ``(re, im)`` planes each, with
    bin ``k`` of the transform ``e[k] + t[k]`` and bin ``k + len(xs) //
    2`` ``e[k] - t[k]``."""
    n = len(xs)
    even, odd = _fft_planes(xs[0::2]), _fft_planes(xs[1::2])
    twiddled = []
    for k, (dr, di) in enumerate(odd):
        if k == 0:
            tr, ti = dr, di
        elif 4 * k == n:  # times -i
            tr, ti = di, -dr
        else:
            wr = np.float32(math.cos(2 * math.pi * k / n))
            wi = np.float32(-math.sin(2 * math.pi * k / n))
            tr, ti = dr * wr - di * wi, dr * wi + di * wr
        twiddled.append((tr, ti))
    return even, twiddled


def _fft_planes(xs: list) -> list:
    """Radix-2 FFT of ``len(xs)`` (a power of two) planar ``(re, im)``
    planes, each any shape: the transform runs ACROSS the list, every
    plane elementwise — for an ``nfft`` far below a vector's width, where
    the frames and not the channels fill the lanes."""
    if len(xs) == 1:
        return xs
    even, twiddled = _fft_halves(xs)
    return ([(er + tr, ei + ti)
             for (er, ei), (tr, ti) in zip(even, twiddled)]
            + [(er - tr, ei - ti)
               for (er, ei), (tr, ti) in zip(even, twiddled)])


# Coarse channels :func:`channelize_lanes` works on at a time.
_LANES_CHANNELS = _SUBLANES


def lanes_block(nfft: int, nint: int, npol: int = 2, ntap: int = 4, *,
                fqav_by: int = 1, dtype: str = "float32") -> int:
    """Words per block of :func:`channelize_lanes` for a carried product
    ``(nfft, nint)``, or 0 where that path does not serve it: one
    integration's samples, ``nint * nfft``, where ``nfft`` is a power of
    two too small to fill a vector's 128 lanes, the block is whole vectors
    and holds the filter state (float32 spectra of two polarizations, not
    frequency-averaged).  The shape alone decides, on every backend."""
    small = nfft < 128 and nfft & (nfft - 1) == 0
    block = nint * nfft
    return block if (small and npol == 2 and block % 128 == 0
                     and nint >= ntap - 1 and fqav_by == 1
                     and dtype == "float32") else 0


def channelize_lanes(
    parts: Sequence[jax.Array], coeffs: jax.Array, *, nfft: int, ntap: int,
    block: int, frames: int, stokes: str = "I",
) -> jax.Array:
    """The channelizer for a SMALL ``nfft`` (rawspec's ``-f 8``), frames
    on the lane axis.  :func:`channelize` lays a block out ``(...,
    frames, nfft)``: at ``nfft`` 8 every float32 intermediate of a
    2^23-sample group is padded 16 times over on a 128-lane machine.
    Nor may the ``nfft`` bins be the second-minor axis, which a vector's
    8 sublanes tile: the FFT runs ACROSS the bins, so every input and
    every result would be one sublane of each tile, and putting the
    results side by side copies them a sublane at a time (on a v5e half
    the program's seconds, PERF.md section 6, PR 35).

    ``parts`` are consecutive runs of int32 words (:func:`sample_words`
    at two polarizations; a filter state and the samples after it, say)
    that make ``gross`` ``(cb, (ntap-1)*nfft + samples)`` when put end to
    end, which is done a few channels at a time and never as a whole
    (1 GiB a group at the reducer's shape).  The samples are cut
    into blocks of ``block = m * nfft`` words (``m`` frames; the caller's
    ``nint``) and ONE transpose of the words puts the blocks on the lanes
    and the coarse channels on the sublanes: ``(cb, groups, block) ->
    (block, cb, groups)``.  After it the words of a block run down MAJOR
    axes, ``(m + ntap - 1, nfft, cb, groups)``: a frame's tap ``k`` is a
    slice ``k`` rows down (the first ``ntap-1`` frames of the next block
    ride below each block's own), the ``nfft`` points of the FFT are
    ``nfft`` planes of whole ``(cb, groups)`` tiles
    (:func:`_fft_planes`), every operation is elementwise over them, and
    the bins are detected a pair at a time (``k`` and ``k + nfft/2``, the
    two ends of one butterfly): only the power is ever put together, bin
    after bin on a major axis.

    Returns the power of frame ``g * m + p`` of channel ``i * c + j`` at
    ``[p, i, :, :, j, g]``, ``(m, cb // c, nif, nfft, c, groups)``
    float32, fftshifted like :func:`channelize`'s: positions major,
    frame groups on the lanes, the layout :func:`integrate_carry` folds
    with ``lanes=True`` as it arrives.  ``c`` is :data:`_LANES_CHANNELS`
    where that divides a larger ``cb``, else ``cb``.  ``groups =
    ceil(frames / m)``; where ``gross`` ends before
    the last block does it is padded with zeros, and where it goes on
    past ``frames`` frames they are computed: either way only the first
    ``frames`` frames are the stream's (the fold's ``nframes``).
    """
    cb = parts[0].shape[0]
    if cb > _LANES_CHANNELS and cb % _LANES_CHANNELS == 0:
        # A sublane's count of channels at a time, one after the other in
        # the one program: the parts joined and the float32 planes
        # between the passes below are held for those channels only.
        # The slabs land on the second axis (the compiler lays the loop's
        # buffer out so: the swap is no copy).
        return jnp.swapaxes(jax.lax.map(
            lambda slab: channelize_lanes(
                slab, coeffs, nfft=nfft, ntap=ntap, block=block,
                frames=frames, stokes=stokes)[:, 0],
            [p.reshape(cb // _LANES_CHANNELS, _LANES_CHANNELS, -1)
             for p in parts]), 0, 1)
    gross = jnp.concatenate(parts, axis=1)
    have = gross.shape[1]
    state, m = (ntap - 1) * nfft, block // nfft
    groups = -(-frames // m)
    need = groups * block + state
    if have < need:
        gross = jnp.pad(gross, ((0, 0), (0, need - have)))
    rows = jnp.transpose(
        gross[:, :groups * block].reshape(cb, groups, block), (2, 0, 1))
    # Below each block's own rows, the first `state` words of the next.
    below = jnp.concatenate(
        [rows[:state, :, 1:], gross[:, groups * block:need].T[:, :, None]],
        axis=2)
    # Held as written: left free, the compiler widens the bytes BEFORE the
    # transpose and transposes four float32 planes for the one of words.
    words = jax.lax.optimization_barrier(
        jnp.concatenate([rows, below], axis=0).reshape(
            m + ntap - 1, nfft, cb, groups))
    # The shift theorem, as in channelize: (-1)^j on the input rolls the
    # spectrum by nfft/2.
    sign = np.where(np.arange(nfft) % 2 == 0, 1.0, -1.0).astype(np.float32)
    taps = coeffs * sign[None, :]

    def plane(byte, i):
        """Byte ``byte`` of point ``i``'s words (0: the first
        polarization's real part), sign-extended, through the filter:
        ``(m, cb, groups)``.  Each tap converts its own slice of the
        words: the float32 plane of all ``m + ntap - 1`` rows is never
        written."""
        def rows_from(k):
            return jax.lax.shift_right_arithmetic(
                jax.lax.shift_left(words[k:k + m, i],
                                   jnp.int32(24 - 8 * byte)),
                jnp.int32(24)).astype(jnp.float32)

        acc = taps[0, i] * rows_from(0)
        for k in range(1, ntap):
            acc = acc + taps[k, i] * rows_from(k)
        return acc

    halves = [_fft_halves([(plane(2 * pol, i), plane(2 * pol + 1, i))
                           for i in range(nfft)]) for pol in range(2)]
    # Bins k and k + nfft/2 of both polarizations -> their power, ONE
    # expression for the pair, e +- t: the compiler then makes the two in
    # one pass over e and t (adding -1 * t is subtracting t, to the bit).
    # Left as e + t here and e - t there it wrote both half-transforms
    # out for eight passes to read back (PERF.md section 6, PR 35).
    pm = jnp.asarray([1.0, -1.0], jnp.float32)[:, None, None, None]
    pairs = []
    for k in range(nfft // 2):
        sr, si = [], []
        for even, twiddled in halves:  # a polarization each
            (er, ei), (tr, ti) = even[k], twiddled[k]
            sr.append(er[None] + pm * tr[None])
            si.append(ei[None] + pm * ti[None])
        # (2, m, npol, cb, groups) -> (2, m, nif, cb, groups)
        pairs.append(detect_stokes_planar(jnp.stack(sr, axis=2),
                                          jnp.stack(si, axis=2), stokes))
    power = jnp.stack(pairs, axis=1)  # (2, nfft/2, ...): bin h*nfft/2 + k
    power = power.reshape((nfft,) + power.shape[2:])
    return jnp.moveaxis(power, 0, 2)[:, None]  # (m, 1, nif, nfft, cb, groups)


def _direct_put(host, then: Callable):
    """The default transfer policy: the jit's own argument transfer."""
    return then(host)


def _channel_groups(nchan: int, channel_block: int) -> range:
    """Group starts for ``channel_block``-sized groups of ``nchan`` coarse
    channels (one group where the block does not split them)."""
    if channel_block <= 0 or channel_block >= nchan:
        channel_block = nchan
    if nchan % channel_block:
        raise ValueError(
            f"channel_block={channel_block} does not divide nchan={nchan}"
        )
    return range(0, nchan, channel_block)


def split_tails(head, channel_block: int) -> list:
    """A stream's head ``(nchan, (ntap-1)*nfft, npol, 2)`` as the per-group
    ``tails`` of its first dispatch (views: nothing is copied)."""
    groups = _channel_groups(head.shape[0], channel_block)
    return [head[c : c + groups.step] for c in groups]


class StreamLeg:
    """One product of a stream: its programs, and what it keeps on the
    device between dispatches, per channel group — the filter state
    (``tails``: the last ``(ntap-1)*nfft`` samples dispatched, the next
    dispatch's first) and, where the integration is CARRIED across
    dispatches, its accumulators (``accs``) and the frames the open
    integration holds (``filled``).  A reduction is a list of legs that
    read the same uploaded samples (:func:`channelize_fanout`).

    ``kw`` are :func:`channelize`'s keywords less ``nint``.  A leg that is
    not carried integrates inside its program (a dispatch's frames are a
    multiple of ``nint``).  ``lanes`` > 0 runs the small-``nfft`` path in
    blocks of that many words (:func:`lanes_block`; carried legs only):
    its power and its accumulators keep :func:`channelize_lanes`'s layout
    (positions major, channels on the sublanes, frame groups on the
    lanes) from the program to the fold, and only closed ROWS are turned
    into the product's.
    ``name`` is the leg's program name in a device trace; ``label`` names
    it in counters (``None``: a reduction of one product)."""

    def __init__(self, coeffs, *, nint: int, carried: bool,
                 name: str = "channelize_stream", label: Optional[str] = None,
                 lanes: int = 0, **kw):
        assert carried or not lanes
        self.coeffs, self.nint, self.carried = coeffs, nint, carried
        self.lanes, self.label, self.kw = lanes, label, kw
        self.nfft = kw["nfft"]
        self.state_words = (kw.get("ntap", 4) - 1) * self.nfft
        self.step, self.head = leg_programs(name)
        self.program = f"jit_{name}"  # both, in a device trace
        self.tails: Optional[list] = None
        self.accs: Optional[list] = None
        self.filled = 0
        self._rows: Tuple[list, list] = ([], [])  # the head's, the chunk's

    def _fold(self, g: int, out, frames: int, at: int, batch: int):
        """A program's output for group ``g``, ``frames`` frames that
        found ``at`` in the open integration → what the host may hold on
        to until the group's input is consumed (never a tail: the next
        dispatch donates it).  Of a carried leg that is the ACCUMULATOR
        alone, ready when the fold is: the fold's row buffer is zeros
        while no row closes (128 MiB a group at 2^20), and one kept alive
        by the link budget's handle and the readback token for a dispatch
        or two leaves the next group's samples no room beside a running
        program (PERF.md section 6, PR 34)."""
        power, self.tails[g] = out
        if not self.carried:
            self._rows[batch].append(power)
            return power
        if self.accs is None:
            self.accs = [None] * len(self.tails)
        acc = self.accs[g]
        if acc is None:
            acc = jnp.zeros(
                power.shape[1:5] if self.lanes
                else power.shape[1:], jnp.float32)
        rows, self.accs[g] = integrate_carry(
            power, acc, np.int32(at),  # data, not static: one program
            nint=self.nint, lanes=bool(self.lanes),
            nframes=frames if self.lanes else None)
        closed = (at + frames) // self.nint
        if closed:
            self._rows[batch].append(rows if closed == rows.shape[0]
                                     else rows[:closed])
        return self.accs[g]

    def _program_kw(self, frames: Optional[int]) -> dict:
        kw = dict(self.kw)
        if not self.carried:
            kw["nint"] = self.nint
        if self.lanes:
            kw["lanes"] = self.lanes
        if frames is not None:
            kw["frames"] = frames
        return kw

    def begin(self, g: int, head_up: jax.Array):
        """Group ``g``'s first step of a stream whose head (``head_up``,
        device words) is longer than this leg's filter state: the samples
        past it are the leg's data."""
        frames = (head_up.shape[1] - self.state_words) // self.nfft
        if not frames:  # the largest filter state is this leg's own
            self.tails[g] = jnp.array(head_up, copy=True)
            return None
        return self._fold(g, self.head(head_up, self.coeffs,
                                       **self._program_kw(None)),
                          frames, 0, 0)

    def advance(self, g: int, body_up: jax.Array, frames: int,
                begun: int = 0):
        """Group ``g``'s dispatch: the first ``frames`` frames of the
        samples ``body_up`` (device words) after the leg's tail;
        ``begun``: the frames :meth:`begin` took in this dispatch."""
        whole = frames * self.nfft == body_up.shape[1]
        return self._fold(g, self.step(
            self.tails[g], body_up, self.coeffs,
            **self._program_kw(None if whole else frames)),
            frames, (self.filled + begun) % self.nint, 1)

    def close(self, frames: int, begun: int = 0) -> list:
        """After every group has stepped ``begun`` + ``frames`` frames:
        the batches of rows that closed (the head's, then the chunk's),
        each ``(k, nif, nchan*nfft)`` assembled on the device — nothing
        where none did, and then no group's partial sum is concatenated,
        fetched or written."""
        batches, self._rows = self._rows, ([], [])
        if self.carried:
            self.filled = (self.filled + begun + frames) % self.nint
        return [rows[0] if len(rows) == 1
                else jnp.concatenate(rows, axis=-1)
                for rows in batches if rows]


def channelize_fanout(
    voltages,
    legs: list,
    frames: list,
    *,
    channel_block: int,
    head=None,
    put: Callable = _direct_put,
    shared: Optional[Callable] = None,
    calling: Optional[Callable] = None,
) -> Tuple[list, list]:
    """One dispatch of a stream's chunk to every leg: host-looped channel
    blocking (the compile-friendly replacement for
    ``channelize(channel_block=)``'s in-jit ``lax.map``, whose XLA loop
    blows compile time past 500 s at nfft=2^20, DESIGN.md §3/§9) with each
    group's samples put ONCE and consumed by all the legs' programs.

    ``voltages`` is the chunk's NEW samples only, host int8 ``(nchan,
    samples, npol, 2)``; ``frames[k]`` the frames leg ``k`` takes of them
    (a flush may give a leg fewer than the samples hold, or none).
    ``head``, on a stream's first dispatch, is its first samples, as long
    as the LARGEST filter state among the legs: it goes up in the same
    transfer as its group's samples (one put of the pair — the leg that
    owns it takes it by donation, so no handle on it may outlive the
    call), is that leg's first tail, and to every other leg its own
    shorter filter state followed by data (:meth:`StreamLeg.begin`).
    Groups are ``channel_block`` coarse channels: ONE compile per leg and
    shape, dispatches enqueued async back-to-back, device-side
    concatenation of the per-group rows.  ``put(host, then=)`` takes host
    memory (a group's :func:`sample_words`, or the pair ``(head,
    samples)`` of them) to the device and returns what the program
    ``then`` makes of it — the caller's transfer policy; by default the
    jit's own argument transfer.  ``shared(programs, nbytes)`` is told,
    per group, how many programs consumed a transfer of ``nbytes`` they
    did not upload.  ``calling(names)`` is a context manager entered
    around one group's program calls (every leg's ``begin`` / ``advance``;
    ``names``: their jit names in call order), for a caller that times
    them.

    Returns ``(rows, token)``: per leg the list of row batches that closed
    (a leg's head step and its dispatch each close their own), and what is
    ready once the chunk's input has been consumed.
    """
    groups = _channel_groups(voltages.shape[0], channel_block)
    if head is not None:
        heads = split_tails(head, channel_block)
        for leg in legs:
            leg.tails, leg.accs, leg.filled = [None] * len(groups), None, 0
        # The leg the head is the filter state of (the first of them).
        owner = max(legs, key=lambda leg: leg.state_words)
        begun = [(head.shape[1] - leg.state_words) // leg.nfft
                 for leg in legs]
    else:
        begun = [0] * len(legs)
    # A group's program calls, by name: the head steps, then the dispatch.
    names = [leg.program for leg, n in zip(legs + legs, begun + list(frames))
             if n]
    token = []
    for g, c in enumerate(groups):
        def programs(up, g=g):
            held = []
            with calling(names) if calling is not None \
                    else contextlib.nullcontext():
                if head is not None:
                    head_up, up = up
                    # Everyone reads the head before its owner donates it.
                    held += [leg.begin(g, head_up) for leg in legs
                             if leg is not owner]
                    owner.tails[g] = head_up
                held += [leg.advance(g, up, n, b)
                         for leg, n, b in zip(legs, frames, begun) if n]
            return held

        body = sample_words(voltages[c : c + groups.step])
        host = body if head is None else (sample_words(heads[g]), body)
        token.append(put(host, then=programs))
        if shared is not None and len(legs) > 1:
            # Programs that ran on this transfer less the one that would
            # have had to upload it, and what the others did not send.
            took = sum(map(bool, frames))
            shared(took + sum(map(bool, begun)) - 1,
                   max(0, took - 1) * sum(
                       a.nbytes for a in jax.tree_util.tree_leaves(host)))
    return [leg.close(n, b) for leg, n, b in zip(legs, frames, begun)], token


# Positions of an integration one fused pass of the fold adds (a chain of
# that many adds per value, the running sums held in registers between
# them): past it the chain is a loop of such passes.
_FOLD_UNROLL = 32


def _seq_sum(start: jax.Array, xg: jax.Array, lo, hi, valid=None):
    """``start + xg[:, lo] + xg[:, lo + 1] + ... + xg[:, hi - 1]``, added
    in that order, one position (axis 1 of ``xg``) at a time; ``lo`` and
    ``hi`` are data.  ``valid(p)``, where given, masks the values a
    position ``p`` does not hold (they are left out, not added as zeros).
    Up to :data:`_FOLD_UNROLL` positions are one elementwise chain; more
    are a loop over blocks of that many, the blocks outside ``[lo, hi)``
    never read."""
    npos, unroll = xg.shape[1], min(xg.shape[1], _FOLD_UNROLL)

    def block(first, s):
        # dynamic_slice clamps a start that would run off the end: the
        # positions it then repeats are below `first`, and masked.
        at = jnp.minimum(first, npos - unroll)
        for u in range(unroll):
            p = at + u
            take = (p >= jnp.maximum(lo, first)) & (p < hi)
            if valid is not None:
                take = take & valid(p)
            s = jnp.where(take, s + jax.lax.dynamic_index_in_dim(
                xg, p, axis=1, keepdims=False), s)
        return s

    if npos <= unroll:
        return block(jnp.int32(0), start)
    return jax.lax.fori_loop(
        lo // unroll, -(-hi // unroll),
        lambda j, s: block(j * unroll, s), start)


def _fold_groups(xg: jax.Array, carry: jax.Array, filled, nint: int,
                 last_valid: int, group_axis: int):
    """Fold a slab of consecutive frame groups into rows.  ``xg`` holds
    group ``g``'s position ``p`` at axis 1 and ``g`` at ``group_axis`` (0
    or -1); every group holds ``xg.shape[1]`` frames (a whole
    integration's ``nint``, unless the slab is one short group) and the
    last only ``last_valid`` of them.  ``carry`` (``xg`` less both axes)
    holds the ``filled`` frames the open integration has.

    A row is its open head (the ``filled`` frames carried in, or the
    previous group's last ``filled``) with the group's first ``nint -
    filled`` added to it in order: the heads of all rows first, from
    zero, then every row's remainder — ``nint`` steps over a ``(groups,
    ...)`` slab, vectorised ACROSS rows and sequential WITHIN one.
    Returns ``(rows, carry)``: a row per group (the last one zeros unless
    its frames closed it), the group axis where it was, and the
    accumulator after the slab."""
    ngroups, npos = xg.shape[group_axis], xg.shape[1]
    assert npos == nint or ngroups == 1, (xg.shape, nint)
    split = jnp.minimum(nint - filled, npos)  # the open row takes these
    valid = None
    if last_valid < npos:  # the last group's frames past `last_valid`
        shape = [1] * (xg.ndim - 1)
        shape[group_axis] = ngroups
        last = jax.lax.broadcasted_iota(
            jnp.int32, shape, group_axis % len(shape)) == ngroups - 1

        def valid(p):
            return ~last | (p < last_valid)

    def at(x, g):
        return jax.lax.index_in_dim(x, g, group_axis, keepdims=False)

    def shifted(first, x):
        """``first`` in front of all of ``x`` but its last group."""
        return jnp.concatenate(
            [jnp.expand_dims(first, group_axis),
             jax.lax.slice_in_dim(x, 0, ngroups - 1, axis=group_axis)],
            axis=group_axis)

    zeros = jnp.zeros(xg.shape[:1] + xg.shape[2:], xg.dtype)
    heads = _seq_sum(zeros, xg, split, jnp.int32(npos), valid)
    rows = _seq_sum(shifted(carry, heads), xg, jnp.int32(0), split, valid)
    closed = last_valid >= nint - filled  # did the last group close a row?
    last_row = at(rows, ngroups - 1)
    carry = jnp.where(closed, at(heads, ngroups - 1), last_row)
    last_row = jnp.where(closed, last_row, jnp.zeros_like(last_row))
    # ... and the rows with the last one as it closed, or zeros.
    rows = jnp.concatenate(
        [jax.lax.slice_in_dim(rows, 0, ngroups - 1, axis=group_axis),
         jnp.expand_dims(last_row, group_axis)], axis=group_axis)
    return rows, carry


@functools.partial(jax.jit, static_argnames=("nint", "nframes", "lanes"))
def integrate_carry(
    power: jax.Array, acc: jax.Array, filled: jax.Array, *, nint: int,
    nframes: Optional[int] = None, lanes: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Integrate one dispatch's spectra into an integration that is longer
    than a dispatch, or straddles its boundary: ONE fold for every leg of
    a reduction (program ``jit_integrate_carry``).

    ``power`` is :func:`channelize`'s product at ``nint=1``, frame-major
    ``(nframes, nif, nchan)``; ``acc`` ``(nif, nchan)`` float32 holds the
    ``filled`` frames (``0 <= filled < nint``, a device scalar: the split
    is data, so one program serves every place the boundary falls) the
    open integration has so far.  Returns ``(rows, acc)``: ``rows``
    ``((nint - 1 + nframes) // nint, nif, nchan)``, of which the first
    ``(filled + nframes) // nint`` closed in this dispatch (the rest are
    zeros), and the accumulator to hand to the next dispatch.

    With ``lanes`` the power is :func:`channelize_lanes`'s: ``(nint, C,
    nif, nfft, c, groups)``, frame ``g * nint + p`` of channel ``i * c +
    j`` at ``[p, i, :, :, j, g]``, the first ``nframes`` of them real.
    The positions are its major axis and the groups its lanes, which is
    how the fold walks it: it is read where the leg wrote it, no copy in
    front.  ``acc`` is ``(C, nif, nfft, c)`` and the rows come back in the
    product's layout, ``(groups, nif, C * c * nfft)`` (a transpose of the
    ROWS, a hundredth of the power).

    The order of addition is part of the result: a row's frames are added
    one at a time in stream order and a fresh integration starts from
    zero, so a row's bits depend on each frame's place in its integration,
    not on where the dispatch grid fell — a reduction resumed at another
    row gives the same bytes.  The work is laid out across rows
    (:func:`_fold_groups`): whole integrations in bulk, the short group
    that ends the dispatch apart.
    """
    if lanes:
        groups = power.shape[-1]
        total = groups * nint if nframes is None else nframes
        rows, acc = _fold_groups(
            power[None], acc[None], filled, nint,
            last_valid=total - (groups - 1) * nint, group_axis=-1)
        nif = rows.shape[2]
        return (jnp.transpose(rows[0], (4, 1, 0, 3, 2)).reshape(
            groups, nif, -1), acc[0])
    total = power.shape[0]
    whole, rest = divmod(total, nint)
    parts = []
    if whole:
        bulk = power[:whole * nint].reshape(
            (whole, nint) + power.shape[1:])
        rows, acc = _fold_groups(bulk, acc, filled, nint, nint, 0)
        parts.append(rows)
    if rest:
        rows, acc = _fold_groups(power[None, whole * nint:], acc, filled,
                                 nint, rest, 0)
        parts.append(rows)
    return (parts[0] if len(parts) == 1 else jnp.concatenate(parts)), acc


@functools.lru_cache(maxsize=None)
def channels_per_dispatch(
    shape: Tuple[int, int, int, int],
    budget_bytes: int,
    **kw,
) -> int:
    """How many coarse channels of a stream's chunk — ``shape`` is its new
    samples' ``(nchan, frames*nfft, npol, 2)`` — one
    :func:`channelize_stream` dispatch (kwargs ``kw``) may take inside
    ``budget_bytes`` of device memory: the ``channel_block`` for
    :func:`channelize_fanout`.  Cached process-wide: a fresh reducer per
    request or per bank asks once.

    The account is the compiler's own, not a model of today's kernels: a
    probe of the program that runs (filter state and new samples in,
    product and next filter state out) is compiled and its argument,
    temporary and output bytes read back (``memory_analysis``: whatever
    the concatenation materialises is in it), less what the program
    aliases (the donated tail) and less the filter state itself — every
    group's stays on the device between dispatches, so it is the caller's
    to count, whole, against ``budget_bytes`` — plus one more group's new
    samples, the next dispatch's, in flight beside it.  Those scale
    linearly with the channel count from about 8 channels up (below that
    the TPU's tiled layouts pad the small batch and the per-channel figure
    is off by up to 2.5x), so the probe runs at 8 channels and the answer
    is the largest divisor of ``shape[0]`` that fits.  One extra compile
    of a few seconds, paid once per chunk shape.  Raises when not even one
    channel fits.
    """
    nchan = shape[0]
    ntap, nfft = kw.get("ntap", 4), kw["nfft"]
    divisors = [d for d in range(1, nchan + 1) if nchan % d == 0]
    probe = min(d for d in divisors if d >= min(8, nchan))
    word = _word_dtype(shape[2], shape[3])
    m = channelize_stream.lower(
        jax.ShapeDtypeStruct((probe, (ntap - 1) * nfft), word),
        jax.ShapeDtypeStruct((probe, shape[1]), word),
        jax.ShapeDtypeStruct((ntap, nfft), jnp.float32),
        **kw,  # `lanes` among them where the leg takes that path
    ).compile().memory_analysis()
    tail = probe * (ntap - 1) * nfft * word.itemsize
    # ... plus the NEXT group's new samples, which go up the link while
    # this group's program runs (PERF.md section 6, PR 34: whatever the
    # host still references must fit beside a running program).
    ahead = probe * shape[1] * word.itemsize
    per_chan = -(-(m.argument_size_in_bytes + m.temp_size_in_bytes
                   + m.output_size_in_bytes - m.alias_size_in_bytes
                   - tail + ahead) // probe)
    fit = budget_bytes // per_chan
    if fit < 1:
        raise MemoryError(
            f"one coarse channel of a {tuple(shape[1:])} chunk needs "
            f"{per_chan} B of device memory per dispatch and the budget "
            f"is {budget_bytes} B — reduce with fewer frames per chunk"
        )
    return max(d for d in divisors if d <= fit)


def channelize_np(
    voltages: np.ndarray,
    coeffs: np.ndarray,
    *,
    nfft: int,
    ntap: int = 4,
    nint: int = 1,
    stokes: str = "I",
) -> np.ndarray:
    """NumPy golden-reference implementation of :func:`channelize` (tests)."""
    v = voltages.astype(np.float32)
    z = v[..., 0] + 1j * v[..., 1]  # (nchan, ntime, npol)
    z = np.moveaxis(z, -1, 1)  # (nchan, npol, ntime)
    nchan, npol, ntime = z.shape
    nblk = ntime // nfft
    nframes = nblk - ntap + 1
    blocks = z.reshape(nchan, npol, nblk, nfft)
    frames = np.zeros((nchan, npol, nframes, nfft), dtype=np.complex64)
    for k in range(ntap):
        frames += coeffs[k] * blocks[:, :, k : k + nframes, :]
    spec = np.fft.fftshift(np.fft.fft(frames, axis=-1), axes=-1)
    xs, ys = (spec[:, 0], spec[:, 1]) if npol == 2 else (spec[:, 0], spec[:, 0])
    xx = (xs.real**2 + xs.imag**2).astype(np.float32)
    yy = (ys.real**2 + ys.imag**2).astype(np.float32)
    if stokes == "I":
        prods = [xx + yy] if npol == 2 else [xx]
    elif stokes == "XX":
        prods = [xx]
    elif stokes == "YY":
        prods = [yy]
    elif stokes == "XXYY":
        prods = [xx, yy]
    elif stokes in ("full", "IQUV"):
        xy = xs * np.conj(ys)
        if stokes == "full":
            prods = [xx, yy, xy.real.astype(np.float32), xy.imag.astype(np.float32)]
        else:
            prods = [
                xx + yy,
                xx - yy,
                (2 * xy.real).astype(np.float32),
                (-2 * xy.imag).astype(np.float32),
            ]
    else:
        raise ValueError(stokes)
    power = np.stack(prods, axis=1)  # (nchan, nif, nframes, nfft)
    if nint > 1:
        power = power.reshape(
            nchan, power.shape[1], nframes // nint, nint, nfft
        ).sum(axis=3)
    out = np.transpose(power, (2, 1, 0, 3))
    return np.ascontiguousarray(out.reshape(out.shape[0], out.shape[1], nchan * nfft))


def output_header(
    raw_header: dict,
    *,
    nfft: int,
    nint: int,
    stokes: str = "I",
) -> dict:
    """Filterbank header for the channelized product, derived from a GUPPI
    RAW block header (rawspec-equivalent metadata path).

    Frequency mapping: coarse channel c (of OBSNCHAN, center frequencies
    spanning OBSBW around OBSFREQ) yields nfft fine channels, fftshifted so
    fine index f maps to offset ``(f - nfft/2) * chan_bw/nfft`` from the
    coarse center.  With the GBT convention OBSBW < 0, channel 0 is the
    highest frequency and ``foff`` is negative (SURVEY.md §0).
    """
    obsnchan = int(raw_header["OBSNCHAN"])
    obsfreq = float(raw_header["OBSFREQ"])
    obsbw = float(raw_header["OBSBW"])
    tbin = float(raw_header.get("TBIN", 0.0) or 0.0)
    chan_bw = obsbw / obsnchan
    foff = chan_bw / nfft
    # Center frequency of coarse channel 0:
    c0 = obsfreq - obsbw / 2 + chan_bw / 2
    # Fine channel 0 of coarse 0 sits nfft/2 fine-widths below its center:
    fch1 = c0 - (nfft / 2) * foff
    return {
        "fch1": fch1,
        "foff": foff,
        "nchans": obsnchan * nfft,
        "nifs": STOKES_NIF[stokes],
        "tsamp": tbin * nfft * nint,
        "nbits": 32,
        "nfpc": nfft,
        "source_name": raw_header.get("SRC_NAME", ""),
        "tstart": _raw_tstart_mjd(raw_header),
    }


def _raw_tstart_mjd(hdr: dict) -> float:
    imjd = float(hdr.get("STT_IMJD", 0))
    smjd = float(hdr.get("STT_SMJD", 0))
    offs = float(hdr.get("STT_OFFS", 0))
    return imjd + (smjd + offs) / 86400.0
