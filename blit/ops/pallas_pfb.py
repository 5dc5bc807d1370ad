"""Pallas TPU kernel fusing dequantization + the polyphase FIR frontend.

Why: the corrected roofline (DESIGN.md §9) shows
dequant+PFB is the channelizer's dominant stage — 90 ms at 64 GB/s (8% of
the HBM roof) vs 25-29 ms at ~230 GB/s for each DFT matmul stage — because
XLA materializes the dequantized gross planes and re-reads them once per
tap, with the (chan, time, pol) → (chan, pol, time) transpose riding
along.  This kernel does the whole stage in ONE pass: the int8 voltages
enter VMEM exactly once (packed — each (npol=2, re/im) sample group is one
int32 lane element, so the awkward size-2 minor axes never meet the lane
dimension), bytes are sign-extended in-register, the ``ntap`` sign-folded
window taps accumulate in f32, and the planar frame tensors stream out in
the compute dtype.  HBM traffic drops from ~(2·gross·esize·ntap reads +
2·plane writes) to (gross int8 read + 2·plane writes).

Opt-in from :func:`blit.ops.channelize.channelize` via
``pfb_kernel="pallas"``; CPU tests run in interpreter mode (golden vs the
jnp path).  npol=2, NBITS=8 only — the GBT recording format
(SURVEY.md §0); other shapes fall back to the jnp path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

# Fine-channel tile per kernel instance (upper bound; shrunk until the
# VMEM budget below holds).  Swept on the chip at the production shape
# (48ch × 8fr bf16): 2048 ≈ 86-90 ms, 4096 ≈ 89, 8192 ≈ 92-95,
# 16384/32768 ≈ 94-95 — smaller tiles pipeline HBM↔VMEM better.
_DEF_TILE_J = 4096

# Per-instance VMEM budget (v5e has ~16 MB; leave room for double
# buffering and the compiler's own scratch).
_VMEM_BUDGET = 6 << 20


def _tile_bytes(tile_j: int, nblk: int, nframes: int, ntap: int,
                esize: int) -> int:
    """VMEM resident bytes for one kernel instance at fine-tile ``tile_j``:
    packed int32 input + 4 decoded f32 gross planes + 4 output frame
    planes (re/im × 2 pols) + the coeff tile."""
    return tile_j * (
        nblk * 4 + 4 * nblk * 4 + 2 * 2 * nframes * esize + ntap * 4
    )


def pick_tile(nfft: int, nblk: int, nframes: int, ntap: int,
              esize: int, target: int = _DEF_TILE_J) -> int:
    """Largest usable divisor of ``nfft`` <= target whose instance fits
    the VMEM budget; 0 if none — the caller falls back to the XLA path.
    Usable = lane-aligned (multiple of 128) or the whole axis: sub-lane
    tiles would technically fit VMEM but serialize the vector unit, which
    is worse than not running the kernel at all."""
    for t in range(min(target, nfft), 0, -1):
        if nfft % t or (t % 128 and t != nfft):
            continue
        if _tile_bytes(t, nblk, nframes, ntap, esize) <= _VMEM_BUDGET:
            return t
    return 0


def fits(nfft: int, nblk: int, ntap: int, dtype: str = "float32") -> bool:
    """True when :func:`pfb_dequant` can run these shapes inside the VMEM
    budget — the gate ``channelize(pfb_kernel="auto")`` uses before
    preferring the kernel (e.g. the '0002' preset's 2048-frame chunks
    exceed any fine tile and must take the XLA path)."""
    esize = 2 if dtype == "bfloat16" else 4
    return pick_tile(nfft, nblk, nblk - ntap + 1, ntap, esize) > 0


def _kernel(nframes: int, ntap: int, out_dtype, v_ref, w_ref, or_ref, oi_ref):
    x = v_ref[0]  # (nblk, tile_j) int32 — packed (p0r, p0i, p1r, p1i) bytes
    w = w_ref[...]  # (ntap, tile_j) f32 (sign-folded window)

    def byte(i: int) -> jax.Array:
        # Little-endian byte i of each int32, sign-extended from int8.
        return ((((x >> (8 * i)) & 0xFF) ^ 0x80) - 0x80).astype(jnp.float32)

    def pfb(p: jax.Array) -> jax.Array:
        # p: (nblk, tile_j) f32 → (nframes, tile_j): windowed tap sums.
        acc = w[0] * p[0:nframes]
        for k in range(1, ntap):
            acc = acc + w[k] * p[k : k + nframes]
        return acc.astype(out_dtype)

    or_ref[0, 0] = pfb(byte(0))
    oi_ref[0, 0] = pfb(byte(1))
    or_ref[0, 1] = pfb(byte(2))
    oi_ref[0, 1] = pfb(byte(3))


def _fused1_kernel(nframes: int, ntap: int, n1: int, out_dtype,
                   v_ref, w_ref, w1r_ref, w1i_ref, tr_ref, ti_ref,
                   or_ref, oi_ref):
    """dequant + PFB + DFT stage 1 (+twiddle), one VMEM pass.

    Blocks (per grid instance, fine columns ``j2``-tiled):
      v:   (1, nblk, n1, tile_m) int32  packed voltages
      w:   (ntap, n1, tile_m)    f32    sign-folded window
      w1:  (n1, n1)              f32    stage-1 DFT matrix (re, im)
      tw:  (n1, tile_m)          f32    stage-1 twiddle (re, im)
      out: (1, npol, nframes, n1, tile_m) out_dtype (re, im)
    """
    x = v_ref[0]  # (nblk, n1, tile_m) int32
    w = w_ref[...]
    w1r = w1r_ref[...]
    w1i = w1i_ref[...]
    tr = tr_ref[...]
    ti = ti_ref[...]

    def byte(i: int) -> jax.Array:
        return ((((x >> (8 * i)) & 0xFF) ^ 0x80) - 0x80).astype(jnp.float32)

    # bf16 mode runs the MXU at full rate: f32-input dots cost 4x on a
    # v5e, and bf16-grade multiplies are exactly what the XLA path's
    # precision=None einsums do anyway (channelize docstring).  The tap
    # accumulation and twiddle stay f32 on the VPU either way.
    dot_dtype = (
        jnp.bfloat16 if out_dtype == jnp.bfloat16 else jnp.float32
    )
    w1r = w1r.astype(dot_dtype)
    w1i = w1i.astype(dot_dtype)

    planes = (byte(0), byte(1), byte(2), byte(3))  # p0r p0i p1r p1i
    for p in range(2):
        re_g, im_g = planes[2 * p], planes[2 * p + 1]
        for f in range(nframes):
            fr = w[0] * re_g[f]
            fi = w[0] * im_g[f]
            for k in range(1, ntap):
                fr = fr + w[k] * re_g[f + k]
                fi = fi + w[k] * im_g[f + k]
            fr = fr.astype(dot_dtype)
            fi = fi.astype(dot_dtype)
            # Stage-1 complex DFT down the n1 axis + twiddle.
            rr = jnp.dot(w1r, fr, preferred_element_type=jnp.float32)
            ii = jnp.dot(w1i, fi, preferred_element_type=jnp.float32)
            ri = jnp.dot(w1r, fi, preferred_element_type=jnp.float32)
            ir = jnp.dot(w1i, fr, preferred_element_type=jnp.float32)
            sr = rr - ii
            si = ri + ir
            or_ref[0, p, f] = (sr * tr - si * ti).astype(out_dtype)
            oi_ref[0, p, f] = (sr * ti + si * tr).astype(out_dtype)


def fused1_fits(nfft: int, nblk: int, ntap: int, n1: int,
                dtype: str = "float32") -> bool:
    """VMEM-fit gate for :func:`pfb_dft1` (see :func:`_fused1_tile`)."""
    return _fused1_tile(nfft, nblk, ntap, n1, dtype) > 0


def _fused1_tile(nfft: int, nblk: int, ntap: int, n1: int,
                 dtype: str, target: int = 512) -> int:
    esize = 2 if dtype == "bfloat16" else 4
    m = nfft // n1
    nframes = nblk - ntap + 1
    for t in range(min(target, m), 0, -1):
        if m % t or (t % 128 and t != m):
            continue
        bts = t * (
            nblk * n1 * 4          # packed input
            + ntap * n1 * 4        # window
            + 2 * n1 * 4           # twiddles
            + 2 * 2 * nframes * n1 * esize  # outputs (2 planes x 2 pols)
        ) + 2 * n1 * n1 * 4        # DFT matrices
        if bts <= _VMEM_BUDGET:
            return t
    return 0


def pfb_dft1(
    voltages: jax.Array,
    coeffs: jax.Array,
    w1r: jax.Array,
    w1i: jax.Array,
    tr: jax.Array,
    ti: jax.Array,
    *,
    dtype: str = "float32",
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused dequant + PFB + first Cooley-Tukey DFT stage.

    One HBM pass replaces three: the PFB frame planes never materialize —
    int8 in, stage-1 spectra (twiddled, ready for the remaining factors of
    :func:`blit.ops.dft._dft_rec`) out.

    Args:
      voltages: int8 ``(nchan, ntime, 2, 2)``.
      coeffs: ``(ntap, nfft)`` f32 sign-folded window.
      w1r, w1i: ``(n1, n1)`` stage-1 DFT matrix parts.
      tr, ti: ``(n1, nfft//n1)`` stage-1 twiddle parts.

    Returns ``(ur, ui)`` shaped ``(nchan, npol, nframes, n1, nfft//n1)``.
    """
    from jax.experimental import pallas as pl

    nchan, ntime, npol, ncomp = voltages.shape
    if npol != 2 or ncomp != 2:
        raise ValueError("pfb_dft1: npol=2 complex int8 input required")
    ntap, nfft = coeffs.shape
    n1 = w1r.shape[0]
    m = nfft // n1
    if ntime % nfft:
        raise ValueError(f"ntime={ntime} not a multiple of nfft={nfft}")
    nblk = ntime // nfft
    nframes = nblk - ntap + 1
    tile_m = _fused1_tile(nfft, nblk, ntap, n1, dtype)
    if tile_m == 0:
        raise ValueError(
            "pfb_dft1: no column tile fits VMEM at these shapes — use the "
            "unfused path"
        )

    packed = jax.lax.bitcast_convert_type(
        voltages.reshape(nchan, nblk, n1, m, npol * ncomp), jnp.int32
    )  # (nchan, nblk, n1, m)
    wv = coeffs.reshape(ntap, n1, m)
    out_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    kern = functools.partial(_fused1_kernel, nframes, ntap, n1, out_dtype)
    out_shape = [
        jax.ShapeDtypeStruct((nchan, npol, nframes, n1, m), out_dtype),
        jax.ShapeDtypeStruct((nchan, npol, nframes, n1, m), out_dtype),
    ]
    ur, ui = pl.pallas_call(
        kern,
        grid=(nchan, m // tile_m),
        in_specs=[
            pl.BlockSpec((1, nblk, n1, tile_m), lambda c, j: (c, 0, 0, j)),
            pl.BlockSpec((ntap, n1, tile_m), lambda c, j: (0, 0, j)),
            pl.BlockSpec((n1, n1), lambda c, j: (0, 0)),
            pl.BlockSpec((n1, n1), lambda c, j: (0, 0)),
            pl.BlockSpec((n1, tile_m), lambda c, j: (0, j)),
            pl.BlockSpec((n1, tile_m), lambda c, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, npol, nframes, n1, tile_m),
                         lambda c, j: (c, 0, 0, 0, j)),
            pl.BlockSpec((1, npol, nframes, n1, tile_m),
                         lambda c, j: (c, 0, 0, 0, j)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(packed, wv, w1r, w1i, tr, ti)
    return ur, ui


def pfb_dequant(
    voltages: jax.Array,
    coeffs: jax.Array,
    *,
    dtype: str = "float32",
    tile_j: int = _DEF_TILE_J,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused int8 dequant + polyphase FIR, one HBM pass.

    Args:
      voltages: int8 ``(nchan, ntime, npol=2, 2)`` with ``ntime`` a
        multiple of ``coeffs.shape[1]`` (GuppiRaw block layout).
      coeffs: ``(ntap, nfft)`` float32 window (fftshift sign already
        folded by the caller, as in :func:`channelize`).

    Returns planar ``(fr, fi)`` shaped ``(nchan, npol, nframes, nfft)`` in
    ``dtype`` — exactly ``pfb_frontend(moveaxis(dequantize(v)))``.
    """
    from jax.experimental import pallas as pl

    nchan, ntime, npol, ncomp = voltages.shape
    if npol != 2 or ncomp != 2:
        raise ValueError("pfb_dequant: npol=2 complex int8 input required")
    ntap, nfft = coeffs.shape
    if ntime % nfft:
        raise ValueError(f"ntime={ntime} not a multiple of nfft={nfft}")
    nblk = ntime // nfft
    nframes = nblk - ntap + 1
    if nframes < 1:
        raise ValueError(f"need >= {ntap} blocks of {nfft}, got {nblk}")
    esize = 2 if dtype == "bfloat16" else 4
    tile_j = pick_tile(nfft, nblk, nframes, ntap, esize, tile_j)
    if tile_j == 0:
        raise ValueError(
            f"pfb_dequant: no fine-channel tile of nfft={nfft} fits VMEM at "
            f"{nblk} blocks ({nframes} frames) — use the XLA path "
            f"(channelize pfb_kernel='xla'; 'auto' gates on pallas_pfb.fits)"
        )

    # Pack each sample's 4 int8 components into one int32 lane element —
    # a pure bitcast of the contiguous buffer (no data movement).
    packed = jax.lax.bitcast_convert_type(
        voltages.reshape(nchan, nblk, nfft, npol * ncomp), jnp.int32
    )  # (nchan, nblk, nfft)
    grid = (nchan, nfft // tile_j)
    out_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    kern = functools.partial(_kernel, nframes, ntap, out_dtype)
    out_shape = [
        jax.ShapeDtypeStruct((nchan, npol, nframes, nfft), out_dtype),
        jax.ShapeDtypeStruct((nchan, npol, nframes, nfft), out_dtype),
    ]
    fr, fi = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nblk, tile_j), lambda c, j: (c, 0, j)),
            pl.BlockSpec((ntap, tile_j), lambda c, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, npol, nframes, tile_j), lambda c, j: (c, 0, 0, j)),
            pl.BlockSpec((1, npol, nframes, tile_j), lambda c, j: (c, 0, 0, j)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(packed, coeffs)
    return fr, fi
