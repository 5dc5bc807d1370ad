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

Both fronts take the samples as :func:`blit.ops.channelize.sample_words`
— ``(nchan, T)`` int32, one word a time sample — and :func:`pfb_dft1`,
the front every hi-res cell takes, reads them WHERE THEY LIE (PERF.md
section 6, PR 46).  On the chip the words are tiled 8 channels x 128
samples, which is, letter for letter, the memory of ``(nchan/8, nblk,
n1*8, m)`` tiled over its last two axes (row ``j1*8 + c``: channel ``c``
of the group, row ``j1`` of the block's ``(n1, m)`` matrix): the view
costs nothing, a kernel's block holds a group's 8 channels, and an
instance takes its own channel's rows by a strided load.  A stream's
``(tail, body)`` are two operands of that kernel, never put end to end.
(:func:`pfb_dequant` wants a channel's BLOCKS on the sublanes, which is
one re-tiling of the words; XLA writes it.)
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

# Per-instance VMEM budget of :func:`pfb_dequant`'s blocks, and of
# :func:`pfb_dft1`'s (whose block of words holds a group's 8 channels: at
# the hi-res shape 5.5 MiB of its 7.9).
_VMEM_BUDGET = 6 << 20
_FUSED1_BUDGET = 12 << 20

# What a kernel may hold at once: its blocks twice (the pipeline fetches
# the next while one is worked on) and room for the compiler's own.  A
# v5e's VMEM is 128 MiB; the compiler's default scope is 16.
_VMEM_LIMIT = 48 << 20

# Channels a block of words holds: the sublanes of the (8, 128) tiles the
# words lie in (:func:`_grouped`).
_GROUP = 8


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


def _channel_rows(ref, lead: tuple, nrows: int, group: int) -> jax.Array:
    """This instance's channel out of a block that holds ``group``
    channels' rows interleaved (row ``r * group + c``): ``ref[lead]``'s
    ``nrows`` rows of channel ``program_id(2)``, one strided load."""
    from jax.experimental import pallas as pl

    if group == 1:
        return ref[lead]
    return ref[lead + (pl.ds(pl.program_id(2), nrows, stride=group),)]


def _byte(x: jax.Array, i: int) -> jax.Array:
    """Little-endian byte ``i`` of each int32 (0: the first polarization's
    real part), sign-extended from int8."""
    return ((((x >> (8 * i)) & 0xFF) ^ 0x80) - 0x80).astype(jnp.float32)


def _kernel(nframes: int, ntap: int, out_dtype, v_ref, w_ref, or_ref, oi_ref):
    x = v_ref[0]  # (nblk, tile_j) int32 — packed (p0r, p0i, p1r, p1i) bytes
    w = w_ref[...]  # (ntap, tile_j) f32 (sign-folded window)

    def pfb(p: jax.Array) -> jax.Array:
        # p: (nblk, tile_j) f32 → (nframes, tile_j): windowed tap sums.
        acc = w[0] * p[0:nframes]
        for k in range(1, ntap):
            acc = acc + w[k] * p[k : k + nframes]
        return acc.astype(out_dtype)

    or_ref[0, 0] = pfb(_byte(x, 0))
    oi_ref[0, 0] = pfb(_byte(x, 1))
    or_ref[0, 1] = pfb(_byte(x, 2))
    oi_ref[0, 1] = pfb(_byte(x, 3))


def _fused1_kernel(nblks: Tuple[int, ...], ntap: int, n1: int, group: int,
                   out_dtype, *refs):
    """dequant + PFB + DFT stage 1 (+twiddle), one VMEM pass.

    Blocks (per grid instance, fine columns ``j2``-tiled):
      v:   (1, nblk_p, n1 * group, tile_m) int32, one per part: packed
           voltages, a group's channels interleaved row by row
      w:   (ntap, n1, tile_m)    f32    sign-folded window
      w1:  (n1, n1)              f32    stage-1 DFT matrix (re, im)
      tw:  (n1, tile_m)          f32    stage-1 twiddle (re, im)
      out: (1, npol, nframes, n1, tile_m) out_dtype (re, im)
    """
    v_refs = refs[:len(nblks)]
    w_ref, w1r_ref, w1i_ref, tr_ref, ti_ref, or_ref, oi_ref = refs[len(nblks):]
    # The parts end to end, block by block: (n1, tile_m) int32 each.
    blocks = [_channel_rows(ref, (0, b), n1, group)
              for ref, nblk in zip(v_refs, nblks) for b in range(nblk)]
    nframes = len(blocks) - ntap + 1
    w = w_ref[...]
    w1r = w1r_ref[...]
    w1i = w1i_ref[...]
    tr = tr_ref[...]
    ti = ti_ref[...]

    # bf16 mode runs the MXU at full rate: f32-input dots cost 4x on a
    # v5e, and bf16-grade multiplies are exactly what the XLA path's
    # precision=None einsums do anyway (channelize docstring).  The tap
    # accumulation and twiddle stay f32 on the VPU either way.
    dot_dtype = (
        jnp.bfloat16 if out_dtype == jnp.bfloat16 else jnp.float32
    )
    w1r = w1r.astype(dot_dtype)
    w1i = w1i.astype(dot_dtype)

    # p0r p0i p1r p1i, each a list over the blocks.
    planes = [[_byte(x, i) for x in blocks] for i in range(4)]
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


def _parts(words) -> Tuple[jax.Array, ...]:
    """``words`` as a tuple of consecutive runs of int32 words."""
    parts = tuple(words) if isinstance(words, (tuple, list)) else (words,)
    nchan = parts[0].shape[0]
    for p in parts:
        if p.ndim != 2 or p.dtype != jnp.int32 or p.shape[0] != nchan:
            raise ValueError(
                "npol=2 complex int8 samples as int32 words (nchan, T) "
                f"required, got {p.dtype}{p.shape}")
    return parts


def _group(nchan: int, cols: int) -> int:
    """Channels interleaved in one block of :func:`_grouped` rows: the 8
    of a tile where the words' tiles are whole ones of the view too;
    else 1, and XLA re-tiles the words (a shape no cell runs)."""
    return _GROUP if nchan % _GROUP == 0 and cols % 128 == 0 else 1


def _grouped(words: jax.Array, rows: int, cols: int) -> jax.Array:
    """``(nchan, nblk * rows * cols)`` words as ``(nchan/g, nblk, rows * g,
    cols)``: row ``r * g + c`` of block ``b`` is channel ``c`` of the
    group's ``[b, r]`` run of ``cols`` samples.  With ``g`` 8 and ``cols``
    a multiple of 128 both shapes tile (8, 128) into the SAME memory, so
    on the chip this is no operation."""
    nchan, nsamp = words.shape
    g = _group(nchan, cols)
    nblk = nsamp // (rows * cols)
    return jnp.transpose(
        words.reshape(nchan // g, g, nblk, rows, cols), (0, 2, 3, 1, 4)
    ).reshape(nchan // g, nblk, rows * g, cols)


def fused1_fits(nfft: int, nblk: int, ntap: int, n1: int,
                dtype: str = "float32") -> bool:
    """VMEM-fit gate for :func:`pfb_dft1` (see :func:`_fused1_tile`)."""
    return _fused1_tile(nfft, nblk, ntap, n1, dtype) > 0


def _fused1_tile(nfft: int, nblk: int, ntap: int, n1: int,
                 dtype: str, target: int = 512, group: int = _GROUP) -> int:
    esize = 2 if dtype == "bfloat16" else 4
    m = nfft // n1
    nframes = nblk - ntap + 1
    if group > 1:
        # A strided load takes its rows from ONE tile's width of lanes.
        target = 128
    for t in range(min(target, m), 0, -1):
        if m % t or (t % 128 and t != m):
            continue
        bts = t * (
            nblk * n1 * 4 * group  # packed input, a group's channels
            + ntap * n1 * 4        # window
            + 2 * n1 * 4           # twiddles
            + 2 * 2 * nframes * n1 * esize  # outputs (2 planes x 2 pols)
        ) + 2 * n1 * n1 * 4        # DFT matrices
        if bts <= (_FUSED1_BUDGET if group > 1 else _VMEM_BUDGET):
            return t
    return 0


def pfb_dft1(
    words,
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
    words in, stage-1 spectra (twiddled, ready for the remaining factors of
    :func:`blit.ops.dft._dft_rec`) out.

    Args:
      words: int32 ``(nchan, ntime)``, one word a time sample
        (:func:`blit.ops.channelize.sample_words`), or a sequence of such
        runs, each whole blocks of ``nfft``, that are consecutive in time
        (a stream's filter state and its new samples): each is an operand
        of the kernel and they are never written end to end.
      coeffs: ``(ntap, nfft)`` f32 sign-folded window.
      w1r, w1i: ``(n1, n1)`` stage-1 DFT matrix parts.
      tr, ti: ``(n1, nfft//n1)`` stage-1 twiddle parts.

    Returns ``(ur, ui)`` shaped ``(nchan, npol, nframes, n1, nfft//n1)``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    parts = _parts(words)
    nchan, npol = parts[0].shape[0], 2
    ntap, nfft = coeffs.shape
    n1 = w1r.shape[0]
    m = nfft // n1
    for p in parts:
        if p.shape[1] % nfft:
            raise ValueError(
                f"ntime={p.shape[1]} not a multiple of nfft={nfft}")
    nblks = tuple(p.shape[1] // nfft for p in parts)
    nblk = sum(nblks)
    nframes = nblk - ntap + 1
    group = _group(nchan, m)
    tile_m = _fused1_tile(nfft, nblk, ntap, n1, dtype, group=group)
    if tile_m == 0 or nframes < 1:
        raise ValueError(
            "pfb_dft1: no column tile fits VMEM at these shapes — use the "
            "unfused path"
        )

    wv = coeffs.reshape(ntap, n1, m)
    out_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    kern = functools.partial(_fused1_kernel, nblks, ntap, n1, group,
                             out_dtype)
    out_shape = [
        jax.ShapeDtypeStruct((nchan, npol, nframes, n1, m), out_dtype),
        jax.ShapeDtypeStruct((nchan, npol, nframes, n1, m), out_dtype),
    ]
    out_spec = pl.BlockSpec((1, npol, nframes, n1, tile_m),
                            lambda g, j, c: (g * group + c, 0, 0, 0, j))
    # The channel of a group is the fastest grid axis: a block of words is
    # fetched once for its ``group`` instances.
    ur, ui = pl.pallas_call(
        kern,
        grid=(nchan // group, m // tile_m, group),
        in_specs=[
            pl.BlockSpec((1, nb, n1 * group, tile_m),
                         lambda g, j, c: (g, 0, 0, j))
            for nb in nblks
        ] + [
            pl.BlockSpec((ntap, n1, tile_m), lambda g, j, c: (0, 0, j)),
            pl.BlockSpec((n1, n1), lambda g, j, c: (0, 0)),
            pl.BlockSpec((n1, n1), lambda g, j, c: (0, 0)),
            pl.BlockSpec((n1, tile_m), lambda g, j, c: (0, j)),
            pl.BlockSpec((n1, tile_m), lambda g, j, c: (0, j)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*(_grouped(p, n1, m) for p in parts), wv, w1r, w1i, tr, ti)
    return ur, ui


def pfb_dequant(
    words,
    coeffs: jax.Array,
    *,
    dtype: str = "float32",
    tile_j: int = _DEF_TILE_J,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused int8 dequant + polyphase FIR, one HBM pass.

    Args:
      words: int32 ``(nchan, ntime)``, one word a time sample of ``npol=2``
        complex int8 (:func:`blit.ops.channelize.sample_words`), ``ntime``
        a multiple of ``coeffs.shape[1]`` (GuppiRaw block layout) — or a
        sequence of such runs, consecutive in time (joined here: the
        kernel's taps slide over ONE run of blocks).
      coeffs: ``(ntap, nfft)`` float32 window (fftshift sign already
        folded by the caller, as in :func:`channelize`).

    Returns planar ``(fr, fi)`` shaped ``(nchan, npol, nframes, nfft)`` in
    ``dtype`` — exactly ``pfb_frontend(moveaxis(dequantize(v)))``.
    """
    from jax.experimental import pallas as pl

    gross = jnp.concatenate(_parts(words), axis=1)
    nchan, ntime = gross.shape
    npol = 2
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

    # A channel's blocks on the sublanes: ONE re-tiling of the words (8
    # channels lie there), which XLA writes; :func:`pfb_dft1`, the front
    # the hi-res cells take, writes none.
    packed = gross.reshape(nchan, nblk, nfft)
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
