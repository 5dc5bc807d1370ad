"""Pallas TPU kernels fusing Stokes-I detection with the DFT tail.

Two kernels, one idea — detection is elementwise, so it can consume the
DFT's internal layouts directly and write each detected tile straight
into its natural-order position, instead of paying materialized untwist
transposes plus a separate detect pass:

- :func:`detect_untwist_i` consumes TWISTED (digit-permuted) spectra —
  the layout ``dft(order="twisted")`` emits for free — and untwists while
  detecting: the twisted axes ``(k1, k2, klast)`` map to natural order by
  axis REVERSAL (blit/ops/dft.untwist), so an output block over reversed
  axes is still a rectangular BlockSpec slice.  One pass replaces
  untwist+untwist+detect.  (The pure-XLA twisted experiment lost 20%
  because XLA lowered the reversed multi-axis power transpose badly,
  DESIGN.md §9 item 5; here the transpose happens tile-wise in VMEM.)

- :func:`tail2_detect_i` goes further: it fuses the final TWO
  Cooley-Tukey levels themselves (pallas_dft.dft_tail2's batched MXU
  dots), the inner untwist, Stokes-I detection across both
  polarizations, AND the channelizer's final product transpose into one
  pass — stage-1 spectra in, f32 natural-order power out, written
  directly in the filterbank product layout ``(frame, chan, fine)``.
  The bf16 tail spectra never exist in HBM and the product needs no
  further transpose.

:func:`detect_untwist_i` is Stokes I only; :func:`tail2_detect` covers
every ``detect_stokes_planar`` product (the polarization pair is
block-resident, so cross products cost only extra output planes).  Both
need ≤ 3 DFT factors (axis reversal == middle-preserving only up to
three digit axes); ineligible shapes keep the unfused path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

# Middle-axis tile: VMEM per instance ≈ npol·2·f1·tile_mid·flast·esize in
# + flast·tile_mid·f1·4 out.  At the hi-res shape (f1=128, flast=64,
# tile_mid=16, bf16): ~1 MB in + 0.5 MB out.
_DEF_TILE_MID = 16

# Per-instance VMEM budget (matches pallas_pfb's stance: leave headroom
# for double buffering on a ~16 MB part).
_VMEM_BUDGET = 6 << 20


def _fit_tile(factors, npol: int, esize: int, tile_mid: int) -> int:
    """Largest mid-axis tile (a divisor of mid, <= tile_mid) whose blocks
    fit the VMEM budget; 0 if none does even at tile_mid=1 — f1/flast are
    never tiled, so huge factor sizes must take the XLA path."""
    f1, flast = factors[0], factors[-1]
    n = 1
    for f in factors:
        n *= f
    mid = n // (f1 * flast)
    while mid % tile_mid:
        tile_mid //= 2
    while tile_mid >= 1:
        per = f1 * tile_mid * flast
        if per * (npol * 2 * esize) + per * 4 <= _VMEM_BUDGET:
            return tile_mid
        tile_mid //= 2
    return 0


def fits(factors, npol: int = 2, esize: int = 2,
         tile_mid: int = _DEF_TILE_MID) -> bool:
    """VMEM-fit gate for :func:`detect_untwist_i` — the check
    ``channelize`` runs before allowing ``detect_kernel="pallas"``."""
    return len(factors) <= 3 and _fit_tile(factors, npol, esize, tile_mid) > 0


def _detect_kernel(sr_ref, si_ref, o_ref):
    # sr/si: (1, npol, 1, f1, tile_mid, flast); o: (1, 1, flast, tile_mid, f1)
    sr = sr_ref[0, :, 0].astype(jnp.float32)
    si = si_ref[0, :, 0].astype(jnp.float32)
    p = (sr * sr + si * si).sum(axis=0)  # Stokes I over pols: (f1, mid, last)
    o_ref[0, 0] = jnp.transpose(p, (2, 1, 0))


def detect_untwist_i(
    sr: jax.Array,
    si: jax.Array,
    factors: Tuple[int, ...],
    *,
    tile_mid: int = _DEF_TILE_MID,
    interpret: bool = False,
) -> jax.Array:
    """Twisted planar spectra → natural-order Stokes-I power, one pass.

    Args:
      sr, si: ``(nchan, npol, nframes, n)`` spectra in the twisted layout
        of ``dft(order="twisted")`` (n = prod(factors)).
      factors: the DFT factorization that produced the twisted layout
        (at most 3 factors — axis reversal handles one middle axis).

    Returns float32 ``(nchan, nframes, n)`` natural-order total power.
    """
    from jax.experimental import pallas as pl

    nchan, npol, nframes, n = sr.shape
    if len(factors) > 3:
        raise ValueError("detect_untwist_i supports at most 3 DFT factors")
    if len(factors) == 1:
        p = sr.astype(jnp.float32) ** 2 + si.astype(jnp.float32) ** 2
        return p.sum(axis=1)
    f1, flast = factors[0], factors[-1]
    mid = n // (f1 * flast)
    sr6 = sr.reshape(nchan, npol, nframes, f1, mid, flast)
    si6 = si.reshape(nchan, npol, nframes, f1, mid, flast)
    tile_mid = _fit_tile(factors, npol, sr.dtype.itemsize, tile_mid)
    if tile_mid == 0:
        raise ValueError(
            f"detect_untwist_i: factor sizes {factors} exceed the VMEM "
            "budget (f1/flast are untiled) — use the XLA detect path"
        )

    in_spec = pl.BlockSpec((1, npol, 1, f1, tile_mid, flast),
                           lambda c, f, j: (c, 0, f, 0, j, 0))
    out_spec = pl.BlockSpec((1, 1, flast, tile_mid, f1),
                            lambda c, f, j: (c, f, 0, j, 0))
    out = pl.pallas_call(
        _detect_kernel,
        grid=(nchan, nframes, mid // tile_mid),
        in_specs=[in_spec, in_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(
            (nchan, nframes, flast, mid, f1), jnp.float32
        ),
        interpret=interpret,
    )(sr6, si6)
    # (flast, mid, f1) row-major IS the natural order: natural index
    # k = k1 + f1*(mid digits) + f1*mid*klast (axis reversal, dft.untwist).
    return out.reshape(nchan, nframes, n)


# nif (product-plane count) per detection product — mirrors
# blit.ops.channelize.detect_stokes_planar's table.
_STOKES_NIF = {"I": 1, "XX": 1, "YY": 1, "XXYY": 2, "full": 4, "IQUV": 4}


def _td_fit_tile(f1: int, f2: int, f3: int, npol: int, esize: int,
                 tile_f1: int, nif: int = 1) -> int:
    """Largest f1-axis tile (a divisor of f1, <= tile_f1) whose blocks fit
    the VMEM budget; 0 when even tile_f1=1 does not (huge f2·f3 panels take
    the unfused path).  Per instance: the planar input pair over
    ``npol*tile`` batch panels, ~6 live f32 scratch panels of the same
    extent, the f32 output tile (``nif`` product planes), and the constant
    DFT/twiddle matrices."""
    consts = (f2 * f2 + f3 * f3 + f2 * f3) * 8
    while tile_f1 >= 1:
        # The tile sits in the output block's sublane dim: mosaic accepts
        # it only 8-divisible or covering the whole f1 axis.
        legal = f1 % tile_f1 == 0 and (tile_f1 % 8 == 0 or tile_f1 == f1)
        if legal:
            per = npol * tile_f1 * f2 * f3
            need = (consts + per * (2 * esize + 6 * 4)
                    + nif * f2 * f3 * tile_f1 * 4)
            if need <= _VMEM_BUDGET:
                return tile_f1
        tile_f1 //= 2
    return 0


def tail2_detect_fits(factors, npol: int = 2, esize: int = 2,
                      tile_f1: int = 16, stokes: str = "I") -> bool:
    """VMEM-fit gate for :func:`tail2_detect` — the check ``channelize``
    runs before resolving the combined pallas tail+detect path."""
    if len(factors) != 3 or stokes not in _STOKES_NIF:
        return False
    if npol == 1 and stokes not in ("I", "XX"):
        return False
    f1, f2, f3 = factors
    return _td_fit_tile(f1, f2, f3, npol, esize, tile_f1,
                        _STOKES_NIF[stokes]) > 0


def _td_panels(f3: int, tile: int, esize: int = 4) -> Tuple[int, int]:
    """``(group, lane)`` of :func:`_td_rows`: the stage-1 rows a block
    interleaves (the sublanes of a tile, where the f1 tile is whole
    tiles) and the lanes a row of it holds."""
    lane = 128 if f3 % 128 == 0 else f3
    # A strided load takes 32-bit rows from ONE tile's width of lanes: an
    # f3 that is not whole tiles (no shape the chip runs) and bfloat16
    # spectra (tiled 16 rows deep; the opt-in) keep the (f1, f2, f3)
    # view, which XLA re-tiles as it did for every shape until PR 46.
    return (8 if tile % 8 == 0 and lane == 128 and esize == 4 else 1), lane


def _td_rows(u: jax.Array, group: int, lane: int) -> jax.Array:
    """Stage-1 spectra ``(nchan, npol, nframes, f1, m)`` as ``(nchan,
    npol, nframes, f1/g, (m/lane)*g, lane)``: row ``q * g + s`` of slab
    ``a`` is lanes ``[q*lane, (q+1)*lane)`` of stage-1 row ``a * g + s``.
    With ``g`` 8 and ``lane`` 128 both shapes tile (8, 128) into the SAME
    memory — :func:`blit.ops.pallas_pfb.pfb_dft1` wrote the tiles, this
    reads them where they lie — so on the chip this is no operation."""
    nchan, npol, nframes, f1, m = u.shape
    return jnp.transpose(
        u.reshape(nchan, npol, nframes, f1 // group, group, m // lane, lane),
        (0, 1, 2, 3, 5, 4, 6),
    ).reshape(nchan, npol, nframes, f1 // group, (m // lane) * group, lane)


def _td_kernel(npol, tile, stokes, f2, f3, group, xr_ref, xi_ref, w2r_ref,
               w2i_ref, w3r_ref, w3i_ref, tr_ref, ti_ref, o_ref):
    """DFT levels 2+3 + inner untwist + Stokes detect, one VMEM pass.

    Blocks: x (1, npol, 1, tile_f1/g, (m/lane)*g, lane), planar stage-1
    rows as :func:`_td_rows` views them: the ``(f2, f3)`` panel of one
    stage-1 row is every ``g * f3/lane``-th row of its slab, a strided
    load (``f3`` on the lanes: 128 of them at every shape the chip runs);
    o (1, nif, 1, f2, tile_f1, f3) — natural order up to ONE final
    transpose ((f2 f1, f3) → (f3, f2 f1)) that the caller leaves to XLA.
    Mosaic requires the last two block dims be (8, 128)-divisible or
    full: f1 is tiled, so it cannot sit in the lane dim, and lane-slice
    stores into a resident full-f1 block need 128-aligned offsets —
    keeping f3 (= 128 at every shape the chip runs) as the lane axis of
    what comes in AND of what goes out satisfies both, and the leftover
    move is in XLA's fastest transpose class rather than the slow fused
    detect pass (DESIGN.md §9).  The DFT body is
    pallas_dft._tail2_kernel's (batched dots and transposes only —
    mosaic rejects reshapes that collapse transposed vector axes); the
    epilogue forms the detection product planes
    (detect_stokes_planar's table) from the per-pol spectra.
    """
    from jax.experimental import pallas as pl

    # bf16 mode runs the dots at the MXU's full (4x) rate.  Accuracy: the
    # bf16-STORED spectra lose nothing (their products are exact in the
    # f32 accumulator), but the f32 DFT matrices and the post-twiddle
    # intermediates ARE rounded to bf16 first — the same operand rounding
    # XLA's precision=None einsums apply, i.e. default-precision grade,
    # not bit-identical to all-f32 dots.  The twiddle multiply stays f32
    # on the VPU.
    dot_dtype = xr_ref.dtype if xr_ref.dtype == jnp.bfloat16 else jnp.float32
    lane = xr_ref.shape[-1]
    pieces = f3 // lane

    def panels(ref):
        """(npol * tile, f2, f3): one stage-1 row's panel after the
        other, each read where the front kernel wrote it."""
        out = []
        for p in range(npol):
            for t in range(tile):
                slab, s = divmod(t, group)
                parts = [ref[0, p, 0, slab,
                             pl.ds(h * group + s, f2, stride=group * pieces)]
                         for h in range(pieces)]
                out.append(parts[0] if pieces == 1
                           else jnp.concatenate(parts, axis=-1))
        return jnp.stack(out).astype(dot_dtype)

    xr = panels(xr_ref)
    xi = panels(xi_ref)
    w2r = w2r_ref[...].astype(dot_dtype)
    w2i = w2i_ref[...].astype(dot_dtype)

    # Stage 2 down the panels' rows: W2 (f2k, f2l) @ panel (f2l, f3),
    # batched over the panels with the matrix repeated — nothing is
    # transposed on either side of it (timed on the chip against the
    # form that contracts the panel's sublane axis and transposes the
    # result back: 24.3 against 25.9 ms a 32-channel dispatch; PERF.md
    # section 6, PR 46).
    nb = xr.shape[0]
    w2rb = jnp.broadcast_to(w2r[None], (nb,) + w2r.shape)
    w2ib = jnp.broadcast_to(w2i[None], (nb,) + w2i.shape)

    def stage2(w, a):
        # (b, f2k, f2l) × (b, f2l, f3) → (b, f2k, f3)
        return jax.lax.dot_general(
            w, a, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    sr = stage2(w2rb, xr) - stage2(w2ib, xi)
    si = stage2(w2rb, xi) + stage2(w2ib, xr)
    tr = tr_ref[...][None]
    ti = ti_ref[...][None]
    ur = (sr * tr - si * ti).astype(dot_dtype)
    ui = (sr * ti + si * tr).astype(dot_dtype)
    w3r = w3r_ref[...].astype(dot_dtype)
    w3i = w3i_ref[...].astype(dot_dtype)

    def stage3(a, w):
        # (b, f2, f3j) × (f3j, f3k) → (b, f2, f3k): ONE matrix product of
        # all the panels' rows where they are whole tiles (leading-axis
        # collapse only: mosaic-safe).
        if f2 % 8 == 0:
            return jnp.dot(a.reshape(nb * f2, f3), w,
                           preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            a, w, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    ar = stage3(ur, w3r)
    bi = stage3(ui, w3i)
    br = stage3(ui, w3r)
    ai = stage3(ur, w3i)
    # (npol, tile, f2, f3) — leading-axis reshape: mosaic-safe.
    vr = (ar - bi).reshape(npol, tile, f2, f3)
    vi = (br + ai).reshape(npol, tile, f2, f3)
    if npol == 1:
        planes = [vr[0] * vr[0] + vi[0] * vi[0]]  # "I"/"XX"
    else:
        pxr, pyr = vr[0], vr[1]
        pxi, pyi = vi[0], vi[1]
        xx = pxr * pxr + pxi * pxi
        yy = pyr * pyr + pyi * pyi
        if stokes == "I":
            planes = [xx + yy]
        elif stokes == "XX":
            planes = [xx]
        elif stokes == "YY":
            planes = [yy]
        elif stokes == "XXYY":
            planes = [xx, yy]
        else:
            # X·conj(Y) cross products (detect_stokes_planar).
            xy_re = pxr * pyr + pxi * pyi
            xy_im = pxi * pyr - pxr * pyi
            if stokes == "full":
                planes = [xx, yy, xy_re, xy_im]
            else:  # IQUV
                planes = [xx + yy, xx - yy, 2 * xy_re, -2 * xy_im]
    # Natural order within a coarse channel is (k3, k2, k1); the block
    # keeps f3 in the lane dim — (f2, tile_f1, f3), the panels with their
    # two leading axes swapped, the lanes where they are — and the
    # caller's final XLA transpose moves k1 innermost.
    for i, p in enumerate(planes):
        o_ref[0, i, 0] = jnp.transpose(p, (1, 0, 2))


def tail2_detect(
    ur: jax.Array,
    ui: jax.Array,
    f2: int,
    f3: int,
    *,
    stokes: str = "I",
    tile_f1: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Fused DFT tail (levels 2+3 + inner untwist) + Stokes detection.

    Consumes the stage-1 outputs of blit/ops/pallas_pfb.pfb_dft1 and
    returns the detected product planes in the channelizer's layout — the
    bf16 tail spectra never hit HBM, and of the unfused path's three
    post-stage-1 passes (untwist, detect, product transpose) only one
    cheap XLA lane swap remains (the reference's detect runs in rawspec
    off-chip; here it is the epilogue of the last DFT pass).  All of
    detect_stokes_planar's products are supported — the polarization pair
    is already resident in the block, so cross products (full/IQUV) cost
    only the extra output planes.

    Args:
      ur, ui: ``(nchan, npol, nframes, f1, m)`` planar stage-1 spectra
        with ``m = f2·f3`` (f32 or bf16).
      f2, f3: the remaining Cooley-Tukey factors.
      stokes: detection product (see ``detect_stokes_planar``).

    Returns f32 ``(nframes, nif, nchan, f1·m)`` natural-order product
    planes — frame-major, ready to reshape to ``(time, nif, chan)``.
    """
    from jax.experimental import pallas as pl

    from blit.ops.dft import dft_matrices, twiddles

    nchan, npol, nframes, f1, m = ur.shape
    if m != f2 * f3:
        raise ValueError(f"tail2_detect: last axis {m} != {f2}*{f3}")
    if stokes not in _STOKES_NIF:
        raise ValueError(f"unknown stokes {stokes!r}")
    if npol == 1 and stokes not in ("I", "XX"):
        raise ValueError(f"stokes={stokes!r} needs 2 pols, got 1")
    nif = _STOKES_NIF[stokes]
    tile = _td_fit_tile(f1, f2, f3, npol, ur.dtype.itemsize, tile_f1, nif)
    if tile == 0:
        raise ValueError(
            f"tail2_detect: ({f2}, {f3}) panels exceed the VMEM budget — "
            "use the unfused tail (channelize tail_kernel='xla')"
        )
    group, lane = _td_panels(f3, tile, ur.dtype.itemsize)
    w2r, w2i = (jnp.asarray(a) for a in dft_matrices(f2, "float32"))
    w3r, w3i = (jnp.asarray(a) for a in dft_matrices(f3, "float32"))
    t2r, t2i = (jnp.asarray(a) for a in twiddles(f2, f3, "float32"))
    kern = functools.partial(_td_kernel, npol, tile, stokes, f2, f3, group)
    x_spec = pl.BlockSpec(
        (1, npol, 1, tile // group, (m // lane) * group, lane),
        lambda c, t, j: (c, 0, t, j, 0, 0))
    # f3 stays the lane dim (128-divisible or full); the tiled f1 sits in
    # the sublane dim where an 8-divisible tile is legal.
    o_spec = pl.BlockSpec((1, nif, 1, f2, tile, f3),
                          lambda c, t, j: (t, 0, c, 0, j, 0))
    w_spec2 = pl.BlockSpec((f2, f2), lambda c, t, j: (0, 0))
    w_spec3 = pl.BlockSpec((f3, f3), lambda c, t, j: (0, 0))
    t_spec = pl.BlockSpec((f2, f3), lambda c, t, j: (0, 0))
    out = pl.pallas_call(
        kern,
        grid=(nchan, nframes, f1 // tile),
        in_specs=[x_spec, x_spec, w_spec2, w_spec2, w_spec3, w_spec3,
                  t_spec, t_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct(
            (nframes, nif, nchan, f2, f1, f3), jnp.float32
        ),
        interpret=interpret,
    )(_td_rows(ur, group, lane), _td_rows(ui, group, lane),
      w2r, w2i, w3r, w3i, t2r, t2i)
    # One XLA transpose finishes natural order — (f3, f2, f1) row-major is
    # the per-channel natural index k = k1 + f1·k2 + f1·f2·k3, and (f2 f1,
    # f3) → (f3, f2 f1) is a plain 2-D transpose of whole tiles.  (A
    # pallas per-tile transpose that put k1 on the lanes was measured
    # SLOWER: 20.2 vs 11.9 ms at the production shape — mosaic's
    # lane⇄sublane relayout loses to XLA's transpose lowering here, so the
    # last move stays in XLA.)
    return jnp.transpose(out, (0, 1, 2, 5, 3, 4)).reshape(
        nframes, nif, nchan, f1 * m)


# Backwards-compatible alias for the Stokes-I-only round-3 entry point.
def tail2_detect_i(ur, ui, f2, f3, *, tile_f1: int = 16,
                   interpret: bool = False) -> jax.Array:
    """Stokes-I :func:`tail2_detect` returning ``(nframes, nchan, n)``."""
    out = tail2_detect(ur, ui, f2, f3, stokes="I", tile_f1=tile_f1,
                       interpret=interpret)
    return out[:, 0]
