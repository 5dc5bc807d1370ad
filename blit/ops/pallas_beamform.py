"""VMEM-resident fused beamform+detect (Pallas, packed layout).

The einsum beamform path materializes the (nbeam, nchan, ntime, npol)
beam-voltage planes in HBM (written by the contraction, read back by
detection) — at the bench shape that is 2x 268 MB of pure intermediate
traffic for a 33 MB detected product.  This kernel keeps the beams in
VMEM: per (chan, time-tile) grid step it holds the channel's weights and
one voltage tile, forms the four real products as dot_generals, squares,
and integrates — voltages are read once, only integrated power is
written.

Measured (interleaved A/B on the chip, nant=64
nbeam=64 nchan=64 ntime=8192 nint=8, f32-equivalent input GB/s,
steady-state rounds):

    einsum bf16 planes      ~76         this kernel bf16  ~160  (2.1x)
    einsum f32 planes       ~59         this kernel f32   ~125  (2.1x)
    tile=2048: 146 (worse than 1024); first call on the rig pays a
    one-off ~19 ms allocation artifact, steady-state thereafter.
    Max rel err vs the einsum path: 4.9e-3 (same bf16 MXU multiplies,
    different reduce orders).

Mosaic shapes this kernel's two non-obvious moves:

- time integration contracts the LANE axis, and lane-axis reshapes are
  rejected — so integration is a matmul against a static 0/1
  block-diagonal S (tile, tile/nint) on the MXU (FLOPs are free next to
  the saved HBM pass);
- the output block's last dim must be 128-divisible, so the tile is
  ``nint * 128`` (tile/nint = one 128-lane block per grid step).

Layouts are PACKED, chan-major (the `beamform(layout="chan")` opt-in,
mirroring the correlator's `vis_layout="packed"`): voltages
``(nchan, nant, npol, ntime)``, weights ``(nchan, nbeam, nant)``, output
``(nchan, nbeam, npol, ntime // nint)``.

Fusing detection under a psum is only valid when the antenna axis is
WHOLE on each chip (power of the sum != sum of powers): the caller gates
on mesh axis size 1 and falls back to einsums + psum + detect otherwise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from blit.ops.dft import Planar

_VMEM_LIMIT = 16 << 20
_SCOPED_FACTOR = 1.7  # measured headroom convention (pallas_xengine)


def pick_tile(
    nant: int,
    nbeam: int,
    npol: int,
    ntime: int,
    nint: int,
    itemsize: int = 4,
) -> Optional[int]:
    """The time tile for :func:`fused_beamform_detect`, or None when the
    kernel does not apply (→ einsum path).  tile = nint*128 satisfies the
    output-lane rule by construction; eligibility needs it to divide
    ``ntime`` and fit the VMEM model."""
    if nint < 1:
        return None
    tile = nint * 128
    if ntime % tile or nbeam % 8:
        return None
    in_bytes = 2 * nant * npol * tile * itemsize  # both voltage planes
    w_bytes = 2 * nbeam * nant * itemsize
    s_bytes = tile * (tile // nint) * 4
    # f32 intermediates (4 products + 2 combines + power) live in VMEM
    # scratch; budget the 4 persistent-ish ones.
    mid_bytes = 4 * nbeam * npol * tile * 4
    out_bytes = nbeam * npol * (tile // nint) * 4
    scoped = (
        (in_bytes + out_bytes) * 2 + w_bytes + s_bytes + mid_bytes
    ) * _SCOPED_FACTOR
    return tile if scoped <= _VMEM_LIMIT else None


def _kernel(vr_ref, vi_ref, wr_ref, wi_ref, s_ref, out_ref):
    vr = vr_ref[0]  # (nant, npol, tile)
    vi = vi_ref[0]
    wr = wr_ref[0]  # (nbeam, nant)
    wi = wi_ref[0]
    dn = (((1,), (0,)), ((), ()))  # W (b,a) x V (a,p,t) -> (b,p,t)
    kw = dict(preferred_element_type=jnp.float32)
    rr = jax.lax.dot_general(wr, vr, dn, **kw)
    ii = jax.lax.dot_general(wi, vi, dn, **kw)
    ri = jax.lax.dot_general(wr, vi, dn, **kw)
    ir = jax.lax.dot_general(wi, vr, dn, **kw)
    br = rr - ii
    bi = ri + ir
    power = br * br + bi * bi  # (nbeam, npol, tile) f32
    out_ref[0] = jax.lax.dot_general(
        power, s_ref[...], (((2,), (0,)), ((), ())), **kw
    )


@functools.partial(jax.jit, static_argnames=("nint", "tile", "interpret"))
def fused_beamform_detect(
    vr: jax.Array,
    vi: jax.Array,
    wr: jax.Array,
    wi: jax.Array,
    *,
    nint: int,
    tile: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Packed-layout fused beamform + detect + integrate.

    ``v``: (nchan, nant, npol, ntime) planar pair; ``w``: (nchan, nbeam,
    nant) planar pair → integrated power (nchan, nbeam, npol,
    ntime//nint) float32.
    """
    nchan, nant, npol, ntime = vr.shape
    nbeam = wr.shape[1]
    if tile is None:
        tile = pick_tile(nant, nbeam, npol, ntime, nint,
                         itemsize=vr.dtype.itemsize)
        if tile is None:
            raise ValueError(
                "shape not eligible for the fused kernel (ntime must "
                "divide into nint*128 tiles inside VMEM); use the einsum "
                "path"
            )
    # Explicit tiles are validated for the SILENT failure modes: an
    # undivided ntime leaves output tail blocks unwritten (garbage), a
    # tile not divisible by nint splits integration windows.  Lane/
    # sublane rules (128 | tile/nint, 8 | nbeam on TPU) are left to
    # Mosaic, whose native refusal is loud — and interpret-mode tests
    # legitimately run smaller tiles.
    if nint < 1 or tile % nint or ntime % tile:
        raise ValueError(
            f"tile={tile} invalid for nint={nint}, ntime={ntime}: "
            "need nint | tile and tile | ntime"
        )
    nto = tile // nint
    spec_v = pl.BlockSpec((1, nant, npol, tile), lambda c, t: (c, 0, 0, t))
    spec_w = pl.BlockSpec((1, nbeam, nant), lambda c, t: (c, 0, 0))
    spec_s = pl.BlockSpec((tile, nto), lambda c, t: (0, 0))
    spec_o = pl.BlockSpec((1, nbeam, npol, nto), lambda c, t: (c, 0, 0, t))
    # S stays f32: the power operand is f32 and 0/1 entries are exact.
    S = np.zeros((tile, nto), np.float32)
    for j in range(nto):
        S[j * nint:(j + 1) * nint, j] = 1.0
    return pl.pallas_call(
        _kernel,
        grid=(nchan, ntime // tile),
        in_specs=[spec_v, spec_v, spec_w, spec_w, spec_s],
        out_specs=spec_o,
        out_shape=jax.ShapeDtypeStruct(
            (nchan, nbeam, npol, ntime // nint), jnp.float32
        ),
        interpret=interpret,
    )(vr, vi, wr, wi, jnp.asarray(S))


def pack_voltages(vr, vi) -> Planar:
    """API-layout (nant, nchan, ntime, npol) planes → packed
    (nchan, nant, npol, ntime) (one transpose pass; prefer loading
    packed directly via ``load_antennas_mesh(layout="chan")``)."""
    return (
        jnp.transpose(vr, (1, 0, 3, 2)),
        jnp.transpose(vi, (1, 0, 3, 2)),
    )


def pack_weights(wr, wi) -> Planar:
    """(nbeam, nant, nchan) weight planes → packed (nchan, nbeam, nant)
    (tiny: one pass over ~MBs)."""
    return (
        jnp.transpose(wr, (2, 0, 1)),
        jnp.transpose(wi, (2, 0, 1)),
    )
