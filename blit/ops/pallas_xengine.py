"""VMEM-resident FX-correlator X-engine (Pallas, packed visibility layout).

The un-parking of DESIGN.md §9's round-4 decision ("pallas X-engine parked
until a real workload's nant makes the tiles MXU-sized"): at the repo's own
array scale of 64 antennas the per-(chan, fine)
baseline matmul is (nant·npol)² = 128² — exactly MXU-sized — and the
measured whole-correlate rates at that shape justify the kernel
(interleaved A/B on the chip, nant=64 nchan=16
nfft=512 nblk=64):

    einsum X-engine            21.1 GB/s input (median)
    pallas ft=8 (this kernel)  25.1 GB/s  (+19%)
    pallas ft=16               24.4 GB/s
    pallas ft=32               VMEM OOM (19.8 MB scoped > 16 MB)

XLA-level alternatives measured first and at parity (the same A/B:
packed-layout einsums 0.996x, bf16-cast operands 0.996x), so the win here
is genuinely the single-pass VMEM residency: per grid step both planes'
``(ft, nap, nframes)`` spectra blocks are loaded once and all four real
products run as batched ``dot_general``s without re-touching HBM — the
4-einsum path reads the spectra planes once per product pair.

Layout: the kernel emits visibilities PACKED as ``(nchan, nfft, ap, bq)``
(``ap`` = antenna-major antenna·pol).  Transposing to the standard
``(a, b, c, f, p, q)`` layout would move 2×vis-size bytes and eat the win,
so the packed layout is an opt-in output format of
:func:`blit.parallel.correlator.correlate` — integrations and most
downstream reductions are layout-indifferent.

Eligibility: ``nap >= 128`` (MXU-sized tiles — below that the einsum path
measures faster: 49 GB/s X-engine stage at nap=16 vs the kernel's win
shape) and ``nfft % ft == 0``.  Off-TPU the caller falls back to packed
einsums (same layout, golden-identical); ``interpret=True`` exists for
unit tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from blit.ops.dft import Planar

FT_DEFAULT = 8

# Scoped-VMEM model for eligibility: block bytes double-buffer, and the
# compiler's scoped allocation runs ~1.6x the naive block arithmetic
# (measured: ft=32 at nframes=61 is 12.4 MB naive but OOM'd at 19.8 MB
# against the 16 MB limit).  The factor carries margin on top of the
# measurement so admitted shapes sit clearly inside the limit.
_VMEM_LIMIT = 16 << 20
_SCOPED_FACTOR = 1.7


def eligible(
    nap: int,
    nfft: int,
    nframes: int,
    ft: int = FT_DEFAULT,
    itemsize: int = 4,
) -> bool:
    """Shapes where the kernel measured faster than the einsum X-engine
    AND fits scoped VMEM (long time segments grow the input blocks
    linearly with ``nframes`` — those fall back to the einsum path
    instead of compile-failing, the channelize.py fits() convention).

    ``itemsize`` is the SPECTRA element size: bf16-staged spectra halve
    the input blocks, so longer segments stay eligible than with f32.
    Outputs always accumulate f32.
    """
    in_bytes = 2 * (ft * nap * nframes) * itemsize
    out_bytes = 2 * (ft * nap * nap) * 4
    scoped = (in_bytes + out_bytes) * 2 * _SCOPED_FACTOR
    return (
        nap >= 128
        and nap % 8 == 0
        and nfft % ft == 0
        and scoped <= _VMEM_LIMIT
    )


def pick_ft(
    nap: int, nfft: int, nframes: int, itemsize: int = 4
) -> Optional[int]:
    """Largest fine tile in {8, 4} that divides ``nfft`` and fits the
    VMEM model, or None (→ einsum path).  ft=8 measured best at nap=128
    (25.1 vs ft=16's 24.4 GB/s); larger nap or longer segments shrink
    the tile one halving instead of falling off the kernel entirely.
    Tiles below 4 are unmeasured territory — those shapes take the
    einsum path rather than extrapolate."""
    for ft in (FT_DEFAULT, 4):
        if eligible(nap, nfft, nframes, ft=ft, itemsize=itemsize):
            return ft
    return None


def _kernel(ar_ref, ai_ref, vr_ref, vi_ref):
    ar = ar_ref[0]  # (ft, nap, nframes)
    ai = ai_ref[0]
    # Contract frames, batch fine channels: (ft, nap, nap) per product.
    # f32 accumulation regardless of operand dtype (bf16 spectra halve
    # the kernel's reads and VMEM blocks; the MXU multiplies at bf16
    # precision either way — the TPU's default matmul precision).
    dn = (((2,), (2,)), ((0,), (0,)))
    kw = dict(preferred_element_type=jnp.float32)
    rr = jax.lax.dot_general(ar, ar, dn, **kw)
    ii = jax.lax.dot_general(ai, ai, dn, **kw)
    ir = jax.lax.dot_general(ai, ar, dn, **kw)
    ri = jax.lax.dot_general(ar, ai, dn, **kw)
    vr_ref[0] = rr + ii
    vi_ref[0] = ir - ri


@functools.partial(jax.jit, static_argnames=("ft", "interpret"))
def xengine_packed(
    sr: jax.Array,
    si: jax.Array,
    *,
    ft: int = FT_DEFAULT,
    interpret: bool = False,
) -> Planar:
    """Cross-multiply + time-integrate planar spectra, packed output.

    ``s``: (nant, nchan, npol, nframes, nfft) planar pair →
    visibilities ``(nchan, nfft, nap, nap)`` as an f32 (re, im) pair with
    ``V[c, f, ap, bq] = Σ_t S_a S_b*`` (``ap`` antenna-major).  One XLA
    transpose packs the spectra to ``(nchan, nfft, nap, nframes)``; the
    kernel then reads every spectra byte exactly once.
    """
    nant, nchan, npol, nframes, nfft = sr.shape
    nap = nant * npol
    if nfft % ft:
        raise ValueError(f"nfft={nfft} must divide into fine tiles of {ft}")

    def pack(s):
        return jnp.transpose(s, (1, 4, 0, 2, 3)).reshape(
            nchan, nfft, nap, nframes
        )

    spec_in = pl.BlockSpec((1, ft, nap, nframes), lambda c, f: (c, f, 0, 0))
    spec_out = pl.BlockSpec((1, ft, nap, nap), lambda c, f: (c, f, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(nchan, nfft // ft),
        in_specs=[spec_in, spec_in],
        out_specs=[spec_out, spec_out],
        out_shape=[
            jax.ShapeDtypeStruct((nchan, nfft, nap, nap), jnp.float32),
            jax.ShapeDtypeStruct((nchan, nfft, nap, nap), jnp.float32),
        ],
        interpret=interpret,
    )(pack(sr), pack(si))
