"""Compression-aware readback narrowing (ISSUE 8 tentpole c).

Some products' on-disk form is narrower than the float32 the reduction
computes: SIGPROC ``.fil`` files carry ``nbits=8/16`` quantized samples
(the survey archive's dominant format — 4x/2x smaller), and the search
plane's ``.hits`` tables are packed int32 (blit/ops/pallas_dedoppler
already narrows those on device).  Where the device→host link is the
bottleneck, shipping float32 across it only to quantize on the host
wastes exactly the bytes the link can't afford.

This module is ONE quantization rule with two bit-identical
implementations:

- :func:`narrow_host` — NumPy, the synchronous path (and the writer-side
  rule for host-resident slabs).
- :func:`narrow_device` — jax.numpy, applied to the reduction output
  *before* D2H, so the async output plane reads back 1/4 (nbits=8) or
  1/2 (nbits=16) of the bytes.

Bit-identity holds because every step is an IEEE-exact f32 op on both
sides: ``y = clip(rint(x * scale + offset), 0, 2^nbits - 1)`` — one f32
multiply, one f32 add (both correctly rounded on CPU/TPU), ``rint``
round-half-to-even (NumPy's and XLA's shared rule), and a clip to the
integer range before an exact small-int cast.  ``tests/test_narrow.py``
pins host == device bitwise and async == sync product byte-identity;
that is what lets the narrowed readback stay the DEFAULT for nbits<32
products rather than an opt-in.  (Narrowings that do NOT commute with
the writer — e.g. reading back bf16 spectra for an f32 product — change
product bytes and stay opt-in; see DESIGN.md §8.)
"""

from __future__ import annotations

import numpy as np

NARROW_DTYPES = {8: np.uint8, 16: np.uint16, 32: np.float32}


def check_quant(nbits: int) -> None:
    if nbits not in NARROW_DTYPES:
        raise ValueError(
            f"nbits={nbits} unsupported (SIGPROC quantized products are "
            f"8/16/32)"
        )


def narrow_host(slab: np.ndarray, nbits: int, scale: float = 1.0,
                offset: float = 0.0) -> np.ndarray:
    """Quantize a float32 slab to the product's ``nbits`` integer form
    (identity for nbits=32).  The synchronous-path twin of
    :func:`narrow_device`."""
    check_quant(nbits)
    if nbits == 32:
        return np.asarray(slab, np.float32)
    lo, hi = np.float32(0.0), np.float32(2.0 ** nbits - 1)
    y = np.rint(
        np.asarray(slab, np.float32) * np.float32(scale) + np.float32(offset)
    )
    return np.clip(y, lo, hi).astype(NARROW_DTYPES[nbits])


def narrow_device(out, nbits: int, scale: float = 1.0,
                  offset: float = 0.0):
    """The on-device twin: same formula in jax.numpy over the (possibly
    still in-flight) reduction output, so only the narrowed bytes cross
    the D2H link.  Bitwise-identical to :func:`narrow_host` (module
    docstring)."""
    import jax.numpy as jnp

    check_quant(nbits)
    if nbits == 32:
        return out
    y = jnp.rint(
        out.astype(jnp.float32) * jnp.float32(scale) + jnp.float32(offset)
    )
    y = jnp.clip(y, jnp.float32(0.0), jnp.float32(2.0 ** nbits - 1))
    return y.astype(NARROW_DTYPES[nbits])
