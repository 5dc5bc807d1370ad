"""Taylor-tree dedoppler: drift-rate transform + on-device hit extraction.

The mission downstream of every BL filterbank is a drift-rate search
(turboSETI-style: Enriquez & Price 2019): for each candidate drift rate,
sum the power along the corresponding sloped path through the
(time, frequency) waterfall and look for outliers.  Brute force costs
O(T·D·F) sums for T spectra, D drifts, F channels; the Taylor tree
(Taylor 1974 — the same log₂-stage shift-and-add that powers incoherent
dedispersion) shares partial path sums between neighbouring drifts and
does all D = T drifts in O(T·log₂T·F).

Layout / path convention (pinned — the golden tests and the ``.hits``
product shape both depend on it):

- input is ``(T, F)`` float32 power with T a power of two, time-major;
- output row ``d`` is the sum over the tree's drift-``d`` path ANCHORED
  AT t=0: ``out[d, f] = Σ_t x[t, f + shift(d, t)]`` with
  ``shift(d, t)`` given by :func:`tree_path_shift` (the classic tree
  recursion: each half inherits drift ``d>>1``; the second half starts
  offset by ``(d+1)>>1``).  Positive drift moves toward increasing
  channel index; negative drifts come from running the tree over the
  frequency-flipped array (:func:`drift_spectra`).
- paths running off the band edge read zeros (the frequency axis is
  zero-padded by T on the high side; wrap-around contamination from the
  rolls provably never reaches the first F columns because every path's
  total shift is < T).

Three execution paths, byte-identical where they overlap:

- the PURE-LAX reference (``kernel="reference"``) — rolls + adds only,
  runs everywhere (the tier-1 CPU path);
- the Pallas TPU kernel (``kernel="pallas"``) — the same stage
  recursion on VMEM-resident frequency tiles (halo >= T columns of real
  neighbour data), grid over tiles; ``interpret=True`` runs it on CPU
  for tests.  Both paths perform the identical per-element add sequence
  (one add per stage), so results agree BITWISE, not just approximately.
- ``kernel="auto"`` resolves to pallas on TPU backends when
  :func:`fits` passes, else reference.

:func:`dedoppler_hits` is the full on-device search step: tree (both
drift signs) → per-drift-row SNR normalization → drift-range mask →
device-side threshold + per-band top-k → one packed int32 array (the
single-fetch output shape the async output plane wants).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blit.device import TPU_BACKEND

# Per-instance VMEM budget for the tiled kernel (pallas_detect's stance:
# leave headroom for double buffering on a ~16 MB part).
_VMEM_BUDGET = 6 << 20

# Default frequency-tile width for the pallas path (lane-aligned).
_DEF_TILE = 512

# The stage loops are python-unrolled (T rows per stage, log2 T stages);
# beyond this the trace/compile cost stops being worth it and callers
# should split the window.
MAX_WINDOW = 1024

# Encoded hit-table columns (:func:`dedoppler_hits` packed output):
# [snr_bits(f32), power_bits(f32), drift_bins(i32), chan(i32)].
HIT_PACK_COLS = 4


# Kernel resolution of the most recent taylor_tree TRACE (the
# blit.ops.channelize._LAST_PLAN convention): 'auto' must be attributable.
_LAST_PLAN: dict = {}


def last_dedoppler_plan() -> dict:
    """Which drift-transform kernel the most recent :func:`taylor_tree`
    trace resolved to (``{"kernel": "pallas" | "reference", ...}``; empty
    until a trace happens — a jit cache hit does not refresh it)."""
    return dict(_LAST_PLAN)


def tree_path_shift(d: int, t: int, T: int) -> int:
    """The frequency shift the tree's drift-``d`` path applies at time
    ``t`` over a window of ``T`` spectra — the EXACT path the transform
    sums, host-side (the brute-force golden reference builds on this).

    Recursion mirrors the tree: the first half-window inherits internal
    drift ``d>>1``; the second half starts ``(d+1)>>1`` bins up and
    inherits the same internal drift (``(d+1)>>1 + d>>1 == d``)."""
    if T == 1:
        return 0
    half = T // 2
    if t < half:
        return tree_path_shift(d >> 1, t, half)
    return ((d + 1) >> 1) + tree_path_shift(d >> 1, t - half, half)


def _check_window(T: int) -> None:
    if T < 2 or T & (T - 1):
        raise ValueError(f"window_spectra must be a power of two >= 2, got {T}")
    if T > MAX_WINDOW:
        raise ValueError(
            f"window_spectra {T} > {MAX_WINDOW}: the unrolled tree stages "
            "stop being compile-affordable — search shorter windows"
        )


def _tree_stages(buf: jax.Array, T: int) -> jax.Array:
    """The reference tree body: ``(T, Fp)`` padded power → ``(T, Fp)``
    drift sums (drifts 0..T-1, module-docstring convention).  Rolls +
    adds only, ONE add per element per stage — the add sequence
    :func:`_tree_kernel` repeats on refs, so both execution paths produce
    bitwise-identical sums."""
    # (nblocks, L, Fp) block view; stage L -> 2L merges block pairs.
    buf = buf[:, None, :]  # (T, 1, Fp)
    L = 1
    while L < T:
        top = buf[0::2]  # (nb2, L, Fp)
        bot = buf[1::2]
        rows = []
        for d in range(2 * L):
            s = (d + 1) >> 1
            r2 = bot[:, d >> 1]
            if s:
                r2 = jnp.roll(r2, -s, axis=-1)
            rows.append(top[:, d >> 1] + r2)
        buf = jnp.stack(rows, axis=1)  # (nb2, 2L, Fp)
        L *= 2
    return buf[0]


def _halo(T: int) -> int:
    """Halo columns per tile: T real neighbour columns, rounded up to a
    whole number of 128-lane vregs so the in-kernel lane rotations stay
    aligned."""
    return -(-T // 128) * 128


def fits(T: int, tile: int = _DEF_TILE) -> bool:
    """VMEM-fit gate for the tiled pallas kernel: two (T, tile+halo) f32
    ping-pong buffers plus the double-buffered input, halo and output
    blocks must fit the budget, and the halo must tile the body."""
    if T < 2 or T & (T - 1) or T > MAX_WINDOW:
        return False
    if tile % _halo(T):
        return False
    per = T * (tile + _halo(T)) * 4
    return 6 * per <= _VMEM_BUDGET


def _bit_reverse_rows(x: jax.Array) -> jax.Array:
    """Rows of ``(T, F)`` into bit-reversed time order (T a power of two):
    the tree pairs rows that differ in the LOWEST time bit first, and in
    this order every stage's pairs are two contiguous half-slabs."""
    T, F = x.shape
    k = T.bit_length() - 1
    x = x.reshape((2,) * k + (F,))
    return x.transpose(tuple(range(k - 1, -1, -1)) + (k,)).reshape(T, F)


def _tree_kernel(T, x_ref, h_ref, o_ref, a_ref, b_ref):
    """One frequency tile of the tree on refs.  ``x_ref`` (T, tile) body
    and ``h_ref`` (T, halo) right-neighbour columns arrive with rows in
    bit-reversed time order; ``a_ref``/``b_ref`` are (T, tile+halo)
    ping-pong scratch.  Rows are laid out ``d * nb + p`` (drift-major, ``p``
    the bit-reversed block index), so stage L -> 2L reads each drift
    half's even blocks as one contiguous slab and its odd blocks as the
    next — static slices only, and the same one add per element per stage
    as :func:`_tree_stages`.  A lane rotation stands in for the roll: it
    wraps, but no path's total shift reaches the halo's width, so the
    wrapped columns never enter the first ``tile`` outputs."""
    from jax.experimental.pallas import tpu as pltpu

    tile = x_ref.shape[1]
    W = a_ref.shape[1]
    a_ref[:, :tile] = x_ref[...]
    a_ref[:, tile:] = h_ref[...]
    src, dst = a_ref, b_ref
    L, nb = 1, T
    while L < T:
        half = nb // 2
        for d in range(2 * L):
            s = (d + 1) >> 1
            base = (d >> 1) * nb
            bot = src[base + half:base + nb, :]
            if s:
                bot = pltpu.roll(bot, W - s, 1)
            dst[d * half:(d + 1) * half, :] = src[base:base + half, :] + bot
        src, dst = dst, src
        L, nb = 2 * L, half
    o_ref[...] = src[:, :tile]


def taylor_tree(
    power: jax.Array,
    *,
    kernel: str = "auto",
    interpret: bool = False,
    tile: int = _DEF_TILE,
) -> jax.Array:
    """Drift-rate transform of one window: ``(T, F)`` float32 power →
    ``(T, F)`` path sums for drifts 0..T-1 (module docstring).

    ``kernel``: "reference" (pure lax), "pallas" (tiled TPU kernel;
    ``interpret=True`` for CPU tests), or "auto".
    """
    T, F = power.shape
    _check_window(T)
    power = power.astype(jnp.float32)
    if kernel == "auto":
        # interpret=True is a request to EXERCISE the pallas kernel (CPU
        # smoke tests) — auto must not silently resolve it away to the
        # reference path.
        want_pallas = interpret or jax.default_backend() == TPU_BACKEND
        kernel = "pallas" if want_pallas and fits(T, tile) else "reference"
    _LAST_PLAN.clear()
    _LAST_PLAN.update(kernel=kernel, window_spectra=T, interpret=interpret)
    if kernel == "reference":
        xp = jnp.pad(power, ((0, 0), (0, T)))
        return _tree_stages(xp, T)[:, :F]
    if kernel != "pallas":
        raise ValueError(f"unknown dedoppler kernel {kernel!r}")
    if not fits(T, tile):
        raise ValueError(
            f"taylor_tree: (T={T}, tile={tile}) exceeds the VMEM budget — "
            "use kernel='reference' or a smaller tile"
        )
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ntiles = -(-F // tile)
    halo = _halo(T)
    # Pad so every tile has a full `tile` body plus `halo` columns of
    # real neighbour data (zeros past the band edge); the halo of tile i
    # is the head of tile i+1, read through a second view of the array.
    xp = _bit_reverse_rows(
        jnp.pad(power, ((0, 0), (0, ntiles * tile + halo - F))))
    out = pl.pallas_call(
        functools.partial(_tree_kernel, T),
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec((T, tile), lambda i: (0, i)),
            pl.BlockSpec((T, halo), lambda i: (0, (i + 1) * (tile // halo))),
        ],
        out_specs=pl.BlockSpec((T, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((T, ntiles * tile), jnp.float32),
        scratch_shapes=[pltpu.VMEM((T, tile + halo), jnp.float32)] * 2,
        interpret=interpret,
    )(xp, xp)
    return out[:, :F]


def drift_spectra(
    power: jax.Array,
    *,
    kernel: str = "auto",
    interpret: bool = False,
    tile: int = _DEF_TILE,
) -> jax.Array:
    """Both-sign drift transform: ``(T, F)`` → ``(2T-1, F)`` with row
    ``i`` holding drift ``i - (T-1)`` bins per window (negative = toward
    decreasing channel index).  Row ``T-1`` (drift 0) is shared between
    the two tree passes and appears once."""
    T = power.shape[0]
    kw = dict(kernel=kernel, interpret=interpret, tile=tile)
    pos = taylor_tree(power, **kw)  # drifts 0..T-1
    neg = taylor_tree(power[:, ::-1], **kw)[:, ::-1]  # drifts 0..-(T-1)
    # neg reversed rows: drifts -(T-1)..-1 (drop its drift-0 duplicate).
    return jnp.concatenate([neg[:0:-1], pos], axis=0)


def drift_rates(T: int) -> np.ndarray:
    """The drift values (bins per window) of :func:`drift_spectra` rows."""
    return np.arange(-(T - 1), T)


def snr_normalize(dd: jax.Array) -> jax.Array:
    """Per-drift-row SNR: ``(dd - mean_f) / std_f`` over the frequency
    axis.  Row-wise because each drift sums a different number of
    in-band bins near the edges; deterministic (single fused pass)."""
    mu = jnp.mean(dd, axis=1, keepdims=True)
    sd = jnp.std(dd, axis=1, keepdims=True)
    return (dd - mu) / jnp.maximum(sd, 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=(
        "top_k", "nbands", "max_drift_bins", "kernel", "interpret", "tile",
    ),
)
def dedoppler_hits(
    power: jax.Array,
    snr_threshold: jax.Array,
    *,
    top_k: int = 8,
    nbands: int = 1,
    max_drift_bins: Optional[int] = None,
    kernel: str = "auto",
    interpret: bool = False,
    tile: int = _DEF_TILE,
) -> jax.Array:
    """The on-device search step: one window of power → packed top hits.

    ``power`` is ``(T, F)`` float32; ``snr_threshold`` a scalar (dynamic,
    so re-tuning it never recompiles).  The frequency axis is split into
    ``nbands`` equal bands (``F % nbands == 0``) and the strongest
    ``top_k`` (drift, channel) cells are extracted PER BAND — the
    waterfall never leaves the device, only ``nbands·top_k`` packed
    records do.

    Jitted at module level with the knobs static (the channelize
    convention): compilations cache PROCESS-wide, so the service layer's
    fresh-reducer-per-request pattern reuses one compiled program, and
    the dynamic ``snr_threshold`` retunes without recompiling.

    Returns int32 ``(nbands, top_k, 4)``: ``[snr_bits, power_bits,
    drift_bins, chan]`` sorted by descending SNR within each band.
    Entries below the threshold are sentineled on device (snr bits set
    to -inf) so the host-side decode just drops non-finite rows —
    device-side thresholding without a data-dependent output shape.
    """
    T, F = power.shape
    if F % nbands:
        raise ValueError(f"nbands={nbands} does not divide F={F}")
    dd = drift_spectra(power, kernel=kernel, interpret=interpret, tile=tile)
    snr = snr_normalize(dd)  # (D, F), D = 2T-1
    D = 2 * T - 1
    if max_drift_bins is not None:
        keep = np.abs(drift_rates(T)) <= max_drift_bins
        snr = jnp.where(jnp.asarray(keep)[:, None], snr, -jnp.inf)
    Fb = F // nbands
    # (D, nbands, Fb) -> (nbands, D*Fb): top_k over every (drift, chan)
    # cell of each band.
    flat_snr = snr.reshape(D, nbands, Fb).transpose(1, 0, 2).reshape(
        nbands, D * Fb
    )
    flat_pow = dd.reshape(D, nbands, Fb).transpose(1, 0, 2).reshape(
        nbands, D * Fb
    )
    vals, idx = jax.lax.top_k(flat_snr, top_k)  # (nbands, k)
    pwr = jnp.take_along_axis(flat_pow, idx, axis=1)
    drift = idx // Fb - (T - 1)
    chan = idx % Fb + jnp.arange(nbands, dtype=idx.dtype)[:, None] * Fb
    # Device-side threshold: sub-threshold entries become -inf sentinels
    # the host decode discards.
    vals = jnp.where(vals >= snr_threshold, vals, -jnp.inf)
    return jnp.stack(
        [
            jax.lax.bitcast_convert_type(vals, jnp.int32),
            jax.lax.bitcast_convert_type(pwr, jnp.int32),
            drift.astype(jnp.int32),
            chan.astype(jnp.int32),
        ],
        axis=-1,
    )


def brute_force_dedoppler(power: np.ndarray) -> np.ndarray:
    """O(T·D·F) host reference summing the EXACT tree paths
    (:func:`tree_path_shift`) in float64 — the golden oracle for the
    transform (zero outside the band, like the padded tree)."""
    T, F = power.shape
    out = np.zeros((T, F), np.float64)
    x = power.astype(np.float64)
    for d in range(T):
        for t in range(T):
            s = tree_path_shift(d, t, T)
            if s < F:
                out[d, : F - s] += x[t, s:]
    return out


def unpack_hits(
    packed: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Decode a fetched :func:`dedoppler_hits` array → parallel arrays
    ``(snr, power, drift_bins, chan, band)`` with the -inf sentinels
    (device-side threshold rejects) already dropped, order preserved
    (band-major, SNR-descending within a band — deterministic)."""
    packed = np.asarray(packed)
    nbands, k, _ = packed.shape
    flat = packed.reshape(nbands * k, HIT_PACK_COLS)
    snr = flat[:, 0].view(np.float32)
    ok = np.isfinite(snr)
    band = np.repeat(np.arange(nbands, dtype=np.int32), k)[ok]
    return (
        snr[ok],
        flat[:, 1].view(np.float32)[ok],
        flat[:, 2][ok],
        flat[:, 3][ok],
        band,
    )
