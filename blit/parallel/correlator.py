"""Distributed FX correlator over the ``(band, bank)`` mesh.

BASELINE.json config 5: "4-band × 8-bank FX correlator: per-chip F-engine +
cross-bank psum visibilities over ICI".

Layout (the scaling-book recipe — pick a mesh, shard the big axes, let the
collectives ride ICI):

- **Frequency** (coarse channels) is sharded over ``bank`` — the same
  frequency-domain sharding the whole framework is built on.  Visibilities
  are per-frequency, so the X-engine's baseline cross-products never need
  cross-bank communication at all.
- **Time** is sharded over ``band`` — each band row correlates a disjoint
  time segment, and the visibility integration completes with one ``psum``
  over ``band``.  That psum is the only collective in the correlator.

Per chip: F-engine = the same PFB frontend + planar matmul DFT as the
single-chip filterbank path (blit/ops/channelize), applied to complex
voltages held as ``(re, im)`` planes; X-engine = the baseline cross-products
summed over frames — 4 real batched einsums per complex product on the MXU.

TPU note: everything is **planar** (blit/ops/dft.py convention; DESIGN.md
§1): real MXU matmuls and real-valued Pallas tiles.  The public
``correlate`` accepts planar pairs (TPU path) or complex arrays (a
convenience; output dtype follows input).  The fftshift every fine spectrum
needs is folded into the PFB window by the shift theorem — the same
two-HBM-passes saving the filterbank path uses (DESIGN.md §2).
"""

from __future__ import annotations

import functools
import time
from typing import Iterable, Optional

from blit.ops.dft import ComplexOrPlanar, Planar, as_planar

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from blit.ops.channelize import fft_planar, pfb_frontend

BAND_AXIS = "band"
BANK_AXIS = "bank"

# Dispatch resolution of the most recent X-engine TRACE (the
# blit.ops.channelize._LAST_PLAN convention, mirrored from
# blit.parallel.beamform.last_beamform_plan): the pallas-vs-einsum gate
# evaluates on per-shard LOCAL shapes inside shard_map, so provenance
# consumers (chip_smoke.py) must read the actual decision here instead of
# re-deriving it from global shapes (ADVICE r5 low finding).
_LAST_PLAN: dict = {}


def last_xengine_plan() -> dict:
    """The most recent X-engine dispatch decision (``{"layout": ...,
    "engine": "pallas" | "einsum"}``; empty until a trace happens — a jit
    cache hit does not refresh it)."""
    return dict(_LAST_PLAN)


def f_engine_planar(
    vr: jax.Array, vi: jax.Array, coeffs: jax.Array
) -> Planar:
    """Fine-channelize complex voltages held as (re, im) planes:
    ``(..., ntime)`` → ``(..., nframes, nfft)`` fftshifted planar spectra.

    The complex-input twin of the filterbank path's PFB+FFT: the FIR runs on
    each plane separately (real VPU work), the DFT is the planar matmul path
    on TPU (complex FFT elsewhere, picked by ``fft_planar``), and the
    fftshift is folded into the window coefficients via the shift theorem
    (input sign flip ↔ spectrum roll by nfft/2; DESIGN.md §2).
    """
    ntap, nfft = coeffs.shape
    if nfft % 2:
        raise ValueError("f_engine_planar: nfft must be even")
    # ±1 is exact in every float dtype: follow the coeffs (bf16 coeffs
    # must not promote the whole FIR back to f32).
    sign = jnp.asarray(
        np.where(np.arange(nfft) % 2 == 0, 1.0, -1.0).astype(np.float32)
    ).astype(coeffs.dtype)
    shifted = coeffs * sign[None, :]
    fr = pfb_frontend(vr, shifted)
    fi = pfb_frontend(vi, shifted)
    return fft_planar(fr, fi)


def f_engine(v: jax.Array, coeffs: jax.Array) -> jax.Array:
    """Complex-dtype convenience over :func:`f_engine_planar` (CPU/GPU)."""
    sr, si = f_engine_planar(jnp.real(v), jnp.imag(v), coeffs)
    return jax.lax.complex(sr, si)


def _xengine_planar(sr: jax.Array, si: jax.Array) -> Planar:
    """Cross-multiply and time-integrate, planar.  ``s``: (nant, nchan, npol,
    nframes, nfft) → visibilities (nant, nant, nchan, nfft, npol, npol) as a
    (re, im) pair.

    ``V[a,b] = Σ_t S_a S_b*``: with planar S the real part is
    ``Σ (ar·br + ai·bi)`` and the imaginary part ``Σ (ai·br − ar·bi)`` —
    4 real batched einsums (MXU) instead of one complex einsum.
    Accumulation is pinned to f32 so bf16 spectra (the bf16-staged path)
    integrate losslessly.

    Measured dead end (DESIGN.md §9 round-4 addendum): computing all four
    block products as ONE einsum over the re/im-stacked operand (a
    (2·nant·npol)² matmul per (chan, fine) batch entry, 4x the work per
    MXU tile) LOSES on the chip — 18.9 vs 20.7 GB/s input rate
    end-to-end (interleaved A/B on the chip): the stack's
    concatenate materializes an extra copy of both spectra planes, and
    the MXU tiles were not the binding resource.
    """
    _LAST_PLAN.clear()
    _LAST_PLAN.update({"layout": "standard", "engine": "einsum"})
    return _xengine_einsums(sr, si, "abcfpq")


def _xengine_einsums(sr: jax.Array, si: jax.Array, out: str) -> Planar:
    """The four real cross-products as einsums, output layout chosen by
    ``out`` subscripts ("abcfpq" standard / "cfapbq" packed) — one copy
    of the rr/ii/ir/ri structure and the f32-accumulation pin."""
    kw = dict(preferred_element_type=jnp.float32)
    rr = jnp.einsum(f"acptf,bcqtf->{out}", sr, sr, **kw)
    ii = jnp.einsum(f"acptf,bcqtf->{out}", si, si, **kw)
    ir = jnp.einsum(f"acptf,bcqtf->{out}", si, sr, **kw)
    ri = jnp.einsum(f"acptf,bcqtf->{out}", sr, si, **kw)
    return rr + ii, ir - ri


def _xengine_packed(sr: jax.Array, si: jax.Array) -> Planar:
    """X-engine emitting the packed ``(c, f, a, p, b, q)`` layout.

    On TPU backends at MXU-sized baseline counts this is the VMEM-resident
    Pallas kernel (blit/ops/pallas_xengine.py — measured +19% on the whole
    correlate call at nant=64, the un-parking of DESIGN.md §9's round-4
    decision); elsewhere, packed-layout einsums (measured at parity with
    the standard layout, so the fallback costs nothing).
    """
    from blit.ops import pallas_xengine
    from blit.device import TPU_BACKEND

    nant, _c, npol = sr.shape[0], sr.shape[1], sr.shape[2]
    nap = nant * npol
    ft = pallas_xengine.pick_ft(
        nap, sr.shape[-1], sr.shape[3], itemsize=sr.dtype.itemsize
    )
    fused = jax.default_backend() == TPU_BACKEND and ft is not None
    _LAST_PLAN.clear()
    _LAST_PLAN.update(
        {"layout": "packed", "engine": "pallas" if fused else "einsum"}
    )
    if fused:
        vr, vi = pallas_xengine.xengine_packed(sr, si, ft=ft)
        shape6 = vr.shape[:2] + (nant, npol, nant, npol)
        return vr.reshape(shape6), vi.reshape(shape6)
    return _xengine_einsums(sr, si, "cfapbq")


def _fx_spectra(vr: jax.Array, vi: jax.Array, h: jax.Array,
                bf16: bool) -> Planar:
    """Per-chip F-engine body shared by every correlator entry point:
    planar voltages ``(nant, nchan_local, ntime_local, npol)`` → fftshifted
    planar spectra ``(nant, nchan_local, npol, nframes, nfft)``, staged in
    bf16 when the planes are bf16-resident (DESIGN.md §9 r5)."""
    if bf16:
        h = h.astype(jnp.bfloat16)
    # Move pol before time so the F-engine framing acts on the last axis.
    sr, si = f_engine_planar(
        jnp.moveaxis(vr, 3, 2), jnp.moveaxis(vi, 3, 2), h
    )
    if bf16:
        sr = sr.astype(jnp.bfloat16)
        si = si.astype(jnp.bfloat16)
    return sr, si


def _fx_xengine(sr: jax.Array, si: jax.Array, vis_layout: str) -> Planar:
    """X-engine dispatch by output layout (shared per-chip body)."""
    if vis_layout == "packed":
        return _xengine_packed(sr, si)
    return _xengine_planar(sr, si)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "nfft", "ntap", "vis_layout", "acc_frames"),
)
def correlate(
    voltages: ComplexOrPlanar,
    coeffs: jax.Array,
    *,
    mesh: Mesh,
    nfft: int,
    ntap: int = 4,
    vis_layout: str = "standard",
    acc_frames: Optional[int] = None,
):
    """Full FX correlation over the mesh.

    Args:
      voltages: ``(nant, nchan, ntime, npol)`` — a planar ``(re, im)``
        float32 pair (TPU path) or one complex64 array (CPU/GPU convenience)
        with ``nchan`` sharded over ``bank`` and ``ntime`` sharded over
        ``band`` (see :func:`correlator_sharding`); ``ntime`` per band must
        be a multiple of ``nfft`` with at least ``ntap`` blocks.
      coeffs: (ntap, nfft) PFB prototype (replicated).
      vis_layout: ``"standard"`` → ``(nant, nant, nchan, nfft, npol,
        npol)``; ``"packed"`` → ``(nchan, nfft, nant, npol, nant, npol)``,
        the TPU-fast layout emitted directly by the VMEM-resident Pallas
        X-engine at MXU-sized baseline counts (nant·npol >= 128; +19%
        whole-call at nant=64 — transposing to the standard layout would
        move 2×vis bytes and eat the win, so the layout is the opt-in).
        Integrations and layout-indifferent reductions should prefer it
        at array scale.

    Returns:
      Visibilities integrated over *all* time (psum over ``band``), with
      the channel axes sharded over ``bank`` like the input — complex64
      when the input was complex, else a planar float32 pair.  Entry
      ``[a, b]`` (standard) or ``[c, f, a, p, b, q]`` (packed) is
      ``⟨S_a S_b*⟩``; the antenna diagonal holds autocorrelation spectra.

    Segment semantics: each band row F-engines its time segment
    independently — the PFB does not run across segment boundaries, so
    ``ntap-1`` frames per boundary are not formed (standard chunked-
    correlator behavior; :func:`correlate_np` with ``nsegments=nband`` is
    the exact golden reference).

    ``acc_frames`` pins the visibility accumulation granularity: each band
    row's frame contraction folds tile-by-tile (``acc_frames`` frames per
    tile, time-ascending) instead of as one contraction.  This is the
    accumulation structure of the windowed streaming path
    (:func:`correlate_stream` with ``window_frames=acc_frames``), so the
    float32 results are byte-identical between the two — the equivalence
    the long-recording tests pin.  ``None`` (default) keeps the single
    contraction (same result to float rounding; one big MXU contraction
    is the fast shape).
    """
    if vis_layout not in ("standard", "packed"):
        raise ValueError(f"bad vis_layout {vis_layout!r}")
    vr, vi, was_complex = as_planar(voltages)
    # bf16-RESIDENT voltages run the F-engine and spectra in bf16
    # (measured +25% end-to-end at nant=64, DESIGN.md §9 r5 addendum:
    # 8-bit RAW samples are exact in bf16, and the MXU truncates f32
    # operands to bf16 anyway — bf16 SPECTRA alone measured visibilities
    # byte-identical to the f32-spectra path).  Visibilities always
    # accumulate and psum in f32.  Opt in by loading bf16 planes
    # (``load_correlator_mesh(dtype="bfloat16")``).
    bf16 = vr.dtype == jnp.bfloat16

    def step(vr, vi, h):
        sr, si = _fx_spectra(vr, vi, h, bf16)  # (a, c, p, frames, nfft)
        nframes = sr.shape[3]
        if acc_frames is None or acc_frames >= nframes:
            visr, visi = _fx_xengine(sr, si, vis_layout)
        else:
            # Tile-by-tile fold, time-ascending — the windowed stream's
            # exact accumulation order (first tile un-added, like the
            # stream's first window, so even signed zeros match).
            visr = visi = None
            for t0 in range(0, nframes, acc_frames):
                pr, pi = _fx_xengine(
                    sr[..., t0:t0 + acc_frames, :],
                    si[..., t0:t0 + acc_frames, :],
                    vis_layout,
                )
                visr = pr if visr is None else visr + pr
                visi = pi if visi is None else visi + pi
        return jax.lax.psum((visr, visi), BAND_AXIS)

    spec_v = P(None, BANK_AXIS, BAND_AXIS)
    out_spec = (
        P(BANK_AXIS) if vis_layout == "packed" else P(None, None, BANK_AXIS)
    )
    visr, visi = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(spec_v, spec_v, P()),
        out_specs=(out_spec, out_spec),
        check_vma=False,  # psum output is band-invariant
    )(vr, vi, coeffs)
    if was_complex:
        return jax.lax.complex(visr, visi)
    return visr, visi


def correlator_sharding(mesh: Mesh) -> NamedSharding:
    """Input sharding for (nant, nchan, ntime, npol) voltages: frequency
    over ``bank``, time over ``band``.  ``jax.device_put`` applies it to a
    planar pair and a complex array alike."""
    return NamedSharding(mesh, P(None, BANK_AXIS, BAND_AXIS))


def visibility_sharding(mesh: Mesh) -> NamedSharding:
    """Output sharding: (nant, nant, nchan, nfft, npol, npol), frequency
    over ``bank``, replicated over ``band``."""
    return NamedSharding(mesh, P(None, None, BANK_AXIS))


# -- windowed streaming correlation ----------------------------------------
#
# The accumulator is BAND-SHARDED partial visibilities with a leading band
# axis — each band row folds its own windows locally and the band psum runs
# exactly once, at the end (``psum(fold(local))``, the same structure as
# ``correlate(acc_frames=...)``'s in-step fold, which is what makes the
# float32 stream byte-identical to the one-shot call).

def _acc_rule(vis_layout: str) -> str:
    """The accumulator's :data:`blit.parallel.mesh.PARTITION_RULES` role."""
    return "vis_acc_packed" if vis_layout == "packed" else "vis_acc_standard"


def _acc_spec(vis_layout: str) -> P:
    """PartitionSpec of the band-sharded partial-visibility accumulator:
    standard ``(nband, nant, nant, nchan, nfft, npol, npol)`` / packed
    ``(nband, nchan, nfft, nant, npol, nant, npol)`` — resolved through
    the sharded plane's partition-rule registry (ISSUE 9: the fold
    accumulator carries its spec; dispatch and readback cannot drift)."""
    from blit.parallel.mesh import partition_rule

    return partition_rule(_acc_rule(vis_layout))


_SPEC_V = P(None, BANK_AXIS, BAND_AXIS)


@functools.partial(jax.jit, static_argnames=("mesh", "vis_layout"))
def _window_vis(vr, vi, h, *, mesh: Mesh, vis_layout: str):
    """First window: per-chip F-engine + X-engine partials, NO psum —
    the band-sharded accumulator's initial value."""
    bf16 = vr.dtype == jnp.bfloat16

    def step(vr, vi, h):
        sr, si = _fx_spectra(vr, vi, h, bf16)
        pr, pi = _fx_xengine(sr, si, vis_layout)
        return pr[None], pi[None]  # leading band block axis

    spec = _acc_spec(vis_layout)
    return jax.shard_map(
        step, mesh=mesh, in_specs=(_SPEC_V, _SPEC_V, P()),
        out_specs=(spec, spec), check_vma=False,
    )(vr, vi, h)


@functools.partial(
    jax.jit, static_argnames=("mesh", "vis_layout"), donate_argnums=(0, 1)
)
def _accum_vis(accr, acci, vr, vi, h, *, mesh: Mesh, vis_layout: str):
    """Subsequent windows: fold this window's partials into the donated
    accumulator (HBM reused in place across the whole stream)."""
    bf16 = vr.dtype == jnp.bfloat16

    def step(ar, ai, vr, vi, h):
        sr, si = _fx_spectra(vr, vi, h, bf16)
        pr, pi = _fx_xengine(sr, si, vis_layout)
        return ar + pr[None], ai + pi[None]

    spec = _acc_spec(vis_layout)
    return jax.shard_map(
        step, mesh=mesh, in_specs=(spec, spec, _SPEC_V, _SPEC_V, P()),
        out_specs=(spec, spec), check_vma=False,
    )(accr, acci, vr, vi, h)


def _fold_vis(value, vr, vi, h, *, mesh: Mesh, vis_layout: str):
    """The :class:`blit.parallel.mesh.ShardedAccumulator` fold adapter:
    ``value`` is the live ``(accr, acci)`` pair, donated through
    :func:`_accum_vis` (its ``donate_argnums``)."""
    accr, acci = value
    return _accum_vis(accr, acci, vr, vi, h, mesh=mesh,
                      vis_layout=vis_layout)


@functools.partial(jax.jit, static_argnames=("mesh", "vis_layout"))
def _finish_vis(accr, acci, *, mesh: Mesh, vis_layout: str):
    """The stream's ONE collective: psum the band-local partials into the
    integrated visibilities, with :func:`correlate`'s output sharding."""

    def step(ar, ai):
        ar, ai = jax.lax.psum((ar, ai), BAND_AXIS)
        return ar[0], ai[0]  # drop the leading band block axis

    spec = _acc_spec(vis_layout)
    out = (
        P(BANK_AXIS) if vis_layout == "packed" else P(None, None, BANK_AXIS)
    )
    return jax.shard_map(
        step, mesh=mesh, in_specs=(spec, spec), out_specs=(out, out),
        check_vma=False,  # psum output is band-invariant
    )(accr, acci)


def correlate_stream(
    feed: Iterable,
    coeffs: jax.Array,
    *,
    mesh: Mesh,
    nfft: int,
    ntap: int = 4,
    vis_layout: str = "standard",
    timeline=None,
) -> Planar:
    """Full FX correlation over a windowed feed
    (:class:`blit.parallel.antenna.CorrelatorStream`) — the arbitrarily-
    long-recording form of :func:`correlate`: per-window local partials
    fold into an on-device band-sharded accumulator (donated, so windows
    reuse HBM), the band psum runs once at the end, and only the final
    visibilities exist whole.

    Pipelining: window ``w``'s dispatch is asynchronous; the blocking wait
    on window ``w-1``'s fold happens AFTER the feed has already
    transferred window ``w`` (and while its producer thread reads window
    ``w+1``), so host reads, host→device transfer and device compute
    overlap — the ``RawReducer.drain`` lag pattern.

    Numerics: byte-identical (float32) to
    ``correlate(..., acc_frames=window_frames)`` on the same span — same
    per-window contractions, same time-ascending fold (the long-recording
    equivalence tests pin this, arbitrary ``start_sample`` included); the
    default one-shot ``correlate`` differs only by float summation order.

    Returns the planar ``(visr, visi)`` pair with :func:`correlate`'s
    output contract.  Stage timings land in ``timeline``: ``dispatch``
    (async window fold), ``device`` (lag-synchronized wait).
    """
    from blit.observability import Timeline

    if vis_layout not in ("standard", "packed"):
        raise ValueError(f"bad vis_layout {vis_layout!r}")
    if coeffs.shape != (ntap, nfft):
        raise ValueError(
            f"coeffs shape {coeffs.shape} != (ntap={ntap}, nfft={nfft})"
        )
    from blit.outplane import FoldInFlight
    from blit.parallel.mesh import (
        ShardedAccumulator,
        psum_ici_bytes,
        record_ici,
    )

    from blit import observability

    tl = timeline if timeline is not None else Timeline()
    # The fold accumulator CARRIES its partition rule (ISSUE 9): the
    # band-sharded partial visibilities and the spec that shards them
    # travel together, donated window to window.
    acc = ShardedAccumulator(mesh, _acc_rule(vis_layout))
    flight = FoldInFlight(tl, depth=1)
    with observability.span("correlate.stream"):
        for win in feed:
            if win.masked:
                # Degraded continuation: the band-sharded accumulator folds
                # this window with the failed antenna zero-weighted; the flag
                # rides the driver's stage tables and the feed's metadata
                # (``masked_antennas`` / header ``_masked_antennas``).
                tl.count("masked_antennas", len(win.masked))
            vr, vi = win.arrays
            # Lag-1 sync (shared FoldInFlight core, ISSUE 4): wait for window
            # w-1's fold only now — the feed already moved window w and is
            # reading w+1 behind it.  The synced fold consumed w-1's arrays,
            # so its slot can refill (Window.release contract).  Must happen
            # BEFORE the next dispatch: _accum_vis donates the accumulator,
            # and a donated token can no longer be waited on.
            flight.make_room()
            with observability.span("correlate.window", i=win.index), \
                    tl.stage("dispatch", byte_free=True):
                if acc.value is None:
                    acc.init(_window_vis(
                        vr, vi, coeffs, mesh=mesh, vis_layout=vis_layout
                    ))
                else:
                    acc.fold(_fold_vis, vr, vi, coeffs,
                             mesh=mesh, vis_layout=vis_layout)
            flight.admit(win, acc.value[0])
        if acc.value is None:
            raise ValueError("correlate_stream: feed yielded no windows")
        nband = mesh.shape[BAND_AXIS]
        with tl.stage("device", byte_free=True):
            if nband > 1:
                # Warm-up dispatch: this is _finish_vis's first call of
                # the stream, so a timed cold call would sample
                # trace+XLA compile, not the collective
                # (.lower().compile() does NOT warm the jit call
                # cache on supported jax).  The warm-up also syncs
                # every fold, so the timed
                # re-dispatch below is the psum program alone — the
                # honest mesh.psum_s sample, one extra end-of-stream
                # collective, never per-window.
                jax.block_until_ready(_finish_vis(
                    *acc.value, mesh=mesh, vis_layout=vis_layout
                ))
                t0 = time.perf_counter()
                visr, visi = _finish_vis(
                    *acc.value, mesh=mesh, vis_layout=vis_layout
                )
                jax.block_until_ready((visr, visi))
                psum_s = time.perf_counter() - t0
            else:
                # Single-band mesh: the psum is the identity, there is
                # no ICI sample to take — one dispatch, no warm-up.
                visr, visi = _finish_vis(
                    *acc.value, mesh=mesh, vis_layout=vis_layout
                )
                jax.block_until_ready((visr, visi))
        if nband > 1:
            per_chip = sum(a.nbytes for a in acc.value) // mesh.size
            record_ici(tl, "psum", psum_ici_bytes(per_chip, nband), psum_s)
        # The finish fetch just proved every fold complete — release the last
        # window without the old second sync of the accumulator (ISSUE 4:
        # "double sync today").
        flight.drain(synced=True)
    return visr, visi


def correlate_np(
    voltages: np.ndarray,
    coeffs: np.ndarray,
    nfft: int,
    ntap: int = 4,
    nsegments: int = 1,
) -> np.ndarray:
    """NumPy golden reference for :func:`correlate` (tests).

    ``nsegments`` mirrors the band-axis time sharding: each segment is
    F-engined independently (the PFB does not run across segment
    boundaries — ``ntap-1`` frames per boundary stay local, matching the
    sharded semantics) and the visibilities sum over segments.
    """
    v = np.moveaxis(voltages, 3, 2)  # (a, c, p, t)
    seg_len = v.shape[-1] // nsegments
    vis = None
    for s in range(nsegments):
        seg = v[..., s * seg_len : (s + 1) * seg_len]
        nblk = seg.shape[-1] // nfft
        nframes = nblk - ntap + 1
        blocks = seg.reshape(seg.shape[:-1] + (nblk, nfft))
        frames = np.zeros(seg.shape[:-1] + (nframes, nfft), dtype=np.complex64)
        for k in range(ntap):
            frames += coeffs[k] * blocks[..., k : k + nframes, :]
        spec = np.fft.fftshift(np.fft.fft(frames, axis=-1), axes=-1)
        part = np.einsum("acptf,bcqtf->abcfpq", spec, np.conj(spec))
        vis = part if vis is None else vis + part
    return vis
