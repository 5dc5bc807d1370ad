"""Coherent multibeam (tied-array) beamforming over the device mesh.

BASELINE.json config 4: "per-bank phase-rotate + psum across 8 chips →
64-beam tied-array filterbank".  The structural analog in SURVEY.md §2.4:
coherent beamforming's cross-chip ``psum`` is the tensor-parallel reduction
of this framework.

Data model: the *antenna* axis is sharded across a mesh axis (default
``bank``) — each chip holds a contiguous block of antennas' voltages for the
whole (local) frequency range.  Per beam, each chip phase-rotates its
antennas by the geometric-delay phasor and partially sums them (one MXU
matmul over the antenna axis); the ``psum`` over the mesh axis completes the
tied-array sum.  Detection + integration then reuse the single-chip kernels.

TPU note: the compute is **planar** — complex values travel as ``(re, im)``
pairs of float32 arrays, the blit-wide convention (blit/ops/dft.py,
DESIGN.md §1): real MXU matmuls and real-valued Pallas tiles.  The public
entry points accept either planar pairs (the TPU path) or complex arrays (a
convenience — output dtype follows input).  One complex contraction = 4
real MXU einsums.

The reference has no beamforming (it reads post-rawspec products) — this is
the capability extension BASELINE.json prescribes, built so the per-chip
math is plain jnp and the collective is a single explicit ``psum``.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from blit.ops.channelize import integrate
from blit.ops.dft import ComplexOrPlanar, Planar, as_planar

ANT_AXIS_DEFAULT = "bank"

# Dispatch resolution of the most recent beamform(layout="chan") TRACE
# (the blit.ops.channelize._LAST_PLAN convention): silent fallbacks must
# be attributable — chip_smoke.py asserts the fused kernel actually ran.
_LAST_PLAN: dict = {}


def last_beamform_plan() -> dict:
    """The most recent chan-layout dispatch decision (``{"layout":
    "chan", "fused": bool}``; empty until a trace happens — a jit cache
    hit does not refresh it)."""
    return dict(_LAST_PLAN)


def delay_weights_planar(
    delays_s: jax.Array,
    freqs_hz: jax.Array,
    amplitudes: Optional[jax.Array] = None,
) -> Planar:
    """Per-(beam, antenna, channel) phasors from geometric delays, planar.

    ``delays_s``: (nbeam, nant) seconds; ``freqs_hz``: (nchan,) sky
    frequencies of the coarse channels.  Returns ``(wr, wi)`` float32 pairs
    shaped (nbeam, nant, nchan) holding ``cos/sin`` of ``-2π f τ`` —
    real-valued trig only, the planar TPU convention.
    Optionally scaled by per-antenna ``amplitudes`` (nbeam, nant) or (nant,).
    """
    phase = (-2.0 * jnp.pi * delays_s[..., None] * freqs_hz[None, None, :]).astype(
        jnp.float32
    )
    wr, wi = jnp.cos(phase), jnp.sin(phase)
    if amplitudes is not None:
        amp = jnp.asarray(amplitudes)
        if amp.ndim == 1:
            amp = amp[None, :]
        wr = wr * amp[..., None]
        wi = wi * amp[..., None]
    return wr, wi


def delay_weights(
    delays_s: jax.Array, freqs_hz: jax.Array, amplitudes: Optional[jax.Array] = None
) -> jax.Array:
    """Complex-dtype convenience over :func:`delay_weights_planar`:
    ``exp(-2πi f τ)`` shaped (nbeam, nant, nchan) complex64.  The TPU path
    uses the planar form directly."""
    wr, wi = delay_weights_planar(delays_s, freqs_hz, amplitudes)
    return jax.lax.complex(wr, wi).astype(jnp.complex64)


def _local_beams_planar(
    vr: jax.Array, vi: jax.Array, wr: jax.Array, wi: jax.Array
) -> Planar:
    """Partial tied-array sum over this chip's antennas, planar.

    ``v``: (nant_local, nchan, ntime, npol); ``w``: (nbeam, nant_local,
    nchan).  Returns (nbeam, nchan, ntime, npol) partial beam voltages as a
    (re, im) pair.  One complex contraction over antennas = 4 real batched
    matmuls (MXU work); XLA fuses the combines.
    """
    rr = jnp.einsum("bac,actp->bctp", wr, vr)
    ii = jnp.einsum("bac,actp->bctp", wi, vi)
    ri = jnp.einsum("bac,actp->bctp", wr, vi)
    ir = jnp.einsum("bac,actp->bctp", wi, vr)
    return rr - ii, ri + ir


@functools.partial(
    jax.jit, static_argnames=("mesh", "axis", "nint", "detect", "layout")
)
def beamform(
    voltages: ComplexOrPlanar,
    weights: ComplexOrPlanar,
    *,
    mesh: Mesh,
    axis: str = ANT_AXIS_DEFAULT,
    nint: int = 1,
    detect: bool = True,
    layout: str = "antenna",
):
    """Form tied-array beams across the mesh.

    Args:
      voltages: ``(nant, nchan, ntime, npol)`` antenna voltages — a planar
        ``(re, im)`` float32 pair (TPU path) or one complex64 array (CPU/GPU
        convenience).  Antenna axis sharded over ``axis`` (see
        :func:`antenna_sharding`).
      weights: ``(nbeam, nant, nchan)`` phasors from
        :func:`delay_weights_planar` (planar) or :func:`delay_weights`
        (complex), antenna axis sharded identically.
      detect: True → per-beam total power ``(nbeam, nchan, ntime_out, npol)``
        float32 integrated by ``nint``; False → raw beam voltages
        ``(nbeam, nchan, ntime, npol)`` — planar pair unless *both* inputs
        were complex (then complex64, for downstream fine channelization on
        complex-capable backends).
      layout: ``"antenna"`` (the shapes above) or ``"chan"`` — the packed,
        chan-major opt-in (voltages ``(nchan, nant, npol, ntime)``,
        weights ``(nchan, nbeam, nant)``, detected output ``(nchan,
        nbeam, npol, ntime_out)``; load packed planes via
        ``load_antennas_mesh(layout="chan")`` and pack weights with
        :func:`blit.ops.pallas_beamform.pack_weights`).  When every
        antenna is chip-local (``mesh.shape[axis] == 1``), ``detect=True``
        runs the VMEM-resident fused beamform+detect kernel — beam planes
        never touch HBM; measured **2.1x** the einsum path at the bench
        shape (DESIGN.md §9 r5) — with einsum fallback elsewhere.

    The only communication is one ``psum`` over ``axis`` — partial antenna
    sums travel, never raw voltages.
    """
    if layout not in ("antenna", "chan"):
        raise ValueError(f"bad layout {layout!r}")
    if layout == "chan":
        return _beamform_chan(
            voltages, weights, mesh=mesh, axis=axis, nint=nint,
            detect=detect,
        )
    vr, vi, v_cplx = as_planar(voltages)
    wr, wi, w_cplx = as_planar(weights)
    complex_out = v_cplx and w_cplx
    # bf16-RESIDENT voltages run the whole contraction + psum in bf16
    # (measured +26% end-to-end at the bench shape, DESIGN.md §9 r5
    # addendum: half the HBM voltage reads and half the ICI psum bytes;
    # 8-bit RAW samples are exact in bf16, the MXU multiplies at bf16
    # precision either way, so the only new rounding is the weight
    # phasors and the bf16 partial sums — ~1e-2 max rel err on detected
    # power).  Opt in by loading bf16 planes
    # (``load_antennas_mesh(dtype="bfloat16")``).
    bf16 = vr.dtype == jnp.bfloat16

    def step(vr, vi, wr, wi):
        if bf16:
            wr, wi = wr.astype(jnp.bfloat16), wi.astype(jnp.bfloat16)
        br, bi = _local_beams_planar(vr, vi, wr, wi)
        br, bi = jax.lax.psum((br, bi), axis)
        if detect:
            br = br.astype(jnp.float32)
            bi = bi.astype(jnp.float32)
            return integrate(br**2 + bi**2, nint)
        return br, bi

    out_specs = P() if detect else (P(), P())
    out = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(None, axis), P(None, axis)),
        out_specs=out_specs,
        check_vma=False,  # psum output is axis-invariant
    )(vr, vi, wr, wi)
    if detect:
        return out
    br, bi = out
    return jax.lax.complex(br, bi) if complex_out else (br, bi)


def _beamform_chan(
    voltages: ComplexOrPlanar,
    weights: ComplexOrPlanar,
    *,
    mesh: Mesh,
    axis: str,
    nint: int,
    detect: bool,
):
    """The packed chan-major path behind ``beamform(layout="chan")``.

    Dispatch: all-antennas-local + detect + TPU backend + eligible shape
    → the fused Pallas kernel (blit/ops/pallas_beamform.py); otherwise
    packed einsums with the same psum/detect semantics as the antenna
    layout.  Detection under a psum is only fusable when the antenna
    axis is whole per chip (power of the sum != sum of powers), hence
    the ``mesh.shape[axis] == 1`` gate.
    """
    from blit.ops import pallas_beamform as PB
    from blit.device import TPU_BACKEND

    vr, vi, v_cplx = as_planar(voltages)
    wr, wi, w_cplx = as_planar(weights)
    complex_out = v_cplx and w_cplx
    bf16 = vr.dtype == jnp.bfloat16
    nchan, nant, npol, ntime = vr.shape
    nbeam = wr.shape[1]
    if detect and nint > 1 and ntime % nint:
        # Same clear error as integrate() on the antenna path — the raw
        # reshape below would fail with a cryptic trace-time message.
        raise ValueError(
            f"integrate: nint={nint} does not divide ntime={ntime}"
        )
    fuse = (
        detect
        and mesh.shape[axis] == 1
        and jax.default_backend() == TPU_BACKEND
        and PB.pick_tile(nant, nbeam, npol, ntime, nint,
                         itemsize=vr.dtype.itemsize) is not None
    )
    # Dispatch provenance, the channelize _LAST_PLAN convention: the
    # fuse/fallback decision is otherwise invisible, and the bench/smoke
    # must be able to assert the pallas path actually ran.
    _LAST_PLAN.clear()
    _LAST_PLAN.update({"layout": "chan", "fused": fuse})

    def step(vr, vi, wr, wi):
        if bf16:
            wr, wi = wr.astype(jnp.bfloat16), wi.astype(jnp.bfloat16)
        if fuse:
            return PB.fused_beamform_detect(vr, vi, wr, wi, nint=nint)
        kw = dict(preferred_element_type=jnp.float32) if not bf16 else {}
        rr = jnp.einsum("cba,capt->cbpt", wr, vr, **kw)
        ii = jnp.einsum("cba,capt->cbpt", wi, vi, **kw)
        ri = jnp.einsum("cba,capt->cbpt", wr, vi, **kw)
        ir = jnp.einsum("cba,capt->cbpt", wi, vr, **kw)
        br, bi = rr - ii, ri + ir
        br, bi = jax.lax.psum((br, bi), axis)
        if detect:
            br = br.astype(jnp.float32)
            bi = bi.astype(jnp.float32)
            power = br**2 + bi**2  # (c, b, p, t): time is LAST here,
            # so blit.ops.channelize.integrate (axis -2) does not apply.
            if nint > 1:
                c_, b_, p_, t_ = power.shape
                power = power.reshape(c_, b_, p_, t_ // nint, nint).sum(-1)
            return power
        return br, bi

    out_specs = P() if (detect or fuse) else (P(), P())
    out = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, None, axis),
                  P(None, None, axis)),
        out_specs=out_specs,
        check_vma=False,
    )(vr, vi, wr, wi)
    if detect:
        return out
    br, bi = out
    # Same complex-output contract as the antenna layout: complex64 when
    # BOTH inputs were complex, else the planar pair.
    return jax.lax.complex(br, bi) if complex_out else (br, bi)


# -- windowed streaming beamforming ----------------------------------------

def beamform_stream(
    feed,
    weights: ComplexOrPlanar,
    *,
    mesh: Mesh,
    axis: str = ANT_AXIS_DEFAULT,
    nint: int = 1,
    layout: str = "antenna",
    timeline=None,
    stall_timeout_s=None,
):
    """Stream detected tied-array beam powers over a windowed feed
    (:class:`blit.parallel.antenna.AntennaStream`) — the arbitrarily-
    long-recording form of ``beamform(detect=True)``.

    Yields one float32 power slab per window, in time order:
    ``(nbeam, nchan, wt // nint, npol)`` (antenna layout) /
    ``(nchan, nbeam, npol, wt // nint)`` (chan layout).  Concatenated
    along the time axis the slabs are byte-identical to the one-shot
    ``beamform`` on the same span — per-sample phase/detect math and the
    per-``nint`` integration folds are window-local, so windowing changes
    no float operation (the equivalence tests pin this, arbitrary
    ``start_sample`` included).

    Every window must hold a whole number of integrations (pick
    ``window_samples`` — and a total span — divisible by ``nint``);
    integration therefore never straddles a window boundary, the same
    chunk rule :class:`blit.pipeline.RawReducer` applies via
    ``chunk_frames``.

    Pipelining rides the shared output plane (blit/outplane.py, ISSUE 4):
    window ``w`` dispatches asynchronously and its device output goes to
    the :class:`~blit.outplane.OutputRotation` readback thread, which
    waits out the collectives and fetches the power slab while this
    thread dispatches ``w+1`` and the feed's producer reads ``w+2`` —
    host read, H2D transfer, compute and D2H readback all overlap.  A
    window's host slot refills the moment its compute synchronized (the
    ``on_consumed`` hook), exactly the old lag-1 release point.

    Stage timings land in ``timeline``: ``dispatch`` (async), ``device``
    (readback-thread wait on a window's collectives), ``readback``
    (device→host slab fetch, bytes).
    """
    from blit.observability import Timeline
    from blit.outplane import OutputRotation
    from blit.parallel.mesh import psum_ici_bytes, record_ici

    tl = timeline if timeline is not None else Timeline()
    # depth=2 reproduces the old lag-1 overlap: put(window w) returns
    # once w-1's slab is fetched, leaving w in un-synchronized flight
    # while this thread dispatches w+1 — and a window's feed slot frees
    # at its sync (before the fetch), so the double-buffered feed
    # (prefetch_depth=2) always has a slot free when the consumer asks
    # for the next window.
    rot = OutputRotation(depth=2, timeline=tl, reuse=False,
                         name="blit-bf-readback",
                         stall_timeout_s=stall_timeout_s)
    from blit import observability

    axis_size = mesh.shape[axis]
    nbeam = np.shape(weights[0] if isinstance(weights, tuple) else weights)[
        1 if layout == "chan" else 0
    ]
    try:
        with observability.span("beamform.stream"):
            for win in feed:
                if win.ntime % nint:
                    raise ValueError(
                        f"window {win.index} holds {win.ntime} samples — not a "
                        f"whole number of nint={nint} integrations; choose "
                        "window_samples (and span) divisible by nint"
                    )
                if win.masked:
                    # Degraded continuation (feed masked a failed antenna): the
                    # accumulated powers carry its zero weight; flag it in the
                    # driver's per-window stage tables too.
                    tl.count("masked_antennas", len(win.masked))
                with observability.span("beamform.window", i=win.index), \
                        tl.stage("dispatch", byte_free=True):
                    out = beamform(
                        win.arrays, weights, mesh=mesh, axis=axis, nint=nint,
                        detect=True, layout=layout,
                    )
                if axis_size > 1:
                    # The fused per-window psum moves the partial beam
                    # planes (pre-detect, full time extent) over ICI —
                    # account it per window (mesh.ici stage + byte hist;
                    # its latency is only separable on the bench's pure
                    # collective leg, MESH_HISTS).
                    vr0 = win.arrays[0]
                    nchan_w = (vr0.shape[0] if layout == "chan"
                               else vr0.shape[1])
                    plane = (2 * nbeam * nchan_w * win.ntime
                             * (vr0.shape[-1 if layout != "chan" else 2])
                             * vr0.dtype.itemsize)
                    record_ici(tl, "psum",
                               psum_ici_bytes(plane, axis_size))
                for slab in rot.put(out, on_consumed=win.release):
                    yield slab.data
            for slab in rot.drain():
                yield slab.data
    finally:
        rot.close()


def beamform_accumulate(
    feed,
    weights: ComplexOrPlanar,
    *,
    mesh: Mesh,
    axis: str = ANT_AXIS_DEFAULT,
    layout: str = "antenna",
    timeline=None,
):
    """Total integrated beam power over a whole windowed feed, the
    integration state carried across window boundaries ON-DEVICE: each
    window's power (integrated over its full extent) folds into a donated
    float32 accumulator, and one ``(nbeam, nchan, 1, npol)`` (antenna
    layout) / ``(nchan, nbeam, npol, 1)`` (chan layout) array crosses
    back at the end — the bounded-output companion to
    :func:`beamform_stream` for total-power monitoring of recordings of
    any length."""
    import jax as _jax

    from blit import observability
    from blit.observability import Timeline
    from blit.outplane import FoldInFlight
    from blit.parallel.mesh import ShardedAccumulator

    tl = timeline if timeline is not None else Timeline()
    # The total-power accumulator carries its partition rule (ISSUE 9):
    # psum output is replicated ("beamform_acc"), and the donated add
    # below preserves that — ShardedAccumulator asserts it per fold.
    acc = ShardedAccumulator(mesh, "beamform_acc")
    flight = FoldInFlight(tl, depth=1)
    add = _jax.jit(lambda a, p: a + p, donate_argnums=0)
    with observability.span("beamform.accumulate"):
        for win in feed:
            if win.masked:
                tl.count("masked_antennas", len(win.masked))
            # Lag-1 (shared FoldInFlight core, ISSUE 4): wait for the
            # previous window's fold (its power output implies its input
            # was consumed) and recycle its slot BEFORE dispatching the
            # next fold.
            flight.make_room()
            with tl.stage("dispatch", byte_free=True):
                p = beamform(
                    win.arrays, weights, mesh=mesh, axis=axis,
                    nint=win.ntime, detect=True, layout=layout,
                )
                if acc.value is None:
                    acc.init(p)
                else:
                    acc.fold(add, p)
            flight.admit(win, p)
        if acc.value is None:
            raise ValueError("beamform_accumulate: feed yielded no windows")
        with tl.stage("device", byte_free=True):
            acc.value.block_until_ready()
        # The terminal sync above proved every fold complete — release the
        # tail without a second wait.
        flight.drain(synced=True)
    return acc.value


def antenna_sharding(mesh: Mesh, axis: str = ANT_AXIS_DEFAULT) -> NamedSharding:
    """Sharding for (nant, nchan, ntime, npol) voltages: antennas over
    ``axis``, everything else replicated.  ``jax.device_put`` applies it to a
    planar pair and a complex array alike (pytree leaves share it)."""
    return NamedSharding(mesh, P(axis))


def weight_sharding(mesh: Mesh, axis: str = ANT_AXIS_DEFAULT) -> NamedSharding:
    """Sharding for (nbeam, nant, nchan) weights, matching
    :func:`antenna_sharding`."""
    return NamedSharding(mesh, P(None, axis))


def beamform_np(voltages: np.ndarray, weights: np.ndarray, nint: int = 1,
                detect: bool = True) -> np.ndarray:
    """NumPy golden reference for :func:`beamform` (tests)."""
    beams = np.einsum("bac,actp->bctp", weights, voltages)
    if not detect:
        return beams
    p = (beams.real**2 + beams.imag**2).astype(np.float32)
    if nint > 1:
        b, c, t, q = p.shape
        p = p.reshape(b, c, t // nint, nint, q).sum(axis=3)
    return p
