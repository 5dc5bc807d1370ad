"""File-fed antenna-array products: per-antenna GUPPI RAW recordings →
sharded planar voltages for the collective products (VERDICT r3 item 4).

BASELINE configs 4-5 prescribe beamforming and FX correlation over the
mesh; :mod:`blit.parallel.beamform` / :mod:`blit.parallel.correlator`
implement the collectives, and this module is the missing data plane: it
maps an antenna array's RAW recordings (one recording per antenna — the
per-element capture layout of BL's array backends; the GBT reference has
no array data, its single-dish recordings are per *bank*,
src/gbt.jl:28-42) onto ``antenna_sharding`` / ``correlator_sharding``
with per-process file locality, the same way blit/parallel/scan.py feeds
the (band, bank) filterbank mesh.

Two access shapes:

- One-shot loaders (:func:`load_antennas_mesh` /
  :func:`load_correlator_mesh`): the whole requested span as one sharded
  array, from any ``start_sample`` — right for recordings that fit.
- Windowed streams (:class:`AntennaStream` / :class:`CorrelatorStream`):
  a bounded-window, double-buffered iterator over the same recordings —
  a producer thread fills a ``prefetch_depth`` rotation of stable host
  buffers (the :class:`blit.pipeline.BufferRotation` core the single-chip
  reducer streams through) while the previous window's sharded
  ``device_put`` + collective dispatch are in flight, so host reads,
  host→device transfer and device compute overlap and host residency is
  ``prefetch_depth`` windows regardless of recording length (the slab
  access of the reference, src/gbtworkerfunctions.jl:171-189, applied to
  the collective data plane).  :class:`CorrelatorStream` windows overlap
  by the F-engine's ``(ntap-1)*nfft`` PFB tail — carried between
  rotation buffers by the same memcpy the reducer uses across chunks —
  so windowed spectra are bit-identical to a one-shot F-engine pass.

Voltages arrive planar — ``(re, im)`` float32 pairs dequantized from the
RAW int8 complex samples — the blit-wide TPU convention (DESIGN.md §1).
"""

from __future__ import annotations

import logging
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from blit import faults, observability
from blit.io.guppi import open_raw, require_native_reader
from blit.observability import Timeline
from blit.parallel.scan import _gapless, _gather_int64, _kept_samples

log = logging.getLogger("blit.antenna")


def _traced_fill(fill, name: str):
    """Wrap a BufferRotation fill callback so the producer thread's whole
    run records as one span (the rotation itself parents its thread on
    the driver span that built it)."""

    def run(rot):
        with observability.span(name):
            fill(rot)

    return run

Planar = Tuple["object", "object"]

_ERR = 1 << 60  # rides the pod-wide agreement; see scan._SAMPS_ERR


def _resolve_plane_dtype(dtype):
    """Device residency dtype for the planar loaders: f32 or bf16 (bf16
    is lossless for 8-bit RAW voltages and halves HBM/ICI traffic in the
    collectives — DESIGN.md §9 r5 addendum)."""
    import jax.numpy as jnp

    d = jnp.dtype(dtype)
    if d not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(f"dtype must be float32 or bfloat16, got {dtype}")
    return d


def _open_antennas(raw_paths: Sequence, needed: Sequence[int]):
    """Open the antenna recordings in ``needed`` (indices into
    ``raw_paths``) and agree (samples, nchan, npol) pod-wide with
    symmetric errors, like the scan loader's player agreement.

    Every process reports a sample count (or the ERR marker) for every
    antenna it was asked to open; the cross-process MIN both finds the
    common span and propagates any opener's failure to every peer before
    the collectives run.  Antennas nobody opened stay at INT64_MAX // 2
    and are caught by the caller's coverage check.
    """
    nant = len(raw_paths)
    raws, errs = {}, {}
    for a in needed:
        try:
            r = open_raw(raw_paths[a])
            if r.nblocks == 0:
                raise ValueError(f"empty RAW file: {r.path}")
            require_native_reader(r)
            raws[a] = r
        except Exception as e:  # noqa: BLE001 — reported pod-wide below
            errs[a] = e

    geo = (0, 0)
    if raws:
        h = raws[sorted(raws)[0]].header(0)
        geo = (h["OBSNCHAN"], 2 if h["NPOL"] > 2 else h["NPOL"])
    samps = np.full(nant, (1 << 62) - 1, np.int64)
    for a, r in raws.items():
        samps[a] = _kept_samples(r)
    for a in errs:
        samps[a] = _ERR
    gathered = _gather_int64(np.concatenate([samps, geo]))
    samps = gathered[:, :-2].min(axis=0)
    failed = [int(a) for a in np.argwhere(samps == _ERR).ravel()]
    if failed:
        mine = "; ".join(
            f"antenna {a}: {type(e).__name__}: {e}"
            for a, e in sorted(errs.items())
        )
        raise ValueError(
            f"antennas {failed} failed to open on their owning process"
            + (f" (this process: {mine})" if mine else "")
        ) from next(iter(errs.values()), None)
    geos = gathered[:, -2:]
    geos = geos[(geos != 0).any(axis=1)]
    if len(geos) and not (geos == geos[0]).all():
        raise ValueError(
            f"processes disagree on (nchan, npol): {[tuple(g) for g in geos]}"
        )
    nchan, npol = (int(geos[0][0]), int(geos[0][1])) if len(geos) else (0, 0)
    return raws, int(samps.min()), nchan, npol


def _planar_block(raw, start: int, ntime: int) -> Tuple[np.ndarray, np.ndarray]:
    """Samples ``[start, start+ntime)`` of one recording as planar float32
    ``(nchan, ntime, npol)`` re/im planes (RAW int8 (re, im) dequantized)."""
    v = _gapless(raw, ntime, skip=start)  # (nchan, ntime, npol, 2) int8
    if v.shape[1] < ntime:
        raise ValueError(
            f"{raw.path}: {v.shape[1]} samples from offset {start}, "
            f"need {ntime}"
        )
    v = v[:, :ntime]
    # astype yields fresh C-contiguous planes; int8 → f32 is exact.
    return v[..., 0].astype(np.float32), v[..., 1].astype(np.float32)


def _span_from(min_samps: int, start_sample: int,
               max_samples: Optional[int]) -> int:
    """Usable samples from ``start_sample`` given the agreed common span
    (every loader/stream's offset arithmetic, in one place)."""
    if start_sample < 0:
        raise ValueError(f"start_sample must be >= 0, got {start_sample}")
    avail = min_samps - start_sample
    if max_samples is not None:
        avail = min(avail, max_samples)
    return avail


def _antenna_shard_plan(mesh, axis: str, layout: str, nant: int):
    """The beamform-layout placement plan shared by the one-shot loader
    and :class:`AntennaStream`: ``(sharding, per, [(device, lo)])`` where
    each addressable device owns antennas ``[lo, lo + per)`` (both
    layouts shard ONLY the antenna dim in equal blocks, so a device's
    block index IS its mesh coordinate along ``axis``)."""
    from blit.parallel.beamform import antenna_sharding

    if layout not in ("antenna", "chan"):
        raise ValueError(f"bad layout {layout!r}")
    ax_size = mesh.shape[axis]
    if nant % ax_size:
        raise ValueError(
            f"nant={nant} must divide over the {ax_size}-way {axis!r} axis"
        )
    per = nant // ax_size
    if layout == "chan":
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P(None, axis))
    else:
        sharding = antenna_sharding(mesh, axis)
    ax_i = list(mesh.axis_names).index(axis)

    def ant_lo(d) -> int:
        pos = np.argwhere(mesh.devices == d)[0]
        return int(pos[ax_i]) * per

    plan = [(d, ant_lo(d)) for d in sharding.addressable_devices]
    return sharding, per, plan


def load_antennas_mesh(
    raw_paths: Sequence,
    *,
    mesh,
    axis: str = "bank",
    start_sample: int = 0,
    max_samples: Optional[int] = None,
    dtype="float32",
    layout: str = "antenna",
) -> Tuple[Dict, Planar]:
    """Load per-antenna RAW recordings onto the beamform layout:
    ``(nant, nchan, ntime, npol)`` planar voltages with the antenna axis
    sharded over ``axis`` (:func:`blit.parallel.beamform.antenna_sharding`).

    Each process opens ONLY the antennas whose chips it owns (the
    per-element twin of the scan loader's player locality); the common
    sample span is agreed pod-wide.  Returns ``(header, (vr, vi))`` where
    ``header`` is the first local antenna's RAW header plus the agreed
    ``ntime``.

    ``raw_paths``: one RAW source per antenna (path / ``.NNNN.raw`` stem /
    path list), length divisible by the ``axis`` mesh size.

    ``start_sample``: gap-free sample offset to start from — an arbitrary
    re-entry point into the recordings (the loaders used to be pinned at
    sample 0; VERDICT r5 missing #2).  ``max_samples`` then caps the span
    from there.

    ``dtype``: device residency of the planes — ``"float32"`` (default)
    or ``"bfloat16"``.  RAW voltages are 8-bit integers, exactly
    representable in bf16, so bf16 residency is LOSSLESS for the data
    plane and halves both HBM reads and ICI psum bytes downstream
    (:func:`blit.parallel.beamform.beamform` runs its whole contraction
    in bf16 for bf16 inputs — measured +26% end-to-end, DESIGN.md §9 r5
    addendum).

    ``layout``: ``"antenna"`` (above) or ``"chan"`` — packed chan-major
    ``(nchan, nant, npol, ntime)`` planes for ``beamform(layout="chan")``
    and its fused detect kernel (measured 2.1x; the pack happens in the
    host copy this loader performs anyway, so it is free here, unlike a
    device-side transpose).
    """
    import jax

    dev_dtype = _resolve_plane_dtype(dtype)
    nant = len(raw_paths)
    sharding, per, plan = _antenna_shard_plan(mesh, axis, layout, nant)

    local_ants = sorted({a for _d, lo in plan for a in range(lo, lo + per)})
    raws, min_samps, nchan, npol = _open_antennas(raw_paths, local_ants)
    ntime = _span_from(min_samps, start_sample, max_samples)
    if ntime <= 0:
        raise ValueError(
            f"no common samples across {nant} antennas from offset "
            f"{start_sample} (common span {min_samps})"
        )

    shards_r, shards_i = [], []
    for d, lo in plan:
        if layout == "chan":
            br = np.empty((nchan, per, npol, ntime), np.float32)
            bi = np.empty_like(br)
            for j, a in enumerate(range(lo, lo + per)):
                pr, pi = _planar_block(raws[a], start_sample, ntime)  # (c,t,p)
                br[:, j] = np.transpose(pr, (0, 2, 1))
                bi[:, j] = np.transpose(pi, (0, 2, 1))
        else:
            br = np.empty((per, nchan, ntime, npol), np.float32)
            bi = np.empty_like(br)
            for j, a in enumerate(range(lo, lo + per)):
                br[j], bi[j] = _planar_block(raws[a], start_sample, ntime)
        # int8-origin values are exact in bf16: the cast loses nothing.
        shards_r.append(jax.device_put(br.astype(dev_dtype, copy=False), d))
        shards_i.append(jax.device_put(bi.astype(dev_dtype, copy=False), d))
    global_shape = (
        (nchan, nant, npol, ntime)
        if layout == "chan"
        else (nant, nchan, ntime, npol)
    )
    vr = jax.make_array_from_single_device_arrays(
        global_shape, sharding, shards_r
    )
    vi = jax.make_array_from_single_device_arrays(
        global_shape, sharding, shards_i
    )
    hdr = dict(raws[local_ants[0]].header(0))
    hdr["_ntime"] = ntime
    hdr["_nant"] = nant
    return hdr, (vr, vi)


def load_correlator_mesh(
    raw_paths: Sequence,
    *,
    mesh,
    nfft: int,
    ntap: int = 4,
    start_sample: int = 0,
    max_samples: Optional[int] = None,
    dtype="float32",
) -> Tuple[Dict, Planar]:
    """Load per-antenna RAW recordings onto the FX-correlator layout:
    ``(nant, nchan, ntime, npol)`` planar voltages with frequency sharded
    over ``bank`` and time over ``band``
    (:func:`blit.parallel.correlator.correlator_sharding`).

    Antennas are replicated across the mesh in this layout, so every
    process reads every antenna's recording — but only its band rows'
    TIME WINDOW of it (the band axis is the file-split that preserves
    locality here; a per-chip channel subset still comes from the same
    bytes because RAW blocks interleave all channels).  Each band row's
    segment is trimmed to whole ``nfft`` blocks with at least ``ntap``
    of them, matching ``correlate``'s segment semantics.

    ``start_sample`` re-enters the recordings at an arbitrary gap-free
    offset (band segmentation then applies to the remaining span);
    ``max_samples`` caps the span from there.

    ``dtype``: ``"float32"`` (default) or ``"bfloat16"`` residency — see
    :func:`load_antennas_mesh`; ``correlate`` runs its bf16-staged path
    for bf16 planes (measured +25% at nant=64, DESIGN.md §9 r5).
    """
    import jax

    from blit.parallel.correlator import correlator_sharding

    dev_dtype = _resolve_plane_dtype(dtype)

    nant = len(raw_paths)
    nband = mesh.shape["band"]
    nbank = mesh.shape["bank"]
    sharding = correlator_sharding(mesh)

    # Every local device needs every antenna: open them all, agree span.
    raws, min_samps, nchan, npol = _open_antennas(
        raw_paths, list(range(nant))
    )
    if nchan % nbank:
        raise ValueError(f"nchan={nchan} must divide over {nbank} banks")
    total = _span_from(min_samps, start_sample, max_samples)
    seg = (total // nband) // nfft * nfft if total > 0 else 0
    if seg // nfft < ntap:
        raise ValueError(
            f"correlator needs >= {ntap} nfft-blocks per band segment; "
            f"have {seg // nfft} (total {total} samples over {nband} bands "
            f"from offset {start_sample})"
        )
    ntime = seg * nband
    cper = nchan // nbank

    # Read each (antenna, band-row) time window ONCE, slice per bank.
    # Devices are grouped by band row so a row's decoded blocks are freed
    # as soon as that row's local devices are fed (device_put has copied
    # them) — host residency is ONE band row of all antennas, not every
    # owned row at once (ADVICE r4: the flat cache held nant * nchan * seg
    # * npol * 8 bytes per owned row simultaneously).
    shards_r, shards_i = [], []
    dev_map = sharding.addressable_devices_indices_map(
        (nant, nchan, ntime, npol)
    )
    by_band: Dict[int, list] = {}
    for d, idx in dev_map.items():
        b = (idx[2].start or 0) // seg  # band row from the time slice
        by_band.setdefault(b, []).append((d, idx))
    for b in sorted(by_band):
        blocks = [
            _planar_block(raws[a], start_sample + b * seg, seg)
            for a in range(nant)
        ]
        for d, idx in by_band[b]:
            k = (idx[1].start or 0) // cper
            br = np.stack([blocks[a][0][k * cper:(k + 1) * cper]
                           for a in range(nant)])
            bi = np.stack([blocks[a][1][k * cper:(k + 1) * cper]
                           for a in range(nant)])
            shards_r.append(jax.device_put(br.astype(dev_dtype, copy=False), d))
            shards_i.append(jax.device_put(bi.astype(dev_dtype, copy=False), d))
        del blocks
    global_shape = (nant, nchan, ntime, npol)
    vr = jax.make_array_from_single_device_arrays(
        global_shape, sharding, shards_r
    )
    vi = jax.make_array_from_single_device_arrays(
        global_shape, sharding, shards_i
    )
    hdr = dict(raws[0].header(0))
    hdr["_ntime"] = ntime
    hdr["_nant"] = nant
    return hdr, (vr, vi)


# -- windowed streaming feeds ---------------------------------------------


class Window:
    """One window of a collective stream: sharded planar ``(vr, vi)``
    global arrays fed from a rotation slot's host buffers.

    The consumer MUST call :meth:`release` once nothing still reads the
    window — in practice, after the device compute that consumed it has
    synchronized: the streaming drivers hand ``release`` to the output
    plane's readback thread as the ``on_consumed`` hook
    (:meth:`blit.outplane.OutputRotation.put`) or release via the shared
    :class:`blit.outplane.FoldInFlight` lag bookkeeping, so the call may
    arrive from a thread other than the iterator's (the rotation's slot
    accounting is lock-guarded for exactly this).  ``arrays`` may
    alias the slot's host buffers until then (CPU backends transfer
    zero-copy when alignment allows), so a released window's arrays must
    not be read again; an unreleased window back-pressures the producer
    exactly like an unreleased :class:`blit.pipeline.RawReducer` chunk.
    """

    __slots__ = ("index", "start", "ntime", "frames", "arrays", "masked",
                 "_rot", "_slot")

    def __init__(self, index: int, start: int, ntime: int,
                 frames: Optional[int], arrays: Planar, rot, slot: int,
                 masked: Tuple[int, ...] = ()):
        self.index = index    # window ordinal in the stream
        self.start = start    # sample (AntennaStream) / frame (Correlator-
        #                       Stream, per band segment) offset
        self.ntime = ntime    # global time extent of ``arrays``
        self.frames = frames  # F-engine frames this window contributes
        #                       (CorrelatorStream only)
        self.arrays = arrays
        self.masked = masked  # antennas zero-weighted in this window
        #                       (degraded continuation; see stream docs)
        self._rot = rot
        self._slot = slot

    def release(self) -> None:
        """Hand the host slot back to the producer (idempotent)."""
        if self._rot is not None:
            rot, self._rot = self._rot, None
            rot.release(self._slot)


def record_mask(masked: set, ident, reason: str, *, header: Dict,
                timeline: Timeline, kind: str = "antenna") -> bool:
    """The one zero-weight mask bookkeeping rule (ISSUE 2 tentpole,
    shared): add ``ident`` to ``masked``, mirror the sorted set into the
    product header (``_masked_<kind>s``), bump the ``<kind>.masked``
    timeline counter and the process-wide ``mask.<kind>`` fault counter,
    and log the degradation — so a degraded run SAYS so everywhere a
    healthy one reports.  Used by the windowed antenna/correlator feeds
    (``kind="antenna"``) and the streaming ingest plane's watermark
    masking (``kind="chunk"``, blit/stream — a missing chunk zero-fills
    exactly like a zero-weighted antenna plane: it contributes nothing
    to any linear product downstream).  Returns True when ``ident`` was
    newly masked."""
    if ident in masked:
        return False
    masked.add(ident)
    header[f"_masked_{kind}s"] = sorted(masked)
    timeline.count(f"{kind}.masked")
    faults.incr(f"mask.{kind}")
    log.warning(
        "%s %s %s; masking it (zero weight) and continuing degraded",
        kind, ident, reason,
    )
    return True


class _DegradedContinuation:
    """Shared degraded-antenna state for the windowed streams (ISSUE 2
    tentpole): with ``on_antenna_error="mask"`` a HARD mid-stream antenna
    failure (truncated recording, retries exhausted, wedged mount
    surfacing as an error) zero-weights that antenna from the failing
    window onward instead of aborting the scan.  Zeroed planes contribute
    exactly nothing to the linear beam sums and baseline cross-products,
    so the collectives need no math changes; the flag rides every
    subsequent :class:`Window` (``masked``), the stream's
    ``masked_antennas`` set, the product header
    (``_masked_antennas``) and the ``antenna.masked`` timeline counter,
    so a degraded run SAYS so in its report.

    Masking is per-process: on multi-process pods each process masks the
    antennas whose files it reads; processes that never read the failed
    recording keep their (already-agreed) span untouched."""

    def _init_degraded(self, on_antenna_error: str,
                       stall_timeout_s: Optional[float]) -> None:
        if on_antenna_error not in ("raise", "mask"):
            raise ValueError(
                f"on_antenna_error must be 'raise' or 'mask', "
                f"got {on_antenna_error!r}"
            )
        self.on_antenna_error = on_antenna_error
        self.stall_timeout_s = stall_timeout_s
        self.masked_antennas: set = set()

    def _mask(self, a: int, err: BaseException) -> None:
        record_mask(
            self.masked_antennas, a,
            f"hard-failed mid-stream ({type(err).__name__}: {err})",
            header=self.header, timeline=self.timeline, kind="antenna",
        )


class AntennaStream(_DegradedContinuation):
    """Windowed, double-buffered feed of per-antenna RAW recordings onto
    the beamform layout — the streaming twin of :func:`load_antennas_mesh`
    (module docstring: the ``RawReducer`` rotation applied to the
    collective data plane).

    Iterating yields :class:`Window`\\ s covering gap-free samples
    ``[start_sample + i*window_samples, ...)`` in order; every sample of
    the agreed span from ``start_sample`` lands in exactly one window
    (the final window is smaller when the span is ragged).  Stage
    timings land in ``timeline``: ``ingest`` (RAW file bytes read),
    ``pack`` (dequant/pack into the planar host buffers), ``transfer``
    (sharded ``device_put``, planar bytes moved).

    Fault tolerance (ISSUE 2): transient read errors already retry inside
    :meth:`blit.io.guppi.GuppiRaw.read_block_into` (invisible here beyond
    the ``retry.io`` counter); ``on_antenna_error="mask"`` turns HARD
    per-antenna failures into degraded continuation
    (:class:`_DegradedContinuation`) instead of a stream abort;
    ``stall_timeout_s`` arms the rotation's producer-progress watchdog so
    a wedged read bounds the hang.
    """

    def __init__(
        self,
        raw_paths: Sequence,
        *,
        mesh,
        axis: str = "bank",
        window_samples: int,
        start_sample: int = 0,
        max_samples: Optional[int] = None,
        dtype="float32",
        layout: str = "antenna",
        prefetch_depth: int = 2,
        timeline: Optional[Timeline] = None,
        on_antenna_error: str = "raise",
        stall_timeout_s: Optional[float] = None,
    ):
        if window_samples <= 0:
            raise ValueError(f"window_samples must be > 0, got {window_samples}")
        self._init_degraded(on_antenna_error, stall_timeout_s)
        self.mesh = mesh
        self.axis = axis
        self.layout = layout
        self.window_samples = window_samples
        self.start_sample = start_sample
        self.prefetch_depth = max(2, prefetch_depth)
        self.timeline = timeline if timeline is not None else Timeline()
        self.dev_dtype = _resolve_plane_dtype(dtype)
        self.nant = len(raw_paths)
        self.sharding, self.per, self.plan = _antenna_shard_plan(
            mesh, axis, layout, self.nant
        )
        local_ants = sorted({
            a for _d, lo in self.plan for a in range(lo, lo + self.per)
        })
        self._local_ants = local_ants
        self._raws, min_samps, self.nchan, self.npol = _open_antennas(
            raw_paths, local_ants
        )
        self.total_samples = _span_from(min_samps, start_sample, max_samples)
        if self.total_samples <= 0:
            raise ValueError(
                f"no common samples across {self.nant} antennas from offset "
                f"{start_sample} (common span {min_samps})"
            )
        # The window plan, identical on every process (derived from the
        # pod-agreed span): (sample offset within the span, samples).
        self.spans: List[Tuple[int, int]] = [
            (w0, min(window_samples, self.total_samples - w0))
            for w0 in range(0, self.total_samples, window_samples)
        ]
        self.header = dict(self._raws[local_ants[0]].header(0))
        self.header["_ntime"] = self.total_samples
        self.header["_nant"] = self.nant
        # Rotation slot storage: per slot, one (br, bi) pair per local
        # device, allocated lazily at the full window shape (ragged final
        # windows fill a prefix and transfer a view).
        self._store: List[Optional[Dict]] = [None] * self.prefetch_depth

    @property
    def nwindows(self) -> int:
        return len(self.spans)

    def _alloc(self, slot: int) -> Dict:
        if self._store[slot] is None:
            W = self.window_samples
            shape = (
                (self.nchan, self.per, self.npol, W)
                if self.layout == "chan"
                else (self.per, self.nchan, W, self.npol)
            )
            self._store[slot] = {
                d: (np.empty(shape, self.dev_dtype),
                    np.empty(shape, self.dev_dtype))
                for d, _lo in self.plan
            }
        return self._store[slot]

    def _zero_antenna(self, br, bi, j: int, wt: int) -> None:
        """Zero-weight one local antenna's planes for this window (the
        masked-antenna contribution to every linear collective is then
        exactly zero)."""
        if self.layout == "chan":
            br[:, j, :, :wt] = 0
            bi[:, j, :, :wt] = 0
        else:
            br[j, :, :wt] = 0
            bi[j, :, :wt] = 0

    def _fill(self, rot) -> None:
        """Producer thread: read + dequant each window into its slot's
        planar buffers (one antenna-window of int8 scratch at a time).
        Hard per-antenna failures mask-and-continue under
        ``on_antenna_error="mask"`` (class docstring)."""
        tl = self.timeline
        scratch = np.empty(
            (self.nchan, self.window_samples, self.npol, 2), np.int8
        )
        for w, (w0, wt) in enumerate(self.spans):
            slot = rot.acquire()
            if slot is None:
                return  # consumer abandoned the stream
            store = self._alloc(slot)
            raw_bytes = self.nchan * wt * self.npol * 2
            for d, lo in self.plan:
                br, bi = store[d]
                for j, a in enumerate(range(lo, lo + self.per)):
                    if a in self.masked_antennas:
                        self._zero_antenna(br, bi, j, wt)
                        continue
                    try:
                        faults.fire(
                            "antenna.produce", key=self._raws[a].path
                        )
                        with tl.stage("ingest", nbytes=raw_bytes):
                            v = _gapless(
                                self._raws[a], wt,
                                skip=self.start_sample + w0, out=scratch,
                            )
                        if v.shape[1] < wt:
                            raise ValueError(
                                f"{self._raws[a].path}: {v.shape[1]} "
                                f"samples from offset "
                                f"{self.start_sample + w0}, need {wt}"
                            )
                        with tl.stage(
                            "pack",
                            nbytes=2 * self.nchan * wt * self.npol
                            * self.dev_dtype.itemsize,
                        ):
                            if self.layout == "chan":
                                br[:, j, :, :wt] = np.transpose(
                                    v[..., 0], (0, 2, 1))
                                bi[:, j, :, :wt] = np.transpose(
                                    v[..., 1], (0, 2, 1))
                            else:
                                br[j, :, :wt] = v[..., 0]
                                bi[j, :, :wt] = v[..., 1]
                    except Exception as e:  # noqa: BLE001 — classified
                        if self.on_antenna_error != "mask":
                            raise
                        self._mask(a, e)
                        self._zero_antenna(br, bi, j, wt)
            rot.emit(slot, (w, w0, wt, tuple(sorted(self.masked_antennas))))

    def __iter__(self) -> Iterator[Window]:
        import jax

        from blit.pipeline import BufferRotation

        tl = self.timeline
        rot = BufferRotation(
            self.prefetch_depth, _traced_fill(self._fill, "antenna.produce"),
            name="blit-antenna-feed",
            stall_timeout_s=self.stall_timeout_s, timeline=tl,
        )
        try:
            for slot, (w, w0, wt, masked) in rot.slots():
                store = self._store[slot]
                if self.layout == "chan":
                    global_shape = (self.nchan, self.nant, self.npol, wt)
                else:
                    global_shape = (self.nant, self.nchan, wt, self.npol)
                nbytes = 0
                with tl.stage("transfer"):
                    shards_r, shards_i = [], []
                    for d, _lo in self.plan:
                        br, bi = store[d]
                        if self.layout == "chan":
                            br, bi = br[..., :wt], bi[..., :wt]
                        else:
                            br, bi = br[:, :, :wt], bi[:, :, :wt]
                        shards_r.append(jax.device_put(br, d))
                        shards_i.append(jax.device_put(bi, d))
                        nbytes += br.nbytes + bi.nbytes
                    vr = jax.make_array_from_single_device_arrays(
                        global_shape, self.sharding, shards_r
                    )
                    vi = jax.make_array_from_single_device_arrays(
                        global_shape, self.sharding, shards_i
                    )
                tl.stages["transfer"].bytes += nbytes
                # The consumer releases (Window docstring): device_put may
                # be zero-copy (CPU) or still in flight (TPU DMA), so the
                # slot is only safe to refill once the compute that read
                # this window has synchronized.
                yield Window(
                    w, self.start_sample + w0, wt, None, (vr, vi), rot,
                    slot, masked=masked,
                )
        finally:
            rot.close()


class CorrelatorStream(_DegradedContinuation):
    """Windowed, double-buffered feed onto the FX-correlator layout — the
    streaming twin of :func:`load_correlator_mesh`.

    The agreed span from ``start_sample`` splits into ``nband`` time
    segments exactly as the one-shot loader's (band axis = disjoint time
    segments, :func:`blit.parallel.correlator.correlator_sharding`); each
    segment's F-engine frames then stream in windows of ``window_frames``.
    Window ``w`` carries frames ``[w*window_frames, ...)`` of EVERY band
    segment: its arrays are ``(nant, nchan, nband*wsamps, npol)`` with
    ``wsamps = (frames + ntap - 1) * nfft``, directly consumable by the
    per-window correlator step.  Consecutive windows overlap by the
    ``(ntap-1)*nfft``-sample PFB tail, memcpy'd between rotation buffers
    (the ``RawReducer`` state-carry; every other byte is read from disk
    exactly once), so the windowed spectra are bit-identical to a
    one-shot F-engine pass over each whole segment —
    :func:`blit.parallel.correlator.correlate_stream` accumulates their
    visibilities across windows on-device.
    """

    def __init__(
        self,
        raw_paths: Sequence,
        *,
        mesh,
        nfft: int,
        ntap: int = 4,
        window_frames: int,
        start_sample: int = 0,
        max_samples: Optional[int] = None,
        dtype="float32",
        prefetch_depth: int = 2,
        timeline: Optional[Timeline] = None,
        on_antenna_error: str = "raise",
        stall_timeout_s: Optional[float] = None,
    ):
        if window_frames <= 0:
            raise ValueError(f"window_frames must be > 0, got {window_frames}")
        self._init_degraded(on_antenna_error, stall_timeout_s)
        self.mesh = mesh
        self.nfft, self.ntap = nfft, ntap
        self.window_frames = window_frames
        self.start_sample = start_sample
        self.prefetch_depth = max(2, prefetch_depth)
        self.timeline = timeline if timeline is not None else Timeline()
        self.dev_dtype = _resolve_plane_dtype(dtype)
        self.nant = len(raw_paths)
        self.nband = mesh.shape["band"]
        self.nbank = mesh.shape["bank"]

        from blit.parallel.correlator import correlator_sharding

        self.sharding = correlator_sharding(mesh)
        self._raws, min_samps, self.nchan, self.npol = _open_antennas(
            raw_paths, list(range(self.nant))
        )
        if self.nchan % self.nbank:
            raise ValueError(
                f"nchan={self.nchan} must divide over {self.nbank} banks"
            )
        self.cper = self.nchan // self.nbank
        total = _span_from(min_samps, start_sample, max_samples)
        self.seg = (total // self.nband) // nfft * nfft if total > 0 else 0
        if self.seg // nfft < ntap:
            raise ValueError(
                f"correlator needs >= {ntap} nfft-blocks per band segment; "
                f"have {self.seg // nfft} (total {total} samples over "
                f"{self.nband} bands from offset {start_sample})"
            )
        self.total_frames = self.seg // nfft - ntap + 1
        # The window plan (identical on every process): frame spans per
        # band segment.
        self.spans: List[Tuple[int, int]] = [
            (f0, min(window_frames, self.total_frames - f0))
            for f0 in range(0, self.total_frames, window_frames)
        ]
        self.header = dict(self._raws[0].header(0))
        self.header["_ntime"] = self.seg * self.nband
        self.header["_nant"] = self.nant
        # Local band rows and their devices (multi-process pods own a
        # subset of rows; every process reads every antenna, but only its
        # rows' time windows — the one-shot loader's locality rule).
        dev_map = self.sharding.addressable_devices_indices_map(
            (self.nant, self.nchan, self.seg * self.nband, self.npol)
        )
        self._by_band: Dict[int, list] = {}
        for d, idx in dev_map.items():
            b = (idx[2].start or 0) // self.seg
            k = (idx[1].start or 0) // self.cper
            self._by_band.setdefault(b, []).append((d, k))
        # Slot storage: per slot, one (br, bi) planar pair per local band
        # row, at the full window sample extent.
        self._store: List[Optional[Dict]] = [None] * self.prefetch_depth
        self._wsamps_max = (window_frames + ntap - 1) * nfft

    @property
    def nwindows(self) -> int:
        return len(self.spans)

    def _alloc(self, slot: int) -> Dict:
        if self._store[slot] is None:
            shape = (self.nant, self.nchan, self._wsamps_max, self.npol)
            self._store[slot] = {
                b: (np.empty(shape, self.dev_dtype),
                    np.empty(shape, self.dev_dtype))
                for b in sorted(self._by_band)
            }
        return self._store[slot]

    def _fill(self, rot) -> None:
        """Producer: each window's fresh samples read + dequantized into
        its slot, the PFB tail memcpy'd from the previous slot's buffers
        (which the consumer may still be reading — a slot is only
        REFILLED after release, exactly the reducer's rotation rule)."""
        tl = self.timeline
        nfft, ntap = self.nfft, self.ntap
        ov = (ntap - 1) * nfft
        scratch = np.empty(
            (self.nchan, self._wsamps_max, self.npol, 2), np.int8
        )
        prev: Optional[Dict] = None
        prev_used = 0
        for w, (f0, fw) in enumerate(self.spans):
            slot = rot.acquire()
            if slot is None:
                return
            store = self._alloc(slot)
            if store is prev:
                # The tail memcpy below reads the PREVIOUS slot; in-order
                # release over >= 2 slots can never hand the producer the
                # tail source itself (slots rotate FIFO), so this is a
                # consumer releasing out of order — fail loud, don't
                # self-copy.
                raise RuntimeError(
                    "correlator feed: window released out of order "
                    "(producer re-acquired its PFB-tail source slot)"
                )
            used = (fw + ntap - 1) * nfft
            fresh0 = 0 if w == 0 else ov  # tail comes from prev buffers
            fresh = used - fresh0
            for b in sorted(self._by_band):
                br, bi = store[b]
                if fresh0:
                    with tl.stage(
                        "state",
                        nbytes=2 * self.nant * self.nchan * ov * self.npol
                        * self.dev_dtype.itemsize,
                    ):
                        pbr, pbi = prev[b]
                        br[:, :, :ov] = pbr[:, :, prev_used - ov:prev_used]
                        bi[:, :, :ov] = pbi[:, :, prev_used - ov:prev_used]
                row_base = self.start_sample + b * self.seg
                raw_bytes = self.nchan * fresh * self.npol * 2
                for a in range(self.nant):
                    if a in self.masked_antennas:
                        # Whole window extent, PFB tail included — a
                        # masked antenna's stale tail must not leak.
                        br[a, :, :used] = 0
                        bi[a, :, :used] = 0
                        continue
                    try:
                        faults.fire(
                            "antenna.produce", key=self._raws[a].path
                        )
                        with tl.stage("ingest", nbytes=raw_bytes):
                            v = _gapless(
                                self._raws[a], fresh,
                                skip=row_base + f0 * nfft + fresh0,
                                out=scratch,
                            )
                        if v.shape[1] < fresh:
                            raise ValueError(
                                f"{self._raws[a].path}: {v.shape[1]} "
                                f"samples from offset "
                                f"{row_base + f0 * nfft + fresh0}, "
                                f"need {fresh}"
                            )
                        with tl.stage(
                            "pack",
                            nbytes=2 * self.nchan * fresh * self.npol
                            * self.dev_dtype.itemsize,
                        ):
                            br[a, :, fresh0:used] = v[..., 0]
                            bi[a, :, fresh0:used] = v[..., 1]
                    except Exception as e:  # noqa: BLE001 — classified
                        if self.on_antenna_error != "mask":
                            raise
                        self._mask(a, e)
                        # The window is masked WHOLE for this antenna,
                        # across every band row of the current slot (some
                        # rows were already packed with its pre-failure
                        # bytes this window).
                        for bb in sorted(self._by_band):
                            bbr, bbi = store[bb]
                            bbr[a, :, :used] = 0
                            bbi[a, :, :used] = 0
            rot.emit(slot, (w, f0, fw, used,
                            tuple(sorted(self.masked_antennas))))
            prev, prev_used = store, used

    def __iter__(self) -> Iterator[Window]:
        import jax

        from blit.pipeline import BufferRotation

        tl = self.timeline
        rot = BufferRotation(
            self.prefetch_depth,
            _traced_fill(self._fill, "correlator.produce"),
            name="blit-correlator-feed",
            stall_timeout_s=self.stall_timeout_s, timeline=tl,
        )
        try:
            for slot, (w, f0, fw, used, masked) in rot.slots():
                store = self._store[slot]
                global_shape = (
                    self.nant, self.nchan, self.nband * used, self.npol
                )
                nbytes = 0
                with tl.stage("transfer"):
                    shards = {}
                    for b in sorted(self._by_band):
                        br, bi = store[b]
                        for d, k in self._by_band[b]:
                            cr = br[:, k * self.cper:(k + 1) * self.cper,
                                    :used]
                            ci = bi[:, k * self.cper:(k + 1) * self.cper,
                                    :used]
                            shards[d] = (jax.device_put(cr, d),
                                         jax.device_put(ci, d))
                            nbytes += cr.nbytes + ci.nbytes
                    vr = jax.make_array_from_single_device_arrays(
                        global_shape, self.sharding,
                        [s[0] for s in shards.values()],
                    )
                    vi = jax.make_array_from_single_device_arrays(
                        global_shape, self.sharding,
                        [s[1] for s in shards.values()],
                    )
                tl.stages["transfer"].bytes += nbytes
                # Consumer releases once its compute synchronized (Window
                # docstring) — the PFB-tail memcpy additionally reads the
                # previous slot, which the rotation's refill-after-release
                # rule already covers.
                yield Window(
                    w, f0, self.nband * used, fw, (vr, vi), rot, slot,
                    masked=masked,
                )
        finally:
            rot.close()
