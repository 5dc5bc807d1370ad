"""The sharded reduction plane (ISSUE 9): one scan as ONE SPMD program,
threaded end to end through the ingest and output planes.

``reduce_scan_mesh_to_files`` (blit/parallel/scan.py) already reduces a
scan as a single sharded computation, but its window loop is serial:
synchronous per-window ``_gapless`` re-reads, a blocking readback, an
inline write.  This module is the same SPMD math with every host leg on
its own thread — the ``RawReducer._pump`` architecture lifted onto the
``(band, bank)`` mesh:

- **feed**: a :class:`blit.pipeline.BufferRotation` whose slots are
  per-local-player pinned host slabs (:mod:`blit.hostmem` pool), filled
  by a producer thread while the mesh computes earlier windows; the
  global sharded voltage array is assembled with ``jax.device_put`` +
  shardings (:func:`blit.parallel.mesh.put_local_shards` — the
  partition-rule-driven replacement for the pool path's per-worker H2D
  scatter);
- **compute**: the per-chip channelize and the cross-bank stitch run as
  two dispatches (``band_reduce(stitch=False)`` +
  :func:`blit.parallel.mesh.stitch_despike`) so the all_gather can be
  timed honestly on probe windows (``mesh.gather_s``) and its ICI bytes
  accounted per window — the per-chip program is bit-identical to the
  pool path's single-chip ``channelize`` (the byte-identity oracle,
  tests/test_sharded.py);
- **readback**: only ADDRESSABLE shards cross D2H — each owned band
  row's bank-0 shard goes through an
  :class:`blit.outplane.OutputRotation` readback thread; processes that
  own no band row sync their window with a fetch-free put (they still
  participate in every collective);
- **write**: per-band products stream write-behind through
  :class:`blit.outplane.AsyncSink` into the SAME writers (and the same
  pod-wide-agreed resume machinery) as the sync loop
  (:func:`blit.parallel.scan._open_band_writers`).

The pool path (:func:`blit.parallel.scan.reduce_scan_pool_to_files`)
stays as the fallback and the correctness oracle: products here are
byte-identical to it — ``.fil``, ``.h5`` and, via
:func:`search_scan_sharded_to_files`, per-player ``.hits`` (each chip
searches its own frequency slice with the identical ``dedoppler_hits``
program the pool-path :class:`blit.search.DedopplerReducer` runs).
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from blit import faults, observability
from blit.monitor import published
from blit.observability import Timeline, profile_trace
from blit.ops.channelize import coeff_bank, usable_frames
from blit.parallel import mesh as M
from blit.parallel.scan import (
    _despike_nfpc,
    _gapless,
    _gather_int64,
    _open_band_writers,
    _open_players,
    _resolve_grid,
    _resolve_out_paths,
    _scan_headers,
    scan_window_frames,
)

log = logging.getLogger("blit.sharded")


class _ShardWindow:
    """One window of the sharded feed: the assembled global voltage
    array plus its frame coordinates.  ``release`` hands the slot back
    to the producer — call it only once the dispatch that consumed
    ``volt`` has synchronized (the ``on_consumed`` discipline)."""

    __slots__ = ("volt", "index", "f0", "frames", "ntime", "_rot", "_slot")

    def __init__(self, volt, index, f0, frames, ntime, rot, slot):
        self.volt = volt
        self.index = index
        self.f0 = f0
        self.frames = frames
        self.ntime = ntime
        self._rot = rot
        self._slot = slot

    def release(self) -> None:
        if self._rot is not None:
            rot, self._rot = self._rot, None
            rot.release(self._slot)


class _ShardFeed:
    """The pipelined per-shard window feed: a producer thread reads each
    LOCAL player's gap-free span for window ``w+1`` into pinned staging
    slabs while the mesh computes window ``w`` — the
    :class:`blit.pipeline.BufferRotation` ingest discipline applied to
    the whole-scan grid.  Stage accounting: ``ingest`` (RAW bytes read,
    producer thread), ``transfer`` (device_put of every local shard)."""

    def __init__(self, raws, local, mesh, nchan, npol, *, nfft, ntap,
                 wf, total, f0_start, timeline,
                 prefetch_depth=2, extra_slots=0, stall_timeout_s=None):
        self.raws, self.local, self.mesh = raws, local, mesh
        self.nchan, self.npol = nchan, npol
        self.nfft, self.ntap = nfft, ntap
        self.tl = timeline
        self.spans: List[Tuple[int, int]] = []
        f0 = f0_start
        while f0 < total:
            n = min(wf, total - f0)
            self.spans.append((f0, n))
            f0 += n
        self.max_ntime = (wf + ntap - 1) * nfft
        self.nslots = max(2, prefetch_depth) + max(0, extra_slots)
        self.stall_timeout_s = stall_timeout_s
        self._store: List[Optional[Dict]] = [None] * self.nslots

    @property
    def nwindows(self) -> int:
        return len(self.spans)

    def _alloc(self, slot: int) -> Dict:
        if self._store[slot] is None:
            from blit import hostmem

            shape = (self.nchan, self.max_ntime, self.npol, 2)
            pool = hostmem.slab_pool()
            self._store[slot] = {
                bk: pool.take(shape, np.int8, self.tl) for bk in self.local
            }
        return self._store[slot]

    def _fill(self, rot) -> None:
        nfft, ntap = self.nfft, self.ntap
        for w, (f0, n) in enumerate(self.spans):
            slot = rot.acquire()
            if slot is None:
                return  # consumer abandoned the stream
            store = self._alloc(slot)
            ntime = (n + ntap - 1) * nfft
            for bk in self.local:
                r = self.raws[bk]
                with self.tl.stage(
                    "ingest", nbytes=self.nchan * ntime * self.npol * 2
                ):
                    v = _gapless(r, ntime, skip=f0 * nfft,
                                 out=store[bk][:, :ntime])
                if (v.shape[0] != self.nchan or v.shape[1] < ntime
                        or v.shape[2:] != (self.npol, 2)):
                    raise ValueError(
                        f"{r.path}: shape {v.shape} incompatible with "
                        f"(nchan={self.nchan}, ntime>={ntime}, "
                        f"npol={self.npol}, 2)"
                    )
            rot.emit(slot, (w, f0, n, ntime))

    def windows(self):
        """Yield :class:`_ShardWindow` in stream order (the consumer MUST
        release every window once its dispatch synchronized)."""
        import jax  # noqa: F401 — device_put inside put_local_shards

        from blit.pipeline import BufferRotation

        nband, nbank = self.mesh.devices.shape
        rot = BufferRotation(
            self.nslots, self._fill, name="blit-mesh-feed",
            stall_timeout_s=self.stall_timeout_s, timeline=self.tl,
        )
        try:
            for slot, (w, f0, n, ntime) in rot.slots():
                store = self._store[slot]
                gshape = (nband, nbank, self.nchan, ntime, self.npol, 2)
                nbytes = 0
                with self.tl.stage("transfer"):
                    blocks = {}
                    for bk in self.local:
                        blk = store[bk][:, :ntime][None, None]
                        if not blk.flags["C_CONTIGUOUS"]:
                            # Only the final ragged window pays a copy —
                            # full windows fill the slab exactly.
                            blk = np.ascontiguousarray(blk)
                        blocks[bk] = blk
                        nbytes += blk.nbytes
                    volt = M.put_local_shards(blocks, self.mesh, gshape)
                self.tl.stages["transfer"].bytes += nbytes
                yield _ShardWindow(volt, w, f0, n, ntime, rot, slot)
        finally:
            rot.close()

    def retire(self) -> None:
        """Return the staging slabs to the process pool — call only
        after a TERMINAL sync (stream drained, sinks closed), never on an
        error path where an un-synced dispatch might still read one."""
        from blit import hostmem

        pool = hostmem.slab_pool()
        for store in self._store:
            if store:
                for slab in store.values():
                    pool.give(slab, self.tl)
        self._store = [None] * self.nslots


def _mesh_probe_windows() -> int:
    from blit.config import mesh_defaults

    return mesh_defaults()["probe_windows"]


@published
def reduce_scan_sharded_to_files(
    raw_paths,
    scan: Optional[str] = None,
    *,
    inventories=None,
    out_dir: Optional[str] = None,
    out_paths: Optional[Sequence[str]] = None,
    nfft: int,
    ntap: int = 4,
    nint: int = 1,
    stokes: str = "I",
    fqav_by: int = 1,
    fft_method: str = "auto",
    window: str = "hamming",
    despike: bool = True,
    max_frames: Optional[int] = None,
    window_frames: Optional[int] = None,
    compression: Optional[str] = None,
    resume: bool = False,
    mesh=None,
    dtype: str = "float32",
    prefetch_depth: Optional[int] = None,
    out_depth: Optional[int] = None,
    probe_windows: Optional[int] = None,
    timeline=None,
    trace_logdir: Optional[str] = None,
    heartbeat=None,
) -> Dict[int, Tuple[str, Dict]]:
    """Reduce one scan across the mesh with the fully-threaded sharded
    plane (module docstring) and stream each stitched band to its
    product.  Call shapes, resume semantics (pod-wide agreed restart)
    and products are those of
    :func:`blit.parallel.scan.reduce_scan_mesh_to_files` — byte-identical
    to it AND to the pool oracle
    (:func:`blit.parallel.scan.reduce_scan_pool_to_files`) at matching
    ``window_frames``.

    New knobs: ``prefetch_depth``/``out_depth`` size the feed rotation
    and the readback/write-behind planes (``None`` = the ingest-plane
    defaults); ``probe_windows`` (default
    ``BLIT_MESH_PROBE`` / SiteConfig ``mesh_probe_windows``) is how many
    leading windows time the stitch collective honestly — those windows
    sync the per-chip compute first, so ``mesh.gather_s`` measures the
    all_gather dispatch alone; steady-state windows stay fully
    overlapped and only account ICI bytes.

    ``heartbeat`` (ISSUE 12) is an optional per-window liveness callback
    ``heartbeat(window_index)``, invoked between windows on the consumer
    thread — the :class:`blit.recover.ScanSupervisor` passes its lease
    refresh here, so a peer that stops making window progress (dead OR
    wedged in a collective) stops beating and the supervisor can detect
    it from outside the SPMD program.  The ``mesh.window`` fault point
    fires at the same cadence (``kill``/``hang`` chaos drills).
    """
    from blit.outplane import (
        AsyncSink,
        OutputRotation,
        readback_extra_slots,
    )

    wf = scan_window_frames(nfft, nint, window_frames)
    if wf % nint:
        # Raised from arguments alone, so on every process alike and
        # before any collective.  Never a window rounded up to nint.
        raise ValueError(
            f"the sharded plane has no carry: an integration of {nint} "
            f"frames does not fit its {wf}-frame window (nfft {nfft}).  "
            "Use the default mesh loop (reduce_scan_mesh_to_files; `blit "
            "scan` without --sharded), which carries it across windows"
        )
    band_ids, raw_paths = _resolve_grid(raw_paths, scan, inventories)
    mesh, local, raws, nchan, npol, min_samps = _open_players(raw_paths, mesh)
    nband, nbank = mesh.devices.shape

    total = usable_frames(min_samps, nfft, ntap, nint)
    if max_frames is not None:
        total = min(total, (max_frames // nint) * nint)
    if total <= 0:
        raise ValueError(
            f"scan too short: {min_samps} samples for nfft={nfft}"
        )
    prefetch = max(2, prefetch_depth or 2)
    depth = max(2, out_depth or prefetch)
    if probe_windows is None:
        probe_windows = _mesh_probe_windows()

    out_paths = _resolve_out_paths(
        band_ids, nband, out_dir, out_paths, compression
    )
    h0, bases, per_bank = _scan_headers(
        raws, local, nfft=nfft, nint=nint, stokes=stokes, fqav_by=fqav_by,
    )
    tl = timeline if timeline is not None else Timeline()
    coeffs = coeff_bank(ntap, nfft, window, tl)
    despike_nfpc = _despike_nfpc(despike, nfft, fqav_by)

    mine, headers, writers, f0_start = _open_band_writers(
        mesh, raws, out_paths, h0=h0, bases=bases,
        per_bank=per_bank, stokes=stokes, nfft=nfft, ntap=ntap, nint=nint,
        window=window, fqav_by=fqav_by, dtype=dtype,
        despike_nfpc=despike_nfpc, compression=compression, resume=resume,
        wf=wf, total=total,
    )

    feed = _ShardFeed(
        raws, local, mesh, nchan, npol, nfft=nfft, ntap=ntap, wf=wf,
        total=total, f0_start=f0_start, timeline=tl,
        prefetch_depth=prefetch,
        extra_slots=readback_extra_slots(depth, prefetch),
    )
    def route(slab) -> None:
        b = slab.payload
        sinks[b].append(slab.data[0], release=slab.release)

    rot = None
    sinks = {}
    nsamps = {}
    try:
        # Construct the readback/write-behind planes INSIDE the guarded
        # region: a failed constructor (e.g. thread creation under
        # resource pressure) must still abort every band's writer — the
        # except below aborts built sinks AND bare not-yet-wrapped
        # writers, so no .partial products or stale cursors leak.
        rot = OutputRotation(depth=depth, timeline=tl, reuse=True,
                             name="blit-mesh-readback")
        for b in mine:
            sinks[b] = AsyncSink(writers[b], depth=depth, timeline=tl)
        with profile_trace(trace_logdir), observability.span(
            "mesh.scan", nfft=nfft, nband=nband, nbank=nbank,
            sharded=True,
        ), tl.stage("stream"):
            for win in feed.windows():
                faults.fire("mesh.window", key=f"w{win.index}")
                if heartbeat is not None:
                    heartbeat(win.index)
                with observability.span("mesh.window", i=win.index), \
                        tl.stage("dispatch", byte_free=True):
                    part = M.band_reduce(
                        win.volt, coeffs, mesh=mesh, nfft=nfft, ntap=ntap,
                        nint=nint, stokes=stokes, fft_method=fft_method,
                        stitch=False, despike_nfpc=0, fqav_by=fqav_by,
                        dtype=dtype,
                    )
                gather_s = None
                if win.index < probe_windows and nbank > 1:
                    # Honest collective probe: sync the per-chip compute
                    # so the timed dispatch below is the all_gather
                    # program alone.  Serializes ONLY these windows.
                    with tl.stage("mesh.probe", byte_free=True):
                        part.block_until_ready()
                        if win.index == 0:
                            # Warm-up: the stream's first stitch call
                            # pays trace+XLA compile — execute it
                            # untimed so every mesh.gather_s sample is
                            # the collective, not the compiler (the
                            # bench leg's own warm-up idiom).
                            M.stitch_despike(
                                part, mesh=mesh,
                                despike_nfpc=despike_nfpc,
                            ).block_until_ready()
                        t0 = time.perf_counter()
                        out = M.stitch_despike(
                            part, mesh=mesh, despike_nfpc=despike_nfpc
                        )
                        out.block_until_ready()
                        gather_s = time.perf_counter() - t0
                else:
                    with tl.stage("dispatch", byte_free=True):
                        out = M.stitch_despike(
                            part, mesh=mesh, despike_nfpc=despike_nfpc
                        )
                if nbank > 1:
                    shard_bytes = part.nbytes // (nband * nbank)
                    M.record_ici(
                        tl, "gather",
                        M.gather_ici_bytes(shard_bytes, nbank), gather_s,
                    )
                # Release the feed slot only when EVERY addressable
                # shard of the window's stitched output is ready: the
                # GLOBAL sync proves every local device consumed its
                # staged voltage block (async H2D transfers included) —
                # syncing one band's shard would not cover devices in
                # OTHER band rows, and the producer would overwrite a
                # pinned slab a transfer still reads.  outs=[]:
                # ordering/back-pressure only, no bytes move; processes
                # owning no band row ride the same put.
                fed = (len(local) * nchan * win.ntime * npol * 2)
                for slab in rot.put(out, nbytes=fed, outs=[],
                                    on_consumed=win.release):
                    route(slab)
                # Readback: ADDRESSABLE shards only — one per owned band
                # row (the stitched band is replicated across the row).
                by_dev = {s.device: s.data for s in out.addressable_shards}
                for b in mine:
                    shard = by_dev[mesh.devices[b, 0]]
                    for slab in rot.put(shard, outs=[(shard, b)]):
                        route(slab)
            # Drain + close run INSIDE the stream stage — its __exit__
            # already covers them (unlike RawReducer._pump, whose stage
            # closes before the drain and must add the tail manually).
            for slab in rot.drain():
                route(slab)
            for b in list(sinks):
                sinks[b].close()
                nsamps[b] = sinks.pop(b).nsamps
    except BaseException:
        for s in sinks.values():
            s.abort()  # the writers' own crash contracts (resume point)
        for b in mine:
            if b not in sinks and b not in nsamps:
                writers[b].abort()  # never wrapped in a sink
        raise
    finally:
        if rot is not None:
            rot.close()
    tl.overlap_efficiency()
    feed.retire()
    for b in mine:
        headers[b]["nsamps"] = nsamps[b]
    return {band_ids[b]: (out_paths[b], headers[b]) for b in mine}


def _mesh_dedoppler_fn():
    """Build (once) the jitted mesh-wide dedoppler step: every chip runs
    the IDENTICAL ``dedoppler_hits`` program the pool path runs on its
    own frequency slice — zero-padded band edges per chip, per-band
    top-k per chip — with no collective at all: hits stay
    ``(band, bank)``-sharded and each process reads back only its own
    players' packed tables."""
    import jax
    from jax.sharding import PartitionSpec as P

    from blit.ops.pallas_dedoppler import dedoppler_hits

    @functools.partial(
        jax.jit,
        static_argnames=("mesh", "top_k", "nbands", "max_drift_bins",
                         "kernel", "interpret"),
    )
    def step(spectra, thr, *, mesh, top_k, nbands, max_drift_bins,
             kernel, interpret):
        def body(x, t):
            return dedoppler_hits(
                x[0], t, top_k=top_k, nbands=nbands,
                max_drift_bins=max_drift_bins, kernel=kernel,
                interpret=interpret,
            )[None, None]

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(M.BAND_AXIS, None, M.BANK_AXIS), P()),
            out_specs=M.partition_rule("packed_hits"),
            check_vma=False,  # per-chip extraction, no collectives
        )(spectra, thr)

    return step


_MESH_DEDOPPLER = None


def _mesh_dedoppler():
    global _MESH_DEDOPPLER
    if _MESH_DEDOPPLER is None:
        _MESH_DEDOPPLER = _mesh_dedoppler_fn()
    return _MESH_DEDOPPLER


@published
def search_scan_sharded_to_files(
    raw_paths,
    scan: Optional[str] = None,
    *,
    inventories=None,
    out_dir: Optional[str] = None,
    out_paths=None,
    nfft: int,
    ntap: int = 4,
    nint: int = 1,
    window: str = "hamming",
    fft_method: str = "auto",
    dtype: str = "float32",
    window_spectra: Optional[int] = None,
    top_k: Optional[int] = None,
    snr_threshold: Optional[float] = None,
    max_drift_bins: Optional[int] = None,
    kernel: str = "auto",
    interpret: bool = False,
    max_frames: Optional[int] = None,
    window_frames: Optional[int] = None,
    resume: bool = False,
    mesh=None,
    prefetch_depth: Optional[int] = None,
    out_depth: Optional[int] = None,
    timeline=None,
    trace_logdir: Optional[str] = None,
    heartbeat=None,
) -> Dict[Tuple[int, int], Tuple[str, Dict]]:
    """Drift-search one scan across the mesh: every chip channelizes AND
    searches its own ``(band, bank)`` frequency slice in one SPMD window
    loop, writing per-player ``.hits`` products BYTE-IDENTICAL to the
    pool path's per-player :meth:`blit.search.DedopplerReducer.
    search_to_file` runs at matching dispatch shapes
    (``chunk_frames == window_frames``; tests/test_sharded.py).

    The spectra never stitch and the packed hit tables never gather —
    frequency stays the sharded axis end to end, each process reads back
    only its ADDRESSABLE players' ``(nbands, top_k, 4)`` tables (a few
    hundred bytes per window per chip crossing D2H instead of the whole
    filterbank), and the owning process writes that player's ``.hits``.

    ``window_frames`` is rounded to a whole number of search windows
    (``window_spectra * nint`` frames each) and the scan span truncated
    to full windows — the pool path's deterministic trailing-partial
    drop, reproduced exactly.  Returns ``{(band_id, bank):
    (path, header)}`` for the players THIS process wrote.

    ``resume=True`` (ISSUE 12) makes the sharded search crash-resumable,
    the :class:`~blit.search.dedoppler.SearchCursor` twin of the reduce
    plane's pod-wide resume: each local player's ``.hits`` carries a
    cursor sidecar claiming windows only after their lines are fsync'd,
    the restart window is the pod-wide-agreed MINIMUM across every
    player (window-aligned — the SPMD loop must restart identically on
    every process), each file truncates to that window's recorded byte
    claim (``SearchCursor.window_claims``), and the finished products
    are byte-identical to an uninterrupted run.  ``heartbeat`` is the
    per-window liveness callback of the reduce plane (the supervisor's
    lease refresh); the ``mesh.window`` fault point fires per window.
    """
    import os

    import jax  # noqa: F401

    from blit.io.hits import HitsWriter, ResumableHitsWriter, WindowHits
    from blit.outplane import OutputRotation, readback_extra_slots
    from blit.pipeline import ReductionCursor
    from blit.search.dedoppler import DedopplerReducer, SearchCursor
    from blit.search.hits import hits_from_packed

    band_ids, raw_paths = _resolve_grid(raw_paths, scan, inventories)
    mesh, local, raws, nchan, npol, min_samps = _open_players(raw_paths, mesh)
    nband, nbank = mesh.devices.shape

    # Knob resolution + per-player headers ride the pool path's OWN
    # reducer (byte-identity demands identical header lines and physical
    # hit mapping).  The probe reducer is never streamed — it only
    # resolves knobs and builds headers.
    sred = DedopplerReducer(
        nfft=nfft, ntap=ntap, nint=nint, window=window,
        fft_method=fft_method, dtype=dtype, window_spectra=window_spectra,
        top_k=top_k, snr_threshold=snr_threshold,
        max_drift_bins=max_drift_bins, kernel=kernel, interpret=interpret,
        prefetch_depth=prefetch_depth or 2, out_depth=out_depth,
    )
    T = sred.window_spectra
    unit = T * nint  # frames per search window

    total = usable_frames(min_samps, nfft, ntap, nint)
    if max_frames is not None:
        total = min(total, (max_frames // nint) * nint)
    nwin_total = total // unit
    if nwin_total <= 0:
        raise ValueError(
            f"scan too short for one search window: {total} frames, "
            f"need {unit} (window_spectra={T} x nint={nint})"
        )
    total = nwin_total * unit  # deterministic trailing-partial drop
    if window_frames is None:
        from blit.config import default_window_frames

        window_frames = default_window_frames(nfft)
    # Whole search windows per scan window, >= 1.
    wf = max((window_frames // unit) * unit, unit)
    prefetch = max(2, prefetch_depth or sred.prefetch_depth)
    depth = max(2, out_depth or sred.out_depth)

    if out_paths is None:
        if out_dir is None:
            raise ValueError("pass out_dir= or out_paths=")
        out_paths = [
            [os.path.join(
                out_dir, f"band{band_ids[b]}bank{k}.hits"
            ) for k in range(nbank)]
            for b in range(nband)
        ]
    if (len(out_paths) != nband
            or any(len(row) != nbank for row in out_paths)):
        raise ValueError("out_paths must be a rectangular nband x nbank "
                         "grid (one .hits per player)")

    hdrs = {bk: sred.header_for(raws[bk]) for bk in local}
    nbands = sred._nbands(nchan * nfft)
    thr = np.float32(sred.snr_threshold)
    tl = timeline if timeline is not None else Timeline()
    coeffs = coeff_bank(ntap, nfft, window, tl)
    jfn = _mesh_dedoppler()

    # Pod-wide-agreed resume point (ISSUE 12): each local player's cursor
    # names the windows it durably claimed; the restart window is the
    # MINIMUM across the whole pod, rounded DOWN to whole SCAN windows so
    # the resumed dispatch shapes match the uninterrupted run's (dispatch
    # shape is part of the byte-identity contract).  Ledger-less cursors
    # (pre-window_claims sidecars) cannot truncate to an arbitrary
    # earlier window, so they count as zero — restart fresh, never splice.
    start_window = 0
    cursors: Dict[Tuple[int, int], SearchCursor] = {}
    if resume:
        swin = wf // unit  # search windows per scan window
        local_done = []
        for bk in local:
            b, k = bk
            path = out_paths[b][k]
            paths_bk = getattr(raws[bk], "paths", None) or raws[bk].path
            cur = SearchCursor.load(path)
            ok = (
                cur is not None
                and cur.matches(sred, paths_bk)
                and cur.window_claims is not None
                and os.path.exists(path)
                and os.path.getsize(path) >= cur.byte_offset
            )
            if ok:
                # Content verification of the claim (ISSUE 13): a flip
                # INSIDE the claimed lines or a tampered sidecar fails
                # closed to a fresh start — the byte-length probe above
                # cannot see either, and the resumed writer would bake
                # the corruption into a fresh manifest.
                from blit import integrity

                ok = integrity.verify_claim(path, cur.windows_done,
                                            fmt="hits") is not False
            if not ok:
                size, mtime_ns = ReductionCursor.stat_raw(paths_bk)
                cur = SearchCursor(
                    paths_bk, nfft, ntap, nint, window=window, dtype=dtype,
                    window_spectra=T, top_k=sred.top_k,
                    snr_threshold=float(sred.snr_threshold),
                    max_drift_bins=(
                        -1 if sred.max_drift_bins is None
                        else int(sred.max_drift_bins)
                    ),
                    raw_size=size, raw_mtime_ns=mtime_ns,
                    window_claims=[],
                )
            cursors[bk] = cur
            local_done.append(cur.windows_done if ok else 0)
        local_min = min(local_done) if local_done else 1 << 61
        agreed = int(_gather_int64(
            np.asarray([local_min], np.int64)
        ).min())
        start_window = min((agreed // swin) * swin, nwin_total)

    feed = _ShardFeed(
        raws, local, mesh, nchan, npol, nfft=nfft, ntap=ntap, wf=wf,
        total=total, f0_start=start_window * unit, timeline=tl,
        prefetch_depth=prefetch,
        extra_slots=readback_extra_slots(depth, prefetch),
    )
    rot = OutputRotation(depth=depth, timeline=tl, reuse=False,
                         name="blit-mesh-search-readback")
    writers = {}
    nwindows = {bk: start_window for bk in local}

    def route(slab) -> None:
        widx, bk = slab.payload
        hits = hits_from_packed(slab.data[0, 0], widx, hdrs[bk])
        tl.observe("search.hits_per_window", len(hits))
        writers[bk].append(WindowHits(widx, hits))
        nwindows[bk] += 1
        slab.release()

    try:
        for bk in local:
            b, k = bk
            if resume:
                writers[bk] = ResumableHitsWriter(
                    out_paths[b][k], hdrs[bk], start_window, cursors[bk])
            else:
                writers[bk] = HitsWriter(out_paths[b][k], hdrs[bk])
        with profile_trace(trace_logdir), observability.span(
            "mesh.search", nfft=nfft, nband=nband, nbank=nbank,
        ), tl.stage("stream"):
            for win in feed.windows():
                faults.fire("mesh.window", key=f"w{win.index}")
                if heartbeat is not None:
                    heartbeat(win.index)
                with observability.span("mesh.window", i=win.index), \
                        tl.stage("dispatch", byte_free=True):
                    part = M.band_reduce(
                        win.volt, coeffs, mesh=mesh, nfft=nfft, ntap=ntap,
                        nint=nint, stokes="I", fft_method=fft_method,
                        stitch=False, despike_nfpc=0, dtype=dtype,
                    )
                # Release the feed slot only when EVERY local chip's
                # channelize is done: the GLOBAL `part` sync proves the
                # staged voltage slab was fully consumed (async H2D
                # included) — syncing one player's packed table would
                # not cover the other local chips.  The later jfn
                # dispatches read `part` (device-resident), never the
                # slab, so releasing here is safe.
                for slab in rot.put(part, outs=[],
                                    on_consumed=win.release):
                    route(slab)
                rows = win.frames // nint
                for j in range(rows // T):
                    widx = win.f0 // unit + j
                    with tl.stage("dispatch", byte_free=True):
                        packed = jfn(
                            part[:, j * T:(j + 1) * T, 0, :], thr,
                            mesh=mesh, top_k=sred.top_k, nbands=nbands,
                            max_drift_bins=sred.max_drift_bins,
                            kernel=sred.kernel, interpret=sred.interpret,
                        )
                    by_dev = {
                        s.device: s.data
                        for s in packed.addressable_shards
                    }
                    for bk in local:
                        shard = by_dev[mesh.devices[bk]]
                        for slab in rot.put(
                                shard, outs=[(shard, (widx, bk))]):
                            route(slab)
            for slab in rot.drain():
                route(slab)
        for bk in list(writers):
            w = writers.pop(bk)
            w.close()
    except BaseException:
        for w in writers.values():
            w.abort()
        raise
    finally:
        rot.close()
    feed.retire()
    out = {}
    for bk in local:
        b, k = bk
        hdr = dict(hdrs[bk])
        hdr["search_windows"] = nwindows[bk]
        out[(band_ids[b], k)] = (out_paths[b][k], hdr)
    return out
