"""Mesh-backed scan loading: RAW files → sharded reduction → stitched band.

The end-to-end BASELINE.json config-3 path: every bank's GUPPI RAW voltages
feed the chip that plays that ``BLP<band><bank>`` player, the per-chip
channelization runs under ``shard_map``, and the 8 banks of each band stitch
over ICI (blit/parallel/mesh.band_stream: the filter state stays on the
chips and every sample goes up once, as one word).  Each player's block is
placed directly on its chip and the global sharded array is assembled from
those per-device shards.  This is the TPU rebuild of the reference's
whole-scan workflow (``loadscan``, src/gbt.jl:90-114, which fetched
per-bank arrays to the main process and ``vcat``-ed them there).
"""

from __future__ import annotations

import logging
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from blit import hostmem, observability
from blit.device import host_link
from blit.io.guppi import GuppiRaw, open_raw, require_native_reader
from blit.monitor import published
from blit.ops.channelize import (
    STOKES_NIF,
    coeff_bank,
    lanes_block,
    output_header,
    sample_words,
    usable_frames,
)
from blit.parallel import mesh as M

log = logging.getLogger("blit.scan")


def _kept_samples(raw: GuppiRaw) -> int:
    """Gap-free samples the file yields — header arithmetic only (block
    sizes and OVERLAP are in the scanned headers; no data read)."""
    return sum(raw.block_ntime_kept(i) for i in range(raw.nblocks))


def _gapless(
    raw: GuppiRaw,
    max_samples: Optional[int],
    skip: int = 0,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """A RAW file's overlap-trimmed voltages — gap-free samples
    ``[skip, skip + max_samples)`` — read ONCE directly into the final
    ``(nchan, total, npol, 2)`` buffer (native threaded pread per block when
    built) — no per-block concatenation, no second pass.  ``skip`` indexes
    the gap-free sample stream (each block's kept prefix), so windowed
    readers can re-enter mid-recording without touching earlier bytes.

    ``out`` reuses a caller-held scratch buffer (``(nchan, >=total, npol,
    2)`` int8) instead of allocating — the window feeds read every window
    into the same scratch rather than churning a fresh GB-scale buffer per
    window.  Returns the filled ``(nchan, total, npol, 2)`` view."""
    hdr = raw.header(0)
    nchan = hdr["OBSNCHAN"]
    npol = 2 if hdr["NPOL"] > 2 else hdr["NPOL"]
    total = max(_kept_samples(raw) - skip, 0)
    if max_samples is not None:
        total = min(total, max_samples)
    if out is not None:
        if (out.dtype != np.int8 or out.shape[0] != nchan
                or out.shape[1] < total or out.shape[2:] != (npol, 2)):
            raise ValueError(
                f"_gapless: scratch shape {out.shape}/{out.dtype} cannot "
                f"hold (nchan={nchan}, total={total}, npol={npol}, 2) int8"
            )
        out = out[:, :total]
    else:
        out = np.empty((nchan, total, npol, 2), np.int8)
    filled = 0
    to_skip = skip
    for i in range(raw.nblocks):
        if filled >= total:
            break
        kept = raw.block_ntime_kept(i)
        if to_skip >= kept:
            to_skip -= kept
            continue
        nt = min(kept - to_skip, total - filled)
        got = raw.read_block_into(i, out[:, filled:], t0=to_skip, ntime_keep=nt)
        to_skip = 0
        filled += got
        if got < nt:
            # Short read (truncated recording, injected truncate fault):
            # return what actually landed — every caller length-checks the
            # result, so the shortfall surfaces as a hard error there
            # instead of shipping a stale-byte tail into the collectives.
            break
    return out[:, :filled]


# Per-player markers riding the pod-wide sample-count agreement.  ERR < UNFED
# so an owner's failure wins the cross-process MIN over "nobody fed it", and
# both exceed any real sample count (~1e11 for a 10-minute bank recording).
_SAMPS_ERR = 1 << 60  # the owning process failed to open/read the player
_SAMPS_UNFED = 1 << 61  # no process fed this player


def _gather_int64(local: np.ndarray) -> np.ndarray:
    """Allgather an int64 array across every process → ``(nproc, ...)`` —
    the pod-wide agreement primitive behind the common-frame-span decision.
    Every process sees every process's values, so any consistency check made
    on the result raises (or passes) SYMMETRICALLY — no process can proceed
    into the collectives while a peer errors out (that asymmetry would trade
    a clean error for a distributed hang).

    ``process_allgather`` canonicalizes dtypes (int64 → int32 with x64 off),
    which would corrupt sample counts past 2^31 — so values ride as exact
    (hi, lo) int32 pairs.  Single-process: ``local[None]``.
    """
    import jax

    if jax.process_count() == 1:
        return local[None]
    from jax.experimental import multihost_utils

    if (local < 0).any() or (local >= (1 << 62)).any():
        raise ValueError("_gather_int64: values must be in [0, 2^62)")
    hi = (local >> 31).astype(np.int32)
    lo = (local & 0x7FFFFFFF).astype(np.int32)
    g = multihost_utils.process_allgather(
        np.stack([hi, lo]).reshape((2,) + local.shape)
    )  # (nproc, 2, ...)
    g = np.asarray(g, np.int64)
    return (g[:, 0] << 31) | g[:, 1]  # (nproc, ...)


def _resolve_grid(raw_paths, scan, inventories):
    """Accept either an explicit ``raw_paths[band][bank]`` grid or the
    inventory-driven ``(session, scan)`` form (the reference's whole-scan
    call shape, ``loadscan(session, scan, suffix)``, src/gbt.jl:99) and
    return ``(band_ids, raw_paths)``.  ``band_ids`` labels each grid row
    with its real band number when resolved from an inventory; an explicit
    grid is labeled 0..nband-1."""
    if isinstance(raw_paths, str):
        if scan is None or inventories is None:
            raise ValueError(
                "session-form call needs load_scan_mesh(session, scan, "
                "inventories=...)"
            )
        from blit.inventory import scan_grid

        band_ids, _, grid = scan_grid(inventories, raw_paths, scan)
        return band_ids, grid
    if scan is not None or inventories is not None:
        raise ValueError(
            "scan=/inventories= only apply to the session-form call; an "
            "explicit raw_paths grid already names every file"
        )
    return list(range(len(raw_paths))), raw_paths


def _open_players(raw_paths, mesh):
    """Shared prologue of the mesh scan entry points: validate the grid,
    build the mesh, open THIS process's players, and agree the usable
    sample span / geometry / per-player failures pod-wide (symmetric
    errors — see ``_gather_int64``).

    Returns ``(mesh, local, raws, nchan, npol, min_samps)`` where ``local``
    is this process's sorted (band, bank) list and ``raws`` maps each of
    its openable entries to a GuppiRaw."""
    import jax

    from blit.parallel.multihost import local_players

    nband = len(raw_paths)
    nbank = len(raw_paths[0])
    if any(len(row) != nbank for row in raw_paths):
        raise ValueError("raw_paths must be rectangular (nband x nbank)")
    if mesh is None:
        mesh = M.make_mesh(nband, nbank)

    local = sorted(local_players(mesh))
    if not local:
        raise ValueError(
            "this process owns no device of the scan mesh "
            f"(process {jax.process_index()}/{jax.process_count()})"
        )
    # Open this process's players.  Failures do NOT raise yet: the owner
    # must first tell the pod via the agreement below, so every process
    # raises together instead of the peers hanging in the collectives.
    raws = {}
    local_errs = {}
    for b, k in local:
        try:
            r = open_raw(raw_paths[b][k])
            if r.nblocks == 0:
                raise ValueError(f"empty RAW file: {r.path}")
            require_native_reader(r)
            raws[(b, k)] = r
        except Exception as e:  # noqa: BLE001 — reported pod-wide below
            local_errs[(b, k)] = e

    if raws:
        first = raws[sorted(raws)[0]].header(0)
        nchan = first["OBSNCHAN"]
        npol = 2 if first["NPOL"] > 2 else first["NPOL"]
    else:
        nchan = npol = 0  # nothing openable; the ERR agreement raises below

    # Common whole-frame span across every player (ragged recordings trim),
    # via the same frame-accounting invariant the streaming pipeline uses.
    # Header arithmetic only — each file's data is read exactly once, later.
    # The span, the (nchan, npol) geometry, and any per-player failures are
    # agreed across processes: every process must assemble the same global
    # array shape — and must error together — or the collectives deadlock.
    samps = np.full((nband, nbank), _SAMPS_UNFED, np.int64)
    for (b, k), r in raws.items():
        samps[b, k] = _kept_samples(r)
    for bk in local_errs:
        samps[bk] = _SAMPS_ERR
    gathered = _gather_int64(np.concatenate([samps.ravel(), [nchan, npol]]))
    samps = gathered[:, :-2].min(axis=0).reshape(nband, nbank)
    failed = [tuple(i) for i in np.argwhere(samps == _SAMPS_ERR)]
    if failed:
        mine = "; ".join(
            f"{bk}: {type(e).__name__}: {e}" for bk, e in sorted(local_errs.items())
        )
        cause = next(iter(local_errs.values()), None)
        raise ValueError(
            f"players {failed} failed to open on their owning process"
            + (f" (this process: {mine})" if mine else "")
        ) from cause
    unfed = [tuple(i) for i in np.argwhere(samps == _SAMPS_UNFED)]
    if unfed:
        raise ValueError(f"no process fed players {unfed}")
    geo = gathered[:, -2:]
    geo = geo[(geo != 0).any(axis=1)]
    if not (geo == geo[0]).all():
        raise ValueError(
            f"processes disagree on (nchan, npol): {[tuple(g) for g in geo]}"
        )
    return mesh, local, raws, int(geo[0][0]), int(geo[0][1]), int(samps.min())


def _read_window(raws, local, nchan, npol, start, ntime, tl=None,
                 staged=None, slab_ntime=None, head_ntime=0):
    """The READ half of a window's feed: gap-free samples ``[start, start
    + ntime)`` of every LOCAL player, read into host memory one player
    after the other (``feed.read`` per read on ``tl``), as
    :func:`blit.ops.channelize.sample_words` (one word a sample: a view
    of what was read, and the form the host link carries at speed).
    Every sample of a scan is read once: a window is its NEW samples, its
    filter state is on the chips already.  Where it is not — a stream's
    first window — ``head_ntime`` asks for the head too, samples ``[start
    - head_ntime, start)``: a read of its own per player.  Returns
    ``(heads, bodies)``, each ``{(band, bank): (1, 1, nchan, n) words}``,
    ``heads`` empty where none was asked for.  Touches no device: the
    scan's feed thread runs it a window ahead of the loop
    (:func:`reduce_scan_mesh_to_files`).

    ``staged`` (a list) makes each read's buffer a slab of the process
    staging pool (blit/hostmem.py) and appends it: the caller gives the
    slabs back once THIS window's dispatch has synchronized — never
    sooner, ``device_put`` returns before the bytes have landed (and on
    the CPU backend may alias the slab outright).  A body's slab has
    ``slab_ntime`` samples per channel (the scan's full window; default
    this window's own) and a shorter window — a scan's last — reads into
    its leading bytes: one shape class per scan, so the pool hands every
    window memory that is already faulted (a ragged last window of a
    shape of its own pushed four full-window slabs out of the pool every
    pass, and the next pass first-touched four fresh ones inside its
    timed reads).  A head's slab has the head's own shape, the same in
    every scan of that ``nfft``.  Without ``staged`` each read goes into
    fresh memory that the returned arrays keep alive."""
    tl = tl if tl is not None else observability.Timeline()

    def slab(n, slab_n):
        buf = hostmem.slab_pool().take(
            (nchan, max(n, slab_n), npol, 2), np.int8, tl)
        staged.append(buf)
        if buf.shape[1] != n:  # contiguous, in the slab's head
            buf = buf.reshape(-1)[:nchan * n * npol * 2].reshape(
                nchan, n, npol, 2)
        return buf

    def read(r, skip, n, buf):
        with tl.stage("feed.read", nchan * n * npol * 2):
            v = _gapless(r, n, skip=skip, out=buf)
        if v.shape != (nchan, n, npol, 2):
            raise ValueError(
                f"{r.path}: shape {v.shape} incompatible with "
                f"(nchan={nchan}, ntime={n}, npol={npol}, 2)"
            )
        return sample_words(v)[None, None]

    heads, bodies = {}, {}
    reads = []  # (into, player, first sample, samples, the slab's samples)
    for bk in local:
        if head_ntime:
            reads.append((heads, bk, start - head_ntime, head_ntime, 0))
        reads.append((bodies, bk, start, ntime, slab_ntime or 0))
    # All of the window's slabs are taken before its first read: what the
    # pool has lent at its peak is then whole windows, the same in every
    # pass.  The pool keeps what a stretch lent at its peak; taken bank by
    # bank, how many the feed thread held when the loop gave a window's
    # back moved with the timing, and a pass that held one more than the
    # pass before first-touched a slab inside its timed reads (0.54 GB,
    # 0.2-0.8 s of a band pass).
    bufs = [slab(n, slab_n) if staged is not None else None
            for _, _, _, n, slab_n in reads]
    for (into, bk, skip, n, _), buf in zip(reads, bufs):
        into[bk] = read(raws[bk], skip, n, buf)
    return heads, bodies


def _put_window(heads, bodies, mesh, tl=None):
    """The PUT half of a window's feed: what :func:`_read_window` read
    goes straight onto each player's chip (``feed.put`` per put) and the
    global sharded arrays are built from the single-device shards (no
    whole-scan host buffer, no device_put to any non-addressable device)
    — the assembly itself is :func:`blit.parallel.mesh.put_local_shards`,
    the ONE partition-rule-driven implementation the sharded plane
    shares.  Returns ``(head, body)``: ``body`` the global ``(nband,
    nbank, nchan, ntime)`` array of one
    :func:`blit.parallel.mesh.band_stream`, ``head`` its filter state
    laid out by the ``filter_state`` rule and free to be donated, or
    ``None`` where no head was read.  The link budget, and the rule that
    a donated array has left it, are written for ONE putting thread: the
    thread that calls the programs."""
    import jax

    def put(blocks, role):
        shape = mesh.devices.shape + next(iter(blocks.values())).shape[2:]
        return M.put_local_shards(blocks, mesh, shape, role, timeline=tl)

    # The heads go up first, each a transfer of its own on the link
    # budget (a head and its body as one transfer were let go of when
    # the smaller body had landed, and the next bank's was then enqueued
    # over the premapped region: 2-3 s of a pass, every other pass).
    tail = put(heads, "filter_state") if heads else None
    body = put(bodies, "voltages")
    if tail is not None:
        # The tail is DONATED to the window's program, and a handle the
        # link budget holds must never be (HostLink.put): the heads are
        # waited in, once per stream, and the budget lets go of them.
        jax.block_until_ready(tail)
        host_link().retire()
    return tail, body


def _scan_headers(raws, local, *, nfft, nint, stokes, fqav_by):
    """Per-band product headers from the players THIS process can see.

    Per-bank headers must tile contiguously in frequency: each local bank k
    implies the band's bank-0 fch1 (``fch1_k - k*per_bank*foff``), and all
    must agree.  With ``fqav_by > 1`` the fine-channel range maps through
    :func:`blit.ops.fqav.fqav_range` (the reference's worker-side ``fqav``
    header math, src/gbtworkerfunctions.jl:16-20).

    Returns ``(h0, bases, per_bank)``: the lowest local player's product
    header, the per-band bank-0 base frequency dict, and the per-bank
    output channel count."""
    from blit.ops.fqav import fqav_range

    hdrs = {}
    for (b, k), r in raws.items():
        h = output_header(r.header(0), nfft=nfft, nint=nint, stokes=stokes)
        if fqav_by > 1:
            fch1, foff, nchans = fqav_range(
                h["fch1"], h["foff"], h["nchans"], fqav_by
            )
            h.update(fch1=fch1, foff=foff, nchans=nchans, nfpc=nfft // fqav_by)
        hdrs[(b, k)] = h
    h0 = hdrs[local[0]]
    foff = h0["foff"]
    per_bank = h0["nchans"]
    bases: Dict[int, float] = {}
    for (b, k), h in sorted(hdrs.items()):
        if abs(h["foff"] - foff) > 1e-12:
            raise ValueError("banks disagree on fine channel width")
        base = h["fch1"] - k * per_bank * foff
        if b in bases and abs(base - bases[b]) > abs(foff) / 2:
            log.warning(
                "band %d bank %d not contiguous: fch1=%.6f expected %.6f",
                b, k, h["fch1"], bases[b] + k * per_bank * foff,
            )
        bases.setdefault(b, base)
    return h0, bases, per_bank


def scan_window_frames(nfft: int, nint: int,
                       window_frames: Optional[int] = None) -> int:
    """PFB frames per device window — the ONE rule of the scan's three
    loops (mesh, ``--sharded``, ``--pool``) and of ``blit scan``'s stats
    line.  A window moves in units of
    :func:`blit.pipeline.fold_frames`: ``nint`` where a dispatch holds an
    integration, 1 where it does not (rawspec's ``-f 1048576 -t 51``) —
    the window then bounds memory "no matter the scan length" AND no
    matter ``nint``, and the integration is carried across windows
    (:func:`blit.parallel.mesh.band_carry`).  ``None`` is the HBM-safe
    default (:func:`blit.config.default_window_frames`).  An explicit
    window below one integration is the caller's measured bound
    (``--window-frames 2`` on four 16 GB chips) and is kept as given, as
    ``RawReducer`` keeps an explicit ``chunk_frames``: never rounded UP
    to ``nint``."""
    from blit.pipeline import fold_frames

    unit = fold_frames(nfft, nint)
    if window_frames is None:
        from blit.config import default_window_frames

        window_frames = default_window_frames(nfft)
    elif 0 < window_frames < nint:
        unit = 1
    return max((window_frames // unit) * unit, unit)


def _bitshuffle_window_chunk_rows(base: int, wrows: int) -> int:
    """Chunk rows for a windowed bitshuffle product: the pod-wide restart
    offset is window-aligned (row-aligned where the integration is
    carried across windows: ``wrows`` 0, one row per chunk) and
    bitshuffle resume points must be
    chunk-aligned, so the rows are ``gcd(default, window rows)`` — which
    silently collapses (to 1 for any window rows coprime with the 16-row
    default), degrading compression ratio and write throughput with no
    operator signal (ADVICE r5).  Output stays correct; warn so the knob
    gets fixed instead of silently eating the regression."""
    import math

    if wrows < 1:
        # An integration carried across windows: rows close one at a
        # time and the restart offset is any whole row.
        log.warning(
            "bitshuffle chunk rows are 1: an integration longer than the "
            "window is carried across windows and rows close one at a "
            "time — a window of whole integrations (window_frames a "
            "multiple of nint) keeps the default %d-row chunk's "
            "compression and write throughput", base,
        )
        return 1
    rows = math.gcd(base, wrows)
    if rows < min(base, wrows):
        log.warning(
            "bitshuffle chunk rows collapse to %d: a window holds %d "
            "rows (window_frames // nint), which share no larger factor "
            "with the default %d-row chunk — pick them to divide (or be "
            "a multiple of) %d to keep compression and write throughput",
            rows, wrows, base, base,
        )
    return rows


def _despike_nfpc(despike: bool, nfft: int, fqav_by: int) -> int:
    """DC-despike width in OUTPUT channels (0 disables).  After fqav the
    repairable fine grid is nfft//fqav_by wide; below 2 channels there is
    no neighbor to clone from, so despike is skipped with a warning — the
    host-side ``load_scan`` parity rule (blit/gbt.py)."""
    if not despike:
        return 0
    nfpc = nfft // fqav_by
    if nfpc < 2:
        log.warning("skipping despike (nfpc=%d after fqav_by=%d)", nfpc, fqav_by)
        return 0
    return nfpc


def _slab_writer(path: str, header: Dict, nif: int, nchans: int,
                 compression: Optional[str], timeline=None):
    """Per-band product writer by extension: ``.h5``/``.hdf5`` streams
    through :class:`blit.io.fbh5.FBH5Writer` (BL's native product format),
    anything else through :class:`_FilWriter`.  Both append slabs at
    bounded memory and land in ``.partial`` siblings renamed on close."""
    if path.endswith((".h5", ".hdf5")):
        from blit.io.fbh5 import FBH5Writer

        return FBH5Writer(path, header, nifs=nif, nchans=nchans,
                          compression=compression, timeline=timeline)
    if compression is not None:
        raise ValueError(".fil products are uncompressed; use .h5 paths "
                         "with compression=")
    from blit.io.sigproc import FilWriter

    return FilWriter(path, header, nif, nchans, timeline=timeline)


def _resolve_out_paths(band_ids, nband, out_dir, out_paths, compression):
    """Per-band product path resolution + the pre-collective compression
    validation (shared by the sync mesh writer and the sharded plane —
    a raise here happens on EVERY process, before any collective)."""
    import os

    if out_paths is None:
        if out_dir is None:
            raise ValueError("pass out_dir= or out_paths=")
        ext = "h5" if compression else "fil"
        out_paths = [
            os.path.join(out_dir, f"band{band_ids[b]}.{ext}")
            for b in range(nband)
        ]
    if len(out_paths) != nband:
        raise ValueError(f"need {nband} out_paths, got {len(out_paths)}")
    if compression is not None:
        bad = [p for p in out_paths if not p.endswith((".h5", ".hdf5"))]
        if bad:
            # Validate BEFORE any collective, on every process: a raise
            # inside the per-band writer loop would fire only on band-
            # owning processes and leave the rest blocked in the window
            # loop's collectives (the deadlock the caller docstrings
            # warn about).
            raise ValueError(
                ".fil products are uncompressed; compression= needs .h5 "
                f"paths, got {bad}"
            )
    return list(out_paths)


def _open_band_writers(
    mesh, raws, out_paths, *, h0, bases, per_bank, stokes,
    nfft, ntap, nint, window, fqav_by, dtype, despike_nfpc,
    compression, resume, wf, total, timeline=None,
):
    """The product-side prologue shared by the sync mesh writer and the
    sharded reduction plane (blit/parallel/sharded.py): which band rows
    THIS process persists (the bank-0 chip owner), their headers, the
    pod-wide-agreed resume restart offset, and the opened writers.

    Returns ``(mine, headers, writers, f0_start)``.  On a construction
    failure the already-built writers are aborted (their own crash
    contracts) before the error re-raises — callers' stream-error paths
    only ever see fully-constructed writer sets.  ``timeline``, where
    given, is handed to the writers (their ``write.digest``)."""
    import os

    import jax

    nband, nbank = mesh.devices.shape
    nif = STOKES_NIF[stokes]
    nchans = nbank * per_bank
    mine = [
        b for b in range(nband)
        if mesh.devices[b, 0].process_index == jax.process_index()
    ]
    headers: Dict[int, Dict] = {}
    for b in mine:
        hdr = dict(h0)
        hdr["fch1"] = bases[b]
        hdr["nchans"] = nchans
        hdr["nifs"] = nif
        headers[b] = hdr

    f0_start = 0
    cursors = {}
    h5_chunk_rows = None
    if resume:
        from types import SimpleNamespace

        from blit.pipeline import ReductionCursor

        comp_id = compression or "none"
        # Mesh .h5-bitshuffle products tie the writer's chunk rows to the
        # window granularity (the pod-wide restart offset is window-
        # aligned, and bitshuffle resume points must be chunk-aligned), so
        # the granularity joins the resume identity: a changed
        # --window-frames restarts fresh instead of splicing mismatched
        # chunk grids.  .fil and plain/gzip .h5 truncate at any row.
        wrows_ident = -1
        if comp_id == "bitshuffle" and any(
            p.endswith((".h5", ".hdf5")) for p in out_paths
        ):
            from blit.io.fbh5 import default_chunks

            wrows = wf // nint
            base = default_chunks(nif, nchans, 4, whole_spectrum=True)[0]
            h5_chunk_rows = _bitshuffle_window_chunk_rows(base, wrows)
            wrows_ident = wrows
        # dtype is output-affecting (bf16 stages round differently), so
        # it joins the resume identity like every other config knob.
        ident = SimpleNamespace(
            nfft=nfft, ntap=ntap, nint=nint, stokes=stokes, window=window,
            fqav_by=fqav_by, dtype=dtype, despike_nfpc=despike_nfpc,
        )
        # This process's fed member files: the input identity a resume
        # must match (a changed recording would splice different spectra).
        members = sorted(
            p
            for r in raws.values()
            for p in (getattr(r, "paths", None) or [r.path])
        )
        local_done = []
        for b in mine:
            cur = ReductionCursor.load(out_paths[b])
            ok = (
                cur is not None
                and cur.matches(ident, members)
                and cur.compression == comp_id
                and cur.window_rows == wrows_ident
                and os.path.exists(out_paths[b])
            )
            if ok and not out_paths[b].endswith((".h5", ".hdf5")):
                # The flat-format crash guard (ISSUE 12): a cursor
                # claiming bytes the file no longer holds restarts the
                # band fresh — BEFORE the pod-wide restart agreement, so
                # every process folds the (now zero) offset symmetrically.
                from blit.pipeline import resume_fil_ok

                if not resume_fil_ok(out_paths[b], nif, nchans,
                                     cur.frames_done // nint):
                    log.warning(
                        "resume target %s is shorter than (or unreadable "
                        "as) the cursor's claimed %d frames "
                        "(crash-corrupted?); restarting the band fresh",
                        out_paths[b], cur.frames_done,
                    )
                    ok = False
            if ok and out_paths[b].endswith((".h5", ".hdf5")):
                # Crash robustness (ADVICE r5 medium): an HDF5 target a
                # SIGKILL left unopenable/unreadable restarts this band
                # fresh, like an identity mismatch — the check runs
                # BEFORE the pod-wide restart agreement, so every
                # process agrees on the (now zero) restart offset
                # instead of deadlocking or wedging on a raise.
                from blit.io.fbh5 import resume_target_ok

                if not resume_target_ok(
                    out_paths[b], nif, nchans, cur.frames_done // nint
                ):
                    log.warning(
                        "resume target %s is not readable as the claimed "
                        "HDF5 product (crash-corrupted metadata?); "
                        "discarding %d claimed frames and restarting the "
                        "band fresh", out_paths[b], cur.frames_done,
                    )
                    ok = False
            if not ok:
                size, mtime_ns = ReductionCursor.stat_raw(members)
                cur = ReductionCursor(
                    members, nfft, ntap, nint, stokes, 0, window=window,
                    raw_size=size, raw_mtime_ns=mtime_ns, fqav_by=fqav_by,
                    dtype=dtype, despike_nfpc=despike_nfpc,
                    compression=comp_id, window_rows=wrows_ident,
                )
            cursors[b] = cur
            local_done.append(cur.frames_done if ok else 0)
        # Pod-wide agreement: the window loop is collective-synchronized,
        # so every process must restart at the SAME offset.  Processes
        # owning no band rows ride a sentinel above any real count.
        local_min = min(local_done) if local_done else 1 << 61
        agreed = int(_gather_int64(
            np.asarray([local_min], np.int64)
        ).min())
        # Whole windows — or, where the integration is carried across
        # windows, whole rows: the cursor claims closed rows only, and
        # the carry's sums do not depend on where the window grid falls.
        unit = wf if wf % nint == 0 else nint
        f0_start = min((agreed // unit) * unit, total)

    writers = {}
    try:
        for b in mine:
            if resume and out_paths[b].endswith((".h5", ".hdf5")):
                from blit.io.fbh5 import ResumableFBH5Writer

                writers[b] = ResumableFBH5Writer(
                    out_paths[b], headers[b], nif, nchans,
                    f0_start // nint, nint, cursors[b],
                    compression=compression,
                    chunks=(
                        (h5_chunk_rows, nif, nchans)
                        if h5_chunk_rows else None
                    ),
                    timeline=timeline,
                )
            elif resume:
                from blit.pipeline import ResumableFilWriter

                writers[b] = ResumableFilWriter(
                    out_paths[b], headers[b], nif, nchans,
                    f0_start // nint, nint, cursors[b], timeline=timeline,
                )
            else:
                writers[b] = _slab_writer(
                    out_paths[b], headers[b], nif, nchans, compression,
                    timeline,
                )
    except BaseException:
        for w in writers.values():
            w.abort()
        raise
    return mine, headers, writers, f0_start


def rawspec_band_path(out_dir: str, band_id, k: int) -> str:
    """Where band ``band_id``'s product ``k`` of several lands: rawspec's
    own suffix on the band's stem."""
    import os

    return os.path.join(out_dir, f"band{band_id}.rawspec.{k:04d}.fil")


class _ScanLeg:
    """One product of a mesh scan: its two programs
    (:func:`blit.parallel.mesh.band_programs`: per chip the one-chip
    reducer's own steps), what it keeps ON THE CHIPS from window to window
    — each bank's filter state and, where the integration is ``folded``
    across windows, each chip's partial sum and the frames it holds — and
    its band writers.  A scan is a list of legs that read the same
    uploaded windows.

    ``total`` frames of ``nfft`` make the product's rows; the stream's
    samples ``[0, end)`` hold them and the leg's filter state.  ``lanes``
    > 0 runs the small-``nfft`` path in blocks of that many words
    (:func:`blit.ops.channelize.lanes_block`; folded legs only).
    ``label`` names the product in counters and spans (``None``: a scan
    of one product, which has none of the per-product rows); ``kw`` are
    the channelizer's other keywords."""

    def __init__(self, mesh, tl, *, nfft: int, nint: int, ntap: int,
                 total: int, folded: bool, out_paths, name: str, label,
                 coeffs, despike_nfpc: int, lanes: int, **kw):
        assert folded or not lanes
        self.mesh, self.tl, self.name, self.label = mesh, tl, name, label
        self.nfft, self.nint = nfft, nint
        self.folded, self.lanes, self.coeffs = folded, lanes, coeffs
        self.despike_nfpc, self.out_paths = despike_nfpc, out_paths
        self.state_ntime = (ntap - 1) * nfft
        self.end = (total + ntap - 1) * nfft
        self.kw = dict(kw, mesh=mesh, nfft=nfft, ntap=ntap)
        # Each bank's filter state and the open integration (each chip's
        # own partial sum): on the mesh from window to window, held once
        # (donated folds).
        self.state = M.ShardedAccumulator(mesh, "filter_state")
        self.acc = M.ShardedAccumulator(
            mesh, "lanes_acc" if lanes else "integration_acc")
        self.filled = 0  # frames the open integration holds
        self.mine, self.headers, self.writers, self.done = [], {}, {}, {}

    def tag(self, span) -> None:
        """Name the product on a ``readback`` / ``write`` span."""
        if span is not None and self.label is not None:
            span.attrs["product"] = self.label

    def _programs(self):
        # (Looked up per call: a test that patches M.band_stream is seen.)
        if self.name == "band_stream":
            return M.band_stream, M.band_programs(self.name)[1]
        return M.band_programs(self.name)

    def _program_kw(self, frames: Optional[int]) -> dict:
        kw = dict(self.kw)
        if self.folded:
            # Per chip, no collective: spectra at nint=1, folded into the
            # chip's own sum.
            kw.update(nint=1, stitch=False, despike_nfpc=0)
        else:
            kw.update(nint=self.nint, stitch=True,
                      despike_nfpc=self.despike_nfpc)
        if self.lanes:
            kw["lanes"] = self.lanes
        if frames is not None:
            kw["frames"] = frames
        return kw

    def begin(self, head, token: list, outs: list) -> None:
        """The first step of a stream whose head (``head``, the largest
        leg's filter state, on the chips) is longer than this leg's own:
        the samples past its state are the leg's data."""
        frames = (head.shape[-1] - self.state_ntime) // self.nfft
        power, tail = self._programs()[1](head, self.coeffs,
                                          **self._program_kw(None))
        self.state.init(tail)
        self._rows(power, frames, token, outs)

    def advance(self, body, frames: int, whole: bool, token: list,
                outs: list) -> None:
        """One window: the first ``frames`` frames of the samples ``body``
        after the filter state on the chips (``whole``: all it holds); the
        state moves on."""
        power = self.state.fold_aux(
            self._programs()[0], body, self.coeffs,
            **self._program_kw(None if whole else frames))
        self._rows(power, frames, token, outs)

    def _rows(self, power, frames: int, token: list, outs: list) -> None:
        """A program's output of ``frames`` frames -> ``token`` gains what
        is ready once its input has been consumed, ``outs`` ``(leg,
        stitched bands)`` of the product rows that closed (nothing where
        none did: only a closed row is gathered, fetched and written)."""
        tl, mesh = self.tl, self.mesh
        nband, nbank = mesh.devices.shape
        if not self.folded:
            token.append(power)
            outs.append((self, power))
            return
        if self.acc.value is None:  # the leg's first frames: zeros of
            # the power's layout less its frames
            self.acc.init(M.carry_zeros(mesh=mesh, **(
                dict(lanes=power.shape[:1] + power.shape[2:6]) if self.lanes
                else dict(nif=power.shape[2], nchans=power.shape[3]))))
        # (band_carry hands back (accumulator, rows): the rows are the aux.)
        part = self.acc.fold_aux(lambda a: M.band_carry(
            a, power, np.int32(self.filled), mesh=mesh, nint=self.nint,
            **(dict(lanes=True, nframes=frames) if self.lanes else {}))[::-1])
        token.append(part)
        closed, self.filled = divmod(self.filled + frames, self.nint)
        if self.filled:  # the window ended with the integration open
            tl.mark("integrate.carry", self.acc.value.nbytes)
        if not closed:
            return
        if closed < part.shape[1]:
            # (A static slice is ONE program on every chip; `part[:, :n]`
            # is a gather plus index conversions on the default device,
            # and the chips' traces then no longer run the same sequence.)
            part = jax.lax.slice_in_dim(part, 0, closed, axis=1)
        # Despike on the integrated row: the clone commutes with the sum,
        # the bits are those of despiking every spectrum.
        out = M.stitch_despike(part, mesh=mesh,
                               despike_nfpc=self.despike_nfpc)
        nbytes = len(self.mine) * out.nbytes // nband
        tl.mark("integrate.emit", nbytes, calls=closed)
        if self.label is not None:  # per product, where several
            tl.mark(f"integrate.emit.{self.label}", nbytes, calls=closed)
            # What the gather moved, all chips: each receives the other
            # banks' shards of the rows.
            tl.mark(f"stitch.{self.label}", nband * nbank * M.gather_ici_bytes(
                out.nbytes // nband // nbank, nbank))
        token.append(out)
        outs.append((self, out))


def load_scan_mesh(
    raw_paths,
    scan: Optional[str] = None,
    *,
    inventories=None,
    nfft: int,
    ntap: int = 4,
    nint: int = 1,
    stokes: str = "I",
    fqav_by: int = 1,
    fft_method: str = "auto",
    window: str = "hamming",
    despike: bool = True,
    max_frames: Optional[int] = None,
    mesh=None,
    dtype: str = "float32",
) -> Tuple[Dict, "object"]:
    """Reduce one scan's RAW files across the mesh and stitch each band.

    Two call shapes:

    - ``load_scan_mesh(raw_paths, ...)`` with an explicit rectangular grid
      ``raw_paths[band][bank]`` — one RAW source per player, all covering
      the same scan (bank-ascending within each band).  Each source may be
      a single file path, a ``.NNNN.raw`` sequence stem, or a path list
      (blit/io/guppi.open_raw): a whole multi-file recording streams as
      one gap-free span per player.
    - ``load_scan_mesh(session, scan, inventories=...)`` — the reference's
      whole-scan call shape (``loadscan(session, scan, suffix)``,
      src/gbt.jl:99): the grid is resolved from ``get_inventories()``
      output via :func:`blit.inventory.scan_grid` (RAW sequences grouped
      per player, bands/banks sorted).

    Multi-process pods are first-class: under ``jax.distributed`` each
    process opens and feeds ONLY the players whose chips it owns
    (:func:`blit.parallel.multihost.local_players`) — the TPU analog of the
    reference's one-worker-per-host file locality (src/gbt.jl:28-42), where
    each ``blc*`` host serves its own disks.  Non-local entries of
    ``raw_paths`` are never touched, so they may name files that exist only
    on the owning host.  The common whole-frame span is agreed pod-wide
    (every process must build the same global array shape).

    Args:
      fqav_by: on-device frequency averaging applied per chip BEFORE the
        stitch collective (reduce before the wire); the returned header's
        fch1/foff/nchans/nfpc map through ``fqav_range``.
      max_frames: cap the PFB frames reduced (bounds HBM for long scans);
        None reduces the longest common whole-frame span.  For long scans
        at bounded memory end-to-end, use
        :func:`reduce_scan_mesh_to_files` (windowed streaming writer).
      mesh: an existing ``(band, bank)`` Mesh; None builds one matching
        the grid's shape over the available devices.

    Returns:
      ``(header, stitched)`` where stitched is a jax.Array
      ``(nband, ntime_out, nif, nbank*nchan*nfft//fqav_by)`` sharded over
      ``band`` (replicated across each band's banks), and ``header`` is the
      full-band filterbank header, derived from this process's lowest
      (band, bank) player.
    """
    _, raw_paths = _resolve_grid(raw_paths, scan, inventories)
    mesh, local, raws, nchan, npol, min_samps = _open_players(raw_paths, mesh)
    nbank = mesh.devices.shape[1]

    frames = usable_frames(min_samps, nfft, ntap, nint)
    if max_frames is not None:
        frames = min(frames, (max_frames // nint) * nint)
    if frames <= 0:
        raise ValueError(
            f"scan too short: {min_samps} samples for nfft={nfft}"
        )
    # One window that is the whole scan: the stream's head and all the
    # rest as its one body.
    head_ntime = (ntap - 1) * nfft
    tail, body = _put_window(
        *_read_window(raws, local, nchan, npol, head_ntime, frames * nfft,
                      head_ntime=head_ntime), mesh)
    coeffs = coeff_bank(ntap, nfft, window, observability.Timeline())
    out, _ = M.band_stream(
        tail,
        body,
        coeffs,
        mesh=mesh,
        nfft=nfft,
        ntap=ntap,
        nint=nint,
        stokes=stokes,
        fft_method=fft_method,
        stitch=True,
        despike_nfpc=_despike_nfpc(despike, nfft, fqav_by),
        fqav_by=fqav_by,
        dtype=dtype,
    )

    h0, bases, per_bank = _scan_headers(
        raws, local, nfft=nfft, nint=nint, stokes=stokes, fqav_by=fqav_by,
    )
    hdr = dict(h0)
    hdr["fch1"] = bases[local[0][0]]
    hdr["nchans"] = nbank * per_bank
    hdr["nsamps"] = int(out.shape[1])
    hdr["nifs"] = STOKES_NIF[stokes]
    return hdr, out


@published
def reduce_scan_mesh_to_files(
    raw_paths,
    scan: Optional[str] = None,
    *,
    inventories=None,
    out_dir: Optional[str] = None,
    out_paths: Optional[Sequence[str]] = None,
    nfft: int,
    ntap: int = 4,
    nint: int = 1,
    also: Sequence[Tuple[int, int]] = (),
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
    timeline=None,
    trace_logdir: Optional[str] = None,
) -> Dict[int, Tuple[str, Dict]]:
    """Reduce one scan across the mesh and STREAM each stitched band to a
    ``.fil`` product — the persistence epilogue ``load_scan_mesh`` lacks.

    The reduction runs ``window_frames`` PFB frames per dispatch, so host
    RSS, HBM, and per-window readback stay bounded no matter the scan
    length and no matter ``nint`` — the mesh analog of
    ``RawReducer.reduce_to_file``'s slab streaming (blit/pipeline.py).
    The window's READ runs one window ahead on a thread of its own
    (:class:`blit.pipeline.BufferRotation`, three slots: window N-1 on
    the chips, N being put and dispatched, N+1 being read into pooled
    slabs; :func:`_read_window` touches no device); the puts, the program
    calls, the fetches and the appends stay on the calling thread, in
    the order a serial feed has them, and a window's slot and slabs are
    given back once its programs have been waited out.  A read that
    fails surfaces here, as itself, when the loop asks for that window.
    Every sample crosses the host link once: a window reads and puts its
    NEW frames only, as ``sample_words``, and each bank's filter state
    (the ``(ntap-1)*nfft`` samples before the window) stays on its chip
    as the previous window's donated output
    (:func:`blit.parallel.mesh.band_stream`, rule ``filter_state``); it
    comes up from the host once per stream — the first window's head, at
    ``f0_start * nfft`` after a ``--resume`` — as a transfer of its own
    that has landed, and left the link budget, before it is donated.
    ``window_frames=None`` (the default) derives an HBM-safe bound from
    ``nfft`` (:func:`blit.config.default_window_frames`); pass a value >=
    the scan length for a deliberate one-window run
    (:func:`scan_window_frames` is the rule).

    Where ``nint`` divides the window every window integrates inside its
    own program, gathers, is fetched and written.  Where it does not —
    an integration longer than the window (rawspec's ``-f 1048576 -t 51``
    at the 2-frame window four 16 GB chips hold) or straddling its
    boundary — the integration is CARRIED: every chip channelizes its
    window at ``nint=1`` (the same stream program, no collective) and
    folds the spectra, frame by frame in stream
    order, into a float32 partial sum that stays on that chip from window
    to window (:func:`blit.parallel.mesh.band_carry`, the accumulator
    sharded over ``bank`` and held once), with no collective; a window
    that closes no row is waited out, gives its staging slabs back and
    fetches nothing; only a closed row is gathered and despiked
    (:func:`blit.parallel.mesh.stitch_despike` — on the integrated row:
    the clone commutes with the sum), fetched from the band owner's chip
    and written.  Trailing frames that fill no integration are dropped.
    Products append slab-by-slab into ``.partial``
    siblings and rename on success (SIGPROC derives nsamps from file size,
    so a crash mid-stream must not leave a valid-looking truncated file).

    Call shapes and reduction parameters match :func:`load_scan_mesh`
    (explicit grid or ``(session, scan, inventories=...)``).

    SEVERAL products from one read (``also=((nfft, nint), ...)`` beside
    the first: rawspec's ``-f 1048576,8,1024 -t 51,128,3072`` on the
    band): ONE window grid, in frames of the largest ``nfft``
    (``window_frames`` and ``max_frames`` count those); each bank's
    window is read once and put once, as words, and on every chip a leg
    per product (:class:`_ScanLeg`; :func:`blit.parallel.mesh.
    band_programs`, the one-chip reducer's own steps under ``shard_map``,
    each under a program name of its own: ``jit_band_stream``,
    ``jit_band_stream_0001``, ...) runs on that SAME device array.  What
    stays on the chips is each leg's own filter state and accumulator;
    the stream's head is the largest ``nfft``'s filter state and DATA to
    the smaller legs, whose head steps ride the first window before the
    head's owner takes it (and donates it with its first step).  Every
    integration is FOLDED across windows (``band_carry``, the ONE fold,
    one accumulator a product, sharded over ``bank``; a small ``nfft``
    runs frames-on-lanes where :func:`blit.ops.channelize.lanes_block`
    says the shape fits, as it does for a single folded product), only a
    product that closed rows in a window is stitched, fetched and handed
    to its writer, and each product is what its own command makes of the
    scan: its own head, rows and dropped tail, the same frames added in
    the same order (to the byte where that command folds too).  A writer
    per product per band at ``<out_dir>/band<id>.rawspec.000k.fil``; all
    of them are renamed and their manifests published, or none
    (``.partial``s dropped, what was published withdrawn).  Counters
    beside the ones below: ``fanout.share`` (``calls`` = programs that
    ran on a bank's upload they did not put, ``bytes`` = H2D not sent
    again), ``integrate.emit.<000k>`` per product, ``stitch.<000k>``
    (``calls`` = stitches, one per window or head step in which the
    product closed rows; ``bytes`` = what the gathers moved, all chips),
    and ``readback`` / ``write`` spans carry ``product``.  Not with
    ``resume``, ``compression`` or ``out_paths``; returns ``{band_id:
    [(path, header), ...]}``, a pair per product.

    ``dtype`` selects the per-chip channelizer stage dtype ("float32" |
    "bfloat16" — the official bench's biggest lever, DESIGN.md §3; the
    products stay float32 and dtype joins the resume identity since
    bf16 stages round differently).

    Observability (SURVEY.md §5 metrics bar): pass ``timeline`` (a
    :class:`blit.observability.Timeline`) to accumulate per-window stage
    timings with byte counts — ``ingest`` (a window's host RAW read, on
    the FEED thread: the bytes it read; inside it one ``feed.read`` per
    read — a bank's new samples, and once per stream its head),
    ``feed.put`` (one per put, on the loop's thread beside ``dispatch``),
    the rotation's two waits, blocked seconds only and rows even at 0 —
    ``wait.chunk`` (the loop waiting for a window that is not read yet:
    the part of the read that is NOT hidden) and ``wait.ingest_slot``
    (the feed waiting for a free slot: the loop is the slower side),
    both counting the times it blocked: ``1 - wait.chunk.calls /
    ingest.calls`` is the share of the windows that were read before the
    loop asked (give or take the one wait for the stream's end) — and
    the counted instants ``state.head`` (``calls`` = local
    banks whose filter state came up from the host: once per stream,
    ``bytes`` = those tails) and ``state.carry`` (``calls`` =
    bank-windows whose filter state was the previous window's output,
    ``bytes`` = tail bytes that stayed on the chips), ``link.put``
    (``bytes`` = the samples fed, once each),
    ``dispatch`` (async window dispatch, ~0 after the first compile),
    ``device`` (the blocking wait on the window's compute+collectives),
    ``readback`` (stitched-band device→host), ``write`` (product
    append) — mirroring the single-chip ``RawReducer`` stages; a carried
    reduction adds ``blit reduce``'s two counted instants,
    ``integrate.carry`` (``calls`` = windows that ended with the
    integration open, ``bytes`` = accumulator bytes held on the mesh over
    those boundaries, all chips) and ``integrate.emit`` (``calls`` = rows
    closed, ``bytes`` = band product bytes handed to the readback), and
    its ``readback`` / ``write`` have one call per closed row;
    ``blit scan`` prints the report as a stats JSON line.
    The pass's two ends are stages too: ``open`` (the grid, the players
    and their block index, the headers, the coefficient bank — the part
    ``coeffs`` inside it — and the writers) and ``close`` (file close,
    rename, manifest); what the product digest takes of ``write`` is the
    part ``write.digest``, and ``link.put`` is a part with the seconds
    each ``device_put`` held the loop.
    ``trace_logdir`` wraps the whole pass, root span included, in a
    device-only JAX profiler trace and writes the pass's spans beside it
    as ``blit-spans.json`` (:func:`blit.observability.profile_trace`).

    Output naming: ``out_paths`` (band-ascending, one per band; ``.fil``
    or ``.h5`` per path) or ``out_dir`` + ``band<id>.fil`` (``.h5`` when
    ``compression`` is set) where ``<id>`` is the real band number from
    the inventory (grid-row index for an explicit grid).  ``.h5`` products
    stream through :class:`blit.io.fbh5.FBH5Writer` — BL's native product
    format — with ``compression`` None | "gzip" | "bitshuffle".

    Multi-process pods: each band's file is written by the process owning
    that band row's bank-0 chip (the stitched product is replicated across
    the row, so one owner suffices and ``out_dir`` may be process-local
    disk).  Returns ``{band_id: (path, header)}`` for the bands THIS
    process wrote.

    ``resume=True`` makes the stream crash-resumable, the mesh twin of
    ``RawReducer.reduce_resumable``: a
    :class:`~blit.pipeline.ReductionCursor` sidecar per band records
    frames durably written after every window (data fsync'd before the
    cursor claims it); re-running truncates any un-checkpointed tail and
    continues from the last window boundary every process agrees on
    (pod-wide MIN, window-aligned — the restart offset must be identical
    on every process or the collectives deadlock).  A carried reduction
    continues from the last whole ROW instead (``rows * nint`` frames,
    no longer a window boundary: the cursor claims closed rows only, a
    run killed inside an integration loses its partial sum and re-adds
    those frames, and since they are added in the order of their place
    in the integration the bytes are the uninterrupted run's).  ``.fil`` products
    truncate by byte length; ``.h5`` products ``resize``-truncate the
    time-resizable dataset
    (:class:`blit.io.fbh5.ResumableFBH5Writer`), including under
    ``compression="bitshuffle"``, whose chunk rows are tied to the window
    granularity so pod restart offsets stay chunk-aligned (a changed
    ``window_frames`` therefore restarts bitshuffle ``.h5`` products
    fresh — it is part of their cursor identity, as is the compression;
    a carried reduction chunks them ONE row at a time, with a warning).
    Cursor identity covers the reduction config and this process's
    locally-fed member files; the finished product is identical to an
    uninterrupted run and the sidecars are removed on completion.
    """
    from blit.config import stream_defaults
    from blit.observability import Timeline, profile_trace
    from blit.pipeline import BufferRotation

    tl = timeline if timeline is not None else Timeline()
    products = ((int(nfft), int(nint)),) + tuple(
        (int(f), int(t)) for f, t in also)
    many = len(products) > 1
    # One grid for every product: the largest nfft's frames.
    big = max(f for f, _ in products)
    if many:
        for what, given in (("resume", resume), ("compression", compression),
                            ("out_paths", out_paths)):
            if given:
                raise ValueError(
                    f"{what}= with several products is not supported "
                    "(ROADMAP B1): one product per call, or all of them "
                    "without it")
        for f, _ in products:
            if big % f or fqav_by > 1 and f % fqav_by:
                raise ValueError(
                    f"nfft={f} of several products: every nfft must "
                    f"divide the largest ({big}) and be a multiple of "
                    f"fqav_by={fqav_by}")
    # The root span and the profile around it hold the whole pass: its two
    # ends are the stages `open` (the grid, the players and their block
    # index, the headers, the coefficient bank, the writers) and `close`.
    legs: list = []
    rot = None
    with profile_trace(trace_logdir), observability.span(
            "scan.reduce", nfft=nfft) as root:
        try:
            with tl.stage("open", byte_free=True):
                band_ids, raw_paths = _resolve_grid(raw_paths, scan,
                                                    inventories)
                mesh, local, raws, nchan, npol, min_samps = _open_players(
                    raw_paths, mesh)
                nband, nbank = mesh.devices.shape
                head_ntime = (ntap - 1) * big
                if many and max_frames is not None:
                    # Of several products each is what its own command
                    # makes of the recording cut to that many frames of
                    # the largest nfft.
                    min_samps = min(min_samps,
                                    head_ntime + max_frames * big)
                # Bounded by default at EVERY entry point (VERDICT r4: an
                # unbounded whole-scan window on the command whose purpose
                # is bounded-window streaming), and by nint never
                # un-bounded (scan_window_frames).  Pass an explicit
                # window_frames >= the scan length for a deliberate
                # one-window run.  Of several products every integration
                # is folded across windows, so none sizes the window.
                # (nint 1 to the window's rule.)
                wf = scan_window_frames(
                    *((big, 1) if many else (nfft, nint)), window_frames)
                for k, (f, t) in enumerate(products):
                    total = usable_frames(min_samps, f, ntap, t)
                    if max_frames is not None and not many:
                        total = min(total, (max_frames // t) * t)
                    if total <= 0:
                        raise ValueError(
                            f"scan too short: {min_samps} samples for "
                            f"nfft={f}")
                    if many:
                        if out_dir is None:
                            raise ValueError("several products need out_dir=")
                        paths = [rawspec_band_path(out_dir, band_ids[b], k)
                                 for b in range(nband)]
                    else:
                        paths = _resolve_out_paths(
                            band_ids, nband, out_dir, out_paths, compression)
                    if root is not None and k == 0:
                        root.attrs["out"] = paths[0]  # as reduce.to_file's
                    folded = many or wf % t != 0
                    leg = _ScanLeg(
                        mesh, tl, nfft=f, nint=t, ntap=ntap, total=total,
                        folded=folded, out_paths=paths,
                        name="band_stream" if k == 0
                        else f"band_stream_{k:04d}",
                        label=f"{k:04d}" if many else None,
                        coeffs=coeff_bank(ntap, f, window, tl),
                        despike_nfpc=_despike_nfpc(despike, f, fqav_by),
                        # (The shape decides, as on one chip.)
                        lanes=folded and lanes_block(
                            f, t, npol, ntap, fqav_by=fqav_by, dtype=dtype),
                        stokes=stokes, fft_method=fft_method,
                        fqav_by=fqav_by, dtype=dtype)
                    legs.append(leg)
                    h0, bases, per_bank = _scan_headers(
                        raws, local, nfft=f, nint=t, stokes=stokes,
                        fqav_by=fqav_by)
                    leg.mine, leg.headers, leg.writers, f0_start = \
                        _open_band_writers(
                            mesh, raws, paths, h0=h0, bases=bases,
                            per_bank=per_bank, stokes=stokes, nfft=f,
                            ntap=ntap, nint=t, window=window,
                            fqav_by=fqav_by, dtype=dtype,
                            despike_nfpc=leg.despike_nfpc,
                            compression=compression, resume=resume, wf=wf,
                            total=total, timeline=tl)
            # The leg whose filter state the stream's head is.
            owner = next(leg for leg in legs if leg.nfft == big)
            mine = legs[0].mine

            def flush(token, outs, staged, slot):
                # Blocking readback of one window's stitched bands -> disk.
                # The compute wait is charged to "device" here (not at the
                # async dispatch): this is where the host actually blocks on
                # the window's programs, mirroring RawReducer's stage
                # semantics — also for a window that closed no row and has
                # nothing to fetch or write.
                with tl.stage("device", byte_free=True):
                    jax.block_until_ready(token)
                # The window has consumed its input: only now may its
                # staging slabs serve another window (the feed thread's
                # next takes them, already faulted), and its slot.
                pool = hostmem.slab_pool()
                for buf in staged:
                    pool.give(buf, tl)
                rot.release(slot)
                # A product is handed the rows that closed in the window,
                # and nothing where none did.
                for leg, out in outs:
                    by_dev = {s.device: s for s in out.addressable_shards}
                    for b in mine:
                        band = by_dev[mesh.devices[b, 0]].data
                        # (Behind the next window's puts on the link budget.)
                        with host_link().fetch(band.nbytes, tl), \
                                tl.stage("readback") as sp:
                            leg.tag(sp)
                            slab = np.ascontiguousarray(np.asarray(band)[0])
                        tl.stages["readback"].bytes += slab.nbytes
                        with tl.stage("write", slab.nbytes) as sp:
                            leg.tag(sp)
                            leg.writers[b].append(slab)

            # Samples, on the grid of the largest nfft: a window is `wf` of
            # its frames and every leg takes the whole frames of its own
            # that the window's samples hold, up to its last row's.
            first = head_ntime + f0_start * nfft
            end = max(leg.end for leg in legs)
            # Every window stages through slabs of the largest window's shape.
            slab_ntime = min(wf * big, end - first)
            # Locally fed voltage bytes: complex int8 = 2 B/sample.
            per_sample = len(raws) * nchan * npol * 2

            def read_ahead(rot):
                # The feed thread: the loop's window grid, read a window
                # ahead of it (no device, no collective: host arrays and
                # the slabs they live in).  A stream's first window
                # brings its head up with it.
                start, head = first, head_ntime
                while start < end and (slot := rot.acquire()) is not None:
                    n = min(wf * big, end - start)
                    staged = []
                    with tl.stage("ingest", per_sample * (head + n)) as sp:
                        if sp is not None:
                            sp.attrs["f0"] = (start - head_ntime) // big
                        heads, bodies = _read_window(
                            raws, local, nchan, npol, start, n, tl, staged,
                            slab_ntime, head)
                    rot.emit(slot, (start, n, head, heads, bodies, staged))
                    start, head = start + n, 0

            # Three windows alive: N-1 on the chips (its flush is what
            # gives its slabs and its slot back), N being put and
            # dispatched here, N+1 being read.  The puts, the program
            # calls, the fetches and the appends stay on this thread, in
            # this order: the link budget and the donation rule are one
            # putting thread's.
            rot = BufferRotation(
                3, read_ahead, timeline=tl,
                stall_timeout_s=stream_defaults()["stall_timeout_s"])
            pending = None
            for slot, window in rot.slots():
                start, n, head, heads, bodies, staged = window
                frames = [max(0, min(leg.end, start + n) - start) // leg.nfft
                          for leg in legs]
                with observability.span("scan.window",
                                        f0=(start - head_ntime) // big):
                    tail, body = _put_window(heads, bodies, mesh, tl)
                    # Filter state by where it comes from: up from the
                    # host (once per bank per stream) or left on the chip
                    # by the last window.
                    for leg, took in zip(legs, frames):
                        if took or head and leg is not owner:
                            tl.mark("state.head" if head else "state.carry",
                                    per_sample * leg.state_ntime,
                                    calls=len(raws))
                    with tl.stage("dispatch", byte_free=True):
                        token, outs = [], []
                        if head:
                            # Everyone reads the head before its owner
                            # takes it (and donates it with its first
                            # window).
                            for leg in legs:
                                if leg is not owner:
                                    leg.begin(tail, token, outs)
                            owner.state.init(tail)
                        for leg, took in zip(legs, frames):
                            if took:
                                leg.advance(body, took, took * leg.nfft == n,
                                            token, outs)
                    if many:
                        # Programs that ran on an upload they did not put
                        # (a bank's), and the H2D bytes not sent again.
                        stepped = sum(map(bool, frames))
                        begun = (len(legs) - 1) * bool(head)
                        tl.mark("fanout.share",
                                max(0, stepped - 1) * per_sample * (head + n),
                                calls=len(raws) * (stepped + begun - 1))
                    if pending is not None:
                        flush(*pending)
                pending = (token, outs, staged, slot)
            if pending is not None:
                flush(*pending)
            try:
                # Every product's last byte is written before the first is
                # renamed into place.
                if many:
                    for leg in legs:
                        for w in leg.writers.values():
                            w.flush()
                # The pass's far end: file close, rename, manifest.
                with tl.stage("close", byte_free=True):
                    for leg in legs:
                        for b in list(leg.writers):
                            # (On failure the finally aborts the rest.)
                            leg.writers[b].close()
                            leg.done[b] = leg.writers.pop(b)
            except BaseException:
                if many:  # all complete, or none at a final path
                    from blit.pipeline import _withdraw

                    for leg in legs:
                        for w in leg.done.values():
                            _withdraw(w)
                raise
        finally:
            if rot is not None:
                rot.close()  # exception path: the feed thread reads no further
            for leg in legs:
                for w in leg.writers.values():  # exception path: drop partials
                    w.abort()
        for leg in legs:
            for b in mine:
                leg.headers[b]["nsamps"] = leg.done[b].nsamps
    if many:
        return {band_ids[b]: [(leg.out_paths[b], leg.headers[b])
                              for leg in legs] for b in mine}
    return {band_ids[b]: (legs[0].out_paths[b], legs[0].headers[b])
            for b in mine}


@published
def reduce_scan_pool_to_files(
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
    dtype: str = "float32",
    pool=None,
    worker_ids: Optional[Sequence[int]] = None,
    timeline=None,
) -> Dict[int, Tuple[str, Dict]]:
    """The POOL path of a whole-scan reduction — the reference's shape
    ("64 workers doing 64 small jobs", ``loadscan``'s main-process
    ``vcat``, src/gbt.jl:90-114) kept as the sharded plane's fallback and
    its CORRECTNESS ORACLE (ISSUE 9): one :class:`blit.pipeline.RawReducer`
    per (band, bank) player, fanned over a :class:`~blit.parallel.pool.
    WorkerPool` when one is given (``pool=``/``worker_ids=``, the
    ``gbt.reduce_raw`` discipline) or run inline, then a host-side
    channel-axis ``vcat`` + DC despike per band and one product write.

    Byte-identity contract (tests/test_sharded.py): with
    ``window_frames`` equal to the sharded path's and a common whole-frame
    span across players, the per-band products are BYTE-IDENTICAL to
    ``reduce_scan_sharded_to_files`` / ``reduce_scan_mesh_to_files``
    output — the per-bank reduction is the same jitted ``channelize`` at
    the same dispatch shapes (``chunk_frames = window_frames``), the
    stitch is an exact concatenation, and the despike an exact
    neighbor-clone, on host here and over ICI there.

    Bounded memory is NOT this path's goal (each band's stitched array is
    materialized host-side, exactly like the reference); the sharded
    plane is the production path.  Returns ``{band_id: (path, header)}``
    for every band (this process writes them all — there is no pod here).
    """
    band_ids, raw_paths = _resolve_grid(raw_paths, scan, inventories)
    nband = len(raw_paths)
    nbank = len(raw_paths[0])
    if any(len(row) != nbank for row in raw_paths):
        raise ValueError("raw_paths must be rectangular (nband x nbank)")

    # Open every player host-side for the span/header agreement (the pool
    # path has no pod: one process sees every file).
    raws = {}
    for b in range(nband):
        for k in range(nbank):
            r = open_raw(raw_paths[b][k])
            if r.nblocks == 0:
                raise ValueError(f"empty RAW file: {r.path}")
            raws[(b, k)] = r
    local = sorted(raws)
    total = min(usable_frames(_kept_samples(r), nfft, ntap, nint)
                for r in raws.values())
    if max_frames is not None:
        total = min(total, (max_frames // nint) * nint)
    if total <= 0:
        raise ValueError("scan too short")
    # The mesh loop's window (scan_window_frames); where it does not hold
    # an integration RawReducer carries it (chunk_frames kept as given).
    wf = scan_window_frames(nfft, nint, window_frames)

    out_paths = _resolve_out_paths(
        band_ids, nband, out_dir, out_paths, compression
    )
    h0, bases, per_bank = _scan_headers(
        raws, local, nfft=nfft, nint=nint, stokes=stokes, fqav_by=fqav_by,
    )
    nif = STOKES_NIF[stokes]
    nchans = nbank * per_bank
    despike_nfpc = _despike_nfpc(despike, nfft, fqav_by)
    rows_total = total // nint

    from blit.observability import Timeline

    tl = timeline if timeline is not None else Timeline()
    red_kw = dict(
        nfft=nfft, ntap=ntap, nint=nint, stokes=stokes, window=window,
        fft_method=fft_method, fqav_by=fqav_by, dtype=dtype,
        chunk_frames=wf,
    )

    def reduce_bank(b, k):
        from blit.pipeline import RawReducer

        _, data = RawReducer(**red_kw).reduce(raw_paths[b][k])
        return data

    written: Dict[int, Tuple[str, Dict]] = {}
    for b in range(nband):
        with tl.stage("read", byte_free=True):
            if pool is not None:
                from blit import workers as wf_mod

                wids = (list(worker_ids) if worker_ids is not None
                        else [(b * nbank + k) % len(pool) + 1
                              for k in range(nbank)])
                results = pool.run_on(
                    wids, wf_mod.reduce_raw,
                    [(raw_paths[b][k],) for k in range(nbank)],
                    kwargs=red_kw,
                )
                banks = [data for _hdr, data in results]
            else:
                banks = [reduce_bank(b, k) for k in range(nbank)]
        short = [k for k, d in enumerate(banks) if d.shape[0] < rows_total]
        if short:
            raise ValueError(
                f"band {band_ids[b]} banks {short} yielded fewer than the "
                f"agreed {rows_total} spectra — players disagree on span"
            )
        # The main-process vcat (exact) + host despike (exact clone) —
        # the reference's stitch, trimmed to the pod-agreed common span.
        stitched = np.concatenate(
            [d[:rows_total] for d in banks], axis=-1
        )
        if despike_nfpc >= 2:
            from blit.ops.despike import despike as _despike

            stitched = np.asarray(_despike(stitched, despike_nfpc))
        hdr = dict(h0)
        hdr["fch1"] = bases[b]
        hdr["nchans"] = nchans
        hdr["nifs"] = nif
        w = _slab_writer(out_paths[b], hdr, nif, nchans, compression)
        try:
            with tl.stage("write", stitched.nbytes):
                w.append(stitched)
            w.close()
        except BaseException:
            w.abort()
            raise
        hdr["nsamps"] = rows_total
        written[band_ids[b]] = (out_paths[b], hdr)
    return written
