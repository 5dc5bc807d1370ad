"""``DedopplerReducer`` — the search plane's streaming driver (ISSUE 6).

RAW voltages → filterbank spectra → Taylor-tree drift search → ``.hits``
products, end to end on the existing planes:

- the INNER reduction is a plain :class:`blit.pipeline.RawReducer`
  (Stokes I, fqav off) — the same pipelined ingest rotation, jitted
  channelizer and async readback every other product rides;
- a :class:`blit.pipeline.BufferRotation` WINDOW FEED re-chunks the
  spectra stream into fixed ``(window_spectra, nchans)`` windows on a
  producer thread (the long-integration windowed-feed shape of ROADMAP
  item 4) — trailing spectra that can't fill a window are dropped,
  deterministically, so resume replays reproduce identical windows;
- each window runs :func:`blit.ops.pallas_dedoppler.dedoppler_hits` on
  device (tree + SNR + threshold + per-band top-k; only the packed hit
  records cross the link), with the packed outputs read back through an
  :class:`blit.outplane.OutputRotation` so window compute, readback and
  hit writing overlap;
- hits stream through :class:`blit.outplane.AsyncSink` write-behind
  into the ``.hits`` writers (blit/io/hits.py) — the ragged sink path.

Determinism contract (tests/test_dedoppler.py): window ``w`` always
covers spectra ``[w·T, (w+1)·T)`` of the gap-free stream, so a resumed
run (``search_resumable`` — skip-windows replay via the reducer's
skip-frames discipline, same rule as ``correlate(acc_frames=)``) and
the sync output path (``BLIT_SYNC_OUTPUT=1`` / ``async_output=False``)
produce BYTE-IDENTICAL ``.hits`` products.

Search knobs left ``None`` resolve from :func:`blit.config.search_defaults`
(SiteConfig fields, overridable per-process via ``BLIT_SEARCH_*`` env).
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from blit import observability
from blit.config import search_defaults
from blit.io.guppi import GuppiRaw, RawSource, open_raw
from blit.io.hits import HitsWriter, ResumableHitsWriter, WindowHits
from blit.observability import Timeline
from blit.ops.pallas_dedoppler import _check_window
from blit.pipeline import BufferRotation, RawReducer, ReductionCursor
from blit.search.hits import HIT_COLS, Hit, hits_from_packed, hits_to_array

log = logging.getLogger("blit.search")


class _Window:
    """A filled search window handed to the consumer; ``view`` aliases
    the rotation buffer until :meth:`release`."""

    __slots__ = ("view", "index", "_idx", "_free")

    def __init__(self, view: np.ndarray, index: int, idx: int, free) -> None:
        self.view = view
        self.index = index
        self._idx = idx
        self._free = free

    def release(self) -> None:
        if self._free is not None:
            free, self._free = self._free, None
            free(self._idx)


@dataclass
class DedopplerReducer:
    """Configured RAW → ``.hits`` drift search (one worker / one chip).

    The filterbank knobs (``nfft``/``ntap``/``nint``/``window``/
    ``dtype``) configure the inner reduction exactly as on
    :class:`~blit.pipeline.RawReducer`; the search knobs bound the
    drift transform and hit extraction.  Every output-affecting knob is
    part of the product fingerprint (:meth:`fingerprint_extra`) and the
    resume identity (:class:`SearchCursor`).
    """

    nfft: int
    ntap: int = 4
    nint: int = 1
    window: str = "hamming"
    fft_method: str = "auto"
    dtype: str = "float32"
    # Search knobs; None -> blit.config.search_defaults() (SiteConfig +
    # BLIT_SEARCH_* env overrides).
    window_spectra: Optional[int] = None
    top_k: Optional[int] = None
    snr_threshold: Optional[float] = None
    max_drift_bins: Optional[int] = None
    # Drift-transform backend (blit/ops/pallas_dedoppler): "reference" |
    # "pallas" | "auto"; interpret=True runs the pallas kernel on CPU.
    kernel: str = "auto"
    interpret: bool = False
    # The inner RawReducer's ingest knobs, with its defaults.
    prefetch_depth: int = 2
    out_depth: Optional[int] = None
    chunk_frames: Optional[int] = None
    timeline: Timeline = field(default_factory=Timeline)
    # Async planes (window feed readback + write-behind hit sink);
    # False — or BLIT_SYNC_OUTPUT=1 — restores the serialized path with
    # byte-identical products (the A/B lever, as on RawReducer).
    async_output: bool = True
    output_stall_timeout_s: Optional[float] = None

    # Fixed facets of the search reduction (the fingerprint reads these
    # off the reducer like any other).
    stokes = "I"
    fqav_by = 1

    def __post_init__(self):
        if os.environ.get("BLIT_SYNC_OUTPUT"):
            self.async_output = False
        d = search_defaults()
        if self.window_spectra is None:
            self.window_spectra = d["window_spectra"]
        if self.top_k is None:
            self.top_k = d["top_k"]
        if self.snr_threshold is None:
            self.snr_threshold = d["snr_threshold"]
        if self.max_drift_bins is None:
            self.max_drift_bins = d["max_drift_bins"]
        if self.max_drift_bins is not None and self.max_drift_bins < 0:
            # The -1 "no limit" header/cursor encoding round-trips back
            # to unlimited (a literal negative limit would mask every
            # drift row and report zero hits without erroring).
            self.max_drift_bins = None
        _check_window(self.window_spectra)
        self._red = RawReducer(
            nfft=self.nfft, ntap=self.ntap, nint=self.nint, stokes="I",
            window=self.window, fft_method=self.fft_method,
            dtype=self.dtype, prefetch_depth=self.prefetch_depth,
            out_depth=self.out_depth,
            chunk_frames=self.chunk_frames, timeline=self.timeline,
            async_output=self.async_output,
            output_stall_timeout_s=self.output_stall_timeout_s,
        )
        # The inner reducer resolved the knobs — mirror them so this
        # reducer's own rotation depths agree.
        self.out_depth = self._red.out_depth
        if self.chunk_frames is None:
            self.chunk_frames = self._red.chunk_frames

    def tuning_provenance(self) -> Dict:
        """Delegated to the inner RawReducer (the knobs are its)."""
        return self._red.tuning_provenance()

    # -- identity ----------------------------------------------------------
    def fingerprint_extra(self) -> Dict:
        """The search-specific fingerprint components
        (:func:`blit.serve.cache.fingerprint_for` merges them into the
        content address; nbands derives from the raw input + nfft, both
        already in the key)."""
        return {
            "product_kind": "hits",
            "window_spectra": int(self.window_spectra),
            "top_k": int(self.top_k),
            "snr_threshold": float(self.snr_threshold),
            "max_drift_bins": (
                None if self.max_drift_bins is None
                else int(self.max_drift_bins)
            ),
        }

    # -- headers -----------------------------------------------------------
    def header_for(self, raw: GuppiRaw) -> Dict:
        """The search product header: the inner filterbank header plus
        the search knobs (everything a ``.hits`` consumer needs to map
        bins back to sky frequency / drift rate)."""
        hdr = self._red.header_for(raw)
        hdr.update(
            search_window_spectra=int(self.window_spectra),
            search_top_k=int(self.top_k),
            search_snr_threshold=float(self.snr_threshold),
            search_max_drift_bins=(
                -1 if self.max_drift_bins is None
                else int(self.max_drift_bins)
            ),
            search_nbands=self._nbands(hdr["nchans"]),
        )
        # The kernel choice is deliberately NOT in the header (or the
        # fingerprint/cursor identity): reference and pallas produce
        # bitwise-identical sums by construction, so the product bytes
        # must not fork on how they were computed.
        return hdr

    def _nbands(self, nchans: int) -> int:
        """Per-band top-k granularity: one band per coarse channel (the
        natural unit frequency is sharded by everywhere else); a channel
        count that isn't coarse-aligned searches as a single band."""
        return nchans // self.nfft if nchans % self.nfft == 0 else 1

    def _open_validated(self, raw_src: RawSource) -> Tuple[GuppiRaw, Dict]:
        raw = open_raw(raw_src)
        if raw.nblocks == 0:
            raise ValueError(f"empty or fully truncated RAW file: {raw.path}")
        return raw, self.header_for(raw)

    # -- window feed -------------------------------------------------------
    def _producer(self, raw: GuppiRaw, skip_windows: int, nchans: int,
                  bufs: List[Optional[np.ndarray]],
                  rot: BufferRotation) -> None:
        """Fill the window rotation from the inner reducer's spectra
        stream (producer thread).  Window ``w`` holds spectra
        ``[w·T, (w+1)·T)`` of the gap-free stream; a trailing partial
        window is dropped (deterministic across resumes)."""
        T = self.window_spectra
        cur: Optional[int] = None
        filled = 0
        widx = skip_windows
        skip_frames = skip_windows * T * self.nint
        for slab in self._red.stream(raw, skip_frames=skip_frames):
            data = slab[:, 0, :]  # Stokes-I plane: (nspectra, nchans)
            pos = 0
            n = data.shape[0]
            while pos < n:
                if cur is None:
                    cur = rot.acquire()
                    if cur is None:
                        return  # consumer abandoned the stream
                    if bufs[cur] is None:
                        bufs[cur] = np.empty((T, nchans), np.float32)
                    filled = 0
                take = min(T - filled, n - pos)
                with self.timeline.stage("search.window_fill",
                                         nbytes=take * nchans * 4):
                    bufs[cur][filled:filled + take] = data[pos:pos + take]
                filled += take
                pos += take
                if filled == T:
                    rot.emit(cur, widx)
                    widx += 1
                    cur = None

    def _windows(self, raw: GuppiRaw, skip_windows: int, nchans: int,
                 extra_slots: int = 0) -> Iterator[_Window]:
        """The pipelined window feed behind the search loop — the
        :meth:`RawReducer._chunks` shape one level up: the consumer MUST
        ``release()`` every window once nothing still reads its buffer."""
        nbufs = max(2, self.prefetch_depth) + max(0, extra_slots)
        bufs: List[Optional[np.ndarray]] = [None] * nbufs
        rot = BufferRotation(
            nbufs,
            lambda r: self._producer(raw, skip_windows, nchans, bufs, r),
            name="blit-search-feed", timeline=self.timeline,
        )
        try:
            for idx, widx in rot.slots():
                yield _Window(bufs[idx], widx, idx, rot.release)
        finally:
            # No cross-call buffer cache (unlike RawReducer's chunk
            # ring): window buffers can run to GBs at wide products and
            # service/CLI callers build a fresh reducer per request —
            # retaining them would pin memory for a reuse that never
            # comes.  `bufs` frees with this frame.
            rot.close()

    # -- device step -------------------------------------------------------
    def _jitted(self, nbands: int):
        """The per-window search step with this reducer's knobs bound.
        ``dedoppler_hits`` is jitted at module level with the knobs
        static, so compilations cache process-wide — a fresh reducer per
        service request (the ProductService pattern) reuses the compiled
        program instead of re-tracing the unrolled tree."""
        import functools

        from blit.ops.pallas_dedoppler import dedoppler_hits

        return functools.partial(
            dedoppler_hits, top_k=self.top_k, nbands=nbands,
            max_drift_bins=self.max_drift_bins, kernel=self.kernel,
            interpret=self.interpret,
        )

    # -- the search stream -------------------------------------------------
    def _search_stream(
        self, raw: GuppiRaw, hdr: Dict, skip_windows: int = 0
    ) -> Iterator[Tuple[int, List[Hit]]]:
        """Yield ``(window_index, hits)`` in stream order.  On the async
        plane the packed device outputs read back on the OutputRotation
        thread while the next window dispatches; the sync fallback times
        each tree step directly (the ``search.tree_s`` histogram)."""
        import jax
        import jax.numpy as jnp

        nchans = hdr["nchans"]
        nbands = self._nbands(nchans)
        jfn = self._jitted(nbands)
        thr = np.float32(self.snr_threshold)

        def decode(packed: np.ndarray, widx: int) -> List[Hit]:
            hits = hits_from_packed(packed, widx, hdr)
            self.timeline.observe("search.hits_per_window", len(hits))
            return hits

        with observability.span(
            "search.stream", nfft=self.nfft, windows=self.window_spectra,
            path=getattr(raw, "path", ""),
        ):
            if not self.async_output:
                for win in self._windows(raw, skip_windows, nchans):
                    try:
                        with observability.span("search.window",
                                                window=win.index):
                            t0 = time.perf_counter()
                            packed = jfn(jnp.asarray(win.view), thr)
                            packed = np.asarray(
                                jax.block_until_ready(packed))
                            self.timeline.observe(
                                "search.tree_s",
                                time.perf_counter() - t0)
                    finally:
                        win.release()
                    yield win.index, decode(packed, win.index)
                return

            from blit.outplane import OutputRotation, readback_extra_slots

            depth = max(2, self.out_depth)
            rot = OutputRotation(
                depth=depth, timeline=self.timeline,
                reuse=False, name="blit-search-readback",
                stall_timeout_s=self.output_stall_timeout_s,
            )
            try:
                extra = readback_extra_slots(depth, self.prefetch_depth)
                for win in self._windows(raw, skip_windows, nchans,
                                         extra_slots=extra):
                    with self.timeline.stage("dispatch", byte_free=True):
                        packed = jfn(jnp.asarray(win.view), thr)
                    for slab in rot.put(packed, nbytes=win.view.nbytes,
                                        outs=[(packed, win.index)],
                                        on_consumed=win.release):
                        yield slab.payload, decode(slab.data, slab.payload)
                        slab.release()
                for slab in rot.drain():
                    yield slab.payload, decode(slab.data, slab.payload)
                    slab.release()
            finally:
                rot.close()

    # -- whole-recording entry points --------------------------------------
    def search(self, raw_src: RawSource) -> Tuple[Dict, List[Hit]]:
        """Search a whole RAW recording (file / ``.NNNN.raw`` sequence)
        in memory → ``(header, hits)`` in window order."""
        raw, hdr = self._open_validated(raw_src)
        hits: List[Hit] = []
        windows = 0
        with observability.span("search", nfft=self.nfft):
            for _, hs in self._search_stream(raw, hdr):
                hits.extend(hs)
                windows += 1
        hdr["search_windows"] = windows
        hdr["search_nhits"] = len(hits)
        return hdr, hits

    def reduce(self, raw_src: RawSource) -> Tuple[Dict, np.ndarray]:
        """The ProductService entry point: like :meth:`search` but the
        hit list comes back as the dense float32 encoding
        (:func:`blit.search.hits.hits_to_array`) under a slab-shaped
        header — so ``.hits`` products flow through the content-addressed
        cache, single-flight coalescing and the disk tier unchanged."""
        hdr, hits = self.search(raw_src)
        arr = hits_to_array(hits)
        hdr = dict(hdr)
        # The cache's disk tier (FBH5) stores (nsamps, nifs, nchans)
        # slabs; the encoded hit table IS one, with the real channel
        # count parked under search_nchans.
        hdr["search_nchans"] = hdr["nchans"]
        hdr.update(nchans=HIT_COLS, nifs=1, nsamps=len(hits))
        return hdr, arr

    def _pump(self, raw: GuppiRaw, hdr: Dict, writer,
              skip_windows: int = 0) -> int:
        """Drive the search stream into a ``.hits`` writer — write-behind
        through :class:`~blit.outplane.AsyncSink` on the async plane —
        and finalize it.  Returns hits written this run.  On error the
        writer ``abort()``s (its own crash contract) and the error
        re-raises.  Runs under :func:`blit.monitor.publishing` like
        :meth:`blit.pipeline.RawReducer._pump` (ISSUE 11)."""
        from blit.monitor import publishing

        with publishing(self.timeline):
            return self._pump_impl(raw, hdr, writer, skip_windows)

    def _pump_impl(self, raw: GuppiRaw, hdr: Dict, writer,
                   skip_windows: int = 0) -> int:
        if not self.async_output:
            try:
                for widx, hits in self._search_stream(raw, hdr,
                                                      skip_windows):
                    writer.append(WindowHits(widx, hits))
                writer.close()
            except BaseException:
                writer.abort()
                raise
            return writer.nsamps

        from blit.outplane import AsyncSink

        sink = AsyncSink(
            writer, depth=max(2, self.out_depth),
            timeline=self.timeline,
            stall_timeout_s=self.output_stall_timeout_s,
        )
        try:
            for widx, hits in self._search_stream(raw, hdr, skip_windows):
                sink.append(WindowHits(widx, hits))
            sink.close()
        except BaseException:
            sink.abort()
            raise
        return sink.nsamps

    def search_to_file(self, raw_src: RawSource, out_path: str) -> Dict:
        """Search and write a ``.hits`` product (atomic ``.partial``
        publish; byte-identical between the sync and async planes)."""
        raw, hdr = self._open_validated(raw_src)
        w = HitsWriter(out_path, hdr)
        with observability.span("search.to_file", out=out_path):
            hdr["search_nhits"] = self._pump(raw, hdr, w)
        hdr["search_windows"] = w.nwindows
        return hdr

    def search_resumable(self, raw_src: RawSource, out_path: str) -> Dict:
        """Search to a ``.hits`` product with crash-resumable streaming:
        a :class:`SearchCursor` sidecar claims each window AFTER its
        lines are durable; a re-run resumes at the claimed window
        boundary via the skip-windows replay and reproduces the exact
        remaining hit lines (the finished product is byte-identical to
        an uninterrupted run)."""
        raw, hdr = self._open_validated(raw_src)
        paths = getattr(raw, "paths", None) or raw.path
        cur = SearchCursor.load(out_path)
        resuming = (
            cur is not None
            and cur.matches(self, paths)
            and os.path.exists(out_path)
        )
        if resuming and os.path.getsize(out_path) < cur.byte_offset:
            # A cursor claiming more bytes than the file holds (crash-
            # corrupted or replaced product): POSIX truncate would EXTEND
            # the file with a NUL hole and the finished product would be
            # unreadable — start fresh instead, the resume_target_ok
            # discipline (blit/pipeline.py) for the ragged format.
            log.warning(
                "resume target %s is shorter than the cursor's claimed "
                "%d bytes (crash-corrupted?); discarding %d claimed "
                "windows and starting fresh",
                out_path, cur.byte_offset, cur.windows_done,
            )
            resuming = False
        if resuming:
            # Content verification of the claim (ISSUE 13): the byte-
            # length probe above cannot see a flipped byte INSIDE the
            # claimed lines or a tampered sidecar — the manifest's claim
            # ledger can.  False = fail closed (fresh start); a product
            # without a manifest keeps the length-only behavior.
            from blit import integrity

            if integrity.verify_claim(out_path, cur.windows_done,
                                      fmt="hits") is False:
                log.warning(
                    "resume target %s fails its claimed-region digest "
                    "(torn write or tampered sidecar); discarding %d "
                    "claimed windows and starting fresh",
                    out_path, cur.windows_done,
                )
                resuming = False
        if resuming:
            log.info("resuming %s at window %d", out_path, cur.windows_done)
        else:
            size, mtime_ns = ReductionCursor.stat_raw(paths)
            cur = SearchCursor(
                paths, self.nfft, self.ntap, self.nint,
                window=self.window, dtype=self.dtype,
                window_spectra=self.window_spectra, top_k=self.top_k,
                snr_threshold=float(self.snr_threshold),
                max_drift_bins=(
                    -1 if self.max_drift_bins is None
                    else int(self.max_drift_bins)
                ),
                raw_size=size, raw_mtime_ns=mtime_ns,
            )
        skip = cur.windows_done if resuming else 0
        w = ResumableHitsWriter(out_path, hdr, skip, cur)
        with observability.span("search.resumable", out=out_path,
                                resumed=bool(resuming)):
            self._pump(raw, hdr, w, skip_windows=skip)
        hdr["search_windows"] = w.nwindows
        hdr["search_nhits"] = w.nsamps
        return hdr


@dataclass
class SearchCursor:
    """Restart state for a streaming drift search, persisted as a JSON
    sidecar next to the ``.hits`` product (the
    :class:`blit.pipeline.ReductionCursor` discipline, windowed).

    ``windows_done`` counts search windows fully extracted *and
    durable*; ``byte_offset`` is the product file length those windows
    claim — resume truncates to it, dropping any un-checkpointed tail.
    Identity guards cover the raw bytes (order-insensitive member
    triples) and every output-affecting knob, filterbank and search
    alike."""

    raw_path: Union[str, List[str]]
    nfft: int
    ntap: int
    nint: int
    window: str = "hamming"
    dtype: str = "float32"
    window_spectra: int = 64
    top_k: int = 8
    snr_threshold: float = 10.0
    max_drift_bins: int = -1
    windows_done: int = 0
    hits_done: int = 0
    byte_offset: int = 0
    raw_size: Union[int, List[int]] = -1
    raw_mtime_ns: Union[int, List[int]] = -1
    # Per-window ``[window, byte_offset, hits]`` claims, appended as
    # each window is claimed (ISSUE 12): windows are RAGGED — a
    # zero-hit window leaves no line — so a resume at an EARLIER window
    # than this cursor's own claim (the sharded plane's pod-wide-agreed
    # minimum) can only find its truncation point here.  The ledger is
    # BOUNDED (blit/io/hits.py trims to the newest CLAIM_LEDGER_MAX
    # entries — per-append cursor I/O must not grow with session
    # length); a window older than the tail resolves to None and that
    # player restarts fresh.  None (pre-existing sidecars) = resumable
    # only at the exact claimed window, the old behavior.
    window_claims: Optional[List[List[int]]] = None

    def claim_at(self, windows: int) -> Optional[Tuple[int, int]]:
        """The ``(byte_offset, hits_done)`` claim after ``windows`` full
        windows, when this cursor recorded it (``windows`` == the full
        claim always resolves; earlier windows need a ``window_claims``
        ledger entry) — :func:`blit.io.hits.ledger_claim_at`, the rule
        shared with :class:`blit.stream.cursor.StreamCursor`."""
        from blit.io.hits import ledger_claim_at

        return ledger_claim_at(windows, self.windows_done,
                               self.byte_offset, self.hits_done,
                               self.window_claims)

    # One sidecar persistence protocol, shared with the pipeline cursor
    # (ReductionCursor's save/load operate on self.__dict__ / cls(**...),
    # so they bind cleanly here) — a durability fix there reaches the
    # search plane automatically.
    path_for = staticmethod(ReductionCursor.path_for)
    save = ReductionCursor.save
    load = classmethod(ReductionCursor.load.__func__)

    def matches(self, red: DedopplerReducer,
                raw_path: Union[str, Sequence[str]]) -> bool:
        try:
            size, mtime_ns = ReductionCursor.stat_raw(raw_path)
        except OSError:
            return False
        return (
            ReductionCursor.normalized_members(
                self.raw_path, self.raw_size, self.raw_mtime_ns)
            == ReductionCursor.normalized_members(raw_path, size, mtime_ns)
            and self.nfft == red.nfft
            and self.ntap == red.ntap
            and self.nint == red.nint
            and self.window == red.window
            and self.dtype == red.dtype
            and self.window_spectra == red.window_spectra
            and self.top_k == red.top_k
            and self.snr_threshold == float(red.snr_threshold)
            and self.max_drift_bins == (
                -1 if red.max_drift_bins is None else int(red.max_drift_bins)
            )
        )
