"""Streaming GUPPI RAW → filterbank reduction driver.

Host-side orchestration of the single-chip compute core
(:mod:`blit.ops.channelize`): reads voltage blocks, maintains the PFB state
across block boundaries (the overlap/edge-sample interaction called out as a
hard part in SURVEY.md §7), feeds fixed-shape chunks to the jitted reduction,
and writes SIGPROC ``.fil`` or FBH5 ``.h5`` products — the rawspec-equivalent
stage the reference assumes has already run on each ``blc*`` node
(SURVEY.md §0 "File products").

Design:

- Every chunk handed to the device has the same static shape, so XLA compiles
  the reduction exactly once and the steady state is pure streaming.
- A chunk is a stream's NEW samples only: ``chunk_frames`` blocks of
  ``nfft`` samples, which yield ``chunk_frames`` PFB frames.  The
  ``(ntap-1) * nfft`` samples before them — the filter state — are on the
  device already: each channel group's program takes ``(tail, body)``,
  reduces ``concat(tail, body)`` and returns the next tail beside its
  product (:func:`blit.ops.channelize.channelize_stream`).  Only the
  stream's HEAD, its first ``(ntap-1) * nfft`` samples, is read into a
  slab of its own and goes up as the first dispatch's tail; no sample
  crosses the host link twice.  What crosses is a VIEW of the int8
  buffers, one machine word per time sample
  (:func:`blit.ops.channelize.sample_words`): the runtime re-tiles an
  int8 ``(..., npol, 2)`` array on the host at a fifth of the rate it
  takes the same bytes as int32.  Frame continuity across chunks is
  exact (golden-tested against a whole-file reduction).
- Where an integration fits a dispatch, ``chunk_frames`` is a multiple of
  ``nint`` and each chunk integrates inside its own program.  Where it does
  not (``nint * nfft`` beyond the per-dispatch sample budget — rawspec's
  ``-f 1048576 -t 51`` — or an explicit ``chunk_frames`` that ``nint`` does
  not divide), the integration is CARRIED: a float32 accumulator per
  channel group stays on the device from dispatch to dispatch
  (:func:`blit.ops.channelize.integrate_carry`, one fold for every
  product), and a row leaves the chip
  only when it closes.  Trailing samples that can't fill an integration are
  dropped, as rawspec does.
- A reduction makes ONE product or SEVERAL from the same read
  (``RawReducer(also=...)``: rawspec's ``-f 1048576,8,1024 -t
  51,128,3072``).  The chunk grid is one — the sample budget's, sized by
  no product's ``nint`` — and the head is the largest ``nfft``'s; every
  channel group goes up the host link once and each product's leg
  (:class:`blit.ops.channelize.StreamLeg`: its own channeliser program,
  filter state and carried integration) consumes the same device array
  (:func:`blit.ops.channelize.channelize_fanout`).  Each product has a
  writer and a write-behind sink of its own; a dispatch may close rows of
  some products and none of others, and is still one put on the readback
  rotation.  One product is a list of one through the same code.
- Ingest is PIPELINED: a producer thread fills a rotation of
  ``prefetch_depth`` stable chunk buffers straight from the file (native
  threaded pread per block when built) while the device works on earlier
  chunks.  Every byte is read from disk exactly once, directly into its
  final position — no ring shifting, no filter-state copy between
  buffers, and no per-chunk stabilization copy before dispatch (the
  buffers themselves are stable until released).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from blit import observability
from blit.device import hbm_bytes_limit, host_link
from blit.io.guppi import GuppiRaw, RawSource, open_raw, require_native_reader
from blit.observability import Timeline, profile_trace
from blit.ops.channelize import (
    STOKES_NIF,
    StreamLeg,
    channelize_fanout,
    channels_per_dispatch,
    coeff_bank,
    lanes_block,
    output_header,
    usable_frames,
)

log = logging.getLogger("blit.pipeline")

# Share of the device's memory limit a reduction plans against; the rest is
# the runtime's own reserve and allocator fragmentation.
_HBM_FRACTION = 0.9
# Samples per coarse channel one device call is sized for.
_DISPATCH_SAMPLES = 1 << 23


def dispatch_frames(nfft: int) -> int:
    """Frames of ``nfft`` samples one device call is sized for."""
    return max(1, _DISPATCH_SAMPLES // nfft)


def fold_frames(nfft: int, nint: int) -> int:
    """The multiple ``chunk_frames`` moves in — THE rule for "does an
    integration fit a dispatch", for the reducer's own sizing and for
    everything else that sizes a chunk: ``nint`` where
    :func:`dispatch_frames` holds one, so it folds inside one program;
    else 1 — the integration is carried across dispatches
    (:func:`blit.ops.channelize.integrate_carry`) and binds no chunk."""
    return nint if nint <= dispatch_frames(nfft) else 1


@dataclass
class ReductionStats:
    """Aggregate throughput view derived from the reducer's stage
    :class:`~blit.observability.Timeline` (SURVEY.md §5 metrics plan)."""

    input_bytes: int = 0
    output_frames: int = 0
    device_seconds: float = 0.0
    wall_seconds: float = 0.0

    @property
    def gbps(self) -> float:
        return self.input_bytes / self.wall_seconds / 1e9 if self.wall_seconds else 0.0


class _Chunk:
    """A filled chunk buffer handed to the consumer.  ``view`` aliases the
    rotation buffer; it stays valid until :meth:`release`, after which the
    producer may refill it.  ``leg_frames`` are the frames each product
    takes of it (``frames``: the first product's).  A stream's first chunk
    also carries its ``head``, the ``(ntap-1)*nfft`` samples before
    ``view`` (of the largest ``nfft``; the reducer's own slab, valid to
    the stream's end); every later one has ``None``."""

    __slots__ = ("view", "leg_frames", "head", "_idx", "_free")

    def __init__(self, view: np.ndarray, leg_frames: Sequence[int],
                 idx: int, free, head: Optional[np.ndarray] = None) -> None:
        self.view = view
        self.leg_frames = tuple(leg_frames)
        self.head = head
        self._idx = idx
        self._free = free

    @property
    def frames(self) -> int:
        return self.leg_frames[0]

    @property
    def nbytes(self) -> int:
        """Host bytes this chunk sends to the device."""
        return self.view.nbytes + (
            0 if self.head is None else self.head.nbytes)

    def release(self) -> None:
        if self._free is not None:
            free, self._free = self._free, None
            free(self._idx)


class _StreamState:
    """What a stream keeps on the device between dispatches: per product a
    :class:`blit.ops.channelize.StreamLeg` (per channel group its filter
    state and, where the integration is carried, its accumulator and the
    frames the open integration has), and ``channel_block``, the group
    size all of it is laid out for — the stream's first chunk's, kept for
    every later one (a smaller flush chunk would fit more channels per
    dispatch, and find no tail or accumulator of that shape).  Both are
    ``None`` until the stream's first dispatch."""

    __slots__ = ("legs", "channel_block")

    def __init__(self) -> None:
        self.legs: Optional[List[StreamLeg]] = None
        self.channel_block: Optional[int] = None

    @property
    def filled(self) -> int:
        """Frames the first product's open integration holds."""
        return self.legs[0].filled if self.legs else 0


class _DirectSink:
    """The synchronous output path's sink (``async_output=False``): the
    :class:`blit.outplane.AsyncSink` interface with every append on the
    caller's thread."""

    def __init__(self, writer) -> None:
        self._writer = writer
        self.flush = getattr(writer, "flush", lambda: None)
        self.close, self.abort = writer.close, writer.abort

    def append(self, slab: np.ndarray, release=None) -> None:
        self._writer.append(slab)
        if release is not None:
            release()

    @property
    def nsamps(self) -> int:
        return self._writer.nsamps


def _withdraw(writer) -> None:
    """Take back a product its writer has already published: one of
    several whose sibling then failed to finish (all complete, or none at
    a final path)."""
    from blit.integrity import manifest_path

    final = getattr(writer, "final_path", None)
    for path in (final, final and manifest_path(final)):
        if path and os.path.exists(path):
            os.unlink(path)


_ROT_ERR = object()  # producer-exception marker on the filled queue


class BufferRotation:
    """The prefetch-rotation core behind every pipelined host feed: one
    producer thread fills slots it acquires from a free ring and emits
    ``(slot, payload)`` descriptors; the consumer iterates :meth:`slots`
    and must :meth:`release` every slot once nothing (host or device)
    still reads its buffers.

    Extracted from :class:`RawReducer`'s ingest machinery so the
    collective window feeds (:mod:`blit.parallel.antenna`) pipeline the
    same way the single-chip reducer does (module docstring).  Slot
    STORAGE belongs to the producer callback — slots are just indices the
    callback maps onto whatever stable host arrays it maintains, so one
    rotation can back an int8 chunk ring (RawReducer) or a set of planar
    per-device window buffers (the antenna feeds) unchanged.

    Contract:

    - ``fill(rot)`` runs in a daemon thread.  It calls ``rot.acquire()``
      for a free slot (``None`` means the consumer abandoned the stream —
      return), fills its buffers, and ``rot.emit(slot, payload)``.
      Returning ends the stream; exceptions re-raise in the consumer.
    - Waiting in ``acquire`` is back-pressure from the consumer, not
      producer work — time it outside any ingest stage.  The rotation
      times its own two waits on ``timeline``, blocked seconds only:
      ``wait.ingest_slot`` (producer, no free slot) and ``wait.chunk``
      (consumer, nothing filled yet).
    - A slot is only refilled after the consumer released it; concurrent
      READS of an emitted slot (the antenna feeds copy a filter-state
      tail into the next slot) are safe.
    - ``stall_timeout_s`` arms a producer-progress watchdog: a live
      producer that neither acquires nor emits for that long (a wedged
      NFS read, a hung decoder) raises in the consumer instead of
      hanging the whole run.  Back-pressure waits count as progress
      (the consumer is the slow side there, not the producer).
    """

    def __init__(self, nslots: int, fill, *, name: str = "blit-feed",
                 stall_timeout_s: Optional[float] = None,
                 timeline: Optional[Timeline] = None):
        self.nslots = max(2, nslots)
        self.stall_timeout_s = stall_timeout_s
        self._tl = timeline if timeline is not None else Timeline()
        self._tl.declare("wait.chunk", "wait.ingest_slot")
        # Captured on the consumer's thread: the producer's stages join
        # the trace of whatever pass built the rotation.
        self._span_ctx = observability.tracer().context()
        self._free: "queue.Queue[int]" = queue.Queue()
        for j in range(self.nslots):
            self._free.put(j)
        self._filled: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._fill = fill
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._started = False
        # Slots yielded to the consumer, not yet released.  Lock-guarded:
        # with the async output plane (blit/outplane.py) releases arrive
        # from the readback thread while the consumer thread increments.
        self._held = 0
        self._held_lock = threading.Lock()
        self._wd = observability.StallWatchdog(
            stall_timeout_s, name,
            what="a wedged read would otherwise hang the stream",
        )

    def _run(self) -> None:
        try:
            with observability.tracer().activate(self._span_ctx):
                self._fill(self)
            self._filled.put(None)
        except BaseException as e:  # noqa: BLE001 — forwarded to the consumer
            self._filled.put((_ROT_ERR, e))

    # -- producer side ----------------------------------------------------
    def acquire(self) -> Optional[int]:
        """Next free slot index; ``None`` once the consumer is gone."""
        with self._tl.wait("wait.ingest_slot") as w:
            while not self._stop.is_set():
                try:
                    slot = self._free.get(block=w.blocking, timeout=0.2)
                except queue.Empty:
                    # Back-pressure from the consumer is not a producer
                    # stall.
                    w.block()
                    self._wd.beat()
                    continue
                self._wd.beat()
                return slot
        return None

    def emit(self, slot: int, payload) -> None:
        self._wd.beat()
        self._filled.put((slot, payload))

    # -- consumer side ----------------------------------------------------
    def release(self, slot: int) -> None:
        with self._held_lock:
            self._held -= 1
        self._free.put(slot)

    def slots(self) -> Iterator[Tuple[int, object]]:
        """Yield ``(slot, payload)`` in stream order, starting the producer
        on first use; re-raises producer exceptions.  A consumer that holds
        every slot unreleased while asking for more gets a loud error, not
        a silent deadlock (the producer can never fill another slot)."""
        self._wd.beat()
        self._thread.start()
        self._started = True
        poll = self._wd.poll_s(0.5)
        try:
            while True:
                with self._tl.wait("wait.chunk") as w:
                    item = self._next_filled(w, poll)
                if item is None:
                    return
                slot, payload = item
                if slot is _ROT_ERR:
                    # Raised from this frame the exception holds the frame
                    # (its traceback), so the frame must not hold the
                    # exception: the cycle kept the CONSUMER's frames — the
                    # writers, the readers, the staged slabs of a run that
                    # died — until some thread's cyclic GC.
                    del item
                    try:
                        raise payload
                    finally:
                        del payload
                with self._held_lock:
                    self._held += 1
                yield slot, payload
        finally:
            self.close()

    def _next_filled(self, w, poll: float):
        """The next item off the filled queue; blocked seconds go to
        ``w``."""
        while True:
            try:
                return self._filled.get(block=w.blocking, timeout=poll)
            except queue.Empty:
                if not w.blocking:
                    w.block()
                    continue
                if self._held >= self.nslots:
                    msg = (
                        f"BufferRotation starved: all {self.nslots} "
                        "slots are held unreleased by the consumer — "
                        "release() earlier chunks/windows before "
                        "requesting more, or raise prefetch_depth"
                    )
                    observability.flight_recorder().dump(msg)
                    raise RuntimeError(msg)
                # The watchdog dumps the incident trail BEFORE the
                # raise unwinds and teardown noise overwrites the
                # flight-recorder ring (ISSUE 5 tentpole #4).
                self._wd.check("producer stalled",
                               active=self._thread.is_alive())

    def close(self, join_timeout_s: float = 10.0) -> None:
        """Stop the producer and join it (idempotent; safe mid-stream).
        The join is bounded: a producer wedged inside a fill (the stall
        watchdog's trigger) must not convert consumer teardown into the
        very hang it detected — the daemon thread is abandoned with a
        warning and exits at its next ``acquire``."""
        self._stop.set()
        if self._started:
            self._thread.join(timeout=join_timeout_s)
            if self._thread.is_alive():
                log.warning(
                    "%s: producer did not exit within %.1fs of close; "
                    "abandoning the daemon thread", self._thread.name,
                    join_timeout_s,
                )


def raw_block_feed(raw: GuppiRaw):
    """The at-rest block feed over an indexed block stream: ``(header,
    kept_samples, read_into)`` triples in stream order — the batch-side
    producer input of :meth:`RawReducer._fill_rotation`.  A live source
    provides the same triples through ``feed_blocks()``
    (blit/stream/plane.py), which is the whole batch≡stream byte-identity
    contract: both paths feed the identical sample stream through the
    identical framing."""
    for i in range(raw.nblocks):
        yield (raw.header(i), raw.block_ntime_kept(i),
               functools.partial(raw.read_block_into, i))


@dataclass
class RawReducer:
    """Configured RAW → filterbank reduction (one worker / one chip).

    Product presets mirror rawspec's (SURVEY.md §0): the hi-res product is
    ``nfft=2**20, nint=1``; the low-res ``0002`` product is small-nfft,
    long-integration.
    """

    nfft: int
    ntap: int = 4
    nint: int = 1
    stokes: str = "I"
    window: str = "hamming"
    fft_method: str = "auto"
    # On-device frequency-averaging epilogue: sum every fqav_by consecutive
    # fine channels before the product leaves the chip (the reference's
    # reduce-before-the-wire lever, src/gbtworkerfunctions.jl:16-20, moved
    # into the jitted kernel).  Headers carry the fqav_range mapping.
    fqav_by: int = 1
    # Chunk buffers in the ingest rotation (>= 2).  2 = classic double
    # buffering: the producer thread reads chunk i+1 from the file while the
    # device works on chunk i.  Host memory held: prefetch_depth chunk-sized
    # int8 buffers.
    prefetch_depth: int = 2
    # Output-plane depth: device outputs in readback flight + write-behind
    # queue slots (blit/outplane.py).  None = follow prefetch_depth.
    # Deeper hides a laggier D2H link at the cost of one pinned chunk
    # buffer (and its HBM output) per extra slot.
    out_depth: Optional[int] = None
    # Working dtype of the channelizer's DFT stages ("float32"|"bfloat16").
    # bf16 halves the inter-stage HBM, fitting ~2x the frames per dispatch
    # at a measured accuracy cost (DESIGN.md §8).
    dtype: str = "float32"
    # Output frames per device call.  None = the per-dispatch sample
    # budget's: a multiple of nint where an integration fits it, else
    # the budget's frames with the integration carried across
    # dispatches.  An explicit value is kept as given (nint need not
    # divide it: the reduction then carries).
    chunk_frames: Optional[int] = None
    # Per-stage timing/byte registry ("ingest" / "stream" on the source
    # side; "dispatch" / "device" / "readback" / "write" on the output
    # plane — see blit/outplane.py; "wait.*" where a pump thread blocked;
    # counted instants "state.head" / "state.carry", "integrate.*"; a
    # pass's two ends "open" / "close"; and PARTS, what a stage's seconds
    # went to: "coeffs", "link.put", "dispatch.call", "write.digest").
    # Every row is also a span of the process tracer.
    timeline: Timeline = field(default_factory=Timeline)
    # When set, a device-only JAX profiler trace wraps every streaming run
    # and the run's spans land beside it as blit-spans.json
    # (observability.profile_trace).
    trace_logdir: Optional[str] = None
    # Asynchronous output plane (ISSUE 4): device outputs are read back on
    # a dedicated thread (device→host overlaps the next chunk's compute)
    # and file products are written write-behind through an AsyncSink.
    # Products are byte-identical either way (tests/test_outplane.py);
    # False — or BLIT_SYNC_OUTPUT=1 in the environment — restores the
    # fully synchronous per-chunk path (the A/B lever and drill escape
    # hatch).
    async_output: bool = True
    # Producer-progress watchdog for the output plane's readback/writer
    # threads (None = wait forever), the BufferRotation stall_timeout_s
    # twin on the result side.
    output_stall_timeout_s: Optional[float] = None
    # Quantized product narrowing (ISSUE 8 tentpole c): nbits=8/16 writes
    # SIGPROC ``.fil`` products in their narrow on-disk integer form —
    # quantized ON DEVICE before D2H on the async plane (4x/2x fewer
    # bytes across the slow link), on the host on the sync path, with
    # bit-identical results either way (blit/ops/narrow.py).  The fixed
    # affine rule is ``clip(rint(x*scale + offset), 0, 2^nbits-1)``;
    # scale/offset are the caller's (global stats don't exist mid-stream).
    nbits: int = 32
    quant_scale: float = 1.0
    quant_offset: float = 0.0
    # Further products from the SAME read, each ``(nfft, nint)``: rawspec's
    # ``-f 1048576,8,1024 -t 51,128,3072`` is ``nfft=1048576, nint=51,
    # also=((8, 128), (1024, 3072))``.  Every channel group goes up once
    # and feeds each product's own channeliser, filter state, integration
    # and writer (:meth:`reduce_to_files`); each product is what the
    # single reduction for its ``(nfft, nint)`` makes of the recording.
    # The chunk grid is ONE, the per-dispatch sample budget's, sized by no
    # product's ``nint``: ``chunk_frames`` counts frames of ``nfft`` (the
    # first product's) and must hold whole frames of every product.
    also: Tuple[Tuple[int, int], ...] = ()

    def __post_init__(self):
        from blit.ops.narrow import check_quant

        self.also = tuple((int(f), int(t)) for f, t in self.also)
        if os.environ.get("BLIT_SYNC_OUTPUT"):
            self.async_output = False
        check_quant(self.nbits)
        self._output_frames = 0
        # Chunk-buffer cache: streams on the same reducer reuse (already
        # page-faulted) rotation buffers — first-touch faults on GB-sized
        # buffers otherwise dominate short runs.  Backed by the process-wide
        # staging pool (blit/hostmem.py): buffers retire to the pool at the
        # end of a completed stream, so the NEXT reducer (a serve-layer
        # request, the next scan window) stages through already-faulted
        # aligned slabs too.  One stream at a time per reducer instance.
        self._buf_cache: List[np.ndarray] = []
        # The slab a stream's head is read into (same discipline).
        self._head_slab: Optional[np.ndarray] = None

        # The three ingest knobs are the caller's, else what this
        # method derives: no state outside the process sets them.
        self._knob_sources = {
            "chunk_frames": "explicit" if self.chunk_frames is not None
            else "default",
            "prefetch_depth": "explicit" if self.prefetch_depth != 2
            else "default",
            "out_depth": "explicit" if self.out_depth is not None
            else "default",
        }
        if self.out_depth is None:
            self.out_depth = max(2, self.prefetch_depth)
        self.out_depth = max(2, self.out_depth)

        # Budget-driven sizing: ~8M samples per coarse channel per device
        # call.  Small-nfft products get many frames per call (amortizes
        # dispatch); the 1M-point hi-res product gets few (the complex64
        # FFT intermediates are what bound HBM, not dispatch overhead).
        budget = dispatch_frames(self.nfft)
        fits = fold_frames(self.nfft, self.nint) == self.nint
        if self.also and self.chunk_frames is None:
            # The shared grid: the sample budget's (one frame of the
            # largest nfft where that is more), whatever the nints.
            self.chunk_frames = max(
                _DISPATCH_SAMPLES, *(f for f, _ in self.products)
            ) // self.nfft
        if self.chunk_frames is None:
            # An integration the budget cannot hold (rawspec's -f 1048576
            # -t 51: 51 frames against 8) does not size the dispatch: the
            # chunk is the budget's and the integration is carried.
            self.chunk_frames = (
                self.nint * max(1, min(64, budget) // self.nint)
                if fits else budget)
        if self.chunk_frames < 1:
            raise ValueError(f"chunk_frames={self.chunk_frames} must be >= 1")
        for nfft, nint in self.products:
            if nfft < 2 or nint < 1:
                raise ValueError(f"bad product (nfft={nfft}, nint={nint})")
            if self.fqav_by > 1 and nfft % self.fqav_by:
                # Averaging groups must not straddle coarse-channel
                # boundaries (despike/nfpc consumers key on
                # fine-per-coarse counts).
                raise ValueError(
                    f"fqav_by={self.fqav_by} does not divide nfft={nfft}"
                )
            if self._chunk_samples % nfft:
                raise ValueError(
                    f"chunk_frames={self.chunk_frames} of nfft={self.nfft} "
                    f"holds no whole number of nfft={nfft} frames")

    @property
    def products(self) -> Tuple[Tuple[int, int], ...]:
        """Every ``(nfft, nint)`` this reduction makes, the first first."""
        return ((self.nfft, self.nint),) + self.also

    @property
    def _chunk_samples(self) -> int:
        """New samples per coarse channel in one dispatch."""
        return self.chunk_frames * self.nfft

    @property
    def _head_samples(self) -> int:
        """A stream's head: the filter state of the largest nfft."""
        return (self.ntap - 1) * max(f for f, _ in self.products)

    def _head_frames(self, nfft: int) -> int:
        """Frames of ``nfft`` the head holds beyond that product's own
        filter state: its data, reduced with the first dispatch."""
        return (self._head_samples - (self.ntap - 1) * nfft) // nfft

    def _leg_carries(self, k: int) -> bool:
        """Does product ``k``'s integration straddle dispatches (module
        docstring)?  Not where every dispatch holds whole integrations:
        the chunk's frames and the head's."""
        nfft, nint = self.products[k]
        return ((self._chunk_samples // nfft) % nint != 0
                or self._head_frames(nfft) % nint != 0)

    def _lanes(self, k: int, npol: int) -> int:
        """Product ``k``'s block on the small-nfft path (0: not taken;
        the shape decides, :func:`blit.ops.channelize.lanes_block`)."""
        if not self._leg_carries(k):
            return 0
        return lanes_block(*self.products[k], npol, self.ntap,
                           fqav_by=self.fqav_by, dtype=self.dtype)

    def _coeffs_for(self, nfft: int):
        """The process's coefficient bank for ``nfft``
        (:func:`blit.ops.channelize.coeff_bank` owns it and records the
        lookup as this reduction's part ``coeffs``), asked for on FIRST
        compute use — not at construction.  The reducer keeps no bank of
        its own: a stream's legs hold the array while they run, and
        nothing donates it."""
        return coeff_bank(self.ntap, nfft, self.window, self.timeline)

    @property
    def _coeffs(self):
        return self._coeffs_for(self.nfft)

    def tuning_provenance(self) -> Dict:
        """This reducer's ingest knobs and where each came from
        (``explicit``: the caller's; ``default``: derived here), so every
        recorded number can name them."""
        return {
            "chunk_frames": self.chunk_frames,
            "prefetch_depth": self.prefetch_depth,
            "out_depth": self.out_depth,
            "sources": dict(self._knob_sources),
        }

    def _narrow_host(self, slab: np.ndarray) -> np.ndarray:
        """The synchronous-path product narrowing (identity at nbits=32):
        the host twin of the device-side narrowing in
        :meth:`_stream_async` (blit/ops/narrow.py pins them bitwise)."""
        from blit.ops.narrow import narrow_host

        if self.nbits == 32:
            return np.ascontiguousarray(slab)
        return narrow_host(slab, self.nbits, self.quant_scale,
                           self.quant_offset)

    def _retire_staging(self) -> None:
        """Return the stream's chunk buffers and head slab to the process
        staging pool (blit/hostmem.py) — called only after a TERMINAL sync
        (stream fully drained / sink closed), never on an error path where
        an un-synced dispatch might still read a buffer."""
        from blit import hostmem

        pool = hostmem.slab_pool()
        for b in self._buf_cache:
            pool.give(b, self.timeline)
        self._buf_cache = []
        if self._head_slab is not None:
            pool.give(self._head_slab, self.timeline)
            self._head_slab = None
        # Every dispatch is synced: the link budget lets go of the stream's
        # last handles now, not at the next reduction's first put.
        host_link().retire()

    @property
    def stats(self) -> ReductionStats:
        """Aggregate counters derived from :attr:`timeline`."""
        st = self.timeline.stages
        return ReductionStats(
            input_bytes=st["ingest"].bytes,
            output_frames=self._output_frames,
            device_seconds=st["device"].seconds,
            wall_seconds=st["stream"].seconds,
        )

    # -- core streaming ---------------------------------------------------
    def _leg_kw(self, k: int, nint: Optional[int] = None) -> Dict:
        """The exact channelize kwarg set of product ``k`` (jax.jit caches
        per call signature, so the kwarg set must be bit-stable across
        callers — fqav_by only appears when active, keeping the
        common-case cache signature identical to callers that never heard
        of it)."""
        nfft, own = self.products[k]
        kw = dict(
            nfft=nfft, ntap=self.ntap, nint=own if nint is None else nint,
            stokes=self.stokes, fft_method=self.fft_method,
        )
        if self.fqav_by > 1:
            kw["fqav_by"] = self.fqav_by
        if self.dtype != "float32":
            kw["dtype"] = self.dtype
        return kw

    @property
    def _channelize_kw(self) -> Dict:
        return self._leg_kw(0)

    def _legs(self, npol: int) -> List[StreamLeg]:
        """A stream's legs, one per product, with nothing on the device
        yet.  The first keeps the program name a reduction of one product
        has always had; the others' device work is named after them."""
        legs = []
        for k, (nfft, nint) in enumerate(self.products):
            kw = self._leg_kw(k)
            del kw["nint"]
            legs.append(StreamLeg(
                self._coeffs_for(nfft), nint=nint,
                carried=self._leg_carries(k), lanes=self._lanes(k, npol),
                name="channelize_stream" if k == 0 else f"channelize_{k:04d}",
                label=f"{k:04d}" if self.also else None, **kw))
        return legs

    def _channel_block(self, shape: Tuple[int, int, int, int]) -> int:
        """Coarse channels per device dispatch for chunks of ``shape`` (a
        chunk's new samples): all of them where the backend reports no
        memory limit (the CPU), else as many as the device holds beside
        what stays resident — every group's filter state, the products
        still in readback flight (``out_depth - 1``), this chunk's
        per-group products and their concatenation; all of that for every
        product, whose programs run one after the other on the same
        uploaded group (the fewest channels any of them fits).  A
        64-channel hi-res chunk is ~2 GB of int8 whose f32 intermediates
        alone exceed a 16 GB chip.  Grouping changes no
        arithmetic — every coarse channel reduces on its own
        (``channelize_fanout``'s golden test) — though a backend may round
        a differently-batched program differently in the last bit."""
        nchan = shape[0]
        limit = hbm_bytes_limit()
        if limit is None:
            return nchan
        resident, probes = 0, []
        for k, (nfft, nint) in enumerate(self.products):
            frames = shape[1] // nfft
            row = (STOKES_NIF[self.stokes] * nchan
                   * (nfft // self.fqav_by) * 4)
            # Every group's filter state (the probe leaves its own out).
            resident += nchan * (self.ntap - 1) * nfft * shape[2] * shape[3]
            if self._leg_carries(k):
                # The accumulators (the old set lives until the new one
                # is written) beside the rows a dispatch may close (the
                # first also those of the head's frames); the probe
                # program is the frame-major one the carry reads.
                head = self._head_frames(nfft)
                product = ((nint - 1 + frames) // nint
                           + (nint - 1 + head) // nint * bool(head)) * row
                resident += 2 * row
                kw = self._leg_kw(k, nint=1)
                lanes = self._lanes(k, shape[2])
                if lanes:
                    kw["lanes"] = lanes
            else:
                product = frames // nint * row
                kw = self._leg_kw(k)
            resident += (max(2, self.out_depth) + 1) * product
            probes.append(kw)
        budget = int(_HBM_FRACTION * limit) - resident
        cb = min(channels_per_dispatch(tuple(shape), budget, **kw)
                 for kw in probes)
        log.info("chunk %s: %d of %d coarse channels per dispatch "
                 "(device limit %d B, %d B resident)",
                 shape, cb, nchan, limit, resident)
        return cb

    @property
    def _carries(self) -> bool:
        """Does the first product's integration straddle dispatches?"""
        return self._leg_carries(0)

    def _dispatch(self, chunk: _Chunk, st: _StreamState):
        """One host chunk → ``(outs, token)``, dispatched async in as
        many channel groups as :meth:`_channel_block` says for the
        stream's first chunk (each group's new samples go up on their own,
        ONCE, and every product's program consumes them there, so the
        whole chunk is never resident as one input; its filter state is
        on the chip already — each leg's ``tails``, the previous
        dispatch's output, or the stream's head going up once).  ``token``
        is ready once the chunk's input has been consumed (the next tails
        leave the same programs).  ``outs`` are the row batches that
        closed, ``(product index, rows)`` in product order: everything a
        product integrated inside its program, the rows that closed in
        this chunk where its integration is carried — nothing when none
        did.  ``st`` is the stream's own (a stream starts on a row
        boundary — ``skip_frames`` is whole rows — and with a head of its
        own) and moves on."""
        first = chunk.head is not None
        if first:
            # The stream's first dispatch builds the legs (a coefficient
            # bank per nfft: half a second at 2^20) while the producer
            # reads ahead, not before it starts.
            st.legs = self._legs(chunk.view.shape[2])
            st.channel_block = self._channel_block(chunk.view.shape)
        rows, token = channelize_fanout(
            chunk.view, st.legs, list(chunk.leg_frames),
            channel_block=st.channel_block, head=chunk.head,
            put=functools.partial(host_link().put, timeline=self.timeline),
            # Programs that consumed a group they did not upload, and the
            # H2D bytes that were not sent again for them.
            shared=lambda programs, nbytes: self.timeline.mark(
                "fanout.share", nbytes, calls=programs),
            calling=self._calling)
        outs = []
        for k, (leg, frames) in enumerate(zip(st.legs, chunk.leg_frames)):
            if not frames:
                continue
            # Filter state by where it came from: up from the host (once
            # per group per stream) or left on the chip by the last
            # dispatch.
            self.timeline.mark(
                "state.head" if first else "state.carry",
                sum(t.nbytes for t in leg.tails), calls=len(leg.tails))
            if leg.carried and leg.filled:  # left an integration open
                self.timeline.mark("integrate.carry",
                                   sum(a.nbytes for a in leg.accs))
            for batch in rows[k]:
                if leg.carried:
                    self.timeline.mark("integrate.emit", batch.nbytes,
                                       calls=batch.shape[0])
                if leg.label is not None:  # per product, where several
                    self.timeline.mark(f"integrate.emit.{leg.label}",
                                       batch.nbytes, calls=batch.shape[0])
                if k == 0:
                    self._output_frames += batch.shape[0] * leg.nint
                outs.append((k, batch))
        return outs, token

    @contextlib.contextmanager
    def _calling(self, programs: List[str]) -> Iterator[None]:
        """The part ``dispatch.call``: one channel group's program calls
        (``calls`` = programs called; attr ``programs``: their jit names
        in call order, which pair the span with its runs in a device
        trace)."""
        with self.timeline.part("dispatch.call", calls=len(programs),
                                byte_free=True) as sp:
            if sp is not None:
                sp.attrs["programs"] = programs
            yield

    def _run_chunk(self, chunk: _Chunk, st: _StreamState
                   ) -> List[Tuple[int, np.ndarray]]:
        import jax

        with self.timeline.stage("device", nbytes=chunk.nbytes):
            outs, token = self._dispatch(chunk, st)
            jax.block_until_ready(token)
            return [(k, np.asarray(out)) for k, out in outs]

    def _one_product(self, what: str) -> None:
        if self.also:
            raise ValueError(
                f"{what} yields ONE product and this reducer makes "
                f"{len(self.products)}: several go to files "
                "(reduce_to_files)")

    def stream(self, raw: GuppiRaw, skip_frames: int = 0) -> Iterator[np.ndarray]:
        """Yield filterbank slabs ``(nspectra, nif, nchan*nfft)`` covering
        the file gap-free (PFB state carried across blocks).  Slabs are
        float32 — or, with ``nbits=8/16``, the same quantized narrow dtype
        :meth:`reduce_to_file` writes (the knob applies uniformly: the
        in-memory product always matches the on-disk bytes).

        ``skip_frames`` skips the first N output frames exactly — frame N's
        PFB window starts at sample ``N*nfft`` of the gap-free stream, so
        skipping that many samples reproduces the remaining frames
        bit-identically (the resume path of :meth:`reduce_resumable`).

        While chunk ``i`` computes, the producer thread is already filling
        the next chunk buffer from the file (module docstring: pipelined
        ingest) and — on the default async output plane — the readback
        thread is fetching chunk ``i-1``'s product, so host read, compute
        and device→host readback all overlap.  Yielded slabs are the
        caller's to keep (never recycled under it); slab VALUES are
        byte-identical to the synchronous path's.
        """
        self._one_product("stream()")
        with profile_trace(self.trace_logdir), observability.span(
            "reduce.stream", nfft=self.nfft, path=getattr(raw, "path", "")
        ):
            for _, data, release in self._slabs(raw, skip_frames,
                                                reuse=False):
                if release is not None:
                    release()
                yield data
            # Normal exhaustion only: every dispatch synced, so the chunk
            # buffers are safe to hand to the next reducer via the pool.
            self._retire_staging()

    def _slabs(self, raw: GuppiRaw, skip_frames: int,
               reuse: bool) -> Iterator[Tuple[int, np.ndarray, object]]:
        """Every product slab of a reduction in stream order, narrowed to
        the product's on-disk form: ``(product index, data, release)``.
        On the synchronous path (``async_output=False``, the seed's
        serialized shape kept for A/B drills) each chunk is waited out
        and narrowed on the host; else :meth:`_stream_async`, narrowed on
        the device."""
        if self.async_output:
            for slab in self._stream_async(raw, skip_frames, reuse=reuse):
                # payload: the product's label (None: the only one)
                yield int(slab.payload or 0), slab.data, slab.release
            return
        st = _StreamState()
        for chunk in self._chunks(raw, skip_frames):
            try:
                outs = self._run_chunk(chunk, st)
            finally:
                chunk.release()
            for k, out in outs:
                yield k, self._narrow_host(out), None

    def _stream_async(self, raw: GuppiRaw, skip_frames: int,
                      reuse: bool) -> Iterator["object"]:
        """The overlapped streaming core behind :meth:`stream` and
        :meth:`_pump`: async-dispatch each chunk, hand the in-flight
        outputs to an :class:`blit.outplane.OutputRotation` readback
        thread, and yield :class:`~blit.outplane.OutputSlab` handles in
        stream order (``payload``: the product's label, which its
        ``readback`` span carries as attr ``product``).  ``reuse=True``
        recycles host slabs through the
        rotation's bounded ring (callers must release only after the
        slab's bytes are consumed — the AsyncSink wiring); ``reuse=False``
        yields caller-owned arrays (the public :meth:`stream` contract).

        In-flight arithmetic (the :meth:`drain` lag window, one thread
        over): with readback depth ``d``, ``put(chunk_w)`` returns once
        chunk ``w-(d-1)`` has been fetched — chunk ``w`` stays in
        un-synchronized flight while the consumer dispatches ``w+1``, so
        compute and readback overlap.  A chunk is ONE put however many
        products closed rows in it.  Un-synced dispatches pin their
        ingest slots (released at ``block_until_ready``, before the
        fetch), so the chunk rotation runs one slot wider
        (``extra_slots=1``) to keep a slot free for the producer's
        read-ahead.  A chunk of a carried reduction that closed no row
        is put sync-only: its slot is released the same
        way, and nothing of it crosses to the host.

        What is on the host link at any instant is a channel group or
        two, not a chunk: :meth:`_dispatch` hands each group up through
        the process's byte budget (:class:`blit.device.HostLink`), so
        chunk ``w+1``'s voltages go up while chunk ``w``'s programs run
        and nothing is enqueued behind a full link (ISSUE 27).
        """
        from blit.outplane import OutputRotation, readback_extra_slots

        depth = max(2, self.out_depth)
        rot = OutputRotation(
            depth=depth,
            timeline=self.timeline, reuse=reuse, name="blit-readback",
            stall_timeout_s=self.output_stall_timeout_s,
        )
        do_narrow = self.nbits < 32
        if do_narrow:
            from blit.ops.narrow import narrow_device
        st = _StreamState()
        try:
            extra = readback_extra_slots(depth, self.prefetch_depth)
            for chunk in self._chunks(raw, skip_frames, extra_slots=extra):
                with self.timeline.stage("dispatch", byte_free=True):
                    outs, token = self._dispatch(chunk, st)
                    if do_narrow and outs:
                        # Quantize to the product's on-disk integer form
                        # BEFORE D2H: 4x (nbits=8) / 2x (nbits=16) fewer
                        # bytes cross the slow link, bit-identical to the
                        # sync path's host-side narrowing
                        # (blit/ops/narrow.py).
                        outs = [(k, narrow_device(
                            out, self.nbits, self.quant_scale,
                            self.quant_offset)) for k, out in outs]
                        token = [out for _, out in outs]
                for slab in rot.put(token, nbytes=chunk.nbytes,
                                    on_consumed=chunk.release,
                                    outs=[(out, st.legs[k].label)
                                          for k, out in outs]):
                    yield slab
            # The chunker's "stream" stage closed when its generator
            # exhausted above; the readback tail it no longer covers is
            # still streaming wall time — account it into the same stage
            # (sequentially, so no double count).
            t0 = time.perf_counter()
            for slab in rot.drain():
                yield slab
            self.timeline.stages["stream"].seconds += time.perf_counter() - t0
        finally:
            rot.close()

    def _pump(self, raw: GuppiRaw, writer, skip_frames: int = 0):
        """Drive the full reduction chain into the product writer(s) — host
        read → H2D → compute → D2H → disk write, every leg on its own
        thread (ingest producer / main dispatch / readback / one sink per
        product) with back-pressure end to end — and finalize them.
        ``writer`` is one writer, or a list with one per product; returns
        the spectra written, likewise.  On error every writer is
        ``abort()``ed (its own crash contract: ``.partial`` dropped,
        resumable file + cursor kept), nothing stays at a final path, and
        the error is re-raised.

        Runs under :func:`blit.monitor.publishing` — every reduction
        (batch, stream, serve, search) streams its live timeline to the
        process publisher when ``BLIT_MONITOR_*`` enables one (ISSUE 11);
        disabled, the scope costs two env reads per reduction."""
        from blit.monitor import publishing

        many = isinstance(writer, (list, tuple))
        with publishing(self.timeline):
            nsamps = self._pump_impl(raw, list(writer) if many else [writer],
                                     skip_frames)
        return nsamps if many else nsamps[0]

    def _pump_impl(self, raw: GuppiRaw, writers: list, skip_frames: int = 0
                   ) -> List[int]:
        from blit.outplane import AsyncSink

        if len(writers) != len(self.products):
            raise ValueError(f"{len(self.products)} products, "
                             f"{len(writers)} writers")
        if self.async_output:
            sinks = [AsyncSink(
                w, depth=max(2, self.out_depth), timeline=self.timeline,
                stall_timeout_s=self.output_stall_timeout_s,
                **(dict(name=f"blit-sink.{k:04d}", product=f"{k:04d}")
                   if self.also else {}))
                for k, w in enumerate(writers)]
        else:
            sinks = [_DirectSink(w) for w in writers]
        closed = []
        try:
            with observability.span(
                "reduce.pump", nfft=self.nfft,
                out=str(getattr(writers[0], "path", "")),
            ):
                for k, data, release in self._slabs(
                        raw, skip_frames, reuse=True):
                    sinks[k].append(data, release=release)
                # Final flush barrier + writer finalization; the write
                # tail is streaming wall time like the readback tail.
                # Every product's last byte is written before the first
                # is renamed into place: all complete, or none there.
                t0 = time.perf_counter()
                for sink in sinks:
                    sink.flush()
                # The pass's far end: file close, rename, manifest.
                with self.timeline.stage("close", byte_free=True):
                    for sink in sinks:
                        sink.close()
                        closed.append(sink)
                self.timeline.stages["stream"].seconds += (
                    time.perf_counter() - t0
                )
        except BaseException:
            for sink, w in zip(sinks, writers):
                if sink in closed:
                    _withdraw(w)
                else:
                    sink.abort()
            raise
        if self.async_output:
            self.timeline.overlap_efficiency()
        self._retire_staging()
        return [sink.nsamps for sink in sinks]

    def _producer(
        self,
        raw: GuppiRaw,
        skip_frames: int,
        bufs: List[Optional[np.ndarray]],
        rot: BufferRotation,
    ) -> None:
        """Fill the chunk-buffer rotation (producer thread, the
        :class:`BufferRotation` fill callback).

        The block sequence comes either from the at-rest file
        (:func:`raw_block_feed` over an indexed :class:`GuppiRaw` /
        :class:`GuppiScan`) or, when the source exposes ``feed_blocks()``,
        from a live stream still being recorded (the watermark-ordered
        feed of :class:`blit.stream.LiveRawStream`) — the chunk framing,
        filter-state carry and flush rule below are shared, which is what
        makes a streamed reduction byte-identical to the batch path.
        """
        feed = (raw.feed_blocks() if hasattr(raw, "feed_blocks")
                else raw_block_feed(raw))
        self._fill_rotation(feed, skip_frames, bufs, rot)

    def _fill_rotation(
        self,
        feed,
        skip_frames: int,
        bufs: List[Optional[np.ndarray]],
        rot: BufferRotation,
    ) -> None:
        """The shared rotation-filling core: consume ``(header,
        kept_samples, read_into)`` triples in stream order and emit
        fixed-shape device chunks.

        The stream's first ``(ntap-1)*nfft`` samples after ``skip_frames``
        — its HEAD, the filter state of its first frame (of the largest
        ``nfft`` where there are several products; the others find their
        own, shorter state at its start and data after it) — are read
        into a slab of their own and ride with the first chunk emitted;
        after that the device holds the filter state
        (:class:`_StreamState`).
        Buffer ``j`` is ``chunk_frames * nfft`` NEW samples per channel,
        so a channel group ``buf[c:c+cb]`` is one contiguous run of host
        memory.  Every sample is read from the source exactly once,
        directly into place (``read_into(dst, t0, take)`` copies samples
        ``[t0, t0+take)`` of the block into ``dst[:, :take]``); payloads
        are ``(frames of each product, samples, head or None)``.
        """
        from blit import hostmem

        nfft, ntap = self.nfft, self.ntap
        chunk_samps = self._chunk_samples
        state = self._head_samples
        to_skip = skip_frames * nfft
        whole = tuple(chunk_samps // f for f, _ in self.products)

        def slab(shape, cached: Optional[np.ndarray]) -> np.ndarray:
            """``cached`` if it has ``shape``, else a page-aligned,
            pool-recycled staging slab (blit/hostmem.py): an
            already-faulted buffer from a previous stream when one
            matches, so steady-state ingest never allocates."""
            if cached is not None and cached.shape == shape:
                return cached
            pool = hostmem.slab_pool()
            if cached is not None:
                pool.give(cached, self.timeline)
            return pool.take(shape, np.int8, self.timeline)

        head: Optional[np.ndarray] = None  # rides with the first emit
        head_left = state  # samples of the head still to read
        cur: Optional[int] = None
        filled = 0
        emitted = 0  # chunks emitted so far
        for hdr, nt, read_into in feed:
            if to_skip >= nt:
                to_skip -= nt
                continue
            t0, nt = to_skip, nt - to_skip
            to_skip = 0
            nchan = hdr["OBSNCHAN"]
            npol = 2 if hdr["NPOL"] > 2 else hdr["NPOL"]
            if head is None and not emitted:
                head = self._head_slab = slab((nchan, state, npol, 2),
                                              self._head_slab)
            if head_left:
                take = min(nt, head_left)
                with self.timeline.stage(
                    "ingest", nbytes=nchan * take * npol * 2
                ):
                    read_into(head[:, state - head_left:], t0, take)
                head_left -= take
                t0 += take
                nt -= take
            while nt > 0:
                if cur is None:
                    # Waiting for a free buffer is back-pressure from
                    # the device, NOT ingest work — keep it outside the
                    # "ingest" stage so the timeline's GB/s is the true
                    # host read rate.
                    cur = rot.acquire()
                    if cur is None:
                        return  # consumer abandoned the stream
                    if bufs[cur] is None:
                        shape = (nchan, chunk_samps, npol, 2)
                        for j, b in enumerate(self._buf_cache):
                            if b.shape == shape:
                                bufs[cur] = self._buf_cache.pop(j)
                                break
                        else:
                            bufs[cur] = slab(shape, None)
                    filled = 0
                take = min(nt, chunk_samps - filled)
                with self.timeline.stage(
                    "ingest", nbytes=nchan * take * npol * 2
                ):
                    read_into(bufs[cur][:, filled:], t0, take)
                filled += take
                t0 += take
                nt -= take
                if filled == chunk_samps:
                    rot.emit(cur, (whole, chunk_samps, head))
                    emitted += 1
                    head, cur = None, None
        if cur is not None and filled > 0:
            # Flush: of each product the whole frames remaining, up to
            # the last that closes an integration (one carried in from
            # earlier chunks counts with the frames it already holds);
            # the samples of the one that takes most go up.
            frames = tuple(
                usable_frames(
                    (ntap - 1) * f + filled, f, ntap, t,
                    open_frames=(self._head_frames(f)
                                 + emitted * (chunk_samps // f)) % t)
                for f, t in self.products)
            if any(frames):
                rot.emit(cur, (frames, max(
                    n * f for n, (f, _) in zip(frames, self.products)),
                    head))
                head = None
        if self.also and head is not None:
            # The head never went up.  A single product ends here with no
            # row; of several the smaller would have had rows out of the
            # head itself.
            raise ValueError(
                "the recording ends inside (or with) the filter state of "
                f"nfft={max(f for f, _ in self.products)}: several "
                "products from one read need samples beyond it")

    def _chunks(
        self, raw: GuppiRaw, skip_frames: int = 0, extra_slots: int = 0
    ) -> Iterator["_Chunk"]:
        """The pipelined chunker behind :meth:`stream` / :meth:`drain`:
        yields :class:`_Chunk` handles in stream order.  The caller MUST
        ``release()`` every chunk once nothing (host or device) still reads
        its buffer; the producer blocks on released buffers to read ahead.

        ``extra_slots`` widens the rotation beyond ``prefetch_depth`` —
        the async output plane holds one chunk in un-synchronized flight
        on top of the one being dispatched, and the producer needs a
        slot free beyond those to keep reading (and to keep the
        rotation's all-slots-held starvation heuristic a true bug
        signal rather than a transient of deeper pipelining).
        """
        require_native_reader(raw)
        nbufs = max(2, self.prefetch_depth) + max(0, extra_slots)
        bufs: List[Optional[np.ndarray]] = [None] * nbufs
        rot = BufferRotation(
            nbufs,
            lambda r: self._producer(raw, skip_frames, bufs, r),
            name="blit-ingest", timeline=self.timeline,
        )
        with self.timeline.stage("stream"):
            try:
                for idx, (frames, samps, head) in rot.slots():
                    chunk = _Chunk(bufs[idx][:, :samps], frames, idx,
                                   rot.release, head)
                    # The stream stage moves every byte it hands
                    # downstream (VERDICT r5 weak #3: the dominant stage
                    # must not report zero bytes).
                    self.timeline.stages["stream"].bytes += chunk.nbytes
                    yield chunk
            finally:
                rot.close()
                # Keep the (faulted) buffers for the next stream.
                self._buf_cache = [b for b in bufs if b is not None][:nbufs]

    def drain(self, raw: GuppiRaw) -> float:
        """Run the full streaming reduction with a device-side sink: each
        chunk's product reduces to a scalar checksum on device and only the
        final float crosses back.

        Dispatch is async with a lag-synchronized window: chunk ``i``'s
        scalar is synced (and its buffer released back to the producer) only
        once ``prefetch_depth - 1`` newer chunks are in flight, so host
        block reads, host→device transfers and device compute overlap —
        this is the steady-state shape of the ingest path with the
        device→host readback taken out.  No stabilization copy is needed:
        the chunk buffers themselves stay untouched until released.
        Returns the checksum (sum over all products).
        """
        import jax
        import jax.numpy as jnp

        # The final syncs must happen INSIDE the trace context, or the
        # profiler stops before the queued tail of the async work it exists
        # to capture.
        with profile_trace(self.trace_logdir):
            total = 0.0
            pending: deque = deque()
            st = _StreamState()

            def retire() -> float:
                done, sums, token = pending.popleft()
                # sync: the device is done with the input
                part = sum(float(s) for s in sums)
                jax.block_until_ready(token)
                done.release()
                return part

            for chunk in self._chunks(raw):
                with self.timeline.stage("device", nbytes=chunk.nbytes):
                    outs, token = self._dispatch(chunk, st)
                    pending.append(
                        (chunk, [jnp.sum(out) for _, out in outs], token))
                while len(pending) >= max(2, self.prefetch_depth):
                    total += retire()
            while pending:
                total += retire()
            self._retire_staging()
            return total

    def _surface_integrity(self, raw, hdr: Dict) -> None:
        """Mirror digest-failed (zero-masked) blocks into the product
        header through the ONE mask bookkeeping rule (ISSUE 13: the
        PR 2/7 ``record_mask`` discipline, kind="block") — a degraded
        product says so everywhere a healthy one reports
        (``_masked_blocks``, the ``block.masked`` timeline counter, the
        process-wide ``mask.block`` fault counter)."""
        bad = sorted(getattr(raw, "bad_blocks", None) or ())
        if not bad:
            return
        from blit.parallel.antenna import record_mask

        masked: set = set()
        for b in bad:
            record_mask(masked, b, "failed digest verification",
                        header=hdr, timeline=self.timeline, kind="block")

    # -- whole-file conveniences ------------------------------------------
    @contextlib.contextmanager
    def _pass(self, root: str, out: str) -> Iterator:
        """One pass to file(s), from its entry point's first line to its
        return: the root span ``root`` (attr ``out``: the first product —
        how a reader finds its own pass's trace) with the device profile
        around it, so every span of the pass shares one trace id and the
        profile holds them all.  Yields that span (``None`` with spans
        off) and the pass's near end, the stage ``open``, for the entry
        point to enter around everything up to the first read being
        possible (the source and its index, the headers, the writers);
        the far end, ``close``, is the pump's (:meth:`_pump_impl`)."""
        with profile_trace(self.trace_logdir), observability.span(
                root, out=out) as sp:
            yield sp, self.timeline.stage("open", byte_free=True)

    def _open_validated(self, raw_src: RawSource):
        """Shared prologue of every whole-recording entry point: open the
        source, reject empty/truncated recordings, derive the product
        header.  Returns ``(raw, header)``."""
        raw = open_raw(raw_src)
        if raw.nblocks == 0:
            raise ValueError(f"empty or fully truncated RAW file: {raw.path}")
        return raw, self.header_for(raw)

    def header_for(self, raw: GuppiRaw, product: int = 0) -> Dict:
        nfft, nint = self.products[product]
        hdr = output_header(
            raw.header(0), nfft=nfft, nint=nint, stokes=self.stokes
        )
        if self.fqav_by > 1:
            from blit.ops.fqav import fqav_range

            fch1, foff, nchans = fqav_range(
                hdr["fch1"], hdr["foff"], hdr["nchans"], self.fqav_by
            )
            hdr.update(
                fch1=fch1, foff=foff, nchans=nchans,
                nfpc=nfft // self.fqav_by,
            )
        return hdr

    def reduce(self, raw_src: RawSource) -> Tuple[Dict, np.ndarray]:
        """Reduce a whole RAW file — or a whole multi-file ``.NNNN.raw``
        scan sequence (path list / stem, blit/io/guppi.open_raw) — in memory
        → ``(filterbank_header, data)`` with data ``(nsamps, nif, nchans)``."""
        from blit.ops.narrow import NARROW_DTYPES

        self._one_product("reduce()")
        raw, hdr = self._open_validated(raw_src)
        with observability.span("reduce", nfft=self.nfft):
            slabs = list(self.stream(raw))
        if slabs:
            data = np.concatenate(slabs, axis=0)
        else:
            # Zero usable frames: shape the empty product off the header so
            # the channel axis stays consistent (fqav_by included).
            data = np.zeros(
                (0, STOKES_NIF[self.stokes], hdr["nchans"]),
                NARROW_DTYPES[self.nbits],
            )
        # stream() already narrowed nbits=8/16 products; the header must
        # say so or a later write_fil of (hdr, data) lies about the dtype.
        hdr["nbits"] = self.nbits
        hdr["nsamps"] = data.shape[0]
        self._surface_integrity(raw, hdr)
        return hdr, data

    def reduce_to_file(self, raw_src: RawSource, out_path: str,
                       compression: Optional[str] = None,
                       chunks: Optional[Tuple[int, int, int]] = None) -> Dict:
        """Reduce and write a ``.fil`` or (``.h5``) FBH5 product.

        Both formats STREAM slab-by-slab to disk at bounded host memory
        regardless of scan length: ``.fil`` appends raw spectra (SIGPROC
        derives nsamps from file size), ``.h5`` grows a time-resizable
        chunked dataset (:class:`blit.io.fbh5.FBH5Writer` — BL's native
        product format, src/gbtworkerfunctions.jl:141-155).  Either path
        lands in a ``.partial`` sibling renamed on success.

        ``compression`` applies to ``.h5`` output only: None | "gzip" |
        "bitshuffle" (BL's production codec, via the native encoder);
        ``chunks`` overrides the writer's clamped default HDF5 chunk shape.
        """
        if out_path.endswith((".h5", ".hdf5")):
            from blit.io.fbh5 import FBH5Writer

            self._one_product("an .h5 product")
            if self.nbits != 32:
                raise ValueError("nbits=8/16 quantized output is a SIGPROC "
                                 ".fil feature; FBH5 products are float32")
            with self._pass("reduce.to_file", out_path) as (_, opening):
                with opening:
                    raw, hdr = self._open_validated(raw_src)
                    w = FBH5Writer(
                        out_path, hdr, nifs=STOKES_NIF[self.stokes],
                        nchans=hdr["nchans"], compression=compression,
                        chunks=chunks, timeline=self.timeline,
                    )
                hdr["nsamps"] = self._pump(raw, w)
                self._surface_integrity(raw, hdr)
            return hdr
        if compression is not None:
            raise ValueError(".fil products are uncompressed; compression "
                             "applies to .h5 output")
        if chunks is not None:
            raise ValueError("chunks applies to .h5 output")
        return self.reduce_to_files(raw_src, [out_path])[0]

    def reduce_to_files(self, raw_src: RawSource,
                        out_paths: Sequence[str]) -> List[Dict]:
        """Reduce ONE read of the recording to every product
        (:attr:`products`, in order) as a ``.fil`` at ``out_paths[k]`` —
        rawspec's ``-f 1048576,8,1024 -t 51,128,3072`` in one pass.
        Each product is what the single reduction for its ``(nfft,
        nint)`` defines over the same recording from sample 0: its own
        filter state, rows, dropped tail and ``tsamp``.  Returns their
        headers.

        Every product streams into its own ``.partial`` sibling, renamed
        on success with a manifest sidecar of its own: SIGPROC derives
        nsamps from file size, so a crash mid-stream must not leave a
        VALID-looking truncated product at a final path (silent data
        loss for consumers that treat existence as completion).  All the
        products finish or none is there: an error in any leaves no
        final path and no ``.partial`` of any.  Resumable partial
        products are reduce_resumable's job — there the cursor sidecar
        marks incompleteness.  nbits<32 writes the narrow quantized form
        (the header's nbits follows the writer dtype)."""
        from blit.io.sigproc import FilWriter
        from blit.ops.narrow import NARROW_DTYPES

        out_paths = list(out_paths)
        if len(out_paths) != len(self.products):
            raise ValueError(f"{len(self.products)} products, "
                             f"{len(out_paths)} paths")
        if len(set(out_paths)) != len(out_paths):
            raise ValueError(f"two products at one path: {out_paths}")
        with self._pass("reduce.to_file", out_paths[0]) as (_, opening):
            with opening:
                raw, _ = self._open_validated(raw_src)
                nif = STOKES_NIF[self.stokes]
                hdrs = [self.header_for(raw, k)
                        for k in range(len(out_paths))]
                writers = []
                try:
                    for path, hdr in zip(out_paths, hdrs):
                        writers.append(FilWriter(
                            path, hdr, nif, hdr["nchans"],
                            dtype=NARROW_DTYPES[self.nbits],
                            timeline=self.timeline))
                except BaseException:
                    for w in writers:
                        w.abort()
                    raise
            for hdr, nsamps in zip(hdrs, self._pump(raw, writers)):
                hdr["nsamps"] = nsamps
            for hdr in hdrs:
                self._surface_integrity(raw, hdr)
        return hdrs

    def reduce_resumable(self, raw_src: RawSource, out_path: str,
                         compression: Optional[str] = None,
                         chunks: Optional[Tuple[int, int, int]] = None) -> Dict:
        """Reduce to a ``.fil`` or ``.h5`` (FBH5) product with
        crash-resumable streaming.

        A :class:`ReductionCursor` sidecar records frames durably written
        after every slab; re-running after an interruption truncates any
        un-checkpointed tail and continues from the last completed chunk
        (block-boundary restart, SURVEY.md §5 "Checkpoint / resume").  The
        finished product's decoded payload is identical to a non-resumed
        run; the sidecar is removed on completion.  Multi-file scan
        sequences resume the same way — the cursor records every member
        file's identity, and the skip-frames restart lands wherever in the
        sequence the frames do (including across a file boundary).

        ``.fil`` products truncate by byte length
        (:class:`ResumableFilWriter`); ``.h5`` products ``resize``-truncate
        the time-resizable dataset
        (:class:`blit.io.fbh5.ResumableFBH5Writer` — BL's native product
        format, src/gbtworkerfunctions.jl:141-155; under bitshuffle the
        cursor claims only full-chunk-flushed rows, so a resume re-reduces
        at most one chunk row).  ``compression``/``chunks`` apply to
        ``.h5`` output only and are part of the resume identity.
        """
        self._one_product("reduce_resumable()")
        is_h5 = out_path.endswith((".h5", ".hdf5"))
        if is_h5 and self.nbits != 32:
            raise ValueError("nbits=8/16 quantized output is a SIGPROC "
                             ".fil feature; FBH5 products are float32")
        if not is_h5 and compression is not None:
            raise ValueError(".fil products are uncompressed; compression "
                             "applies to .h5 output")
        if not is_h5 and chunks is not None:
            raise ValueError("chunks applies to .h5 output")
        with self._pass("reduce.resumable", out_path) as (root, opening):
            with opening:
                raw, hdr, w, start_rows, resuming = self._resume_point(
                    raw_src, out_path, compression, chunks)
            if root is not None:
                root.attrs["resumed"] = bool(resuming)
            # _pump aborts the writer on error — file + cursor stay as
            # the resume point (the writer's own crash contract); under
            # the async plane the cursor may simply sit a few
            # queued-but-unwritten slabs earlier, which the skip-frames
            # replay re-reduces identically.
            hdr["nsamps"] = self._pump(raw, w,
                                       skip_frames=start_rows * self.nint)
            self._surface_integrity(raw, hdr)
        return hdr


    def _resume_point(self, raw_src: RawSource, out_path: str,
                      compression: Optional[str],
                      chunks: Optional[Tuple[int, int, int]]):
        """:meth:`reduce_resumable`'s near end: the source, the cursor a
        re-run may continue from, and the writer opened on it ->
        ``(raw, header, writer, rows to start at, resuming)``."""
        is_h5 = out_path.endswith((".h5", ".hdf5"))
        raw, hdr = self._open_validated(raw_src)
        # Cursor identity: the member path list (single files keep the plain
        # string so pre-existing sidecars stay valid).
        paths = getattr(raw, "paths", None) or raw.path
        nif = STOKES_NIF[self.stokes]
        comp_id = compression or "none"

        chunks_id = list(chunks) if chunks is not None else None
        cur = ReductionCursor.load(out_path)
        resuming = (
            cur is not None
            and cur.matches(self, paths)
            and cur.compression == comp_id
            and cur.chunks == chunks_id
            and os.path.exists(out_path)
        )
        if resuming and is_h5:
            # Crash robustness: libhdf5 metadata is not crash-atomic, so a
            # SIGKILL can leave an unopenable/unreadable target while the
            # cursor still parses — treat that like an identity mismatch
            # (fresh start), never a raise (ADVICE r5 medium).
            from blit.io.fbh5 import resume_target_ok

            if not resume_target_ok(
                out_path, nif, hdr["nchans"], cur.frames_done // self.nint
            ):
                log.warning(
                    "resume target %s is not readable as the claimed HDF5 "
                    "product (crash-corrupted metadata?); discarding %d "
                    "claimed frames and starting fresh",
                    out_path, cur.frames_done,
                )
                resuming = False
        if resuming and not is_h5:
            # The flat-format twin (ISSUE 12 satellite): a cursor claiming
            # bytes the file no longer holds must restart fresh — the
            # writer's truncate-to-claim would otherwise EXTEND the short
            # file with a NUL hole and finish an unreadable product.
            from blit.ops.narrow import NARROW_DTYPES

            if not resume_fil_ok(
                out_path, nif, hdr["nchans"], cur.frames_done // self.nint,
                dtype=NARROW_DTYPES[self.nbits],
            ):
                log.warning(
                    "resume target %s is shorter than (or unreadable as) "
                    "the cursor's claimed %d frames (crash-corrupted?); "
                    "starting fresh", out_path, cur.frames_done,
                )
                resuming = False
        if resuming:
            log.info("resuming %s at frame %d", out_path, cur.frames_done)
        else:
            size, mtime_ns = ReductionCursor.stat_raw(paths)
            cur = ReductionCursor(
                paths, self.nfft, self.ntap, self.nint, self.stokes, 0,
                window=self.window, raw_size=size, raw_mtime_ns=mtime_ns,
                fqav_by=self.fqav_by, dtype=self.dtype,
                compression=comp_id, chunks=chunks_id,
                nbits=self.nbits, quant_scale=self.quant_scale,
                quant_offset=self.quant_offset,
            )
        start_rows = cur.frames_done // self.nint if resuming else 0
        if is_h5:
            from blit.io.fbh5 import ResumableFBH5Writer

            w = ResumableFBH5Writer(
                out_path, hdr, nif, hdr["nchans"], start_rows, self.nint,
                cur, compression=compression, chunks=chunks,
                timeline=self.timeline,
            )
        else:
            from blit.ops.narrow import NARROW_DTYPES

            w = ResumableFilWriter(
                out_path, hdr, nif, hdr["nchans"], start_rows, self.nint,
                cur, dtype=NARROW_DTYPES[self.nbits],
                timeline=self.timeline,
            )
        return raw, hdr, w, start_rows, resuming

def resume_fil_ok(path: str, nif: int, nchans: int, rows: int,
                  dtype=np.float32) -> bool:
    """May a ``.fil`` resume target honor a cursor claiming ``rows``
    spectra?  The file must parse a SIGPROC header AND hold at least the
    claimed bytes: :class:`ResumableFilWriter` truncates *down* to the
    claim, and POSIX ``truncate`` on a SHORTER file would silently
    EXTEND it with a NUL hole — a crash-corrupted (or replaced) product
    must restart fresh instead (the ``resume_target_ok`` discipline of
    blit/io/fbh5.py, applied to the flat format; ISSUE 12 satellite).

    When a manifest sidecar exists the length check is UPGRADED to
    content verification (ISSUE 13): the claimed region's digest must
    match the bytes on disk — a torn write *inside* the claim, a
    tampered sidecar, or a replaced product all fail closed (fresh
    start) where the byte-length probe alone would have resumed onto
    corrupt spectra.  No manifest keeps the length-only behavior
    (legacy products stay resumable)."""
    from blit.io.sigproc import read_fil_header

    try:
        _, off = read_fil_header(path)
        size = os.path.getsize(path)
    except (OSError, ValueError):
        return False
    row_bytes = nif * nchans * np.dtype(dtype).itemsize
    if size < off + rows * row_bytes:
        return False
    from blit import integrity

    return integrity.verify_claim(path, rows, fmt="fil",
                                  row_bytes=row_bytes) is not False


class ResumableFilWriter:
    """Append-directly ``.fil`` writer whose incompleteness marker is a
    :class:`ReductionCursor` sidecar instead of a ``.partial`` rename:
    slabs are fsync'd BEFORE the cursor claims them, so a crash leaves a
    resumable prefix, never a cursor ahead of the bytes.  Backs BOTH
    resumable streaming paths — :meth:`RawReducer.reduce_resumable` and
    the mesh scan writer (blit/parallel/scan.py) — so the durability
    protocol lives in one place (the FilWriter rule, blit/io/sigproc.py).

    ``start_rows`` > 0 resumes: the product is truncated to that many
    spectra (dropping any un-checkpointed tail) and the cursor clamped
    to match; 0 (or a missing file) starts fresh.
    """

    def __init__(self, path: str, header: Dict, nif: int, nchans: int,
                 start_rows: int, nint: int, cursor: "ReductionCursor",
                 dtype=np.float32, timeline=None):
        from blit import integrity
        from blit.io.sigproc import read_fil_header, write_fil

        self.path = path
        self._nint = nint
        self._nif = nif
        self._nchans = nchans
        self.dtype = np.dtype(dtype)
        self.cursor = cursor
        row_bytes = nif * nchans * self.dtype.itemsize
        self._mf = integrity.ManifestWriter(
            path, "fil", row_bytes=row_bytes,
            writer=type(self).__name__, timeline=timeline)
        if start_rows > 0 and os.path.exists(path):
            # The cursor may record more frames than the agreed restart
            # point (the mesh writer restarts at a pod-wide minimum): clamp
            # it DOWN with the truncation, or a crash before the first new
            # append would leave it claiming bytes the truncate dropped.
            _, off = read_fil_header(path)
            with open(path, "r+b") as f:
                f.truncate(off + start_rows * row_bytes)
            cursor.frames_done = start_rows * nint
            cursor.save(path)
            # Rebuild the manifest's running CRC over the truncated file
            # (one pass; callers already content-verified the claim via
            # resume_fil_ok) so every later claim digests correctly.
            self._mf.data_offset = off
            self._mf.fold_path(path)
            self._mf.claim(start_rows)
            self._mf.save()
        else:
            start_rows = 0
            write_fil(path, header, np.zeros((0, nif, nchans), self.dtype))
            cursor.frames_done = 0
            cursor.save(path)
            self._mf.data_offset = os.path.getsize(path)
            self._mf.fold_path(path)
            self._mf.save()
        self._f = open(path, "ab")
        self.nsamps = start_rows

    def append(self, slab: np.ndarray) -> None:
        from blit.io.sigproc import validate_slab

        slab = validate_slab(slab, self._nif, self._nchans, self.dtype)
        slab.tofile(self._f)
        # Durable data BEFORE the cursor claims it (power-loss ordering).
        self._f.flush()
        os.fsync(self._f.fileno())
        self.nsamps += slab.shape[0]
        # Manifest BETWEEN the data fsync and the cursor claim
        # (ISSUE 13): the ledger then always holds an entry for every
        # row count a cursor can legally claim — a crash between the
        # two leaves the manifest AHEAD of the cursor (a harmless extra
        # entry), never behind (an unverifiable gap a resume would
        # truncate into).
        self._mf.fold(slab)
        self._mf.claim(self.nsamps)
        self._mf.save()
        self.cursor.frames_done = self.nsamps * self._nint
        self.cursor.save(self.path)

    def close(self) -> None:
        """Finish: the sidecar's absence is the completeness marker.
        The cursor names its own sidecar path — StreamCursor rides this
        writer with a ``.stream-cursor`` sibling (blit/stream/cursor.py).
        The manifest flips to complete (whole-file digest) and STAYS —
        it is the finished product's verification surface (blit fsck)."""
        self._f.close()
        self._mf.publish()
        sidecar = self.cursor.path_for(self.path)
        if os.path.exists(sidecar):
            os.unlink(sidecar)

    def abort(self) -> None:
        # The file + cursor ARE the resume point: keep both.
        self._f.close()


# rawspec-equivalent product presets (SURVEY.md §0: products 0000/0001/0002).
# BL's published reduction is ``rawspec -f 1048576,8,1024 -t 51,128,3072``
# (Lebofsky+ 2019); "0000" and "0002" still say nint 1 and 2048 because the
# benchmark's ``bank.hires`` cell runs ``--product 0000`` and its traffic
# file states nint 1: a benchmark PR names ``--nfft 1048576 --nint 1`` there
# first, then these become (1 << 20, 51) and (1 << 10, 3072).  Until then
# the published products are ``--nfft 1048576 --nint 51`` (the integration
# is carried across dispatches) and ``--nfft 1024 --nint 3072``.
PRODUCT_PRESETS = {
    # name: (nfft, nint)
    "0000": (1 << 20, 1),  # hi-res: ~3 Hz channels
    "0001": (1 << 3, 128),  # mid-res time product
    "0002": (1 << 10, 1 << 11),  # low-res survey product
}


def reducer_for_product(product: str, **kw) -> RawReducer:
    """A :class:`RawReducer` configured like rawspec's standard product
    ``product`` ("0000" | "0001" | "0002")."""
    nfft, nint = PRODUCT_PRESETS[product]
    return RawReducer(nfft=nfft, nint=nint, **kw)


@dataclass
class ReductionCursor:
    """Restart state for a streaming reduction, persisted as a JSON sidecar
    next to the output product (SURVEY.md §5 "Checkpoint / resume":
    stream-job cursors restarting at block boundaries).

    ``frames_done`` counts raw PFB frames fully reduced *and written* — a
    multiple of ``nint`` by construction, so resumption never re-splits an
    integration window.

    Identity guards: the full reduction config *including the PFB window*
    must match, and the RAW input must be the same bytes it was
    (size + mtime_ns recorded at cursor creation) — otherwise a resume would
    silently splice spectra from different configs/inputs into one product.
    For multi-file scan sequences ``raw_path``/``raw_size``/``raw_mtime_ns``
    hold per-member lists: every member of the sequence must be unchanged.
    """

    raw_path: Union[str, List[str]]
    nfft: int
    ntap: int
    nint: int
    stokes: str
    frames_done: int = 0
    window: str = "hamming"
    raw_size: Union[int, List[int]] = -1
    raw_mtime_ns: Union[int, List[int]] = -1
    fqav_by: int = 1
    dtype: str = "float32"
    # DC-despike width of the product (mesh scan writer; -1 = the path has
    # no despike, RawReducer's case).  Output-affecting, so it must be part
    # of resume identity: splicing despiked and non-despiked spectra into
    # one product would corrupt it silently.
    despike_nfpc: int = -1
    # Product compression ("none" | "gzip" | "bitshuffle") — .h5 resume
    # identity: a dataset's filter pipeline is fixed at creation, so a
    # writer expecting a different codec must start fresh, not corrupt.
    # Compared at the call sites (not in matches(), whose `red` argument
    # has no compression attribute).
    compression: str = "none"
    # Mesh .h5-bitshuffle resume identity: the writer's chunk rows derive
    # from the window granularity, so a changed --window-frames must start
    # fresh rather than hit the writer's chunk-mismatch refusal.  -1 =
    # not applicable (.fil products and the single-chip path tolerate
    # window changes).
    window_rows: int = -1
    # Explicit .h5 chunk shape (reduce_resumable's chunks= knob) — resume
    # identity for the same reason as compression: a dataset's chunk grid
    # is fixed at creation, so a resume under different chunks must start
    # fresh, not die on the writer's chunk-mismatch refusal.  None = the
    # writer's clamped default (deterministic for a given product shape).
    chunks: Optional[List[int]] = None
    # Quantized-product identity (ISSUE 8): nbits and the affine quantize
    # rule change every product byte, so a resume under different
    # quantization must start fresh — splicing 8-bit and float spectra
    # into one file would corrupt it silently.  Defaults keep pre-existing
    # sidecars loadable (they claim the f32 identity they were).
    nbits: int = 32
    quant_scale: float = 1.0
    quant_offset: float = 0.0

    @staticmethod
    def stat_raw(raw_path: Union[str, Sequence[str]]) -> Tuple:
        """(size, mtime_ns) of a single path, or parallel lists for a
        sequence of paths."""
        if isinstance(raw_path, str):
            st = os.stat(raw_path)
            return st.st_size, st.st_mtime_ns
        stats = [os.stat(p) for p in raw_path]
        return [s.st_size for s in stats], [s.st_mtime_ns for s in stats]

    @staticmethod
    def path_for(out_path: str) -> str:
        return out_path + ".cursor"

    def save(self, out_path: str) -> None:
        import json

        tmp = self.path_for(out_path) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.__dict__, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path_for(out_path))

    @classmethod
    def load(cls, out_path: str) -> Optional["ReductionCursor"]:
        import json

        try:
            with open(cls.path_for(out_path)) as f:
                return cls(**json.load(f))
        except (OSError, ValueError, TypeError):
            return None

    @staticmethod
    def normalized_members(
        raw_path: Union[str, Sequence[str]],
        raw_size: Union[int, Sequence[int]],
        raw_mtime_ns: Union[int, Sequence[int]],
    ) -> List[Tuple[str, int, int]]:
        """The raw-input identity as an order-insensitive list of
        ``(path, size, mtime_ns)`` member triples, sorted by path.

        A multi-file scan sequence is the SAME recording whatever order a
        glob happened to list its members in — ``open_raw`` sorts members
        before reading, so the reduced bytes are order-independent and the
        resume/cache identity must be too (ISSUE 3 satellite: cache keys
        must be stable across glob orderings)."""

        def norm(x):
            return list(x) if isinstance(x, (list, tuple)) else [x]

        return sorted(zip(norm(raw_path), norm(raw_size), norm(raw_mtime_ns)))

    def matches(self, red: "RawReducer", raw_path: Union[str, Sequence[str]]) -> bool:
        try:
            size, mtime_ns = self.stat_raw(raw_path)
        except OSError:
            return False

        return (
            self.normalized_members(self.raw_path, self.raw_size,
                                    self.raw_mtime_ns)
            == self.normalized_members(raw_path, size, mtime_ns)
            and self.nfft == red.nfft
            and self.ntap == red.ntap
            and self.nint == red.nint
            and self.stokes == red.stokes
            and self.window == red.window
            and self.fqav_by == red.fqav_by
            and self.dtype == red.dtype
            and self.despike_nfpc == getattr(red, "despike_nfpc", -1)
            and self.nbits == getattr(red, "nbits", 32)
            and self.quant_scale == getattr(red, "quant_scale", 1.0)
            and self.quant_offset == getattr(red, "quant_offset", 0.0)
        )
