"""Asynchronous output plane: overlapped device→host readback and
write-behind product sinks.

The ingest side of the framework has been pipelined since PR 1 (the
:class:`blit.pipeline.BufferRotation` prefetch core), but the OUTPUT side
stayed serialized: every streaming driver synced a chunk's product with
``np.asarray(jax.block_until_ready(out))`` on the consumer thread and then
wrote it to disk before dispatching the next chunk — device compute,
device→host readback and FBH5/SIGPROC appends ran one-at-a-time, so the
end-to-end rate was the sum of the three legs.  The paper's premise is
per-node reduction *so only small products cross the slow link*; the
framework must therefore hide that link behind compute the same way the
ingest rotation hides file reads.

This module is the result-side mirror of ``BufferRotation``:

- :class:`OutputRotation` keeps up to ``depth`` device outputs in flight,
  reads them back on a dedicated thread (``block_until_ready`` +
  host fetch) into a bounded ring of reusable host slabs, and hands
  completed :class:`OutputSlab` handles back to the consumer in stream
  order.  Back-pressure is two-sided: :meth:`OutputRotation.put` blocks
  while ``depth`` outputs are pending (bounding device HBM), and the
  readback thread blocks when every ring slab is held downstream
  (bounding host RSS at ``depth + 1`` slabs).
- :class:`AsyncSink` is a bounded-queue write-behind writer: product
  appends run on a background thread against any slab writer
  (``FBH5Writer`` / ``FilWriter`` / the resumable twins), with
  :meth:`AsyncSink.flush` barriers for resume checkpoints, writer-thread
  failures re-raised cleanly on the consumer side, and ``sink.write`` /
  ``sink.flush`` fault-injection points (blit/faults.py).
- :class:`FoldInFlight` is the shared lag-``depth`` bookkeeping for the
  on-device fold drivers (``correlate_stream``, ``beamform_accumulate``):
  a window slot frees once the fold that consumed it has synchronized,
  and :meth:`FoldInFlight.drain` releases the tail *without* a second
  sync when the caller's terminal sync already proved completion.

Both threaded stages reuse ``BufferRotation``'s liveness discipline: a
producer-progress stall watchdog (back-pressure waits count as progress),
and a bounded close-join that abandons a wedged daemon thread with a
warning instead of converting teardown into the hang it detected.

Stage accounting (:class:`blit.observability.Timeline`): the readback
thread times ``device`` (the lag-synchronized wait on a dispatch; carries
the input bytes when the caller supplies them, else byte-free) and
``readback`` (host fetch, product bytes); the sink thread times ``write``
(bytes appended).  ``Timeline.overlap_efficiency`` turns those plus the
driver's wall stage into the overlap gauge operators read when diagnosing
a slow link (docs/WORKFLOWS.md).

Outputs are byte-identical to the synchronous path: the readback thread
processes dispatches strictly in put order, ring slabs receive exact
copies of the fetched products, and the sink appends in queue order —
no float operation moves, only the waiting does.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from collections import deque
from typing import Callable, Iterator, List, Optional

import numpy as np

from blit import faults, observability
from blit.device import host_link
from blit.observability import Timeline

log = logging.getLogger("blit.outplane")

_EOF = object()

# The output plane's per-chunk histograms.
INGEST_HISTS = ("out.chunk_latency_s", "out.readback_lag_s", "out.write_s")


def readback_extra_slots(out_depth: int, prefetch_depth: int) -> int:
    """Chunk-rotation widening required by the readback plane: a
    readback deeper than the producer's prefetch pins more un-synced
    chunk buffers than ``prefetch_depth`` provides, so the rotation must
    grow by the difference plus one read-ahead slot — otherwise the
    producer starves (and the all-slots-held starvation heuristic stops
    being a true bug signal).  Shared by every plane that pairs a chunk
    :class:`~blit.pipeline.BufferRotation` with an
    :class:`OutputRotation` (reduce and search) so the invariant cannot
    drift between them."""
    return 1 + max(0, max(2, out_depth) - max(2, prefetch_depth))


class OutputSlab:
    """A completed readback handed to the consumer: ``data`` is the host
    product (an exact copy in a ring slab when the rotation reuses slabs,
    else the fetched array itself).  The consumer MUST :meth:`release`
    every slab once nothing still reads ``data`` — in ring mode the slab
    storage is recycled for a later chunk after that (idempotent)."""

    __slots__ = ("data", "payload", "_release")

    def __init__(self, data: np.ndarray, payload, release) -> None:
        self.data = data
        self.payload = payload
        self._release = release

    def release(self) -> None:
        if self._release is not None:
            rel, self._release = self._release, None
            rel()


class OutputRotation:
    """The prefetch rotation of the result side: a dedicated readback
    thread turns in-flight device outputs into host slabs while the
    caller keeps dispatching (class docstring; the
    :class:`blit.pipeline.BufferRotation` contract mirrored).

    Contract:

    - :meth:`put` hands an async-dispatched device array to the readback
      thread and returns any slabs completed so far (stream order).  It
      blocks while ``depth`` outputs are already pending (one, for a
      product whose fetch takes the whole host link,
      :meth:`blit.device.HostLink.fetch`) — that wait is
      the device-memory bound AND where compute/readback overlap happens
      (the caller's *next* dispatch is already queued device-side).
    - ``on_consumed`` fires on the readback thread right after the
      output synchronizes — the moment the dispatch's *inputs* are free
      (release an ingest chunk / feed window there).
    - :meth:`drain` ends the stream: yields the remaining slabs in
      order, then returns.  Readback-thread exceptions re-raise in the
      consumer from :meth:`put`/:meth:`drain`.
    - ``reuse=True`` decouples emitted slabs from jax-owned memory:
      fetches that alias the device buffer (CPU backends) copy into a
      bounded recycling ring (``depth + 1`` resident); fetches that
      already allocated fresh host memory (TPU/GPU D2H) are emitted
      as-is, with no second copy.  ``reuse=False`` emits the fetched
      arrays directly (callers that hand slabs to arbitrary consumers —
      the public ``RawReducer.stream`` — must not recycle under them).
    """

    def __init__(self, depth: int = 1, *, timeline: Optional[Timeline] = None,
                 reuse: bool = False, name: str = "blit-readback",
                 stall_timeout_s: Optional[float] = None):
        self.depth = max(1, depth)
        self.reuse = reuse
        self.stall_timeout_s = stall_timeout_s
        self._tl = timeline if timeline is not None else Timeline()
        self._tl.declare("wait.out_slot", "wait.out_drain", "wait.slab")
        self._in: "queue.Queue" = queue.Queue()
        self._cv = threading.Condition()
        self._pending = 0        # put but not yet emitted (readback bound)
        self._done: deque = deque()  # completed slabs, stream order
        self._exc: Optional[BaseException] = None
        self._eof = False
        self._stop = threading.Event()
        self._free: List[np.ndarray] = []  # released ring slabs (reuse)
        self._nslabs = 0
        # Ring slabs resident at most: one more than the puts in flight,
        # times the outputs one put brings (:meth:`put`).
        self._slab_limit = self.depth + 1
        self._wd = observability.StallWatchdog(
            stall_timeout_s, name,
            what="a wedged device fetch would otherwise hang the stream",
        )
        # Captured at construction (the consumer's thread): the readback
        # thread's lifetime span parents onto whatever driver span built
        # the rotation, keeping the output plane causally linked in a
        # trace (ISSUE 5 tentpole #1).
        self._span_ctx = observability.tracer().context()
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()

    # -- readback thread ---------------------------------------------------
    def _run(self) -> None:
        tr = observability.tracer()
        with tr.activate(self._span_ctx), tr.span("outplane.readback"):
            self._run_inner()

    def _run_inner(self) -> None:
        import jax

        try:
            while True:
                try:
                    item = self._in.get(timeout=0.2)
                except queue.Empty:
                    if self._stop.is_set():
                        return
                    continue
                if item is _EOF:
                    with self._cv:
                        self._eof = True
                        self._cv.notify_all()
                    return
                out, nbytes, outs, on_consumed, t_enq = item
                t_got = time.perf_counter()
                # Queue-side lag distribution (ISSUE 5 tentpole #2): how
                # long dispatches wait before the readback thread reaches
                # them — the leading indicator of a saturating D2H link.
                self._tl.observe("out.readback_lag_s", t_got - t_enq)
                self._wd.beat()
                # The wait on the dispatch IS the device stage: overlapped
                # with the consumer thread's next dispatch and the ingest
                # producer's next read.
                if nbytes is None:
                    with self._tl.stage("device", byte_free=True):
                        jax.block_until_ready(out)
                else:
                    with self._tl.stage("device", nbytes=nbytes):
                        jax.block_until_ready(out)
                if on_consumed is not None:
                    # Output ready ⇒ inputs consumed: ingest slots refill.
                    on_consumed()
                self._wd.beat()
                # Drop the dispatch's own reference NOW: what is fetched
                # below frees as soon as its host copy exists, not when
                # the product hits disk.
                del out, item
                # Nothing to fetch (the sharded plane's non-writer pod
                # processes, ISSUE 9; a dispatch that closed no row): the
                # dispatch had to be waited out — it pins feed slots and
                # orders the stream — but nothing reads its bytes
                # host-side, so no slab is emitted.
                slabs = []
                while outs:
                    arr, payload = outs.pop(0)
                    slab = self._fetch(arr, payload)
                    del arr
                    if slab is None:
                        return  # closed while waiting for a slab
                    slabs.append(slab)
                self._wd.beat()
                # Per-chunk service latency (sync wait + host fetch) —
                # the distribution behind the aggregate device/readback
                # stage seconds.
                if slabs:
                    self._tl.observe("out.chunk_latency_s",
                                     time.perf_counter() - t_got)
                with self._cv:
                    self._pending -= 1
                    self._done.extend(slabs)
                    self._cv.notify_all()
        except BaseException as e:  # noqa: BLE001 — forwarded to the consumer
            with self._cv:
                self._exc = e
                self._cv.notify_all()

    def _fetch(self, out, payload) -> Optional[OutputSlab]:
        """One device array to a host slab (``None``: closed meanwhile).
        The fetch draws on the process's link budget
        (blit.device.HostLink): it waits for what is going up."""
        recycled = False
        with host_link().fetch(getattr(out, "nbytes", 0), self._tl), \
                self._tl.stage("readback") as sp:
            if sp is not None and payload is not None:
                sp.attrs["product"] = payload
            host = np.asarray(out)
            if self.reuse and (host.base is not None
                               or not host.flags.owndata):
                # The fetch was a zero-copy VIEW aliasing the jax
                # buffer (CPU backends): copy into a ring slab so
                # the buffer frees now and the slab recycles.  On
                # backends where the fetch itself allocated fresh
                # host memory (TPU/GPU D2H), that array IS the
                # slab — a second product-sized memcpy on this
                # (critical, slow-link) thread would buy nothing,
                # and the ring could never avoid the allocation
                # np.asarray already made.
                slab = self._take_slab(host.shape, host.dtype)
                if slab is None:
                    return None
                np.copyto(slab, host)
                host = slab
                recycled = True
        self._tl.stages["readback"].bytes += host.nbytes
        release = (
            (lambda s=host: self._release_slab(s))
            if recycled else None
        )
        return OutputSlab(host, payload, release)

    def _take_slab(self, shape, dtype) -> Optional[np.ndarray]:
        """A free ring slab matching ``(shape, dtype)`` — allocating up to
        ``depth + 1`` resident slabs (per output of a put), retiring a mismatched free slab when
        at the limit (the final flush chunk is smaller than steady state),
        else waiting for the consumer to release one.  That wait is
        back-pressure from the sink, not a readback stall — the beat keeps
        ticking.  Returns None if closed while waiting."""
        alloc_shape = None
        evicted = None
        with self._tl.wait("wait.slab") as w, self._cv:
            while True:
                for i, s in enumerate(self._free):
                    if s.shape == shape and s.dtype == dtype:
                        return self._free.pop(i)
                if self._nslabs < self._slab_limit:
                    self._nslabs += 1
                    alloc_shape = shape
                    break
                if self._free:  # at the limit, none match: replace one
                    evicted = self._free.pop()
                    alloc_shape = shape
                    break
                if self._stop.is_set():
                    return None
                self._wd.beat()
                w.block()
                self._cv.wait(timeout=0.2)
        # Aligned, pool-recycled staging (blit/hostmem.py): a previous
        # stream's already-faulted slab when one matches.
        from blit import hostmem

        pool = hostmem.slab_pool()
        if evicted is not None:
            # The replaced steady-state slab retires to the staging pool
            # (the close() rule) — not to the GC.
            pool.give(evicted, self._tl)
        return pool.take(alloc_shape, dtype, self._tl)

    def _release_slab(self, slab: np.ndarray) -> None:
        with self._cv:
            if not self._stop.is_set():
                self._free.append(slab)
                self._cv.notify_all()
                return
        # Released after close() swept the ring (e.g. the AsyncSink
        # draining its write-behind tail): retire straight to the staging
        # pool — appending to a closed rotation's _free just feeds the GC
        # and makes the next stream re-pay allocation + first-touch
        # faults for its tail slabs.
        from blit import hostmem

        hostmem.slab_pool().give(slab, self._tl)

    # -- consumer side -----------------------------------------------------
    def _poll(self) -> float:
        return self._wd.poll_s(0.2)

    def _check(self) -> None:
        """Raise under ``self._cv``: forwarded readback error or stall.
        The error re-raises on EVERY call — a consumer that swallowed one
        raise must not see the rotation as healthy afterwards."""
        if self._exc is not None:
            raise self._exc
        if self._pending > 0:
            self._wd.check("readback stalled",
                           active=self._thread.is_alive())

    def put(self, out, *, nbytes: Optional[int] = None,
            on_consumed: Optional[Callable[[], None]] = None,
            outs: Optional[list] = None) -> List[OutputSlab]:
        """Enqueue an async-dispatched device array for readback; return
        the slabs completed so far (possibly empty), blocking while
        ``depth`` outputs are pending.  ``nbytes`` (the dispatch's input
        bytes) lands on the ``device`` stage; omitted ⇒ byte-free.
        ``out`` is what is waited on (any tree of arrays) and ``outs`` the
        ``(array, payload)`` pairs to fetch, each its own slab, in that
        order: ``out`` itself, with no payload, by default; several where
        a dispatch made several products; none (``[]``) to sync the
        dispatch (and fire ``on_consumed``) without a device→host fetch —
        no slab is ever emitted for it."""
        if outs is None:
            outs = [(out, None)]
        # A product whose fetch takes the whole host link is waited out
        # here: its slab is handed on at once, and the next chunk's
        # voltages do not get on the link in front of it.
        depth = 1 if any(host_link().fetch_takes_all(
            getattr(a, "nbytes", 0)) for a, _ in outs) else self.depth
        with self._cv:
            self._check()
            self._pending += 1
            self._slab_limit = max(self._slab_limit,
                                   (self.depth + 1) * len(outs))
        self._in.put((out, nbytes, list(outs), on_consumed,
                      time.perf_counter()))
        ready: List[OutputSlab] = []
        with self._tl.wait("wait.out_slot") as w, self._cv:
            while True:
                while self._done:
                    ready.append(self._done.popleft())
                self._check()
                if self._pending < depth:
                    return ready
                w.block()
                self._cv.wait(timeout=self._poll())

    def drain(self) -> Iterator[OutputSlab]:
        """End the stream: yield every remaining slab in order."""
        self._in.put(_EOF)
        while True:
            batch: List[OutputSlab] = []
            finished = False
            with self._tl.wait("wait.out_drain") as w, self._cv:
                while True:
                    while self._done:
                        batch.append(self._done.popleft())
                    self._check()
                    if self._eof:
                        finished = True
                        break
                    if batch:
                        break
                    w.block()
                    self._cv.wait(timeout=self._poll())
            # Yield OUTSIDE the lock: consumers release slabs (and the
            # sink thread releases ring slabs) re-entering _cv.
            for slab in batch:
                yield slab
            if finished:
                return

    def close(self, join_timeout_s: float = 10.0) -> None:
        """Stop the readback thread and join it (idempotent).  Bounded:
        a thread wedged inside a device wait is abandoned with a warning
        (the BufferRotation close rule) rather than hanging teardown."""
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        self._thread.join(timeout=join_timeout_s)
        if self._thread.is_alive():
            log.warning(
                "%s: readback thread did not exit within %.1fs of close; "
                "abandoning the daemon thread", self._thread.name,
                join_timeout_s,
            )
            return
        # Joined cleanly: retire the free ring slabs to the process
        # staging pool (blit/hostmem.py) so the next stream's readback
        # ring reuses already-faulted host memory.  Slabs still held by
        # consumers stay theirs; _release_slab retires them to the pool
        # too once they come back (the sink's write-behind tail).
        from blit import hostmem

        pool = hostmem.slab_pool()
        with self._cv:
            free, self._free = self._free, []
        for s in free:
            pool.give(s, self._tl)


class _FlushBarrier:
    __slots__ = ("event",)

    def __init__(self) -> None:
        self.event = threading.Event()


_SINK_STOP = object()


class AsyncSink:
    """Bounded-queue write-behind product writer.

    Wraps any slab writer with the ``append(slab)`` / ``close()`` /
    ``abort()`` contract (``FBH5Writer``, ``FilWriter``,
    ``ResumableFBH5Writer``, ``ResumableFilWriter``): :meth:`append`
    enqueues and returns — the disk write happens on a background thread
    while the caller dispatches the next chunk.  The queue is bounded at
    ``depth`` slabs, so a slow disk back-pressures the whole plane
    instead of buffering the product in RAM.

    Durability semantics are the WRAPPED writer's, unchanged: the
    resumable writers fsync data before their cursor claims it *inside*
    ``append``, which now runs on the sink thread — a crash still leaves
    the cursor at-or-behind the durable bytes, so ``resume_target_ok``
    and the skip-frames replay behave exactly as on the synchronous path
    (the cursor may simply sit a few queued-but-unwritten slabs earlier).
    :meth:`flush` is the resume-checkpoint barrier: when it returns,
    every prior append has been applied and the writer's own flush hook
    (when it has one) has run.

    Failure contract: a writer-thread exception is held and re-raised on
    the CONSUMER side at the next :meth:`append`/:meth:`flush`/
    :meth:`close`; queued slabs after the failure are skipped but still
    released (the readback ring must not leak), the thread keeps
    draining to its stop sentinel so teardown always joins — no orphaned
    daemon — and :meth:`abort` leaves the wrapped writer's crash
    artifacts exactly as the synchronous path would (``.partial``
    dropped; resumable file + cursor kept).  ``sink.write`` and
    ``sink.flush`` are fault-injection points (blit/faults.py), keyed by
    the writer's path.
    """

    def __init__(self, writer, *, depth: int = 2,
                 timeline: Optional[Timeline] = None,
                 name: str = "blit-sink", key=None,
                 stall_timeout_s: Optional[float] = None,
                 product: Optional[str] = None):
        self._writer = writer
        # Which of a reduction's products this sink writes (an attr of
        # its `write` spans; None: the only one).
        self._product = product
        self._tl = timeline if timeline is not None else Timeline()
        self._tl.declare("wait.sink", "wait.sink_flush")
        self._key = key if key is not None else getattr(writer, "path", None)
        self.stall_timeout_s = stall_timeout_s
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._exc: Optional[BaseException] = None
        self._stopped = False
        self._stop_ev = threading.Event()
        self._wd = observability.StallWatchdog(
            stall_timeout_s, name,
            what="a wedged disk append would otherwise hang the plane",
        )
        self._span_ctx = observability.tracer().context()
        self._thread = threading.Thread(
            target=self._run, name=name, daemon=True
        )
        self._thread.start()

    # -- writer thread -----------------------------------------------------
    def _run(self) -> None:
        tr = observability.tracer()
        with tr.activate(self._span_ctx), tr.span(
            "outplane.sink", path=str(self._key or "")
        ):
            self._run_inner()

    def _run_inner(self) -> None:
        while True:
            try:
                item = self._q.get(timeout=0.2)
            except queue.Empty:
                # Polling get: teardown must never need to squeeze a stop
                # sentinel into a FULL queue behind a wedged writer.
                if self._stop_ev.is_set():
                    return
                continue
            if item is _SINK_STOP:
                return
            self._wd.beat()
            if isinstance(item, _FlushBarrier):
                if self._exc is None:
                    try:
                        faults.fire("sink.flush", key=self._key)
                        fl = getattr(self._writer, "flush", None)
                        if fl is not None:
                            with self._tl.stage("flush", byte_free=True):
                                fl()
                    except BaseException as e:  # noqa: BLE001 — consumer re-raises
                        self._exc = e
                # FIFO ⇒ every append before the barrier was applied (or
                # the failure is recorded); wake the waiter either way.
                item.event.set()
                continue
            slab, release = item
            if self._exc is None:
                try:
                    faults.fire("sink.write", key=self._key)
                    t0 = time.perf_counter()
                    with self._tl.stage("write", nbytes=slab.nbytes) as sp:
                        if sp is not None and self._product is not None:
                            sp.attrs["product"] = self._product
                        self._writer.append(slab)
                    # Per-append latency distribution (ISSUE 8 satellite:
                    # the bench tables report write p50/p99, not just the
                    # stage mean — a bursty disk hides behind an average).
                    self._tl.observe("out.write_s",
                                     time.perf_counter() - t0)
                except BaseException as e:  # noqa: BLE001 — consumer re-raises
                    self._exc = e
            # Release even after a failure: later slabs are skipped, but
            # the readback ring they live in must keep rotating so the
            # consumer reaches its next append() and sees the error.
            if release is not None:
                release()
            self._wd.beat()

    # -- consumer side -----------------------------------------------------
    def _check(self) -> None:
        # Re-raise on EVERY call: close() after a swallowed append error
        # must refuse to finalize, not rename a truncated product.
        if self._exc is not None:
            raise self._exc

    def _put(self, item) -> None:
        poll = self._wd.poll_s(0.2)
        with self._tl.wait("wait.sink") as w:
            while True:
                try:
                    self._q.put(item, block=w.blocking, timeout=poll)
                    return
                except queue.Full:
                    if not w.blocking:
                        w.block()
                        continue
                    self._check()
                    self._wd.check("writer stalled",
                                   active=self._thread.is_alive())

    def append(self, slab: np.ndarray,
               release: Optional[Callable[[], None]] = None) -> None:
        """Enqueue a product slab (write-behind).  ``release`` fires on
        the sink thread once the write (or post-failure skip) is done —
        hand the slab's :meth:`OutputSlab.release` here so ring slabs
        recycle only after their bytes are on disk."""
        self._check()
        self._put((slab, release))

    def flush(self) -> None:
        """Barrier: every append enqueued before this call has been
        applied by the wrapped writer when it returns (re-raising a
        writer-thread failure instead).  The resume-checkpoint hook —
        crash semantics stay those of the wrapped writer."""
        self._check()
        barrier = _FlushBarrier()
        self._put(barrier)
        poll = self._wd.poll_s(0.5)
        with self._tl.wait("wait.sink_flush") as w:
            while not barrier.event.is_set():
                w.block()
                if barrier.event.wait(timeout=poll):
                    break
                self._wd.check("writer stalled inside flush barrier",
                               active=self._thread.is_alive())
                if not self._thread.is_alive():
                    break  # died without recording? _check below decides
        self._check()

    def _join(self, join_timeout_s: float) -> bool:
        if not self._stopped:
            self._stopped = True
            self._stop_ev.set()
            try:
                # Prompt exit when the queue has room; the stop event
                # alone suffices otherwise (never block teardown).
                self._q.put_nowait(_SINK_STOP)
            except queue.Full:
                pass
        self._thread.join(timeout=join_timeout_s)
        if self._thread.is_alive():
            log.warning(
                "%s: writer thread did not exit within %.1fs; abandoning "
                "the daemon thread (writer left un-finalized)",
                self._thread.name, join_timeout_s,
            )
            return False
        return True

    def close(self, join_timeout_s: float = 10.0) -> None:
        """Flush, stop the thread, then finalize the wrapped writer on
        the calling thread (rename-into-place / sidecar removal happen
        exactly as on the synchronous path).  Re-raises a writer-thread
        failure BEFORE finalizing — a failed product must not be
        renamed complete."""
        self.flush()
        joined = self._join(join_timeout_s)
        self._check()
        if joined:
            self._writer.close()

    def abort(self, join_timeout_s: float = 10.0) -> None:
        """Teardown on the error path: stop the thread (queued slabs are
        dropped — exactly what a synchronous crash at this point would
        not have written) and ``abort()`` the wrapped writer.  Never
        raises; the caller is already propagating the real error."""
        joined = self._join(join_timeout_s)
        if joined:
            try:
                self._writer.abort()
            except Exception:  # noqa: BLE001 — teardown must not mask the cause
                log.exception("async sink: writer abort failed")

    @property
    def nsamps(self) -> int:
        return self._writer.nsamps


class FoldInFlight:
    """Lag-``depth`` bookkeeping for on-device fold drivers: each admitted
    window carries the device token whose readiness implies the window's
    arrays were consumed (the fold output).  :meth:`make_room` — called
    BEFORE dispatching the next fold — synchronizes and releases the
    oldest windows down to ``depth`` in flight; the order matters because
    the next fold *donates* the previous accumulator
    (``correlate_stream``), so its token must be synced before dispatch
    deletes it.  :meth:`drain` releases the tail; ``synced=True`` skips
    the redundant wait when the caller's terminal sync (the finish-psum
    fetch) already proved every fold complete — the correlator's old tail
    path synced the accumulator twice for exactly this reason."""

    def __init__(self, timeline: Optional[Timeline] = None, depth: int = 1):
        self._tl = timeline if timeline is not None else Timeline()
        self.depth = max(1, depth)
        self._pending: deque = deque()

    def make_room(self) -> None:
        import jax

        while len(self._pending) >= self.depth:
            win, token = self._pending.popleft()
            with self._tl.stage("device", byte_free=True):
                jax.block_until_ready(token)
            win.release()

    def admit(self, win, token) -> None:
        self._pending.append((win, token))

    def drain(self, synced: bool = False) -> None:
        import jax

        while self._pending:
            win, token = self._pending.popleft()
            if not synced:
                with self._tl.stage("device", byte_free=True):
                    jax.block_until_ready(token)
            win.release()
