"""Pinned, persistent host staging memory (ISSUE 8 tentpole b).

Every leg of the ingest plane stages bytes through big host buffers: the
chunk rotation's int8 voltage buffers (blit/pipeline.py), the output
plane's readback ring slabs (blit/outplane.py), and the collective
feeds' window planes.  Before this module each stream allocated its
buffers fresh — GB-sized ``np.empty`` calls whose first-touch page
faults land INSIDE the timed stream (BENCH_r05's ingest leg measured
the fault storm, not the disk) and whose pages are cold again for the
next reduction the serve layer runs.  The staging pool makes host
buffers rig-persistent:

- :func:`aligned_empty` allocates page-aligned arrays, so ``readinto``/
  pread paths hit the kernel's aligned fast path and a future pinned
  (``cudaHostRegister``-style) registration has stable addresses to pin.
- :class:`SlabPool` is a process-wide free list keyed by
  ``(shape, dtype)`` under a byte budget: ``take`` reuses an
  already-faulted buffer when one matches (O(1) dict pop), ``give``
  returns a buffer at stream teardown.  Reuse across *streams* — not
  just within one — is the point: the serve layer reduces many
  recordings of the same product shape back to back, and window ``w+1``
  of a scan stages through the slabs window ``w`` just released.

The pool is deliberately dumb: exact shape+dtype match only (a near-miss
realloc is as cheap as the old path), FIFO eviction when over budget,
and counters (``staging.reuse`` / ``staging.alloc`` / ``staging.drop``)
on the process timeline — and on the timeline of the reduction that
took or gave, so its own ``stages`` report says whether it staged
through faulted memory — so the hit rate is observable in every
telemetry report.

What it may hold follows what it can see (ISSUE 25).  A recorder-width
hi-res chunk buffer is ``(64, 8*2**20, 2, 2)`` int8 = 2.15 GB and a
rotation up to three of them (beside the stream's 0.81 GB head slab);
any constant sized for smaller streams
throws such a rotation away at every teardown, and the next reduction
first-touches it anew (on the v5e machine 0.9 GB/s of ``ingest``
against 15 GB/s into the same buffer again, PERF.md §5/§6).  So the pool keeps a
ledger of the buffers it has lent out: what they weigh together at
their peak is what one stretch of work held, and between stretches the
pool may keep that much (never less than ``_DEFAULT_BUDGET``, so small
shapes coexist as before).  A stretch ends when the last lent buffer
is back; only the newest stretch's peak counts from then on, so a
process that moves to smaller shapes lets the big slabs go, oldest
shape first.  ``BLIT_STAGING_BYTES`` (or
``SiteConfig.staging_pool_bytes``) replaces all of that with a fixed
byte cap per process, exactly as before: nothing larger than the cap is
kept, and ``0`` disables pooling entirely — every ``take`` allocates,
every ``give`` drops — the A/B lever.
"""

from __future__ import annotations

import os
import threading
import weakref
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

_ALIGN = 4096  # page size: the readinto/pread alignment contract

# What the pool may always keep when no cap is set: room for the small
# shapes of a serving process or a test suite to coexist without
# hoarding RSS.  It is a floor, not the budget: a recorder-width
# rotation weighs 3 x 2.15 GB, and the pool keeps what the last stretch
# of work held at its peak (module docstring).  Per-process.
_DEFAULT_BUDGET = 2 << 30


_COUNTERS = ("staging.reuse", "staging.alloc", "staging.drop")


def aligned_empty(shape, dtype, align: int = _ALIGN) -> np.ndarray:
    """An uninitialized C-contiguous array whose data pointer is
    ``align``-byte aligned (page-aligned by default).  NumPy's own
    allocations guarantee only 16/64-byte alignment; O_DIRECT-grade
    reads and host-memory registration both want pages."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    raw = np.empty(nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    # The slice keeps ``raw`` alive via .base — no dangling storage.
    return raw[off:off + nbytes].view(dtype).reshape(shape)


class SlabPool:
    """Process-wide staging-buffer free list (module docstring).

    Thread-safe: producers (BufferRotation fill threads), readback
    threads and consumers all take/give concurrently.  A taken buffer is
    the caller's until given back; the pool never hands one buffer to
    two callers.

    ``budget_bytes`` is the explicit byte cap (argument, else
    ``BLIT_STAGING_BYTES``, else ``SiteConfig.staging_pool_bytes``);
    ``None`` when none is set and the cap follows the working set.
    """

    def __init__(self, budget_bytes: Optional[int] = None):
        if budget_bytes is None:
            env = os.environ.get("BLIT_STAGING_BYTES")
            if env is not None:
                budget_bytes = int(env)
            else:
                from blit.config import DEFAULT

                cfg = getattr(DEFAULT, "staging_pool_bytes", None)
                budget_bytes = None if cfg is None else int(cfg)
        self.budget_bytes = budget_bytes
        self._lock = threading.Lock()
        # (shape, dtype.str) -> list of free arrays; OrderedDict gives
        # FIFO key eviction (oldest shape class dropped first).
        self._free: "OrderedDict[Tuple, List[np.ndarray]]" = OrderedDict()
        self._free_bytes = 0
        # The ledger of lent buffers, id -> array, held weakly: a buffer
        # its taker dropped on an error path leaves the ledger when it
        # is collected, so a leak cannot inflate the working set.
        self._lent: "weakref.WeakValueDictionary[int, np.ndarray]" = (
            weakref.WeakValueDictionary()
        )
        self._peak = 0  # most bytes lent at once in the running stretch
        self._settled = 0  # ... in the last stretch that ended
        self.reused = 0
        self.allocated = 0
        self.dropped = 0

    def _count(self, name: str, n: int, timeline) -> None:
        try:  # telemetry must never break staging
            from blit import observability

            observability.process_timeline().count(name, n)
            if timeline is not None:
                # All three rows, so a reduction that never allocated
                # reads ``staging.alloc`` 0, not a missing row.
                timeline.declare(*_COUNTERS)
                timeline.count(name, n)
        except Exception:  # noqa: BLE001 — counters are best-effort
            pass

    def _lent_bytes(self) -> int:
        """Bytes out on loan now (under the lock)."""
        return sum(a.nbytes for a in list(self._lent.values()))

    def _cap(self) -> int:
        """Bytes the free list may hold (under the lock)."""
        if self.budget_bytes is not None:
            return self.budget_bytes
        return max(_DEFAULT_BUDGET, self._settled, self._peak)

    def _evict(self) -> int:
        """FIFO-evict down to the cap (under the lock) -> slabs dropped."""
        n = 0
        cap = self._cap()
        while self._free_bytes > cap and self._free:
            k, lst = next(iter(self._free.items()))  # oldest shape class
            old = lst.pop(0)
            if not lst:
                del self._free[k]
            self._free_bytes -= old.nbytes
            n += 1
        self.dropped += n
        return n

    def take(self, shape, dtype=np.int8, timeline=None) -> np.ndarray:
        """A free buffer of exactly ``(shape, dtype)`` — already faulted
        when reused — else a fresh aligned allocation.  ``timeline`` (the
        taker's) receives the ``staging.*`` count beside the process's."""
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            lst = self._free.get(key)
            if lst:
                arr = lst.pop()
                if not lst:
                    del self._free[key]
                self._free_bytes -= arr.nbytes
                self.reused += 1
            else:
                arr = None
                self.allocated += 1
        fresh = arr is None
        if fresh:
            arr = aligned_empty(shape, dtype)
        with self._lock:
            self._lent[id(arr)] = arr
            self._peak = max(self._peak, self._lent_bytes())
        self._count("staging.alloc" if fresh else "staging.reuse", 1,
                    timeline)
        return arr

    def give(self, arr: Optional[np.ndarray], timeline=None) -> None:
        """Return a buffer to the pool (dropped when over budget or not
        pool-eligible — non-contiguous views stage nothing)."""
        if arr is None or not arr.flags.c_contiguous:
            return
        key = (arr.shape, arr.dtype.str)
        with self._lock:
            was_lent = self._lent.pop(id(arr), None) is not None
            cap = self._cap()
            if cap <= 0 or arr.nbytes > cap:
                self.dropped += 1
                ndrop = 1
            else:
                self._free.setdefault(key, []).append(arr)
                self._free_bytes += arr.nbytes
                ndrop = self._evict()
            if was_lent and not self._lent:
                # The last lent buffer is back: the stretch is over, and
                # from here on only what IT held at its peak is kept.
                self._settled, self._peak = self._peak, 0
                ndrop += self._evict()
        if ndrop:
            # Budget-driven evictions count too: the telemetry counter
            # must agree with stats()["dropped"], or an operator A/B-ing
            # BLIT_STAGING_BYTES via telemetry sees a healthy pool that
            # is actually thrashing.
            self._count("staging.drop", ndrop, timeline)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "free_bytes": self._free_bytes,
                "free_slabs": sum(len(v) for v in self._free.values()),
                "lent_bytes": self._lent_bytes(),
                "reused": self.reused,
                "allocated": self.allocated,
                "dropped": self.dropped,
                "budget_bytes": self._cap(),
            }

    def clear(self) -> None:
        with self._lock:
            self._free.clear()
            self._free_bytes = 0


_POOL: Optional[SlabPool] = None
_POOL_LOCK = threading.Lock()


def slab_pool() -> SlabPool:
    """The process-wide staging pool (lazily constructed so the env
    budget is read at first use, not import)."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = SlabPool()
        return _POOL


def _reset_pool() -> None:
    """Drop the global pool (tests re-read the env budget)."""
    global _POOL
    with _POOL_LOCK:
        _POOL = None
