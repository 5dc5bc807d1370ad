"""The plain reference, computed ONCE a run, in processes of its own.

A task is one (product, coarse channel) pair, and WHAT it computes is the
product kind's to say (``products/<kind>.py``: ``reference_tasks`` lists a
product's tasks, ``compute`` is one task's work — for the filterbank kind
one call of the unchanged plain reference over that channel's whole int8
stream, all rows).  Each runs in a child process (``python3 refpool.py
<the kind's module> <volt.npy> <out> <arguments as JSON>``: it imports
NumPy, ``reference`` and the kind and nothing else, so no interpreter lock
is shared with the harness or with another task and no accelerator runtime
is ever forked), as many at once as the host has cores to spare and memory
over the guard's floor.  The harness starts them where nothing is being
timed — beside the warm-up pass where that is a whole pass — joins them
before it takes ``setup_s`` or starts the next pass, and keeps what they
return for the run: every later comparison (the kind's
``against_reference``) reads what was kept, none computes it again.

The channel streams go to the children as ``.npy`` files on the
recording's RAM-backed scratch, mapped, not copied; the harness drops its
own copy once they are written, and the files go when the last child has
ended (a kind that reads the whole band has as many bytes of streams out
as the recording holds: they do not stay beside the passes).
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

CHILD = [sys.executable, os.path.abspath(__file__)]
CHILD_BYTES = 1 << 29     # a task's working set (under 0.35 GB at nfft 2^20)
LIMIT_S = 240.0           # from start() to the last row, or the run is wrong
SPARE_CORES = 2           # left to the pass that runs beside the children
# The reference makes some 0.4 GB of fresh arrays per frame of 2^20 points;
# glibc maps and unmaps each, and on the chip's sandboxed host the first
# touch of fresh pages (0.9 GB/s) was half of a child's seconds.  Told to
# keep what is freed on its heap, the allocator hands the same pages out
# again: the same arithmetic, the same bytes, no faults (25.3 -> 13.8 s a
# 0000 channel on the builder's CPU, system time 11.5 -> 0.3 s).
CHILD_ENV = {"MALLOC_MMAP_MAX_": "0", "MALLOC_TRIM_THRESHOLD_": str(1 << 40)}


class ReferenceFailed(Exception):
    """A task gave no rows; the message is the child's own last words."""


class ReferencePool:
    """``products``: one ``(the plan's entry, its kind's module, the slices
    that kind is given)`` a product; a slice is what ``run.write_inputs``
    returns (it holds its channel's ``volt``, which ``start`` moves to
    ``workdir`` and removes from the dict).  A task is ``(product, slot,
    cost, the kind's module name, arguments)``.  ``mem_floor`` is the free
    memory (bytes) below which no child is started while another is
    alive."""

    def __init__(self, products, *, ntap: int, despike: bool,
                 workdir: str, mem_free=None, mem_floor: int = 0,
                 child=None, limit_s: float = LIMIT_S):
        self.slices = {s["slot"]: s for _, _, ss in products for s in ss}
        self.workdir = workdir
        self.mem_free, self.mem_floor = mem_free, mem_floor
        self.child, self.limit_s = list(child or CHILD), limit_s
        # the longest first: a kind states each task's cost
        self.tasks = sorted(
            ((p["name"], slot, cost, kind.__name__, args)
             for p, kind, slices in products
             for slot, cost, args in kind.reference_tasks(
                 p, slices, ntap=ntap, despike=despike)),
            key=lambda t: -t[2])
        self.workers = max(1, min(len(self.tasks),
                                  (os.cpu_count() or 2) - SPARE_CORES))
        self.launched = self.most_at_once = 0
        self.started_at = self.joined_at = None
        self.child_s, self._rows, self._failed = {}, {}, {}
        self._alive, self._done = {}, threading.Event()
        self._thread = None

    # -- the harness's side ----------------------------------------------------

    def start(self) -> None:
        """Take the streams from the slices and start the scheduler, which
        writes them out and runs the tasks; returns at once."""
        volts = {slot: s.pop("volt") for slot, s in self.slices.items()}
        self.started_at = time.perf_counter()
        self._thread = threading.Thread(target=self._schedule, args=(volts,),
                                        name="reference-pool")
        self._thread.start()

    def wait(self) -> dict:
        """Join every task (at most ``limit_s`` after ``start``) -> what
        the pool did.  A task still running then is killed and counts as
        failed; nothing here raises."""
        self._thread.join()
        return {"tasks": len(self.tasks), "workers": self.workers,
                "launched": self.launched, "most_at_once": self.most_at_once,
                "pool_s": self.joined_at - self.started_at,
                "child_s": {f"{n}/{slot}": s
                            for (n, slot), s in self.child_s.items()},
                "failed": {f"{n}/{slot}": why
                           for (n, slot), why in self._failed.items()}}

    def rows(self, product: str, slot: int):
        """What the task of ``product`` in coarse slot ``slot`` kept (for
        ``fil`` the reference rows ``(nspectra, nfft)`` float64; an array,
        or a mapping of arrays); waits for the pool where it still runs."""
        self.wait()
        key = (product, slot)
        if key in self._failed:
            raise ReferenceFailed(
                f"the reference of product {product}, coarse slot {slot}, "
                f"gave no rows: {self._failed[key]}")
        if key not in self._rows:
            kept = np.load(self._out(*key))
            self._rows[key] = kept if isinstance(kept, np.ndarray) \
                else dict(kept)
        return self._rows[key]

    def close(self) -> None:
        """End whatever still runs (a run that is being abandoned)."""
        self._done.set()
        if self._thread is not None:
            self._thread.join()

    # -- the scheduler's thread --------------------------------------------------

    def _volt(self, slot) -> str:
        return os.path.join(self.workdir, f"volt.{slot}.npy")

    def _out(self, product, slot) -> str:
        return os.path.join(self.workdir, f"rows.{product}.{slot}.npy")

    def _room(self) -> bool:
        if not self._alive or self.mem_free is None:
            return True   # one task always runs: the guard watches it
        return self.mem_free() - CHILD_BYTES >= self.mem_floor

    def _launch(self, task) -> None:
        product, slot, _, kind, args = task
        words = [kind, self._volt(slot), self._out(product, slot),
                 json.dumps(args)]
        log = open(self._out(product, slot) + ".log", "w+")
        try:
            proc = subprocess.Popen(self.child + words, stdout=log,
                                    stderr=subprocess.STDOUT,
                                    stdin=subprocess.DEVNULL,
                                    env={**os.environ, **CHILD_ENV})
        except OSError as e:
            log.close()
            self._failed[product, slot] = f"not started: {e}"
            return
        self.launched += 1
        self._alive[product, slot] = (proc, log, time.perf_counter())
        self.most_at_once = max(self.most_at_once, len(self._alive))

    def _reap(self, key, why=None) -> None:
        proc, log, t0 = self._alive.pop(key)
        self.child_s[key] = time.perf_counter() - t0
        log.seek(0)
        said = log.read().strip()
        log.close()
        if why is None and proc.returncode == 0 \
                and os.path.exists(self._out(*key)):
            return
        self._failed[key] = (why or f"exit {proc.returncode}") \
            + (": " + said[-600:] if said else "")

    def _schedule(self, volts: dict) -> None:
        waiting = list(self.tasks)
        slots = list(volts)
        deadline = self.started_at + self.limit_s
        try:
            os.makedirs(self.workdir, exist_ok=True)
            for slot in slots:   # each goes as soon as it is written
                np.save(self._volt(slot), volts.pop(slot))
            while (waiting or self._alive) and not self._done.is_set():
                while waiting and len(self._alive) < self.workers \
                        and self._room():
                    self._launch(waiting.pop(0))
                for key in [k for k, (p, _, _) in self._alive.items()
                            if p.poll() is not None]:
                    self._reap(key)
                if time.perf_counter() > deadline:
                    break
                self._done.wait(0.02)
        finally:
            late = f"no rows {self.limit_s:g} s after the pool started"
            for key in list(self._alive):
                proc = self._alive[key][0]
                if proc.poll() is None:
                    proc.kill()
                    proc.wait()
                    self._reap(key, "ended with the run"
                               if self._done.is_set() else late)
                else:
                    self._reap(key)
            for product, slot, *_ in waiting:
                self._failed[product, slot] = "never started: " + late
            for slot in slots:
                with contextlib.suppress(OSError):
                    os.remove(self._volt(slot))
            self.joined_at = time.perf_counter()


def main(argv) -> int:
    """The child: one task of the product kind whose module is ``kind``,
    what it keeps to ``out`` (an array, or a mapping of arrays; written
    whole or not at all), anything it has to say to its standard output."""
    kind, volt, out, args = argv
    kept = importlib.import_module(kind).compute(
        np.load(volt, mmap_mode="r"), json.loads(args))
    with open(out + ".tmp", "wb") as f:
        if isinstance(kept, np.ndarray):
            np.save(f, kept)
        else:
            np.savez(f, **kept)
    os.replace(out + ".tmp", out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
