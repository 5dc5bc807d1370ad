"""Observability: spans, per-stage timing, latency histograms, fleet
telemetry harvest, a crash/stall flight recorder, profiler traces, and
structured per-host logging.

SURVEY.md §5: the reference's only observability is three ``@warn`` sites
plus the host name stamped into inventory rows.  blit keeps the host/worker
stamping and adds what a GB/s-class serving stack needs (ISSUE 5 tentpole):

- a stage-timing registry (:class:`Timeline` — cheap, always on), now
  **mergeable** across processes so a worker fan-out folds into one fleet
  report (:meth:`Timeline.merge` / :func:`merge_fleet`);
- **spans** (:class:`Span`/:class:`Tracer`): request-scoped traces whose
  context propagates through the worker fan-out (pool dispatch, the agent
  wire) so one driver run parents per-worker child spans, exportable as
  Chrome-trace-event JSON (Perfetto-loadable, complementing the JAX
  profiler traces of :func:`profile_trace`);
- **histograms** (:class:`HistogramStats`): log-bucketed, bounded-memory,
  mergeable latency distributions (p50/p90/p99 + exact max) — the load
  signals averages hide;
- a **flight recorder** (:class:`FlightRecorder`): a fixed-size ring of
  recent span/stage/fault events per process, dumped to JSON when a stall
  watchdog trips, a breaker opens, or an agent dies — rendered by
  ``python -m blit trace-view``;
- optional JAX profiler traces (TensorBoard/Perfetto) and log records that
  carry host/worker context (now also as JSON lines for fleet ingestion).
"""

from __future__ import annotations

import contextlib
import itertools
import json
import logging
import math
import os
import socket
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

log = logging.getLogger("blit.observability")

_HOSTNAME: Optional[str] = None


# Captured once at import: the process's (epoch, monotonic) clock pair.
# Monotonic readings from different processes are incomparable (each
# starts at an arbitrary origin); shipping this anchor beside every
# spool sample, span batch and flight dump lets a forensics reader
# (blit/history.py incident bundles) project any monotonic-relative
# reading onto shared wall-clock time — and quantifies inter-host skew
# when two anchors disagree about "now" (ISSUE 20 satellite).
_WALL_ANCHOR = {"epoch": round(time.time(), 6),
                "mono": round(time.monotonic(), 6)}


def wall_anchor() -> Dict[str, float]:
    """This process's wall-clock anchor: one ``{"epoch", "mono"}`` pair
    captured at import.  ``epoch - mono`` is the process's monotonic
    origin in wall time; two processes' timelines align by comparing
    origins instead of trusting their skewed starts."""
    return dict(_WALL_ANCHOR)


def hostname() -> str:
    """This process's host name (cached — span creation must stay cheap)."""
    global _HOSTNAME
    if _HOSTNAME is None:
        _HOSTNAME = socket.gethostname()
    return _HOSTNAME


# Worker id stamped into spans/snapshots (0 = the driver process by the
# pool's convention); set by configure_logging(worker=...) at worker startup.
_WORKER = 0


@dataclass
class StageStats:
    """Accumulated wall time + optional byte counts for one pipeline stage."""

    calls: int = 0
    seconds: float = 0.0
    bytes: int = 0
    # Declared byte-free: the stage times something that moves no payload
    # (an async dispatch, a blocking wait).  Every OTHER stage with nonzero
    # seconds must report nonzero bytes — the stage table is only
    # sanity-summable against end-to-end GB/s when no stage silently drops
    # its byte count (VERDICT r5 weak #3), and tests pin that invariant.
    byte_free: bool = False

    @property
    def gbps(self) -> float:
        return self.bytes / self.seconds / 1e9 if self.seconds else 0.0


@dataclass
class GaugeStats:
    """A sampled level (queue depth, wait seconds): last value plus the
    observed envelope.  Unlike :class:`StageStats` a gauge is not a running
    total — re-sampling replaces ``last`` instead of accumulating."""

    last: float = 0.0
    lo: float = 0.0
    hi: float = 0.0
    n: int = 0

    def sample(self, value: float) -> None:
        if self.n == 0:
            self.lo = self.hi = value
        else:
            self.lo = min(self.lo, value)
            self.hi = max(self.hi, value)
        self.last = value
        self.n += 1


# Log-bucketed histogram geometry: bucket i covers (base*2^(i-1), base*2^i]
# with base = 1 µs; 64 buckets span 1 µs .. ~584 000 years, so no latency a
# process can observe falls off the top.
_HIST_BASE = 1e-6
_HIST_NBUCKETS = 64
_LOG2 = math.log(2.0)

# Histogram exemplars (ISSUE 15 tentpole #3): when enabled, every
# histogram retains the most recent trace id per bucket, so a p99 bucket
# that pages an SLO resolves to an actual request's trace instead of an
# anonymous count.  Bounded by construction (one (trace, value, t)
# triple per non-empty bucket, 64 buckets).  BLIT_EXEMPLARS=0 is the
# kill switch (the BLIT_SPANS discipline); SiteConfig.exemplars reaches
# here through blit.config.request_log_defaults + set_exemplars().
_EXEMPLARS = os.environ.get("BLIT_EXEMPLARS", "1").lower() not in (
    "0", "false", "off", "")


def set_exemplars(enabled: bool) -> None:
    """Flip per-bucket trace-id exemplar retention process-wide."""
    global _EXEMPLARS
    _EXEMPLARS = bool(enabled)


def exemplars_enabled() -> bool:
    return _EXEMPLARS


def hist_bucket_edges() -> List[float]:
    """The UPPER edge of every histogram bucket, in order: bucket 0
    holds values <= 1 µs, bucket i (i >= 1) covers
    ``(base·2^(i-1), base·2^i]`` — so edge ``i`` is ``base·2^i``.  The
    Prometheus ``le`` labels of the native exposition
    (:func:`render_prometheus`) and the SLO bad-sample cut
    (:func:`blit.monitor.bad_fraction`) both derive from this one list."""
    return [_HIST_BASE * 2.0 ** i for i in range(_HIST_NBUCKETS)]


class HistogramStats:
    """Log-bucketed value distribution: bounded memory (64 counters),
    mergeable across processes, quantiles good to one bucket (a factor of
    2) — latency must be reported as a distribution, not an average
    (ISSUE 5 tentpole #2).  Exact ``min``/``max``/``sum`` ride along so the
    tail operators page on (``max``) is never a bucket estimate."""

    __slots__ = ("counts", "n", "total", "vmin", "vmax", "exemplars")

    def __init__(self):
        self.counts = [0] * _HIST_NBUCKETS
        self.n = 0
        self.total = 0.0
        self.vmin = 0.0
        self.vmax = 0.0
        # bucket index -> [trace_id, value, epoch seconds] of the most
        # recent exemplar landing there; None until one lands (ISSUE 15).
        self.exemplars: Optional[Dict[int, List]] = None

    def observe(self, value: float, trace_id: Optional[str] = None) -> None:
        v = float(value)
        if v <= _HIST_BASE:
            i = 0
        else:
            i = min(_HIST_NBUCKETS - 1,
                    int(math.ceil(math.log(v / _HIST_BASE) / _LOG2)))
        self.counts[i] += 1
        if self.n == 0:
            self.vmin = self.vmax = v
        else:
            self.vmin = min(self.vmin, v)
            self.vmax = max(self.vmax, v)
        self.n += 1
        self.total += v
        if trace_id is None and _EXEMPLARS:
            # The ambient trace (thread-local read — cheap, and only
            # when a span is actually active): the sample becomes that
            # trace's exemplar in its latency bucket.
            ctx = _TRACER.context()
            if ctx:
                trace_id = ctx["trace"]
        if trace_id:
            ex = self.exemplars
            if ex is None:
                ex = self.exemplars = {}
            ex[i] = [trace_id, v, time.time()]

    def tail_exemplar(self) -> Optional[Dict]:
        """The exemplar of the HIGHEST bucket that has one — the trace
        behind the tail latency an operator is chasing.  Returns
        ``{"bucket", "le", "trace", "value", "t"}`` or None."""
        if not self.exemplars:
            return None
        i = max(self.exemplars)
        trace, v, t = self.exemplars[i]
        return {"bucket": i, "le": _HIST_BASE * 2.0 ** i,
                "trace": trace, "value": v, "t": t}

    def percentile(self, p: float) -> float:
        """Quantile estimate (0.0 when empty): the midpoint of the bucket
        the rank falls in, clamped to the observed [min, max] envelope so
        the extremes are exact."""
        if self.n == 0:
            return 0.0
        # Nearest-rank: the 0-based index of the p-th sample.
        rank = min(self.n - 1, max(0, int(math.ceil(p * self.n)) - 1))
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if c and acc > rank:
                lo = _HIST_BASE * 2.0 ** (i - 1) if i else 0.0
                hi = _HIST_BASE * 2.0 ** i
                return min(max((lo + hi) / 2.0, self.vmin), self.vmax)
        return self.vmax

    def merge(self, other: "HistogramStats") -> "HistogramStats":
        """Fold ``other`` into self (commutative/associative: bucket counts
        and totals sum, the envelope widens)."""
        if other.n:
            if self.n == 0:
                self.vmin, self.vmax = other.vmin, other.vmax
            else:
                self.vmin = min(self.vmin, other.vmin)
                self.vmax = max(self.vmax, other.vmax)
        for i, c in enumerate(other.counts):
            if c:
                self.counts[i] += c
        self.n += other.n
        self.total += other.total
        if other.exemplars:
            # "Most recent per bucket" stays true across the fold: the
            # newer timestamp wins, whichever process observed it.
            ex = self.exemplars
            if ex is None:
                ex = self.exemplars = {}
            for i, rec in other.exemplars.items():
                if i not in ex or rec[2] >= ex[i][2]:
                    ex[i] = list(rec)
        return self

    def reset(self) -> None:
        """Zero IN PLACE, preserving identity (the Timeline.reset rule)."""
        for i in range(_HIST_NBUCKETS):
            self.counts[i] = 0
        self.n = 0
        self.total = 0.0
        self.vmin = self.vmax = 0.0
        self.exemplars = None

    def report(self) -> Dict[str, float]:
        mean = self.total / self.n if self.n else 0.0
        return {"n": self.n, "mean": round(mean, 6),
                "p50": round(self.percentile(0.50), 6),
                "p90": round(self.percentile(0.90), 6),
                "p99": round(self.percentile(0.99), 6),
                "max": round(self.vmax, 6)}

    def state(self) -> Dict:
        """JSON-serializable raw state (the harvest wire format — reports
        round, state doesn't, so fleet merges stay exact)."""
        st = {"counts": list(self.counts), "n": self.n,
              "total": self.total, "vmin": self.vmin, "vmax": self.vmax}
        if self.exemplars:
            # JSON keys are strings; from_state re-ints them.
            st["exemplars"] = {str(i): list(rec)
                               for i, rec in self.exemplars.items()}
        return st

    def since(self, st: Dict) -> "HistogramStats":
        """A NEW histogram holding only the samples observed after ``st``
        (a prior :meth:`state`).  Bucket counts / n / total subtract
        exactly; the [min, max] envelope is not invertible, so the delta
        keeps the cumulative one — quantile bucket midpoints stay
        correct, only the envelope clamp is wider than the true window."""
        h = HistogramStats()
        old = st.get("counts", [])
        for i in range(_HIST_NBUCKETS):
            prev = old[i] if i < len(old) else 0
            h.counts[i] = max(0, self.counts[i] - prev)
        h.n = max(0, self.n - int(st.get("n", 0)))
        h.total = max(0.0, self.total - float(st.get("total", 0.0)))
        h.vmin, h.vmax = self.vmin, self.vmax
        if self.exemplars:
            # Exemplars are "most recent", not a running total: the
            # delta keeps the cumulative ones (a tail sample in this
            # window overwrote its bucket's entry anyway).
            h.exemplars = {i: list(rec)
                           for i, rec in self.exemplars.items()}
        return h

    @classmethod
    def from_state(cls, st: Dict) -> "HistogramStats":
        h = cls()
        counts = list(st.get("counts", []))[:_HIST_NBUCKETS]
        h.counts[: len(counts)] = [int(c) for c in counts]
        h.n = int(st.get("n", 0))
        h.total = float(st.get("total", 0.0))
        h.vmin = float(st.get("vmin", 0.0))
        h.vmax = float(st.get("vmax", 0.0))
        for i, rec in (st.get("exemplars") or {}).items():
            try:
                bucket = int(i)
                trace, v, t = rec
            except (TypeError, ValueError):
                continue
            if h.exemplars is None:
                h.exemplars = {}
            h.exemplars[bucket] = [str(trace), float(v), float(t)]
        return h


class StageWait:
    """Where a pump thread may block (``with timeline.wait(name) as w``):
    call :meth:`block` right before each blocking call.  The first one
    enters the stage, leaving the ``with`` closes it, and a path that
    never blocked records nothing — the row's seconds are blocked
    seconds only."""

    __slots__ = ("_tl", "_name", "_cm")

    def __init__(self, timeline: "Timeline", name: str):
        self._tl = timeline
        self._name = name
        self._cm = None

    @property
    def blocking(self) -> bool:
        return self._cm is not None

    def block(self) -> None:
        if self._cm is None:
            self._cm = self._tl.stage(self._name, byte_free=True)
            self._cm.__enter__()

    def __enter__(self) -> "StageWait":
        return self

    def __exit__(self, *exc) -> None:
        if self._cm is not None:
            cm, self._cm = self._cm, None
            cm.__exit__(None, None, None)


@dataclass
class Timeline:
    """A registry of named stage timings (one per pipeline/driver)."""

    stages: Dict[str, StageStats] = field(default_factory=lambda: defaultdict(StageStats))
    gauges: Dict[str, GaugeStats] = field(default_factory=lambda: defaultdict(GaugeStats))
    hists: Dict[str, HistogramStats] = field(
        default_factory=lambda: defaultdict(HistogramStats)
    )

    @contextlib.contextmanager
    def _timed(self, name: str, kind: str, nbytes: int, calls: int,
               byte_free: bool = False) -> Iterator[Optional["Span"]]:
        """The one record behind :meth:`stage`, :meth:`mark` and
        :meth:`part`: a row of the table (``calls``, ``seconds``,
        ``bytes``) and the interval itself, as a span of the process
        tracer under the ambient span, attrs ``bytes`` and ``kind``=1.
        ``BLIT_SPANS=0`` keeps the row, drops the span and yields
        ``None``.  Span and row carry the same duration."""
        sp = _TRACER.open_span(name, {"bytes": nbytes, kind: 1})
        t0 = time.perf_counter()
        try:
            yield sp
        finally:
            dt = time.perf_counter() - t0
            s = self.stages[name]
            s.calls += calls
            s.seconds += dt
            s.bytes += nbytes
            if byte_free:
                s.byte_free = True
            if sp is None:
                _FLIGHT.stage_event(name, dt, nbytes)
            else:  # the span is the record's one entry in the flight ring
                _TRACER.close_span(sp, dt)

    def stage(
        self, name: str, nbytes: int = 0, byte_free: bool = False,
        calls: int = 1,
    ):
        """Time one stage — what its thread is doing: the sums of the
        table and, so the seconds can be laid against another clock, a
        span (attr ``stage=1``).  Yields that live span, for a caller
        with attrs of its own to add (``None`` under ``BLIT_SPANS=0``);
        ``calls`` is what the row counts it as (:meth:`mark`)."""
        return self._timed(name, "stage", nbytes, calls, byte_free)

    def mark(self, name: str, nbytes: int = 0, calls: int = 1) -> None:
        """A counted instant: a zero-length stage (row and span, on the
        clock of every other stage) standing for ``calls`` events."""
        with self.stage(name, nbytes=nbytes, calls=calls):
            pass

    def part(self, name: str, nbytes: int = 0, calls: int = 1,
             byte_free: bool = False):
        """Time one PART of a stage — what some of the stage's seconds
        were spent on (the coefficient bank inside ``dispatch``, the
        digest inside ``write``): the same record as a stage, but its
        span carries ``part=1`` and no ``stage``.  A part is not a state
        of its thread: whatever names seconds by the innermost open
        STAGE reads what it read without the part, and the enclosing
        stage's row keeps its total."""
        return self._timed(name, "part", nbytes, calls, byte_free)

    def wait(self, name: str) -> "StageWait":
        """A byte-free ``wait.<what>`` stage that starts only when the
        thread is about to block (:class:`StageWait`)."""
        return StageWait(self, name)

    def declare(self, *names: str) -> None:
        """Byte-free rows that exist from now on: a wait that never
        blocked then reads 0 calls instead of being absent."""
        for name in names:
            self.stages[name].byte_free = True

    def count(self, name: str, n: int = 1) -> None:
        """Record a byte-free event counter as a stage (``calls`` carries
        the count) — retry/mask/degradation events land here so they show
        up in :meth:`report` and in the per-window :meth:`since` tables
        (ISSUE 2: a degraded run must say so in its report)."""
        s = self.stages[name]
        s.calls += n
        s.byte_free = True
        _FLIGHT.event("count", name, n=n)

    def observe(self, name: str, value: float) -> None:
        """Record one sample into a log-bucketed latency/size histogram
        (bounded memory; p50/p90/p99 + max in :meth:`report`) — chunk
        latency, queue wait, readback lag and retry backoff live here
        instead of on gauges, because their tails are the signal."""
        self.hists[name].observe(value)

    def gauge(self, name: str, value: float) -> None:
        """Sample a level gauge (queue depth, per-job wait seconds — the
        serving layer's load signals, ISSUE 3).  Gauges live beside the
        stage table: levels are point samples, not running totals, so they
        must not pollute the byte-summable stage accounting."""
        self.gauges[name].sample(value)

    def reset(self) -> None:
        """Zero every stage and gauge IN PLACE, preserving object
        identity.  This — not ``stages.clear()`` — is how a rig discards
        warmup passes: ``clear()`` orphans any :class:`StageStats` a
        concurrent thread (an output-plane readback/writer thread, a feed
        producer) or a captured local still holds, so their subsequent
        byte/second updates land in objects the report never sees — the
        failure shape behind BENCH_r05's ``"stream": {"s": 350.3,
        "bytes": 0}`` (ISSUE 4 satellite; tests/test_outplane.py pins the
        rig sequence)."""
        for s in list(self.stages.values()):
            s.calls = 0
            s.seconds = 0.0
            s.bytes = 0
        for g in list(self.gauges.values()):
            g.last = g.lo = g.hi = 0.0
            g.n = 0
        for h in list(self.hists.values()):
            h.reset()

    def overlap_efficiency(self, wall: str = "stream",
                           work: Iterable[str] = ("device", "readback",
                                                  "write")) -> float:
        """Record + return the output plane's overlap gauge
        (``overlap.<wall>``): seconds of per-stage work retired per
        wall-clock second of the ``wall`` stage.

        ≈ 1.0 means the plane ran serialized (the wall clock paid for
        every stage in full — the synchronous-output shape BENCH_r05
        measured); → N means N stages fully hid behind each other.
        *Below* 1.0 the wall stage is dominated by something the work
        stages don't time — usually the host read leg (``ingest``) or
        dispatch gaps.  0.0 when the wall stage never ran.  See
        docs/WORKFLOWS.md "Diagnosing a slow link"."""
        wall_s = self.stages[wall].seconds if wall in self.stages else 0.0
        work_s = sum(
            self.stages[k].seconds for k in work if k in self.stages
        )
        eff = work_s / wall_s if wall_s > 0 else 0.0
        self.gauge(f"overlap.{wall}", eff)
        return eff

    def report(self, include_faults: bool = False) -> Dict[str, Dict]:
        out = {}
        # list(): producer threads (the window feeds) insert stage keys
        # concurrently with consumer-side reporting — never iterate the
        # live dict (CPython raises on resize-mid-iteration).  Torn
        # per-stage reads are acceptable for reporting.
        for k, v in sorted(list(self.stages.items())):
            row = {"calls": v.calls, "seconds": round(v.seconds, 6),
                   "bytes": v.bytes, "gbps": round(v.gbps, 3)}
            if v.byte_free:
                row["byte_free"] = True
            out[k] = row
        if self.gauges:
            out["gauges"] = {
                k: {"last": round(g.last, 6), "lo": round(g.lo, 6),
                    "hi": round(g.hi, 6), "n": g.n}
                for k, g in sorted(list(self.gauges.items()))
            }
        if self.hists:
            out["hists"] = {
                k: h.report() for k, h in sorted(list(self.hists.items()))
            }
        if include_faults:
            # Process-wide failure/recovery totals (blit/faults.py):
            # retry.io / retry.remote / mask.antenna / breaker.trip /
            # fault.<point>.<mode>.  Global (not per-timeline) by design —
            # retries deep inside the I/O layer have no timeline in hand.
            from blit import faults

            c = faults.counters()
            if c:
                out["faults"] = c
        return out

    def snapshot(self) -> Dict[str, tuple]:
        """Cheap point-in-time stage counters, for :meth:`since`
        (safe against concurrent producer-thread stage insertion)."""
        return {k: (v.calls, v.seconds, v.bytes)
                for k, v in list(self.stages.items())}

    def hist_quantiles(self, names: Optional[Iterable[str]] = None
                       ) -> Dict[str, Dict]:
        """p50/p99 (+n, max) per named histogram — the compact tail block
        the bench tables embed beside stage means (ISSUE 8 satellite:
        operators read readback/write/chunk-latency TAILS, an average
        hides the burst that actually stalled the plane).  ``names=None``
        reports every histogram with samples."""
        keys = list(self.hists) if names is None else list(names)
        out = {}
        for k in keys:
            h = self.hists.get(k)
            if h is None or h.n == 0:
                continue
            # One quantile-report implementation: project the compact
            # shape out of HistogramStats.report so rounding/percentile
            # changes there propagate here.
            rep = h.report()
            out[k] = {f: rep[f] for f in ("n", "p50", "p99", "max")}
        return out

    def since(self, snap: Dict[str, tuple]) -> Dict[str, Dict]:
        """Per-stage deltas since a :meth:`snapshot` — the per-window stage
        record the windowed drivers report (seconds/bytes spent in each
        stage by ONE window, not the whole run)."""
        out = {}
        for k, v in list(self.stages.items()):
            c0, s0, b0 = snap.get(k, (0, 0.0, 0))
            if v.calls != c0 or v.bytes != b0 or v.seconds != s0:
                out[k] = {"calls": v.calls - c0,
                          "seconds": round(v.seconds - s0, 6),
                          "bytes": v.bytes - b0}
        return out

    def merge(self, other: "Timeline") -> "Timeline":
        """Fold ``other`` into self — the fleet-harvest fold (ISSUE 5
        tentpole #3).  Stage and histogram merges are commutative and
        associative (sums / bucket sums), so a per-host fold and a flat
        fleet fold give the same totals whatever order workers answered
        in (tests/test_telemetry.py pins this).  Gauges keep the widened
        [lo, hi] envelope and the sample count; ``last`` keeps self's
        unless self never sampled (point samples from different processes
        have no meaningful merged "last")."""
        for k, s in list(other.stages.items()):
            d = self.stages[k]
            d.calls += s.calls
            d.seconds += s.seconds
            d.bytes += s.bytes
            if s.byte_free:
                d.byte_free = True
        for k, g in list(other.gauges.items()):
            d = self.gauges[k]
            if g.n:
                if d.n == 0:
                    d.last, d.lo, d.hi = g.last, g.lo, g.hi
                else:
                    d.lo = min(d.lo, g.lo)
                    d.hi = max(d.hi, g.hi)
                d.n += g.n
        for k, h in list(other.hists.items()):
            self.hists[k].merge(h)
        return self

    def state(self) -> Dict:
        """Full JSON-serializable raw state — the telemetry-harvest wire
        format (:func:`telemetry_snapshot`).  Unlike :meth:`report` nothing
        is rounded, so :meth:`from_state` + :meth:`merge` is exact."""
        return {
            "stages": {
                k: {"calls": v.calls, "seconds": v.seconds,
                    "bytes": v.bytes, "byte_free": v.byte_free}
                for k, v in list(self.stages.items())
            },
            "gauges": {
                k: {"last": g.last, "lo": g.lo, "hi": g.hi, "n": g.n}
                for k, g in list(self.gauges.items())
            },
            "hists": {k: h.state() for k, h in list(self.hists.items())},
        }

    @classmethod
    def from_state(cls, st: Dict) -> "Timeline":
        tl = cls()
        for k, v in (st.get("stages") or {}).items():
            s = tl.stages[k]
            s.calls = int(v.get("calls", 0))
            s.seconds = float(v.get("seconds", 0.0))
            s.bytes = int(v.get("bytes", 0))
            s.byte_free = bool(v.get("byte_free", False))
        for k, v in (st.get("gauges") or {}).items():
            g = tl.gauges[k]
            g.last = float(v.get("last", 0.0))
            g.lo = float(v.get("lo", 0.0))
            g.hi = float(v.get("hi", 0.0))
            g.n = int(v.get("n", 0))
        for k, v in (st.get("hists") or {}).items():
            tl.hists[k] = HistogramStats.from_state(v)
        return tl

    def log(self, logger: Optional[logging.Logger] = None) -> None:
        (logger or logging.getLogger("blit.timeline")).info(
            "timeline %s", json.dumps(self.report())
        )


@contextlib.contextmanager
def profile_trace(logdir: Optional[str]) -> Iterator[None]:
    """JAX profiler trace around a region, device ops only, plus the
    region's spans beside it.  ``logdir=None`` is a no-op, so call sites
    need no conditionals.

    The host and Python tracers are off and the TPU traces XLA ops only:
    at the profiler's default levels a 1.5 s pass took 45 s on the v5e
    machine and grew the process past 10 GB (PERF.md section 3).  What
    the host did is in ``<logdir>/blit-spans.json`` instead (Chrome trace
    events, ``ts`` in epoch microseconds): every pump stage and wait of
    the region is a span there, and the ``.xplane.pb``'s ``Task
    Environment`` plane stamps its start in epoch nanoseconds, so the two
    lie on one clock (docs/WORKFLOWS.md "Diagnosing a slow link")."""
    if logdir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    cursor = _TRACER._total
    with jax.profiler.trace(logdir, profiler_options=opts):
        yield
    _TRACER.export_chrome(os.path.join(logdir, "blit-spans.json"),
                          since=cursor)


# -- spans ------------------------------------------------------------------

_id_counter = itertools.count(1)
# Per-process id prefix: spans harvested from N worker processes must not
# collide in the merged trace.  pid alone recycles; add 2 random bytes.
_ID_PREFIX = f"{os.getpid():x}{os.urandom(2).hex()}"
_ID_PID = os.getpid()


def _new_id() -> str:
    global _ID_PREFIX, _ID_PID
    pid = os.getpid()
    if pid != _ID_PID:
        # Forked child (the process pool backend forks on Linux): the
        # inherited prefix AND counter position would collide span ids
        # across every sibling worker — re-key the prefix per process.
        _ID_PREFIX = f"{pid:x}{os.urandom(2).hex()}"
        _ID_PID = pid
    return f"{_ID_PREFIX}.{next(_id_counter):x}"


class Span:
    """One finished traced operation: name, wall start (epoch seconds),
    duration, host/worker/thread identity, trace linkage (trace id, span
    id, parent span id) and small free-form attrs.  Cheap by design —
    created on context-manager entry, recorded on exit."""

    __slots__ = ("name", "t0", "duration_s", "trace_id", "span_id",
                 "parent_id", "host", "worker", "tid", "attrs", "stack")

    def __init__(self, name: str, trace_id: str, span_id: str,
                 parent_id: Optional[str], attrs: Optional[Dict]):
        self.name = name
        self.t0 = time.time()
        self.duration_s = 0.0
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.host = hostname()
        self.worker = _WORKER
        self.tid = threading.get_ident() & 0x7FFFFFFF
        self.attrs = attrs
        self.stack = None  # the Tracer's, while the span is open

    def as_dict(self) -> Dict:
        d = {"name": self.name, "t0": self.t0,
             "duration_s": self.duration_s, "trace": self.trace_id,
             "span": self.span_id, "parent": self.parent_id,
             "host": self.host, "worker": self.worker, "tid": self.tid}
        if self.attrs:
            d["attrs"] = self.attrs
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "Span":
        sp = cls(d.get("name", "?"), d.get("trace", ""), d.get("span", ""),
                 d.get("parent"), d.get("attrs") or None)
        sp.t0 = float(d.get("t0", 0.0))
        sp.duration_s = float(d.get("duration_s", 0.0))
        sp.host = d.get("host", sp.host)
        sp.worker = int(d.get("worker", 0))
        sp.tid = int(d.get("tid", 0))
        return sp


class Tracer:
    """Always-on, cheap span recorder with ambient (thread-local) trace
    context.

    A :meth:`span` opened with no ambient context starts a new trace; one
    opened inside another span (same thread) or under :meth:`activate`
    (an adopted cross-thread/cross-process context) becomes its child.
    :meth:`context` exports the current ``{"trace", "span"}`` pair — the
    pool dispatch ships it to workers so their spans parent onto the
    driver's (ISSUE 5 tentpole #1).  Finished spans land in a bounded
    deque (oldest dropped) and in the process flight recorder.

    ``enabled=False`` (or ``BLIT_SPANS=0`` in the environment) turns
    :meth:`span` into a near-free no-op — the A/B lever for the ≤1 %
    overhead acceptance bound."""

    def __init__(self, max_spans: int = 16384, enabled: Optional[bool] = None):
        if enabled is None:
            enabled = os.environ.get("BLIT_SPANS", "1").lower() not in (
                "0", "false", "off", "")
        self.enabled = enabled
        self._spans: deque = deque(maxlen=max_spans)
        # Monotonic count of spans EVER recorded — the cursor behind
        # spans_since(), so interval publishers ship each span once
        # without draining the deque out from under export_chrome.
        # The (append, += 1) pair is guarded: `+= 1` alone is not
        # atomic, and a lost increment would silently drop the tail of
        # a spool batch.
        self._total = 0
        self._span_lock = threading.Lock()
        self._tls = threading.local()

    def _stack(self) -> List:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[Optional[Span]]:
        """Time a traced operation.  Yields the live :class:`Span` (or
        ``None`` when tracing is disabled); extra keyword args become
        span attrs."""
        sp = self.open_span(name, attrs or None)
        if sp is None:
            yield None
            return
        p0 = time.perf_counter()
        try:
            yield sp
        finally:
            self.close_span(sp, time.perf_counter() - p0)

    def open_span(self, name: str, attrs: Optional[Dict]) -> Optional[Span]:
        """Start a span under the ambient one and make it this thread's
        ambient span (``None`` when tracing is disabled).  Pair with
        :meth:`close_span`; :meth:`span` is the two as a context
        manager."""
        if not self.enabled:
            return None
        stack = self._stack()
        if stack:
            trace_id, parent_id = stack[-1]
        else:
            trace_id, parent_id = _new_id(), None
        sp = Span(name, trace_id, _new_id(), parent_id, attrs)
        sp.stack = stack
        stack.append((trace_id, sp.span_id))
        return sp

    def close_span(self, sp: Span, duration_s: float) -> None:
        """Record ``sp`` and take ITS OWN entry off the stack it was
        pushed on — not whatever is on top: a span held across a
        ``yield`` (``reduce.stream``, the ``stream`` stage) closes when
        its generator does, with spans the consumer opened since then
        still above it."""
        sp.duration_s = duration_s
        stack, sp.stack = sp.stack, None
        entry = (sp.trace_id, sp.span_id)
        if stack and stack[-1] == entry:
            stack.pop()
        elif stack and entry in stack:
            stack.remove(entry)
        with self._span_lock:
            self._spans.append(sp)
            self._total += 1
        _FLIGHT.span_event(sp)

    @contextlib.contextmanager
    def activate(self, ctx: Optional[Dict]) -> Iterator[None]:
        """Adopt a ``{"trace", "span"}`` context exported by
        :meth:`context` in another thread or process: spans opened inside
        become children of that remote span."""
        if not ctx or not self.enabled:
            yield
            return
        stack = self._stack()
        stack.append((str(ctx.get("trace", "")), str(ctx.get("span", ""))))
        try:
            yield
        finally:
            stack.pop()

    def context(self) -> Optional[Dict]:
        """The ambient ``{"trace", "span"}`` pair (None outside any span
        or with tracing disabled) — ship it across the fan-out."""
        if not self.enabled:
            return None
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return None
        trace_id, span_id = stack[-1]
        return {"trace": trace_id, "span": span_id}

    def spans(self) -> List[Span]:
        return list(self._spans)

    def span_dicts(self) -> List[Dict]:
        return [s.as_dict() for s in self._spans]

    def spans_since(self, cursor: int) -> Tuple[int, List[Dict]]:
        """Span dicts recorded after a prior cursor → ``(new cursor,
        spans)`` — the interval publisher's batch surface (ISSUE 15
        tentpole #4): each tick ships only the spans finished since the
        last one, so a spool line stays proportional to the interval,
        not the run.  Spans that aged out of the bounded deque between
        slow ticks are lost (by design — the deque bounds memory)."""
        with self._span_lock:
            total = self._total
            new = total - int(cursor)
            if new <= 0:
                return total, []
            recent = list(self._spans)
        if new < len(recent):
            recent = recent[-new:]
        return total, [s.as_dict() for s in recent]

    def ingest(self, span_dicts: Iterable[Dict]) -> None:
        """Adopt foreign spans (a fleet harvest) into this tracer so one
        :meth:`export_chrome` covers driver and workers."""
        for d in span_dicts:
            try:
                sp = Span.from_dict(d)
            except (TypeError, ValueError):  # malformed harvest entry
                continue
            with self._span_lock:
                self._spans.append(sp)
                self._total += 1

    def reset(self) -> None:
        self._spans.clear()

    def export_chrome(self, path: Optional[str] = None,
                      extra: Optional[Iterable[Dict]] = None,
                      since: int = 0):
        """Render the recorded spans as Chrome trace events (Perfetto /
        ``chrome://tracing`` loadable).  ``extra`` takes harvested span
        dicts to merge in; ``since`` (a :meth:`spans_since` cursor) keeps
        only the spans recorded after it.  Returns the event document;
        writes JSON to ``path`` when given and returns the path instead."""
        spans = self.spans()
        if since:
            new = self._total - since
            spans = spans[-new:] if new > 0 else []
        if extra:
            spans = spans + [Span.from_dict(d) for d in extra]
        # Dedupe by span id: with the in-process pool backends a harvest
        # returns the driver's own spans, so recorded + ``extra`` overlap.
        seen, unique = set(), []
        for sp in spans:
            if sp.span_id in seen:
                continue
            seen.add(sp.span_id)
            unique.append(sp)
        spans = unique
        # Stable pid per (host, worker) so each worker renders as its own
        # process track, named.
        pids: Dict = {}
        events: List[Dict] = []
        for sp in spans:
            key = (sp.host, sp.worker)
            pid = pids.get(key)
            if pid is None:
                pid = pids[key] = len(pids) + 1
                events.append({"ph": "M", "pid": pid, "tid": 0,
                               "name": "process_name",
                               "args": {"name": f"{sp.host}/w{sp.worker}"}})
            ev = {"name": sp.name, "cat": "blit", "ph": "X",
                  "ts": sp.t0 * 1e6, "dur": max(sp.duration_s, 1e-7) * 1e6,
                  "pid": pid, "tid": sp.tid,
                  "args": {"trace": sp.trace_id, "span": sp.span_id,
                           "parent": sp.parent_id}}
            if sp.attrs:
                ev["args"].update(sp.attrs)
            events.append(ev)
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is None:
            return doc
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


_TRACER = Tracer()


def tracer() -> Tracer:
    """The process-wide tracer (workers harvest it; drivers export it)."""
    return _TRACER


def span(name: str, **attrs):
    """Module-level convenience: ``with observability.span("leg"): ...``"""
    return _TRACER.span(name, **attrs)


def new_id() -> str:
    """A fresh process-unique id in the span-id format — request ids
    (:class:`RequestLog`) share the spans' id space so a record, a span
    and a log line are all greppable by the same token."""
    return _new_id()


# -- flight recorder --------------------------------------------------------


class FlightRecorder:
    """A fixed-size ring of recent span/stage/fault events, dumped to JSON
    when something trips (ISSUE 5 tentpole #4): a rotation stall watchdog,
    an opened circuit breaker, a dead agent.  Recording must be cheap
    enough to leave on (bounded deque appends, no locks — CPython deque
    appends are atomic); dumping is rate-limited so a retry storm writes
    one incident file, not hundreds.  ``python -m blit trace-view``
    renders a dump into an incident summary."""

    # Bound on distinct rate-limit clocks (ISSUE 15 satellite): reasons
    # carry per-instance detail, so the keyed dict must not grow without
    # bound under adversarial reason churn.
    _MAX_DUMP_KEYS = 64

    def __init__(self, capacity: int = 512, min_interval_s: float = 60.0):
        self._ring: deque = deque(maxlen=capacity)
        self.min_interval_s = min_interval_s
        # Rate limiting is PER REASON CLASS (ISSUE 15 satellite), not
        # one global clock: an SLO-breach dump must not starve a
        # first-of-kind stall dump that lands seconds later.  Keys are
        # the reason's leading "name" segment (before the first ":" or
        # "—"), or an explicit dump(key=...).
        self._last_dump: Dict[str, float] = {}
        self._dump_seq = 0
        self._dump_lock = threading.Lock()

    @staticmethod
    def _reason_key(reason: str) -> str:
        head = reason.split("—", 1)[0].split(":", 1)[0].strip()
        return head[:64] or "dump"

    # -- recording (hot paths) --------------------------------------------
    def event(self, kind: str, name: str, **fields) -> None:
        e = {"t": time.time(), "kind": kind, "name": name}
        if fields:
            e.update(fields)
        self._ring.append(e)

    def span_event(self, sp: Span) -> None:
        ev = {"t": sp.t0, "kind": "span", "name": sp.name,
              "dur_s": round(sp.duration_s, 6),
              "span": sp.span_id, "parent": sp.parent_id}
        if sp.attrs and (sp.attrs.get("stage") or sp.attrs.get("part")):
            ev["bytes"] = sp.attrs.get("bytes", 0)  # a Timeline row's span
        self._ring.append(ev)

    def stage_event(self, name: str, seconds: float, nbytes: int) -> None:
        """A stage that recorded no span (``BLIT_SPANS=0``)."""
        self._ring.append({"t": time.time(), "kind": "stage", "name": name,
                           "s": round(seconds, 6), "bytes": nbytes})

    def events(self) -> List[Dict]:
        return list(self._ring)

    def clear(self) -> None:
        self._ring.clear()

    # -- dumping (incident path) ------------------------------------------
    def dump(self, reason: str, path: Optional[str] = None,
             force: bool = False, key: Optional[str] = None) -> Optional[str]:
        """Write the incident JSON (ring + fault counters + process
        timeline + recent spans) and return its path.  Never raises (the
        caller is already mid-incident); returns None when rate-limited
        (``force=True`` overrides) or when ``BLIT_FLIGHT_DISABLE`` is
        set.  The rate limit is per reason CLASS (``key``, default the
        reason's leading name segment) — distinct incident kinds never
        starve each other (ISSUE 15 satellite)."""
        if os.environ.get("BLIT_FLIGHT_DISABLE"):
            return None
        try:
            now = time.monotonic()
            k = key if key is not None else self._reason_key(reason)
            with self._dump_lock:
                last = self._last_dump.get(k, float("-inf"))
                if not force and now - last < self.min_interval_s:
                    return None
                if (k not in self._last_dump
                        and len(self._last_dump) >= self._MAX_DUMP_KEYS):
                    # Evict the stalest clock: new incident kinds keep
                    # their own limiter without unbounded growth.
                    self._last_dump.pop(
                        min(self._last_dump, key=self._last_dump.get))
                self._last_dump[k] = now
            from blit import faults

            doc = {
                "reason": reason,
                "t": time.time(),
                "host": hostname(),
                "pid": os.getpid(),
                "worker": _WORKER,
                "anchor": wall_anchor(),
                "events": self.events(),
                "faults": faults.counters(),
                "timeline": process_timeline().report(),
                "spans": [s.as_dict() for s in _TRACER.spans()[-64:]],
            }
            # Correlate the incident with the request that tripped it
            # (ISSUE 15 satellite): when a span is active on the dumping
            # thread, its trace/span ids land in the dump — a flight
            # record and a stitched fleet trace become greppable by one
            # token.
            ctx = _TRACER.context()
            if ctx:
                doc["trace"] = ctx.get("trace")
                doc["span"] = ctx.get("span")
            if path is None:
                d = os.environ.get("BLIT_FLIGHT_DIR")
                if not d:
                    import tempfile

                    d = tempfile.gettempdir()
                # The per-process sequence number keeps two same-second
                # dumps (now possible: rate limiting is per REASON) from
                # overwriting each other's file.
                with self._dump_lock:
                    self._dump_seq += 1
                    seq = self._dump_seq
                path = os.path.join(
                    d, f"blit-flight-{hostname()}-{os.getpid()}-"
                       f"{int(doc['t'])}-{seq}.json")
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
            log.error("flight recorder dumped to %s (%s)", path, reason)
            return path
        except Exception:  # noqa: BLE001 — never mask the real incident
            log.warning("flight recorder dump failed", exc_info=True)
            return None


_FLIGHT = FlightRecorder()


def flight_recorder() -> FlightRecorder:
    """The process-wide flight recorder."""
    return _FLIGHT


class StallWatchdog:
    """The one producer-progress stall discipline behind every threaded
    plane (ISSUE 7 satellite: this used to be four near-identical poll
    loops).  A thread that owns real progress calls :meth:`beat`;
    back-pressure waits count as progress (the waiter is the slow side
    there, not the producer).  The poll side sizes its waits with
    :meth:`poll_s` and calls :meth:`check` on every empty poll — when no
    beat landed for ``timeout_s`` while the watched thread is still
    ``active``, the flight recorder dumps the incident trail (BEFORE the
    raise unwinds and teardown noise overwrites the ring) and a
    ``RuntimeError`` bounds the hang.  ``timeout_s=None`` disarms
    (checks are no-ops; polls use their base interval).

    Users: :class:`blit.pipeline.BufferRotation` (ingest producer),
    :class:`blit.outplane.OutputRotation` (readback thread),
    :class:`blit.outplane.AsyncSink` (writer thread, append and flush
    sides), and the streaming chunk feed
    (:class:`blit.stream.LiveRawStream`)."""

    def __init__(self, timeout_s: Optional[float], name: str,
                 what: str = "a wedged producer would otherwise hang"):
        self.timeout_s = timeout_s
        self.name = name
        self.what = what
        self._beat = time.monotonic()

    def beat(self) -> None:
        """Mark producer progress (cheap; called from the owning thread —
        concurrent float stores are atomic in CPython)."""
        self._beat = time.monotonic()

    def age_s(self) -> float:
        """Seconds since the last beat — the raw staleness the supervisor
        planes (blit/recover.py) report as detection latency when a
        watchdog (or its cross-process twin, a heartbeat lease) expires."""
        return time.monotonic() - self._beat

    def poll_s(self, base: float = 0.2) -> float:
        """The poll interval a waiter should use: ``base`` unarmed, else
        clamped so the stall fires within ~half a timeout of reality."""
        if self.timeout_s is None:
            return base
        return min(base, max(0.05, self.timeout_s / 2))

    def stalled(self, active: bool = True) -> bool:
        return (
            self.timeout_s is not None
            and active
            and time.monotonic() - self._beat > self.timeout_s
        )

    def trip(self, detail: str) -> None:
        """Dump the incident and raise (call sites that already know
        they stalled)."""
        msg = (
            f"{self.name}: {detail} — no progress for > "
            f"{self.timeout_s}s (stall watchdog; {self.what})"
        )
        flight_recorder().dump(msg)
        raise RuntimeError(msg)

    def check(self, detail: str, active: bool = True) -> None:
        """Raise via :meth:`trip` iff stalled; no-op otherwise."""
        if self.stalled(active):
            self.trip(detail)


def render_flight_dump(doc: Dict, tail: int = 40) -> str:
    """A flight-recorder dump as a readable incident summary (the
    ``python -m blit trace-view`` body): what tripped, where, the fault
    counters, and the last events before the trip."""
    lines = []
    t = doc.get("t", 0.0)
    when = time.strftime("%Y-%m-%d %H:%M:%S", time.gmtime(t)) if t else "?"
    lines.append("=== blit flight record ===")
    lines.append(f"reason : {doc.get('reason', '?')}")
    lines.append(f"where  : {doc.get('host', '?')}/w{doc.get('worker', 0)} "
                 f"pid {doc.get('pid', '?')}")
    lines.append(f"when   : {when} UTC")
    anchor = doc.get("anchor") or {}
    if anchor:
        # epoch - mono = the dumping process's monotonic origin on the
        # wall clock — what cross-process bundle timelines align on.
        origin = anchor.get("epoch", 0.0) - anchor.get("mono", 0.0)
        lines.append(f"anchor : epoch={anchor.get('epoch')} "
                     f"mono={anchor.get('mono')} "
                     f"(mono origin {origin:.3f})")
    if doc.get("trace"):
        # The ambient trace at dump time (ISSUE 15): follow it into the
        # stitched fleet trace (`blit trace-view --fleet ... --trace`).
        lines.append(f"trace  : {doc['trace']} "
                     f"(span {doc.get('span', '?')})")
    faults_c = doc.get("faults") or {}
    if faults_c:
        lines.append("fault counters:")
        for k, v in sorted(faults_c.items()):
            lines.append(f"  {k:<32} {v}")
    tl = doc.get("timeline") or {}
    stages = {k: v for k, v in tl.items()
              if isinstance(v, dict) and "calls" in v}
    if stages:
        lines.append("process timeline (stages):")
        for k, v in sorted(stages.items()):
            lines.append(
                f"  {k:<20} calls={v.get('calls', 0):<8} "
                f"s={v.get('seconds', 0.0):<12} bytes={v.get('bytes', 0)}")
    events = doc.get("events") or []
    lines.append(f"last {min(tail, len(events))} of {len(events)} recorded "
                 "events (oldest first):")
    for e in events[-tail:]:
        ts = time.strftime("%H:%M:%S", time.gmtime(e.get("t", 0.0)))
        kind = e.get("kind", "?")
        name = e.get("name", "?")
        rest = {k: v for k, v in e.items()
                if k not in ("t", "kind", "name")}
        detail = " ".join(f"{k}={v}" for k, v in rest.items())
        lines.append(f"  {ts} [{kind:<5}] {name} {detail}".rstrip())
    return "\n".join(lines)


# -- per-request access records (ISSUE 15 tentpole #2) -----------------------


class RequestLog:
    """A bounded JSON-lines log of per-request access records — the
    serving planes' flight-data recorder for REQUESTS: one line per
    request with request/trace id, fingerprint, client, priority,
    deadline remaining, tier outcome, queue wait, routed peer, hedge
    outcome, bytes and status (`python -m blit requests` tails,
    filters and aggregates a spool of these).

    Bounded by SIZE ROTATION: when the live file passes ``max_bytes``
    it rotates to ``<path>.1`` .. ``<path>.<max_files-1>`` and the
    oldest rolls off — a busy front door's log occupies
    ``max_bytes * max_files`` at most, forever.  Appends are one
    ``json.dumps`` + write under a lock; :meth:`record` never raises
    (access logging must not fail a request)."""

    def __init__(self, path: str, *, max_bytes: int = 8 << 20,
                 max_files: int = 4):
        self.path = path
        self.max_bytes = max(4096, int(max_bytes))
        self.max_files = max(1, int(max_files))
        self._lock = threading.Lock()
        self._f = None
        self._size = 0

    def _open(self) -> None:
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(self.path, "a")
        self._size = self._f.tell()

    def _rotate_locked(self) -> None:
        self._f.close()
        self._f = None
        if self.max_files == 1:
            os.remove(self.path)  # a one-file budget truncates in place
        else:
            for i in range(self.max_files - 1, 0, -1):
                src = self.path if i == 1 else f"{self.path}.{i - 1}"
                dst = f"{self.path}.{i}"
                if os.path.exists(src):
                    os.replace(src, dst)
        self._open()

    def record(self, **fields) -> None:
        """Append one access record (a ``t`` timestamp is stamped in;
        None-valued fields are dropped so lines stay compact)."""
        try:
            doc = {"t": round(time.time(), 6)}
            doc.update({k: v for k, v in fields.items() if v is not None})
            line = json.dumps(doc) + "\n"
            with self._lock:
                if self._f is None:
                    self._open()
                self._f.write(line)
                self._f.flush()
                self._size += len(line)
                if self._size >= self.max_bytes:
                    self._rotate_locked()
        except Exception:  # noqa: BLE001 — logging must not fail requests
            log.warning("request log append failed", exc_info=True)

    def files(self) -> List[str]:
        """Every rotation member that exists, oldest first."""
        out = [f"{self.path}.{i}"
               for i in range(self.max_files - 1, 0, -1)
               if os.path.exists(f"{self.path}.{i}")]
        if os.path.exists(self.path):
            out.append(self.path)
        return out

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                with contextlib.suppress(OSError):
                    self._f.close()
                self._f = None


def request_log_for(role: str, config=None) -> Optional[RequestLog]:
    """The configured :class:`RequestLog` for a serving component
    (``role`` names it in the spool: ``requests-<role>-<host>-<pid>``),
    or None when request logging is disabled — the disabled path is one
    dict lookup per request (:func:`blit.config.request_log_defaults`:
    ``BLIT_REQUEST_LOG`` / ``SiteConfig.request_log_dir``).

    Also applies the config's ``exemplars`` knob (process-wide — every
    serving component constructs through here, so a peer/service-only
    process honors ``SiteConfig.exemplars=False`` exactly like a door;
    last constructor wins when configs disagree in one process)."""
    from blit.config import DEFAULT, request_log_defaults

    d = request_log_defaults(DEFAULT if config is None else config)
    set_exemplars(d["exemplars"])
    if not d["dir"]:
        return None
    path = os.path.join(
        d["dir"], f"requests-{role}-{hostname()}-{os.getpid()}.jsonl")
    return RequestLog(path, max_bytes=d["max_bytes"],
                      max_files=d["files"])


# -- fleet trace stitching (ISSUE 15 tentpole #4) ----------------------------


def span_process(span_id: str) -> str:
    """The process prefix of a span/trace id (everything before the
    counter): ids are minted as ``<pid-hex + 2 random bytes>.<n>``, so
    two spans share a prefix iff one process recorded them."""
    return str(span_id).split(".", 1)[0]


def cross_process_pairs(span_dicts: Iterable[Dict]) -> int:
    """How many parent→child span edges CROSS a process boundary — the
    stitched-trace acceptance metric (ISSUE 15): a fleet request whose
    peer-side spans parent onto the front-door span contributes at
    least one."""
    spans = list(span_dicts)
    by_id = {s.get("span"): s for s in spans if s.get("span")}
    pairs = 0
    for s in spans:
        parent = s.get("parent")
        if not parent or parent not in by_id:
            continue
        if span_process(parent) != span_process(s.get("span", "")):
            pairs += 1
    return pairs


def trace_summary(span_dicts: Iterable[Dict]) -> Dict:
    """Shape of a stitched span set: totals, distinct traces/processes,
    and the cross-process edge count."""
    spans = list(span_dicts)
    traces = {s.get("trace") for s in spans if s.get("trace")}
    procs = {span_process(s.get("span", "")) for s in spans
             if s.get("span")}
    return {"spans": len(spans), "traces": len(traces),
            "processes": len(procs),
            "cross_process_pairs": cross_process_pairs(spans)}


def render_trace_tree(span_dicts: Iterable[Dict], trace_id: str,
                      max_spans: int = 200) -> str:
    """One trace as an indented parent→child tree (the ``blit
    trace-view --fleet --trace`` body): every span's name, duration,
    host/process and hedge tag, children under parents, orphans (their
    parent aged out of a bounded buffer) at the root."""
    spans = [s for s in span_dicts if s.get("trace") == trace_id]
    spans.sort(key=lambda s: s.get("t0", 0.0))
    spans = spans[:max_spans]
    ids = {s.get("span") for s in spans}
    children: Dict[str, List[Dict]] = {}
    roots: List[Dict] = []
    for s in spans:
        parent = s.get("parent")
        if parent and parent in ids:
            children.setdefault(parent, []).append(s)
        else:
            roots.append(s)
    lines = [f"trace {trace_id}: {len(spans)} span(s)"]

    def walk(s: Dict, depth: int) -> None:
        attrs = s.get("attrs") or {}
        tag = " hedge=1" if attrs.get("hedge") else ""
        where = f"{s.get('host', '?')}/{span_process(s.get('span', ''))}"
        lines.append(
            f"  {'  ' * depth}{s.get('name', '?'):<24} "
            f"{s.get('duration_s', 0.0) * 1e3:9.3f} ms  [{where}]{tag}")
        for c in children.get(s.get("span"), []):
            walk(c, depth + 1)

    for r in roots:
        walk(r, 0)
    return "\n".join(lines)


# -- process telemetry / fleet harvest --------------------------------------

_PROCESS_TL = Timeline()


def process_timeline() -> Timeline:
    """The process-wide ambient :class:`Timeline` — what worker-side entry
    points (``blit.workers.reduce_raw``, retry backoff, ...) record on so
    :func:`telemetry_snapshot` has one table to ship when the driver
    harvests the fleet."""
    return _PROCESS_TL


def telemetry_snapshot(reset: bool = False, spans: bool = True) -> Dict:
    """This process's telemetry, JSON/pickle-safe (plain builtins only —
    it crosses the agent wire): host/pid/worker identity, the process
    timeline's raw state, the fault counters, and the finished spans.
    The harvest endpoint ``WorkerPool.harvest_telemetry`` broadcasts.

    ``reset=True`` zeroes the process timeline (identity-preserving) and
    drains the span buffer after snapshotting — interval-scrape mode."""
    from blit import faults

    out = {
        "host": hostname(),
        "pid": os.getpid(),
        "worker": _WORKER,
        "anchor": wall_anchor(),
        "timeline": _PROCESS_TL.state(),
        "faults": faults.counters(),
        "spans": _TRACER.span_dicts() if spans else [],
    }
    if reset:
        _PROCESS_TL.reset()
        _TRACER.reset()
    return out


def merge_fleet(snapshots: Iterable[Optional[Dict]],
                errors: Optional[Dict[str, str]] = None) -> Dict:
    """Fold :func:`telemetry_snapshot` results into ONE per-host-keyed
    fleet report (ISSUE 5 tentpole #3): every host gets its merged stage
    table and fault counters, and the ``fleet`` entry is the whole-run
    fold.  Snapshots from the same (host, pid) are counted once — with
    the thread/local backends every "worker" answers from the driver
    process, and double-merging would inflate every counter."""
    hosts: Dict[str, Dict] = {}
    fleet = Timeline()
    fleet_faults: Dict[str, int] = {}
    spans: List[Dict] = []
    # One snapshot per (host, pid), keeping the RICHEST: with the
    # thread/local backends every "worker" answers from one process, and
    # under reset=True whichever call ran first drained the telemetry —
    # the later calls return empty snapshots that must not shadow the
    # populated one (first-wins would nondeterministically drop the run).
    best: Dict = {}
    for snap in snapshots:
        if not isinstance(snap, dict) or "host" not in snap:
            continue
        key = (snap["host"], snap.get("pid"))
        richness = (len((snap.get("timeline") or {}).get("stages") or {})
                    + len(snap.get("spans") or []))
        if key not in best or richness > best[key][0]:
            best[key] = (richness, snap)
    for _, snap in best.values():
        entry = hosts.setdefault(
            snap["host"], {"workers": [], "tl": Timeline(), "faults": {}})
        entry["workers"].append(
            {"pid": snap.get("pid"), "worker": snap.get("worker", 0)})
        tl = Timeline.from_state(snap.get("timeline") or {})
        entry["tl"].merge(tl)
        fleet.merge(tl)
        for k, v in (snap.get("faults") or {}).items():
            entry["faults"][k] = entry["faults"].get(k, 0) + v
            fleet_faults[k] = fleet_faults.get(k, 0) + v
        spans.extend(snap.get("spans") or [])
    report = {
        "hosts": {
            h: {"workers": e["workers"], "stages": e["tl"].report(),
                # Raw (unrounded) bucket counts per histogram: what the
                # native Prometheus histogram series render from
                # (ISSUE 11 satellite) — the quantile block in "stages"
                # is a rounded projection, not mergeable or bucketable.
                "hist_state": {k: hh.state()
                               for k, hh in list(e["tl"].hists.items())},
                "faults": e["faults"]}
            for h, e in sorted(hosts.items())
        },
        "fleet": fleet.report(),
        "faults": fleet_faults,
        "spans": spans,
    }
    if errors:
        report["errors"] = dict(errors)
    return report


def local_fleet_report() -> Dict:
    """The degenerate single-process fleet report (driver only) — what a
    run with no pool, or the tier-1 CI job, publishes."""
    return merge_fleet([telemetry_snapshot()])


def maybe_write_report(path: Optional[str] = None) -> Optional[str]:
    """Write :func:`local_fleet_report` JSON to ``path`` (default: the
    ``BLIT_TELEMETRY_OUT`` environment variable; no-op when unset).  The
    CI artifact hook — never raises."""
    path = path or os.environ.get("BLIT_TELEMETRY_OUT")
    if not path:
        return None
    try:
        with open(path, "w") as f:
            json.dump(local_fleet_report(), f)
        return path
    except Exception:  # noqa: BLE001 — reporting must not fail the run
        log.warning("telemetry report write to %s failed", path,
                    exc_info=True)
        return None


def prom_escape(value) -> str:
    """Prometheus label-VALUE escaping (exposition format: backslash,
    double quote and newline are the three escapes)."""
    return (str(value).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


# The two exposition content types a /metrics endpoint can answer with:
# exemplars are only legal in the OpenMetrics format, so the servers
# negotiate via the Accept header (the prometheus_client discipline) —
# a legacy text-format scrape must never see an exemplar suffix its
# parser would reject.
PROM_CTYPE = "text/plain; version=0.0.4"
OPENMETRICS_CTYPE = "application/openmetrics-text; version=1.0.0; charset=utf-8"


def wants_openmetrics(accept: Optional[str]) -> bool:
    """Did the scraper negotiate OpenMetrics (exemplar-capable)?"""
    return bool(accept) and "application/openmetrics-text" in accept


def render_prometheus(report: Dict, *, openmetrics: bool = False) -> str:
    """A fleet report (:func:`merge_fleet`) in Prometheus exposition
    format — one scrape body with host-labelled stage/gauge/histogram/
    fault series (the ``python -m blit telemetry --format prom`` output
    and the monitor endpoint's ``/metrics`` body, blit/monitor.py).

    Histograms are NATIVE Prometheus histogram series (ISSUE 11
    satellite): cumulative ``_bucket`` counts at the log2 bucket edges
    (:func:`hist_bucket_edges`) plus exact ``_sum``/``_count``, rendered
    from the per-host raw ``hist_state`` a :func:`merge_fleet` report
    carries — so a real Prometheus server computes any quantile over any
    window, instead of scraping our precomputed p50/p90/p99 (which still
    ride along as ``blit_latency_quantile`` gauges, and are all a saved
    legacy report without raw state can offer).

    ``openmetrics=True`` (the Accept-negotiated mode, ISSUE 15) adds
    per-bucket trace-id EXEMPLARS in OpenMetrics exemplar syntax and the
    ``# EOF`` trailer; the default text format stays exemplar-free —
    the legacy Prometheus text parser rejects the suffix."""
    lines: List[str] = []

    def head(metric: str, mtype: str, help_: str) -> None:
        lines.append(f"# HELP {metric} {help_}")
        lines.append(f"# TYPE {metric} {mtype}")

    head("blit_stage_seconds_total", "counter",
         "Accumulated wall seconds per pipeline stage")
    head("blit_stage_calls_total", "counter", "Stage invocations")
    head("blit_stage_bytes_total", "counter", "Bytes moved per stage")
    head("blit_gauge", "gauge", "Last sampled level")
    head("blit_latency_seconds", "histogram",
         "Log-bucketed latency distribution (64 log2 buckets from 1 us)")
    head("blit_latency_quantile", "gauge",
         "Precomputed latency quantiles (seconds; bucket-midpoint "
         "estimates)")
    head("blit_fault_total", "counter", "Failure/recovery counters")
    edges = hist_bucket_edges()
    for host, e in (report.get("hosts") or {}).items():
        hl = prom_escape(host)
        stages = e.get("stages") or {}
        for k, row in stages.items():
            if k in ("gauges", "hists", "faults") or not isinstance(row, dict):
                continue
            lab = f'{{host="{hl}",stage="{prom_escape(k)}"}}'
            lines.append(f"blit_stage_seconds_total{lab} {row.get('seconds', 0)}")
            lines.append(f"blit_stage_calls_total{lab} {row.get('calls', 0)}")
            lines.append(f"blit_stage_bytes_total{lab} {row.get('bytes', 0)}")
        for k, g in (stages.get("gauges") or {}).items():
            lines.append(
                f'blit_gauge{{host="{hl}",name="{prom_escape(k)}"}} '
                f'{g.get("last", 0)}')
        hist_state = e.get("hist_state") or {}
        for k, h in (stages.get("hists") or {}).items():
            nl = prom_escape(k)
            st = hist_state.get(k)
            if st:
                exemplars = st.get("exemplars") or {}
                acc = 0
                for i, c in enumerate(st.get("counts") or []):
                    if not c:
                        continue
                    acc += int(c)
                    line = (
                        f'blit_latency_seconds_bucket{{host="{hl}",'
                        f'name="{nl}",le="{edges[i]:.10g}"}} {acc}')
                    ex = (exemplars.get(str(i)) or exemplars.get(i)
                          if openmetrics else None)
                    if ex:
                        # OpenMetrics exemplar syntax (ISSUE 15): the
                        # most recent trace id that landed in this
                        # bucket, so a dashboard's tail bucket links
                        # straight to a stitched trace.
                        trace, v, t = ex
                        line += (f' # {{trace_id="{prom_escape(trace)}"}}'
                                 f' {float(v):.9g} {float(t):.3f}')
                    lines.append(line)
                lines.append(
                    f'blit_latency_seconds_bucket{{host="{hl}",'
                    f'name="{nl}",le="+Inf"}} {int(st.get("n", 0))}')
                lines.append(
                    f'blit_latency_seconds_sum{{host="{hl}",name="{nl}"}} '
                    f'{st.get("total", 0.0)}')
                lines.append(
                    f'blit_latency_seconds_count{{host="{hl}",'
                    f'name="{nl}"}} {int(st.get("n", 0))}')
            for q, key in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
                lines.append(
                    f'blit_latency_quantile{{host="{hl}",name="{nl}",'
                    f'quantile="{q}"}} {h.get(key, 0)}')
        for k, v in (e.get("faults") or {}).items():
            lines.append(
                f'blit_fault_total{{host="{hl}",'
                f'counter="{prom_escape(k)}"}} {v}')
    if openmetrics:
        lines.append("# EOF")
    return "\n".join(lines) + "\n"


def render_fleet_text(report: Dict) -> str:
    """A fleet report as a human-readable per-host summary (the default
    ``python -m blit telemetry`` output)."""
    lines: List[str] = []
    for host, e in (report.get("hosts") or {}).items():
        workers = e.get("workers") or []
        lines.append(f"host {host} ({len(workers)} worker"
                     f"{'s' if len(workers) != 1 else ''})")
        stages = e.get("stages") or {}
        rows = [(k, v) for k, v in stages.items()
                if isinstance(v, dict) and "calls" in v]
        if rows:
            lines.append(f"  {'stage':<22} {'calls':>8} {'seconds':>12} "
                         f"{'bytes':>16} {'GB/s':>8}")
            for k, v in sorted(rows):
                lines.append(
                    f"  {k:<22} {v.get('calls', 0):>8} "
                    f"{v.get('seconds', 0.0):>12} {v.get('bytes', 0):>16} "
                    f"{v.get('gbps', 0.0):>8}")
        for k, h in sorted((stages.get("hists") or {}).items()):
            lines.append(
                f"  hist {k:<18} n={h.get('n', 0):<7} "
                f"p50={h.get('p50', 0)} p99={h.get('p99', 0)} "
                f"max={h.get('max', 0)}")
        for k, v in sorted((e.get("faults") or {}).items()):
            lines.append(f"  fault {k:<20} {v}")
    errs = report.get("errors") or {}
    for host, msg in sorted(errs.items()):
        lines.append(f"host {host}: HARVEST FAILED — {msg}")
    fleet = report.get("fleet") or {}
    nstages = sum(1 for v in fleet.values()
                  if isinstance(v, dict) and "calls" in v)
    lines.append(f"fleet: {len(report.get('hosts') or {})} hosts, "
                 f"{nstages} stages, "
                 f"{len(report.get('spans') or [])} spans")
    return "\n".join(lines)


class HostContextFilter(logging.Filter):
    """Injects ``host`` and ``worker`` fields into every record so the
    fan-out logs stay attributable (the reference stamps host into every
    inventory row for the same reason, src/gbtworkerfunctions.jl:74)."""

    def __init__(self, worker: int = 0):
        super().__init__()
        self.host = socket.gethostname()
        self.worker = worker

    def filter(self, record: logging.LogRecord) -> bool:
        record.host = self.host
        record.worker = self.worker
        return True


class JsonLineFormatter(logging.Formatter):
    """One JSON object per record (ts/level/host/worker/name/msg) so fleet
    logs are machine-parseable (ISSUE 5 satellite) — a harvest pipeline
    must never re-parse the human format's free text."""

    def format(self, record: logging.LogRecord) -> str:
        doc = {
            "ts": round(record.created, 3),
            "level": record.levelname,
            "host": getattr(record, "host", hostname()),
            "worker": getattr(record, "worker", 0),
            "name": record.name,
            "msg": record.getMessage(),
        }
        if record.exc_info:
            doc["exc"] = self.formatException(record.exc_info)
        return json.dumps(doc)


def configure_logging(level: int = logging.INFO, worker: int = 0,
                      json_lines: bool = False, stream=None) -> None:
    """Structured stderr logging with host/worker context for every blit
    logger.  Idempotent: re-calling replaces the previous blit handler (a
    worker re-configuring with its id must not duplicate output).

    ``json_lines=True`` emits one JSON object per record
    (:class:`JsonLineFormatter`) instead of the human format — worker
    startup threads it via ``BLIT_LOG_JSON`` in the agent environment
    (:mod:`blit.agent`).  ``stream`` overrides the handler target
    (tests capture it); default stderr."""
    global _WORKER
    _WORKER = worker  # stamp spans/snapshots with the same identity
    root = logging.getLogger("blit")
    for h in list(root.handlers):
        if getattr(h, "_blit_handler", False):
            root.removeHandler(h)
    handler = logging.StreamHandler(stream)
    handler._blit_handler = True
    handler.addFilter(HostContextFilter(worker))
    if json_lines:
        handler.setFormatter(JsonLineFormatter())
    else:
        handler.setFormatter(
            logging.Formatter(
                "%(asctime)s %(levelname)s %(host)s/w%(worker)d %(name)s: %(message)s"
            )
        )
    root.setLevel(level)
    root.addHandler(handler)
    # Our handler owns blit output; don't duplicate through root handlers.
    root.propagate = False
