"""Whose fault the chip's idle seconds are: the program's stage spans laid
against the device ops on one clock.

The clock.  A ``jax.profiler`` trace with the host tracer off still
carries a plane ``Task Environment`` with two stats, ``profile_start_time``
and ``profile_stop_time``, in Unix nanoseconds, and every device event's
``start_ns`` counts from that start.  So ``profile_start_time + start_ns``
is a device op's epoch time: the clock ``Span.t0`` (``time.time()``) is
on.  The program records one span per pump stage and per pump wait
(``Timeline.stage``: attrs ``stage=1``, ``bytes``; PERF.md section 3 has
the list), with the thread that spent it.

The rule.  The chip idles because the thread that enqueues programs has
not enqueued the next one.  For every idle instant of the first chip:

- inside a program of the first chip: ``inside <program>``; with several
  chips, up to the moment the last chip launched that run it is ``launch
  skew>`` + what the host was doing then (the chain below), because the
  chip sits in the program waiting for the others to be fed;
- else the dispatching thread's innermost open stage (the thread that
  owns the ``dispatch`` spans; ``stream`` wraps the pump and is skipped).
  If that is a ``wait.*``, go on to the thread it waits on and take that
  thread's innermost open stage, and so on: ``wait.chunk>ingest``,
  ``wait.out_drain>readback``, ``wait.sink_flush>write``, ``dispatch``,
  ``feed.read``.  A chain that ends on a thread with no stage open ends
  in ``unnamed``.

The window is the traced pass as ``xplane.reduce_trace`` has it
(``window_s``, the harness's host clock), placed on the epoch clock by the
pass's own spans: first span start to last span end, and what the window
has beyond that (the command's entry and exit, outside every span) is
``unnamed``.  So the causes add up to ``xplane``'s idle total on the first
chip.

The check.  ``clock_skew_ms``: on the first chip the programs of the k-th
dispatch must not start before the k-th ``dispatch`` span does, and the
k-th ``device`` wait must not return before their last op ends.  The worst
violation is printed; over 5 ms the mapping is wrong.

``read`` takes the ``.xplane.pb`` from the evidence's ``trace_path``
where the harness hands one on.  Today it does not, so the path comes
from this process's own spans: the last pass's root span (``reduce.to_file``,
``scan.reduce``) names its product in attr ``out``, the harness writes the
trace to ``trace/`` in the scratch directory that holds that product, and
the trace is taken only if its profile window holds that root span.  No
directory is searched by name or by age: a trace another process left
behind is never read.
"""

from __future__ import annotations

import functools
import glob
import json
import os

import numpy as np
from readers.xplane import (DEVICE_PLANE, MODULES_LINE, OPS_LINE, _events,
                            _program, _union)

CLOCK_PLANE = "Task Environment"
WRAPPERS = ("stream",)  # stages that wrap the pump, not a stage of it
ROOTS = ("reduce.to_file", "scan.reduce")  # a pass; attr `out`: its product
# wait -> the stages of the thread it waits on (how that thread is found)
WAITS_ON = {
    "wait.chunk": ("ingest", "wait.ingest_slot"),
    "wait.ingest_slot": ("device", "readback", "wait.slab"),
    "wait.out_slot": ("device", "readback", "wait.slab"),
    "wait.out_drain": ("device", "readback", "wait.slab"),
    "wait.slab": ("write", "flush"),
    "wait.sink": ("write", "flush"),
    "wait.sink_flush": ("write", "flush"),
}
UNNAMED = "unnamed"
SKEW = "launch skew>"


@functools.lru_cache(maxsize=2)
def _profile(path: str):
    """The parsed trace (several metrics read the same one)."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def clock(path: str):
    """(profile start, profile stop) in epoch seconds, or ``None`` where
    the trace does not say."""
    for plane in _profile(path).planes:
        if plane.name == CLOCK_PLANE:
            stats = dict(plane.stats)
            if "profile_start_time" in stats and "profile_stop_time" in stats:
                return (stats["profile_start_time"] / 1e9,
                        stats["profile_stop_time"] / 1e9)
    return None


def device(path: str, start_s: float) -> list:
    """Per chip, in plane order: its merged busy intervals, its last op's
    end and its program runs ``(start, end, program, run_id)``, all in
    epoch seconds."""
    chips = []
    for plane in _profile(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        _, starts, ends = _events(lines[OPS_LINE])
        if not len(starts):
            continue
        s, e = _union(starts, ends)
        runs = []
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else ()):
            runs.append((start_s + ev.start_ns / 1e9,
                         start_s + (ev.start_ns + ev.duration_ns) / 1e9,
                         _program(ev.name), dict(ev.stats).get("run_id")))
        chips.append({"name": plane.name, "busy": (start_s + s / 1e9,
                                                   start_s + e / 1e9),
                      "op_ends": np.sort(start_s + ends / 1e9),
                      "runs": sorted(runs)})
    return sorted(chips, key=lambda c: c["name"])


def launch_skew(chips: list) -> dict:
    """run_id -> (earliest, latest) start of that run over the chips."""
    by_run = {}
    for chip in chips:
        for start, _, _, run_id in chip["runs"]:
            lo, hi = by_run.get(run_id, (start, start))
            by_run[run_id] = (min(lo, start), max(hi, start))
    return by_run


class _Threads:
    """The stage spans by thread: who dispatches, who a wait waits on, and
    a thread's innermost open stage at an instant."""

    def __init__(self, spans):
        self.by_tid = {}
        for sp in spans:
            if sp["name"] not in WRAPPERS:
                self.by_tid.setdefault(sp["tid"], []).append(
                    (sp["t0"], sp["t0"] + sp["duration_s"], sp["name"]))
        self.dispatcher = self.owner(("dispatch",))

    def owner(self, names, other_than=None):
        counts = {tid: sum(1 for _, _, n in sps if n in names)
                  for tid, sps in self.by_tid.items() if tid != other_than}
        tid = max(counts, key=counts.get, default=None)
        return tid if tid is not None and counts[tid] else None

    def innermost(self, tid, t):
        open_ = [sp for sp in self.by_tid.get(tid, ()) if sp[0] <= t < sp[1]]
        # innermost: the latest to start and, of those, the first to end
        return max(open_, key=lambda sp: (sp[0], -sp[1]), default=None)

    def chain(self, t) -> str:
        tid, names, seen = self.dispatcher, [], set()
        while tid is not None and tid not in seen:
            seen.add(tid)
            sp = self.innermost(tid, t)
            if sp is None:
                names.append(UNNAMED)
                break
            names.append(sp[2])
            tid = self.owner(WAITS_ON[sp[2]], other_than=tid) \
                if sp[2] in WAITS_ON else None
        return ">".join(names) or UNNAMED

    def edges(self):
        return [t for sps in self.by_tid.values() for sp in sps
                for t in sp[:2]]


def causality(chip: dict, threads: _Threads):
    """The worst of: a program starting before its dispatch did, a
    ``device`` wait returning before its programs' last op -> (seconds,
    pairs checked).  Pairs one dispatch with its programs where they
    divide evenly, else only the first and the last."""
    def on(tid, name):
        return sorted(sp for sp in threads.by_tid.get(tid, ())
                      if sp[2] == name)

    dispatches = on(threads.dispatcher, "dispatch")
    waits = on(threads.owner(("device",)), "device")
    runs = chip["runs"]
    if not dispatches or not runs:
        return None, 0
    per = len(runs) // len(dispatches) \
        if len(runs) % len(dispatches) == 0 else 0
    groups = [runs[k * per:(k + 1) * per] for k in range(len(dispatches))] \
        if per else [runs]
    starts = dispatches if per else dispatches[:1]
    worst = max(d[0] - g[0][0] for d, g in zip(starts, groups))
    checked = len(groups)
    if len(waits) == len(dispatches) or not per:
        ends = waits if per else waits[-1:]
        for w, g in zip(ends, groups if per else [runs]):
            done = chip["op_ends"][np.searchsorted(
                chip["op_ends"], g[-1][1], side="right") - 1]
            worst = max(worst, done - w[1])
            checked += 1
    return max(worst, 0.0), checked


def attribute(path: str, spans: list, window_s: float | None = None):
    """The idle seconds of the first chip by cause, or ``None`` (and why,
    printed) where the trace or the spans do not allow it."""
    when = clock(path)
    if when is None:
        print(f"[spans] no '{CLOCK_PLANE}' start/stop stats in {path}: "
              "device ops cannot be put on the epoch clock", flush=True)
        return None
    chips = device(path, when[0])
    if not chips:
        print(f"[spans] no device op in {path}", flush=True)
        return None
    inside = [sp for sp in spans if when[0] <= sp["t0"] <= when[1]]
    stages = [sp for sp in inside if (sp.get("attrs") or {}).get("stage") == 1]
    if not stages:
        print("[spans] the program recorded no stage span inside the "
              f"profile ({len(spans)} spans in all; BLIT_SPANS=0, or a "
              "program from before the stages were spans)", flush=True)
        return None
    threads = _Threads(stages)
    first, skews = chips[0], launch_skew(chips)
    lo = min(sp["t0"] for sp in inside)
    hi = max(sp["t0"] + sp["duration_s"] for sp in inside)
    busy_s, busy_e = first["busy"]
    keep = (busy_e > lo) & (busy_s < hi)
    busy_s, busy_e = np.clip(busy_s[keep], lo, hi), np.clip(busy_e[keep],
                                                            lo, hi)
    idle = list(zip(np.concatenate([[lo], busy_e]),
                    np.concatenate([busy_s, [hi]])))
    edges = np.unique(np.asarray(
        threads.edges() + [t for r in first["runs"] for t in r[:2]]
        + [late for _, late in skews.values()]))
    causes = {}
    for a, b in idle:
        cuts = np.concatenate([[a], edges[(edges > a) & (edges < b)], [b]])
        for t0, t1 in zip(cuts[:-1], cuts[1:]):
            if t1 <= t0:
                continue
            mid = (t0 + t1) / 2
            run = next((r for r in first["runs"] if r[0] <= mid < r[1]), None)
            if run is None:
                cause = threads.chain(mid)
            elif len(chips) > 1 and mid < skews[run[3]][1]:
                cause = SKEW + threads.chain(mid)
            else:
                cause = f"inside {run[2]}"
            causes[cause] = causes.get(cause, 0.0) + (t1 - t0)
    spanned = hi - lo
    beyond = max((window_s or spanned) - spanned, 0.0)
    if beyond:
        causes[UNNAMED] = causes.get(UNNAMED, 0.0) + beyond
    total = sum(causes.values())
    unnamed = sum(v for k, v in causes.items() if k.endswith(UNNAMED))
    skew_s, pairs = causality(first, threads)
    return {
        "trace": path, "chips": len(chips), "stage_spans": len(stages),
        "profile_s": when[1] - when[0], "spanned_s": spanned,
        "window_s": window_s, "beyond_spans_s": beyond,
        "idle_s": total, "unnamed_s": unnamed, "idle_by_cause": causes,
        "launch_skew_s": {str(r): late - early
                          for r, (early, late) in sorted(
                              skews.items(), key=lambda kv: kv[1])}
        if len(chips) > 1 else None,
        "clock_skew_ms": None if skew_s is None else 1e3 * skew_s,
        "causality_pairs": pairs,
    }


def _spans(ev: dict) -> list:
    """The program's spans: the evidence's, else this process's tracer's."""
    if ev.get("spans") is not None:
        return ev["spans"]
    from blit.observability import tracer

    return tracer().span_dicts()


def find_trace(ev: dict, spans: list):
    """The traced pass's ``.xplane.pb``: the evidence's ``trace_path`` if
    the harness gives one, else the one in ``trace/`` of the directory
    that holds the last pass's product (the root span's ``out``; the band
    driver puts its products one directory further down), and only if the
    profile holds that root span.  ``None``, and why, otherwise; looked
    up once and kept on the evidence as ``trace_path``."""
    if "trace_path" not in ev:
        ev["trace_path"] = _trace_beside_product(spans)
    return ev["trace_path"]


def _trace_beside_product(spans: list):
    roots = [sp for sp in spans if sp["name"] in ROOTS
             and (sp.get("attrs") or {}).get("out")]
    if not roots:
        print(f"[spans] no root span ({', '.join(ROOTS)}) names a product: "
              "the trace cannot be found (a program from before the stages "
              "were spans, or BLIT_SPANS=0)", flush=True)
        return None
    root = max(roots, key=lambda sp: sp["t0"])
    below = os.path.dirname(os.path.abspath(root["attrs"]["out"]))
    for d in (below, os.path.dirname(below)):
        for path in sorted(glob.glob(os.path.join(
                d, "trace", "plugins", "profile", "*", "*.xplane.pb")),
                reverse=True):
            when = clock(path)
            if when and when[0] <= root["t0"] \
                    and root["t0"] + root["duration_s"] <= when[1]:
                return path
    print(f"[spans] no trace beside {root['attrs']['out']} whose profile "
          f"holds the pass's {root['name']} span", flush=True)
    return None


def attribution(ev: dict):
    """Once per traced pass (kept on the evidence), printed as the
    ``[spans]`` line: the run's idle seconds by cause."""
    if "spans_attribution" not in ev:
        got = None
        if ev.get("trace"):
            spans = _spans(ev)
            path = find_trace(ev, spans)
            if path:
                got = attribute(path, spans, ev["trace"]["window_s"])
        if got:
            shown = dict(got, idle_by_cause=sorted(
                got["idle_by_cause"].items(), key=lambda kv: -kv[1]),
                xplane_idle_s=sum(ev["trace"]["idle_gaps_s"].values()))
            print("[spans] " + json.dumps(shown), flush=True)
        ev["spans_attribution"] = got
    return ev["spans_attribution"]


def read(args: dict, ev: dict):
    what = args["value"]
    gb = ev["traced_raw_bytes"] / 1e9
    if what == "launch_skew_s_per_GB":  # the device trace alone
        path = find_trace(ev, _spans(ev)) if ev.get("trace") else None
        when = path and clock(path)
        if not when:
            return None
        chips = device(path, when[0])
        if len(chips) < 2:
            return None
        return sum(late - early
                   for early, late in launch_skew(chips).values()) / gb
    got = attribution(ev)
    if not got:
        return None
    if what == "named_share":
        return 100.0 * (1.0 - got["unnamed_s"] / got["idle_s"]) \
            if got["idle_s"] else None
    if what == "idle_s_per_GB":
        return sum(v for k, v in got["idle_by_cause"].items()
                   if k.rsplit(">", 1)[-1] in args["ends_in"]) / gb
    raise ValueError(f"spans reader: unknown value {what!r}")
