"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a trace of
this machine holds (TPU v5 lite, jax 0.9.0, ``tpu_trace_mode``
``TRACE_ONLY_XLA``, host tracer off; PERF.md section 3 has the hand
reading): one plane per chip named ``/device:TPU:<n>``; its line ``XLA
Modules`` has one event per executed program (``jit_channelize(<hash>)``),
its line ``XLA Ops`` one event per executed HLO instruction, named by the
instruction's whole text (``%fusion.14 = f32[32,1024]{...} fusion(...)``),
on the device's clock, in ns from the start of the trace.  ``Async XLA
Ops`` (copy-start/done pairs that overlap compute) is not counted as busy.
The host planes are empty: the host tracer is off because on this machine
it makes a 1.5 s pass take 45 s and grow the process by over 10 GB.

- busy: union of a chip's op intervals, mean over the chips that ran any;
- window: the traced pass's host-clock seconds, handed in by the harness,
  which starts the trace just before the pass and stops it just after;
  without it, first to last device op;
- per-op time: self time (an op's duration minus the ops nested in it)
  summed by ``<program>/<instruction name>``, mean over chips;
- collective time: self time of the instructions whose text says
  all-gather, all-reduce, collective-permute, all-to-all or
  reduce-scatter;
- idle gaps: the complement of busy on the first chip, summed by where the
  chip waited: inside a program, or between two programs (the host had not
  enqueued the next one), and the window's ends outside the first and last
  op.

``reduce_trace`` is the whole reduction; ``read`` serves one per-layer
metric from it.
"""

from __future__ import annotations

import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter",
    re.I)
MIN_GAP_NS = 1e5


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals of possibly nested/overlapping ones -> (s, e)."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    return s[first], e[last]


def _self_seconds(starts, ends) -> np.ndarray:
    """Each event's self seconds: its duration minus the events directly
    nested in it."""
    own = (ends - starts).astype(float)
    stack = []  # indices of the events still open
    for i in sorted(range(len(starts)), key=lambda i: (starts[i], -ends[i])):
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= ends[i] - starts[i]
        stack.append(i)
    return np.maximum(own, 0.0) / 1e9


def _events(line):
    names, starts, ends = [], [], []
    for ev in line.events:
        names.append(ev.name)
        starts.append(ev.start_ns)
        ends.append(ev.start_ns + ev.duration_ns)
    return names, np.asarray(starts, float), np.asarray(ends, float)


def _program(name: str) -> str:
    """``jit_channelize(17141464157075156353)`` -> ``jit_channelize``."""
    return re.sub(r"\(\d+\)$", "", name)


def _instruction(text: str) -> str:
    """An op event's display name: the instruction's own name, marked
    where it is a Pallas kernel (the text carries no kernel name today:
    ``kernel_metadata={}``)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return name + "[pallas]" if 'target="tpu_custom_call"' in text else name


class _Programs:
    """The ``XLA Modules`` line of one chip: which program ran when."""

    def __init__(self, modules):
        names, starts, ends = modules or ([], np.zeros(0), np.zeros(0))
        order = np.argsort(starts)
        self.names = [_program(names[i]) for i in order]
        self.starts, self.ends = starts[order], ends[order]

    def at(self, t):
        i = int(np.searchsorted(self.starts, t, side="right")) - 1
        return self.names[i] if i >= 0 and t < self.ends[i] else None

    def around(self, t):
        i = int(np.searchsorted(self.starts, t)) - 1
        return (self.names[i] if i >= 0 else "?",
                self.names[i + 1] if i + 1 < len(self.names) else "?")


def _idle_gaps(s, e, programs: _Programs) -> dict:
    """where -> idle seconds between the merged busy intervals (s, e)."""
    gaps = {}
    for a, b in zip(e[:-1], s[1:]):
        mid = (a + b) / 2
        if b - a < MIN_GAP_NS:
            where = "gaps under 0.1 ms"
        elif (inside := programs.at(mid)) is not None:
            where = f"inside {inside}"
        else:
            where = "host: after {}, before {}".format(*programs.around(mid))
        gaps[where] = gaps.get(where, 0.0) + (b - a) / 1e9
    return gaps


def reduce_trace(path: str, window_s: float | None = None) -> dict | None:
    """``None`` when the trace holds no device op (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    chips = []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines
                     if line.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE, ([],))[0]:
                chips.append((plane.name, lines[OPS_LINE],
                              _Programs(lines.get(MODULES_LINE))))
    if not chips:
        return None
    chips.sort(key=lambda c: c[0])
    busy, per_op, collective = [], {}, 0.0
    for _, (names, starts, ends), programs in chips:
        s, e = _union(starts, ends)
        busy.append(float((e - s).sum()) / 1e9)
        for text, start, own in zip(names, starts,
                                    _self_seconds(starts, ends)):
            shown = f"{programs.at(start) or '?'}/{_instruction(text)}"
            per_op[shown] = per_op.get(shown, 0.0) + own / len(chips)
            if COLLECTIVE.search(text):
                collective += own / len(chips)
    # Idle gaps and the span of activity, on the first chip.
    _, (_, starts, ends), programs = chips[0]
    s, e = _union(starts, ends)
    gaps, span = _idle_gaps(s, e, programs), float(e[-1] - s[0]) / 1e9
    if window_s is None:
        window_s = span
    elif window_s > span:
        gaps["host: before the first op and after the last"] = \
            window_s - span
    return {
        "chips": [c[0] for c in chips],
        "window_s": window_s,
        "busy_s": float(np.mean(busy)),
        "busy_s_by_chip": busy,
        "per_op_s": per_op,
        "collective_s": collective,
        "idle_gaps_s": gaps,
    }


def read(args: dict, ev: dict):
    """One per-layer metric from the traced pass, or ``None`` where the
    trace has no device plane."""
    tr = ev.get("trace")
    if not tr:
        return None
    gb = ev["traced_raw_bytes"] / 1e9
    what = args["value"]
    if what == "busy_s_per_GB":
        return tr["busy_s"] / gb
    if what == "idle_share":
        return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
    if what == "collective_s_per_GB":
        return tr["collective_s"] / gb
    if what == "hbm_roof_share":
        peak = ev["peaks"][ev["device_kind"]]["hbm_GBps"] * 1e9
        return 100.0 * (ev["traced_least_bytes"] / peak) / tr["busy_s"]
    raise ValueError(f"xplane reader: unknown value {what!r}")


def look(path: str, top: int = 12) -> None:
    """Print what a trace holds, for reading one by hand: every plane,
    its lines, and each line's most time-consuming event names."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            names, starts, ends = _events(line)
            if not names:
                continue
            by = {}
            for n, s, e in zip(names, starts, ends):
                by[n] = by.get(n, 0.0) + (e - s) / 1e9
            print(f"  LINE {line.name!r}: {len(names)} events, "
                  f"{(ends.max() - starts.min()) / 1e9:.3f} s span")
            for n, t in sorted(by.items(), key=lambda kv: -kv[1])[:top]:
                print(f"      {t:10.4f} s  x{names.count(n):<6d} {n[:110]}")


if __name__ == "__main__":
    import json
    import sys

    look(sys.argv[1])
    red = reduce_trace(sys.argv[1])
    if red:
        red["per_op_s"] = dict(sorted(red["per_op_s"].items(),
                                      key=lambda kv: -kv[1])[:20])
    print(json.dumps(red, indent=1))
