"""What a stage's idle seconds were spent on: the program's PART spans laid
against the device ops the way ``readers/spans.py`` lays its stages.

A part (``Timeline.part``: attr ``part=1``, no ``stage``; PERF.md section 3)
says what some of a stage's seconds went to (the coefficient bank inside
``dispatch``, each ``device_put``, the product digest inside ``write``) and
is no state of its thread: ``spans.py`` does not see it, and every stage
keeps its idle seconds.  Here the parts of the traced pass are marked as
stages in a COPY of the spans, each under the name ``<enclosing
stage>|<part>``, and ``spans.attribute`` runs once more on the same trace
and window.  An idle instant inside a part is then named by it
(``wait.sink_flush>write|write.digest``), every other one as before.

The ``[parts]`` line also carries two checks, no metric:

- ``conservation_ms``: per enclosing stage, the idle seconds ``[spans]``
  names by it less what ``[parts]`` names by it and by its parts; the worst
  is printed, and over 1 ms the promotion moved seconds between stages.
- ``call_skew_ms``: a ``dispatch.call`` span names the programs it called
  (attr ``programs``), so the k-th run of a program on the first chip is the
  k-th call that names it, however many programs a dispatch has: the worst
  of a run starting before its call did.  Over 5 ms the clocks disagree.

A pass without parts (a program from before they existed, ``BLIT_SPANS=0``)
reads nothing: ``None``, never 0.
"""

from __future__ import annotations

import json

from readers import spans

SEP = "|"
CALL = "dispatch.call"


def _is(sp: dict, kind: str) -> bool:
    return (sp.get("attrs") or {}).get(kind) == 1


def promote(pass_spans: list) -> list:
    """A copy in which every part is a stage ``<enclosing stage>|<part>``:
    the innermost stage of its own thread open at its middle."""
    stages = [sp for sp in pass_spans
              if _is(sp, "stage") and sp["name"] not in spans.WRAPPERS]
    out = []
    for sp in pass_spans:
        if not _is(sp, "part"):
            out.append(sp)
            continue
        mid = sp["t0"] + sp["duration_s"] / 2
        around = [st for st in stages if st["tid"] == sp["tid"]
                  and st["t0"] <= mid < st["t0"] + st["duration_s"]]
        host = max(around, key=lambda st: (st["t0"], -st["duration_s"]),
                   default={"name": spans.UNNAMED})["name"]
        out.append(dict(sp, name=host + SEP + sp["name"],
                        attrs=dict(sp["attrs"], stage=1)))
    return out


def _tails(causes: dict) -> dict:
    """Idle seconds by the last link of their cause."""
    by = {}
    for cause, s in causes.items():
        tail = cause.rsplit(">", 1)[-1]
        by[tail] = by.get(tail, 0.0) + s
    return by


def call_skew(runs: list, pass_spans: list):
    """(worst ms of a program's run starting before the call that names
    it, pairs checked, runs and named calls left unpaired)."""
    named = {}
    for sp in sorted(pass_spans, key=lambda sp: sp["t0"]):
        if sp["name"] == CALL:
            for program in (sp.get("attrs") or {}).get("programs") or ():
                named.setdefault(program, []).append(sp["t0"])
    worst, pairs, unpaired = 0.0, 0, 0
    for program, calls in named.items():
        started = [r[0] for r in runs if r[2] == program]
        unpaired += abs(len(started) - len(calls))
        for call, run in zip(calls, started):
            worst, pairs = max(worst, call - run), pairs + 1
    return (1e3 * worst if pairs else None), pairs, unpaired


def attribution(ev: dict):
    """Once per traced pass (kept on the evidence), printed as the
    ``[parts]`` line: the pass's idle seconds with the parts promoted."""
    if "parts_attribution" not in ev:
        got, base = None, spans.attribution(ev)
        when = base and spans.clock(base["trace"])
        if when:
            mine = [sp for sp in spans._spans(ev)
                    if when[0] <= sp["t0"] <= when[1]]
            if any(_is(sp, "part") for sp in mine):
                got = spans.attribute(base["trace"], promote(mine),
                                      ev["trace"]["window_s"])
        if got:
            before, after = _tails(base["idle_by_cause"]), \
                _tails(got["idle_by_cause"])
            by_part, moved = {}, dict(before)
            for tail, s in after.items():
                stage, _, part = tail.partition(SEP)
                moved[stage] = moved.get(stage, 0.0) - s
                if part:
                    by_part[part] = by_part.get(part, 0.0) + s
            skew, pairs, unpaired = call_skew(
                spans.device(base["trace"], when[0])[0]["runs"], mine)
            got = {"idle_by_cause": got["idle_by_cause"],
                   "idle_by_part": by_part,
                   "part_spans": sum(_is(sp, "part") for sp in mine),
                   "conservation_ms": 1e3 * max(map(abs, moved.values())),
                   "call_skew_ms": skew, "call_pairs": pairs,
                   "call_unpaired": unpaired}
            print("[parts] " + json.dumps(dict(got, idle_by_cause=sorted(
                ((k, v) for k, v in got["idle_by_cause"].items()
                 if SEP in k), key=lambda kv: -kv[1]))), flush=True)
        ev["parts_attribution"] = got
    return ev["parts_attribution"]


def read(args: dict, ev: dict):
    got = attribution(ev)
    if not got:
        return None
    if args["value"] == "idle_s_per_GB":
        return sum(got["idle_by_part"].get(p, 0.0)
                   for p in args["ends_in"]) / (ev["traced_raw_bytes"] / 1e9)
    raise ValueError(f"parts reader: unknown value {args['value']!r}")
