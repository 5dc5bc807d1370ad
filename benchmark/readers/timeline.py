"""Per-layer metrics from the program's own stage table (``Timeline``
report of the traced pass: stage -> calls, seconds, bytes).  Host-side
busy/wait seconds and bytes of the pump's threads; the stage called
``device`` is a wait on a dispatch and never device busy time, so no
metric reads it.

The program declares its ``wait.*`` rows (``Timeline.declare``), so a wait
that never blocked is in the table with 0 calls and 0 seconds: that is a
reading, 0.0 s/GB, and not the same as a table without the row (a program
from before the wait existed), which gives nothing."""

from __future__ import annotations


def read(args: dict, ev: dict):
    stages = ev.get("stages") or {}
    row = next((stages[s] for s in args["stages"] if s in stages), None)
    if row is None or "seconds" not in row:
        return None
    if args["value"] == "rate_GBps":
        return row["bytes"] / row["seconds"] / 1e9 \
            if row["seconds"] and row.get("bytes") else None
    if args["value"] == "seconds_per_GB":
        return row["seconds"] / (ev["traced_raw_bytes"] / 1e9)
    raise ValueError(f"timeline reader: unknown value {args['value']!r}")
