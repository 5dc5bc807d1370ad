"""Per-layer metrics from the program's own stage table (``Timeline``
report of the traced pass: stage -> calls, seconds, bytes).  Host-side
busy/wait seconds and bytes of the pump's threads; the stage called
``device`` is a wait on a dispatch and never device busy time, so no
metric reads it."""

from __future__ import annotations


def read(args: dict, ev: dict):
    stages = ev.get("stages") or {}
    row = next((stages[s] for s in args["stages"] if s in stages), None)
    if row is None or not row.get("seconds"):
        return None
    if args["value"] == "rate_GBps":
        return row["bytes"] / row["seconds"] / 1e9 if row.get("bytes") \
            else None
    if args["value"] == "seconds_per_GB":
        return row["seconds"] / (ev["traced_raw_bytes"] / 1e9)
    raise ValueError(f"timeline reader: unknown value {args['value']!r}")
