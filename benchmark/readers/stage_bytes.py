"""A stage's bytes from the program's own stage table (``Timeline`` report
of the traced pass), per GB of RAW: a count the program keeps, not a time.
A table without the row gives nothing."""

from __future__ import annotations


def read(args: dict, ev: dict):
    row = (ev.get("stages") or {}).get(args["stage"])
    if row is None or "bytes" not in row:
        return None
    if args["value"] == "MB_per_GB":
        return row["bytes"] / 1e6 / (ev["traced_raw_bytes"] / 1e9)
    raise ValueError(f"stage_bytes reader: unknown value {args['value']!r}")
