"""What the measured window's passes showed on the host's clock, for a
cell where the number is too unsteady to carry a bound (two 27 s passes to
a run) and so is kept as a per-layer metric."""

from __future__ import annotations

import statistics


def read(args: dict, ev: dict):
    if args["value"] == "first_product_median_s":
        waits = ev.get("window_first_product_s")
        return statistics.median(waits) if waits else None
    raise ValueError(f"passes reader: unknown value {args['value']!r}")
