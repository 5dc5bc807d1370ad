"""What the measured window's passes showed on the host's clock, for a
cell where the number is too unsteady to carry a bound (two 27 s passes to
a run; a product disk that stalls) and so is kept as a per-layer metric:
the same statistic over the same passes as the end-to-end metric of that
name takes in the cells where it is steady."""

from __future__ import annotations

import statistics


def read(args: dict, ev: dict):
    if args["value"] == "first_product_median_s":
        waits = ev.get("window_first_product_s")
        return statistics.median(waits) if waits else None
    if args["value"] == "median_pass_rate_GBps":
        walls = ev.get("window_wall_s")
        return ev["traced_raw_bytes"] / statistics.median(walls) / 1e9 \
            if walls else None
    raise ValueError(f"passes reader: unknown value {args['value']!r}")
