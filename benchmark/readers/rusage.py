"""Host CPU seconds (user + system, this process and its children) the
measured window cost, per GB of RAW reduced in it: the cores a node must
give a chip for that rate."""

from __future__ import annotations


def read(args: dict, ev: dict):
    if not ev.get("window_raw_bytes"):
        return None
    return ev["window_cpu_s"] / (ev["window_raw_bytes"] / 1e9)
