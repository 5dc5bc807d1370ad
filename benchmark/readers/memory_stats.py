"""Peak device memory: ``memory_stats()["peak_bytes_in_use"]``, the
fullest of the cell's chips, over the life of the process (warm-up, the
window and the traced pass)."""

from __future__ import annotations


def read(args: dict, ev: dict):
    peak = ev.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
