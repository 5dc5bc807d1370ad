"""The carried integration's own device time (``blit.ops.channelize.
integrate_carry``, program ``jit_integrate_carry`` in the trace: the trace
names programs, not Pallas calls, PERF.md section 3) and its share of the
HBM roof.

The program has no arithmetic to speak of (one add per value), so its roof
is bytes alone.  The least a pass must move for it, ``least_bytes``: every
frame's float32 power read once (``nint`` frames to a row, each as large as
a product row), and the accumulators, one row's worth, read once and
written once per dispatch.  Rows closed are written once more, which is
under 2% here and left out, so the share reads a little low, never high.

A program from before the carry has no such ops: ``read`` returns nothing.
"""

from __future__ import annotations


def self_seconds(trace: dict, program: str) -> float:
    """Self seconds of the program's ops (``xplane.reduce_trace``
    ``per_op_s``: ``<program>/<instruction>`` -> seconds)."""
    return sum(s for op, s in trace["per_op_s"].items()
               if op.split("/", 1)[0] == program)


def least_bytes(rows: int, row_bytes: int, nint: int, dispatches: int) -> int:
    return rows * nint * row_bytes + 2 * dispatches * row_bytes


def read(args: dict, ev: dict):
    tr = ev.get("trace")
    if not tr:
        return None
    busy = self_seconds(tr, args["program"])
    if not busy:
        return None
    if args["value"] == "busy_s_per_GB":
        return busy / (ev["traced_raw_bytes"] / 1e9)
    if args["value"] == "roof_share":
        stages = ev.get("stages") or {}
        emit, disp = stages.get("integrate.emit"), stages.get("dispatch")
        if not emit or not emit.get("calls") or not disp:
            return None
        least = least_bytes(emit["calls"], emit["bytes"] // emit["calls"],
                            args["nint"], disp["calls"])
        peak = ev["peaks"][ev["device_kind"]]["hbm_GBps"] * 1e9
        return 100.0 * (least / peak) / busy
    raise ValueError(f"carry reader: unknown value {args['value']!r}")
