"""The mesh's carried integration (``blit.parallel.mesh.band_carry``,
program ``jit_band_carry`` in the trace: the trace names programs, not
ops, PERF.md section 3): its own device time and its share of the HBM
roof, PER CHIP.

``readers/carry.py`` reads the one-chip reducer's ``jit_integrate_carry``
against ``integrate.emit``'s bytes.  On the mesh ``xplane.reduce_trace``'s
``per_op_s`` is a mean over the chips while ``integrate.emit``'s bytes are
the whole band's, so that count would be too high by the number of chips:
here the bytes are one chip's.

The program has no arithmetic to speak of (one add per value), so its roof
is bytes alone.  The least one chip must move for it in a pass,
``least_bytes``: every frame's float32 power of its own bank read once
(``nint`` frames to a row, each as large as that chip's share of a band
row), and its accumulator, one such share, read once and written once per
window.  The row buffer each window hands on (zeros unless the row closed)
is left out, so the share reads a little low, never high.

A program from before the carry has no such ops: ``read`` returns nothing.
"""

from __future__ import annotations

from readers.carry import self_seconds  # <program>/<instruction> -> seconds


def least_bytes(rows: int, chip_row_bytes: int, nint: int,
                windows: int) -> int:
    """Bytes one chip moves at the least: ``rows * nint`` frames of power
    in, the accumulator in and out once per window."""
    return rows * nint * chip_row_bytes + 2 * windows * chip_row_bytes


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
        chips = len(tr.get("chips") or ())
        if not emit or not emit.get("calls") or not disp or not chips:
            return None
        least = least_bytes(emit["calls"],
                            emit["bytes"] // emit["calls"] // chips,
                            args["nint"], disp["calls"])
        peak = ev["peaks"][ev["device_kind"]]["hbm_GBps"] * 1e9
        return 100.0 * (least / peak) / busy
    raise ValueError(f"band_carry reader: unknown value {args['value']!r}")
