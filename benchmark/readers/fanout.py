"""Roofline shares of the programs a reduction of SEVERAL products runs
on one upload (``blit reduce --nfft 1048576,8,1024 --nint 51,128,3072``):
each small leg's own program and the one fold that serves all legs.  The
trace names programs, not Pallas calls (PERF.md section 3), so each leg's
device work has a program name of its own.

None of these programs has arithmetic to speak of beside its bytes, so
the roof is HBM alone (``peaks.json``).  The least a pass must move:

- a leg (``leg_least_bytes``): the RAW's int8 samples in once and the
  leg's product, float32 rows, out once.  Today a leg writes frame-major
  power for the fold to read, which is not among the least bytes;
- the fold (``fold_least_bytes``): per product every frame's float32
  power in once (``rows x nint`` frames, each as large as a product row),
  the rows out once, and the accumulator, one row's worth, read and
  written once per dispatch.  The frames of an integration still open at
  the pass's end are left out, so the share reads a little low, never
  high.

Rows and row bytes come from the program's own counters
(``integrate.emit.<product>``), dispatches from ``dispatch``.  A program
that makes one product per command has neither the programs nor the
counters: ``read`` returns nothing.
"""

from __future__ import annotations

from readers.carry import self_seconds


def leg_least_bytes(raw_bytes: int, product_bytes: int) -> int:
    return raw_bytes + product_bytes


def fold_least_bytes(rows: int, row_bytes: int, nint: int,
                     dispatches: int) -> int:
    return (rows * nint + rows + 2 * dispatches) * row_bytes


def _emitted(stages: dict, product: str):
    """(rows, bytes) of one product, or ``None`` without its counter."""
    row = stages.get(f"integrate.emit.{product}")
    if not row or not row.get("calls"):
        return None
    return row["calls"], row["bytes"]


def read(args: dict, ev: dict):
    tr, stages = ev.get("trace"), ev.get("stages") or {}
    if not tr:
        return None
    busy = self_seconds(tr, args["program"])
    if not busy:
        return None
    if args["value"] == "leg_roof_share":
        got = _emitted(stages, args["product"])
        if got is None:
            return None
        least = leg_least_bytes(ev["traced_raw_bytes"], got[1])
    elif args["value"] == "fold_roof_share":
        disp = stages.get("dispatch")
        if not disp or not disp.get("calls"):
            return None
        least = 0
        for p in args["products"]:
            got = _emitted(stages, p["name"])
            if got is None:
                return None
            rows, nbytes = got
            least += fold_least_bytes(rows, nbytes // rows, p["nint"],
                                      disp["calls"])
    else:
        raise ValueError(f"fanout reader: unknown value {args['value']!r}")
    peak = ev["peaks"][ev["device_kind"]]["hbm_GBps"] * 1e9
    return 100.0 * (least / peak) / busy
