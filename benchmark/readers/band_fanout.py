"""The programs a band scan of SEVERAL products runs on one upload of
every mesh window (``blit scan --nfft 1048576,8,1024 --nint 51,128,3072``:
``blit.parallel.mesh.band_programs``, ``band_carry``, ``stitch_despike``):
each small leg's own device time, the fold's, their shares of the HBM
roof PER CHIP, and what the per-product stitches moved.

``readers/fanout.py`` reads the one-chip reducer's legs against the whole
recording's bytes.  On the mesh ``xplane.reduce_trace``'s ``per_op_s`` is a
MEAN over the chips while the program's counters (``integrate.emit.<k>``)
and ``traced_raw_bytes`` are the whole band's, so that count would be too
high by the number of chips: here every byte count is ONE chip's (as
``readers/band_carry.py`` is to ``readers/carry.py``).

None of these programs has arithmetic to speak of beside its bytes, so
the roof is HBM alone (``peaks.json``).  The least ONE chip must move:

- a leg (``leg_least_bytes``): its own bank's int8 samples in once and
  its share of the product's float32 rows out once.  Today a leg writes
  per-frame power for the fold to read, which is not among the least
  bytes;
- the fold (``fold_least_bytes``), summed over the products: every
  frame's float32 power of that chip's bank in once (``rows x nint``
  frames, each as large as the chip's share of a band row), the rows out
  once, and the accumulator, one such share, read and written once per
  window.  The frames of an integration still open at the pass's end and
  the small legs' extra fold of their head steps are left out, so the
  share reads a little low, never high.

Rows and row bytes come from the program's own counters
(``integrate.emit.<product>``), windows from ``dispatch``, chips from the
trace.  A program that makes one product per scan has neither the
programs nor the counters: ``read`` returns nothing.
"""

from __future__ import annotations

from readers.carry import self_seconds  # <program>/<instruction> -> seconds


def leg_least_bytes(raw_bytes: int, product_bytes: int, chips: int) -> int:
    """One chip's: its bank of the RAW in, its share of the rows out."""
    return (raw_bytes + product_bytes) // chips


def fold_least_bytes(rows: int, chip_row_bytes: int, nint: int,
                     windows: int) -> int:
    """One chip's, one product's: ``rows * nint`` frames of power in, the
    rows out, the accumulator in and out once per window."""
    return (rows * nint + rows + 2 * windows) * chip_row_bytes


def _emitted(stages: dict, product: str):
    """(rows, bytes) of one band product, or ``None`` without its counter."""
    row = stages.get(f"integrate.emit.{product}")
    if not row or not row.get("calls"):
        return None
    return row["calls"], row["bytes"]


def read(args: dict, ev: dict):
    stages = ev.get("stages") or {}
    if args["value"] == "stitch_MB_per_GB":
        rows = [stages.get(f"stitch.{p}") for p in args["products"]]
        if not all(r and "bytes" in r for r in rows):
            return None
        return sum(r["bytes"] for r in rows) / 1e6 \
            / (ev["traced_raw_bytes"] / 1e9)
    tr = ev.get("trace")
    if not tr:
        return None
    busy = self_seconds(tr, args["program"])
    chips = len(tr.get("chips") or ())
    if not busy or not chips:
        return None
    if args["value"] == "busy_s_per_GB":
        return busy / (ev["traced_raw_bytes"] / 1e9)
    if args["value"] == "leg_roof_share":
        got = _emitted(stages, args["product"])
        if got is None:
            return None
        least = leg_least_bytes(ev["traced_raw_bytes"], got[1], chips)
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
            least += fold_least_bytes(rows, nbytes // rows // chips,
                                      p["nint"], disp["calls"])
    else:
        raise ValueError(f"band_fanout reader: unknown value "
                         f"{args['value']!r}")
    peak = ev["peaks"][ev["device_kind"]]["hbm_GBps"] * 1e9
    return 100.0 * (least / peak) / busy
