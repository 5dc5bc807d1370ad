"""Product kind ``fil``: a SIGPROC filterbank of float32 Stokes I whose size
follows from the plan (a traffic file's ``products[]`` entry without
``kind``).  Everything the harness does to a product that depends on the
FORMAT is asked of a module like this one, found by the entry's ``kind``
as a driver is by its name:

sizing      ``sized`` (the plan's entry: rows and the bytes of one),
            ``nothing`` (why a pass would leave this product empty),
            ``bytes_at``, ``rows_under``, ``samples_for``, ``frames``
            (the cut under a cap on the size of one file; a kind with
            ``RAGGED`` true never sizes that cut);
landed      when the watcher calls the product's first rows landed;
checked     ``guarantees`` (the facts kept of the verified product),
            ``sample`` and ``same_product`` (every later pass against the
            verified one), ``reference_tasks`` / ``compute`` (what the
            pool's children compute from a channel's stream and keep) and
            ``against_reference`` -> ``(said, compared)``: the log line
            and every number compared, under the names ``limits`` gives;
roof        ``least_bytes``, this product's part of what the device must
            move (``hbm_roof_share``).

This file is ``check.py``'s and ``run.plan_pass``'s code of PRs 22-39,
moved; ``reference.stokes_i`` stays where it is and is this kind's task.
"""

from __future__ import annotations

import os
import struct

import numpy as np

import reference
from check import Incorrect, manifest, rel_err
from scratch import FIL_HEADER_ROOM

RAGGED = False          # its size follows from the plan
ALL_CHANNELS = False    # its reference reads the checked channels only
FIRST_ROWS_BYTES = 1 << 16   # header + the first rows have landed

_STRING_KEYS = {"source_name", "rawdatafile"}
_INT_KEYS = {"telescope_id", "machine_id", "data_type", "barycentric",
             "pulsarcentric", "nbits", "nsamples", "nchans", "nifs",
             "nbeams", "ibeam", "nbins"}


# -- sizing --------------------------------------------------------------------

def sized(spec: dict, samples: int, *, nslots: int, ntap: int) -> dict:
    """The plan's entry for a pass of ``samples`` samples a coarse channel."""
    return {"name": spec["name"], "nfft": spec["nfft"], "nint": spec["nint"],
            "tolerance": spec["tolerance"],
            "row_bytes": nslots * spec["nfft"] * 4,
            "rows": (samples // spec["nfft"] - (ntap - 1)) // spec["nint"]}


def nothing(p: dict):
    """What a pass that leaves this product empty would hold none of."""
    if p["rows"] < 1:
        return (f"no row of product {p['name']!r} at nfft {p['nfft']}, "
                f"nint {p['nint']}")
    return None


def bytes_at(p: dict, rows=None) -> int:
    """The file's size at ``rows`` rows (the plan's where not given)."""
    return (p["rows"] if rows is None else rows) * p["row_bytes"] \
        + FIL_HEADER_ROOM


def rows_under(p: dict, cap: int) -> int:
    return (cap - FIL_HEADER_ROOM) // p["row_bytes"]


def frames(p: dict, rows: int) -> int:
    """PFB frames of this product's ``nfft`` that ``rows`` rows integrate."""
    return rows * p["nint"]


def samples_for(p: dict, rows: int, ntap: int) -> int:
    return (frames(p, rows) + ntap - 1) * p["nfft"]


def least_bytes(p: dict) -> int:
    """Every float32 product value out once."""
    return p["bytes"]


# -- the file ------------------------------------------------------------------

def read_header(path: str):
    """SIGPROC header -> ``(dict, data offset)``; every keyword that is
    neither a known string nor a known int is a double."""
    def string(f):
        (n,) = struct.unpack("<i", f.read(4))
        if not 0 < n < 256:
            raise Incorrect(f"{path}: header string length {n}")
        return f.read(n).decode("ascii")

    hdr = {}
    with open(path, "rb") as f:
        if string(f) != "HEADER_START":
            raise Incorrect(f"{path}: not a SIGPROC filterbank file")
        while (key := string(f)) != "HEADER_END":
            if key in _STRING_KEYS:
                hdr[key] = string(f)
            elif key in _INT_KEYS:
                (hdr[key],) = struct.unpack("<i", f.read(4))
            else:
                (hdr[key],) = struct.unpack("<d", f.read(8))
        off = f.tell()
    row = hdr["nchans"] * hdr["nifs"] * hdr["nbits"] // 8
    payload = os.path.getsize(path) - off
    if payload % row:
        raise Incorrect(f"{path}: {payload} B of payload is not whole rows "
                        f"of {row} B")
    hdr["nsamps"] = payload // row
    return hdr, off


def open_fil(path: str):
    hdr, off = read_header(path)
    data = np.memmap(path, np.float32, "r", offset=off,
                     shape=(hdr["nsamps"], hdr["nifs"], hdr["nchans"]))
    return hdr, off, data


def landed(path: str) -> bool:
    """The first rows are in the file or its ``.partial``."""
    for p in (path + ".partial", path):
        try:
            if os.path.getsize(p) > FIRST_ROWS_BYTES:
                return True
        except OSError:
            pass
    return False


# -- checked -------------------------------------------------------------------

def guarantees(path: str, p: dict, want_rows: int, read_all: bool) -> dict:
    """What the non-``--resume`` writer promises, held against the file:
    the product is at its final path only when complete (no ``.partial``
    beside it, the rows the pass should yield), and its manifest sidecar
    states the size and, where ``read_all``, the CRC of exactly those
    bytes (the whole file is read: 4 GiB take about ten seconds here, so
    the harness asks for it where that read fits the run)."""
    if os.path.exists(path + ".partial"):
        raise Incorrect(f"{path}.partial left behind")
    hdr, off = read_header(path)
    if hdr["nsamps"] != want_rows:
        raise Incorrect(f"{path}: {hdr['nsamps']} rows, want {want_rows}")
    doc = manifest(path, want_rows, read_all)
    with open(path, "rb") as f:
        header = f.read(off)
    return {"bytes": os.path.getsize(path),
            "crc32": str(doc["crc32"]).lower(),
            "rows": hdr["nsamps"], "header": header, "read_all": read_all}


def reference_tasks(p: dict, slices, *, ntap: int, despike: bool) -> list:
    """One task a checked channel: ``(slot, cost, arguments)``, the cost
    in the unit the seconds grow with."""
    return [(s["slot"], p["nfft"],
             {"nfft": p["nfft"], "ntap": ntap, "nint": p["nint"],
              "despike": despike})
            for s in slices]


def compute(volt, args: dict):
    """The child's work: one call of the unchanged ``reference.stokes_i``
    over the channel's whole int8 stream, all rows."""
    return reference.stokes_i(volt, nfft=args["nfft"], ntap=args["ntap"],
                              nint=args["nint"],
                              despike=bool(args["despike"]))


def limits(p: dict) -> dict:
    """The names ``against_reference`` compares under, each with its
    limit."""
    return {f"rel_err.{p['name']}": p["tolerance"]}


def against_reference(path: str, p: dict, slices, kept_of, *, rows: int,
                      nslots: int):
    """``slices``: one dict per checked coarse channel with ``slot`` (its
    index among the product's coarse channels), ``raw_hdr`` (its bank's RAW
    header), ``chan`` (its index in that bank) and ``tone_fine_offset`` (or
    None where no still tone was injected), counted in fine channels of
    ``tone_nfft`` (the pass's finest product: the same slices check every
    product of a pass, each at its own ``nfft``).  ``kept_of(slot)`` gives
    that channel's reference rows ``(nspectra, nfft)`` of THIS product: the
    run computes them once (``refpool``) and every call — the warm-up's, a
    pass's, the traced pass's — compares against the same kept rows."""
    nfft, nint, tolerance = p["nfft"], p["nint"], p["tolerance"]
    hdr, _, data = open_fil(path)
    geometry = {"nchans": nslots * nfft, "nifs": 1, "nbits": 32,
                "nsamps": rows}
    for k, v in geometry.items():
        if hdr[k] != v:
            raise Incorrect(f"product header {k}={hdr[k]}, want {v}")
    first = min(slices, key=lambda s: s["slot"])
    want = reference.product_header(first["raw_hdr"], nfft=nfft, nint=nint)
    want["fch1"] -= (first["slot"] - first["chan"]) * nfft * want["foff"]
    for k, v in want.items():
        if abs(hdr[k] - v) > 1e-9 * max(1.0, abs(v)):
            raise Incorrect(f"product header {k}={hdr[k]}, want {v}")
    errs, tones = {}, {}
    name = f"rel_err.{p['name']}"
    for s in slices:
        lo = s["slot"] * nfft
        got = data[:, 0, lo:lo + nfft]
        if not np.isfinite(got).all():
            raise Incorrect(f"non-finite product in coarse slot {s['slot']}")
        if s["tone_fine_offset"] is not None:
            rh = s["raw_hdr"]
            chan_bw = rh["OBSBW"] / rh["OBSNCHAN"]
            f_sky = (rh["OBSFREQ"] - rh["OBSBW"] / 2
                     + (s["chan"] + 0.5) * chan_bw
                     + s["tone_fine_offset"] * chan_bw / s["tone_nfft"])
            predicted = int(round((f_sky - hdr["fch1"]) / hdr["foff"]))
            found = {lo + int(np.argmax(got[t])) for t in range(rows)}
            if found != {predicted}:
                raise Incorrect(f"tone found in channels {sorted(found)}, "
                                f"headers predict {predicted}")
            tones[s["slot"]] = predicted
        errs[s["slot"]] = rel_err(got, kept_of(s["slot"])[:rows])
    over = {slot: e for slot, e in errs.items() if e > tolerance}
    if over:
        raise Incorrect("; ".join(
            f"coarse slot {slot}: rel err {e:.3g} > {tolerance}"
            for slot, e in over.items()), {name: max(errs.values())})
    return ({"header": geometry, "tone_channel_by_slot": tones,
             "rel_err_by_slot": errs, "tolerance": tolerance},
            {name: max(errs.values())} if errs else {})


def sample(path: str, facts: dict, seed: int, segments: int = 8,
           seg_bytes: int = 1 << 22) -> dict:
    """A seeded sample of the product's bytes: its first and last
    ``seg_bytes`` and ``segments`` more, ``{offset: bytes}`` (40 MiB for a
    4 GiB product), so that the verified product itself need not be kept."""
    size = facts["bytes"]
    rng = np.random.default_rng([seed, size])
    starts = {0, max(0, size - seg_bytes),
              *(int(s) for s in rng.integers(0, max(1, size - seg_bytes),
                                             segments))}
    with open(path, "rb") as f:
        return {s: (f.seek(s), f.read(seg_bytes))[1] for s in sorted(starts)}


def same_product(path: str, facts: dict, golden: dict, seed: int) -> None:
    """The same bytes in must give the same bytes out: size, rows, header,
    the writer's running CRC of the whole file (the manifest's, which
    ``guarantees`` holds against the bytes wherever it reads them all) and
    the seeded sample of segments, byte for byte, against the verified
    product's (``golden``: its ``guarantees`` facts plus ``sample``)."""
    for k in ("bytes", "rows", "header", "crc32"):
        if facts[k] != golden[k]:
            raise Incorrect(f"{path}: {k} differs from the verified "
                            f"product's ({facts[k]!r:.80} / {golden[k]!r:.80})")
    for start, want in sample(path, facts, seed).items():
        if want != golden["sample"][start]:
            raise Incorrect(f"{path}: bytes at {start} differ from the "
                            "verified product's")

