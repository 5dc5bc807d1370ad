"""The comparison that decides ``correct``.  All of it runs outside every
timed interval.

- header geometry, and fch1/foff/tsamp as the RAW header implies them;
- the injected tone in the product channel the headers predict;
- chosen coarse channels x all spectra against ``reference.stokes_i``'s
  rows, which the run computes once (``refpool``) and keeps;
- the guarantees the path gives today (configs/*.json ``guarantees``): no
  ``.partial`` left, the size the header implies, the manifest sidecar's
  size and CRC against the bytes on disk;
- every later product against the verified one: size, header bytes, whole
  file CRC, and a seeded sample of segments byte for byte.

A pass may make several products (``run.py``): each function here is
given one of them, with that product's own ``nfft``, ``nint``, rows and
tolerance, and the harness calls it once per product.
"""

from __future__ import annotations

import json
import os
import struct
import zlib

import numpy as np

import reference

_STRING_KEYS = {"source_name", "rawdatafile"}
_INT_KEYS = {"telescope_id", "machine_id", "data_type", "barycentric",
             "pulsarcentric", "nbits", "nsamples", "nchans", "nifs",
             "nbeams", "ibeam", "nbins"}
MANIFEST_SUFFIX = ".manifest.json"


class Incorrect(AssertionError):
    """The product is wrong.  The run goes on to print ``correct: false``.
    ``rel_err_by_slot``: the errors read, where the check got that far."""

    def __init__(self, said: str, rel_err_by_slot=None):
        super().__init__(said)
        self.rel_err_by_slot = rel_err_by_slot or {}


def read_fil_header(path: str):
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
    hdr, off = read_fil_header(path)
    data = np.memmap(path, np.float32, "r", offset=off,
                     shape=(hdr["nsamps"], hdr["nifs"], hdr["nchans"]))
    return hdr, off, data


def crc32_file(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        while chunk := f.read(1 << 26):
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def guarantees(path: str, want_rows: int, read_all: bool) -> dict:
    """What the non-``--resume`` writer promises, held against the file:
    the product is at its final path only when complete (no ``.partial``
    beside it, the rows the pass should yield), and its manifest sidecar
    states the size and, where ``read_all``, the CRC of exactly those
    bytes (the whole file is read: 4 GiB take about ten seconds here, so
    the harness asks for it where that read fits the run)."""
    if os.path.exists(path + ".partial"):
        raise Incorrect(f"{path}.partial left behind")
    hdr, off = read_fil_header(path)
    if hdr["nsamps"] != want_rows:
        raise Incorrect(f"{path}: {hdr['nsamps']} rows, want {want_rows}")
    mpath = path + MANIFEST_SUFFIX
    if not os.path.exists(mpath):
        raise Incorrect(f"{path}: no manifest sidecar published")
    with open(mpath) as f:
        doc = json.load(f)
    size = os.path.getsize(path)
    if not doc.get("complete"):
        raise Incorrect(f"{mpath}: not marked complete")
    if int(doc["bytes"]) != size or int(doc["rows"]) != want_rows:
        raise Incorrect(f"{mpath}: says {doc['bytes']} B in {doc['rows']} "
                        f"rows, file is {size} B in {want_rows}")
    if read_all and int(str(doc["crc32"]), 16) != (crc := crc32_file(path)):
        raise Incorrect(f"{mpath}: crc32 {doc['crc32']} but the bytes give "
                        f"{crc:08x}")
    with open(path, "rb") as f:
        header = f.read(off)
    return {"bytes": size, "crc32": str(doc["crc32"]).lower(),
            "rows": hdr["nsamps"], "header": header, "read_all": read_all}


def rel_err(got, want) -> float:
    """max|got - want| / max|want| — scale-relative, as
    tests/test_channelize.py pins it for MXU-grade arithmetic."""
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


def against_reference(path: str, slices, rows_of, *, nslots: int, nfft: int,
                      nint: int, rows: int, tolerance: float) -> dict:
    """``slices``: one dict per checked coarse channel with ``slot`` (its
    index among the product's coarse channels), ``raw_hdr`` (its bank's RAW
    header), ``chan`` (its index in that bank) and ``tone_fine_offset`` (or
    None where no tone was injected), counted in fine channels of
    ``tone_nfft`` (the pass's finest product: the same slices check every
    product of a pass, each at its own ``nfft``).  ``rows_of(slot)`` gives
    that channel's reference rows ``(nspectra, nfft)`` of THIS product: the
    run computes them once (``refpool``) and every call — the warm-up's, a
    pass's, the traced pass's — compares against the same kept rows."""
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
        errs[s["slot"]] = rel_err(got, rows_of(s["slot"])[:rows])
    over = {slot: e for slot, e in errs.items() if e > tolerance}
    if over:
        raise Incorrect("; ".join(
            f"coarse slot {slot}: rel err {e:.3g} > {tolerance}"
            for slot, e in over.items()), errs)
    return {"header": geometry, "tone_channel_by_slot": tones,
            "rel_err_by_slot": errs, "tolerance": tolerance}


def sample(path: str, size: int, seed: int, segments: int = 8,
           seg_bytes: int = 1 << 22) -> dict:
    """A seeded sample of the product's bytes: its first and last
    ``seg_bytes`` and ``segments`` more, ``{offset: bytes}`` (40 MiB for a
    4 GiB product), so that the verified product itself need not be kept."""
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
    for start, want in sample(path, facts["bytes"], seed).items():
        if want != golden["sample"][start]:
            raise Incorrect(f"{path}: bytes at {start} differ from the "
                            "verified product's")
