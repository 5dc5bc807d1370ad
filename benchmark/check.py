"""What every product kind's comparison shares.  All of it runs outside
every timed interval.

What decides ``correct`` for a product is its KIND's to say
(``products/<kind>.py``: the guarantees the path gives, the plain
reference, every later product against the verified one); here is what
no kind writes again: the exception a wrong product raises, the CRC of a
file, the scale-relative error, and the manifest sidecar's check (every
writer of blit publishes the same ``<product>.manifest.json``: complete,
the size, the rows it claimed and the CRC of exactly the product's bytes).

A pass may make several products (``run.py``): a kind's functions are
given one of them, with that product's own entry of the plan, and the
harness calls them once per product.
"""

from __future__ import annotations

import json
import os
import zlib

import numpy as np

MANIFEST_SUFFIX = ".manifest.json"


class Incorrect(AssertionError):
    """The product is wrong.  The run goes on to print ``correct: false``.
    ``compared``: the numbers read, ``{name: number}`` under the names the
    kind's ``limits`` gives, where the check got that far."""

    def __init__(self, said: str, compared=None):
        super().__init__(said)
        self.compared = compared or {}


def crc32_file(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        while chunk := f.read(1 << 26):
            crc = zlib.crc32(chunk, crc)
    return crc & 0xFFFFFFFF


def manifest(path: str, want_rows: int, read_all: bool) -> dict:
    """The sidecar of the product at ``path``, held against the file: it is
    there, marked complete, states the file's size and ``want_rows`` rows
    and, where ``read_all``, the CRC of exactly those bytes (the whole file
    is read) -> the document."""
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
    return doc


def rel_err(got, want) -> float:
    """max|got - want| / max|want| — scale-relative, as
    tests/test_channelize.py pins it for MXU-grade arithmetic."""
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())
