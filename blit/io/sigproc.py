"""SIGPROC filterbank (.fil) codec.

Replaces Blio.jl's ``Filterbank.Header`` / ``Filterbank.mmap``
(reference usage: src/gbtworkerfunctions.jl:131-139, 171-177).

Format: a binary header of length-prefixed keyword items bracketed by
``HEADER_START``/``HEADER_END``, followed by raw samples.  Sample layout is
time-major — for each time sample, ``nifs`` spectra of ``nchans`` values —
i.e. C-order ``(nsamps, nifs, nchans)``, memory-identical to the reference's
column-major ``(nchans, nifs, nsamps)`` (see blit/ops/fqav.py layout note).
"""

from __future__ import annotations

import os
import struct
from typing import BinaryIO, Dict, Optional, Tuple

import numpy as np

# Keyword -> value type.  The SIGPROC header is self-describing only in
# keyword names, so the codec needs this table (same set Blio.jl understands).
_STRING_KEYS = {"source_name", "rawdatafile"}
_INT_KEYS = {
    "telescope_id",
    "machine_id",
    "data_type",
    "barycentric",
    "pulsarcentric",
    "nbits",
    "nsamples",
    "nchans",
    "nifs",
    "nbeams",
    "ibeam",
    "nbins",
}
_DOUBLE_KEYS = {
    "az_start",
    "za_start",
    "src_raj",
    "src_dej",
    "tstart",
    "tsamp",
    "fch1",
    "foff",
    "refdm",
    "period",
}

_DTYPES = {8: np.uint8, 16: np.uint16, 32: np.float32}


def _read_string(f: BinaryIO) -> str:
    (n,) = struct.unpack("<i", f.read(4))
    if not 0 < n < 256:
        raise ValueError(f"sigproc: implausible header string length {n}")
    return f.read(n).decode("ascii")


def _write_string(f: BinaryIO, s: str) -> None:
    b = s.encode("ascii")
    f.write(struct.pack("<i", len(b)))
    f.write(b)


def read_fil_header(path: str) -> Tuple[Dict, int]:
    """Read a SIGPROC header.  Returns ``(header_dict, data_offset_bytes)``.

    The dict holds the raw on-disk keywords plus computed ``nsamps`` (from
    file size when ``nsamples`` is absent/zero, as Blio does).
    """
    hdr: Dict = {}
    with open(path, "rb") as f:
        magic = _read_string(f)
        if magic != "HEADER_START":
            raise ValueError(f"{path}: not a SIGPROC filterbank file")
        while True:
            key = _read_string(f)
            if key == "HEADER_END":
                break
            if key in _STRING_KEYS:
                hdr[key] = _read_string(f)
            elif key in _INT_KEYS:
                (hdr[key],) = struct.unpack("<i", f.read(4))
            elif key in _DOUBLE_KEYS:
                (hdr[key],) = struct.unpack("<d", f.read(8))
            else:
                raise ValueError(f"{path}: unknown sigproc header keyword {key!r}")
        offset = f.tell()
    nbits = hdr.get("nbits", 32)
    nchans = hdr.get("nchans", 1)
    nifs = hdr.get("nifs", 1)
    sample_bytes = nchans * nifs * nbits // 8
    data_bytes = os.path.getsize(path) - offset
    hdr["nsamps"] = data_bytes // sample_bytes if sample_bytes else 0
    return hdr, offset


def read_fil_data(
    path: str, header: Optional[Dict] = None, mmap: bool = True
) -> Tuple[Dict, np.ndarray]:
    """Return ``(header, data)`` with data shaped ``(nsamps, nifs, nchans)``.

    ``mmap=True`` returns a read-only memmap (the analog of
    ``Filterbank.mmap``, src/gbtworkerfunctions.jl:173); callers slice it and
    the memmap is unmapped when garbage-collected.
    """
    if header is None:
        header, offset = read_fil_header(path)
    else:
        _, offset = read_fil_header(path)
    nbits = header.get("nbits", 32)
    if nbits not in _DTYPES:
        raise ValueError(f"{path}: unsupported nbits={nbits}")
    # Header-vs-payload cross-check (ISSUE 13 satellite, closing the
    # gap the validate_slab docstring documents): SIGPROC derives nsamps
    # from file size, so a payload that is not a whole number of
    # (nifs, nchans) spectra means the header lies about the layout
    # (torn write, wrong nchans/nbits, foreign bytes) — REFUSE with a
    # clear error instead of returning a silently mis-shaped array.
    nifs = header.get("nifs", 1)
    nchans = header["nchans"]
    sample_bytes = nchans * nifs * nbits // 8
    payload = os.path.getsize(path) - offset
    if sample_bytes <= 0 or payload % sample_bytes:
        raise ValueError(
            f"{path}: payload of {payload} bytes is not a whole number "
            f"of (nifs={nifs}, nchans={nchans}, nbits={nbits}) spectra "
            f"of {sample_bytes} bytes — truncated or corrupt product "
            "(header disagrees with the bytes on disk)"
        )
    shape = (header["nsamps"], header.get("nifs", 1), header["nchans"])
    if mmap:
        data = np.memmap(path, dtype=_DTYPES[nbits], mode="r", offset=offset, shape=shape)
    else:
        with open(path, "rb") as f:
            f.seek(offset)
            data = np.fromfile(f, dtype=_DTYPES[nbits]).reshape(shape)
    return header, data



def validate_slab(slab: np.ndarray, nifs: int, nchans: int,
                  dtype: np.dtype) -> np.ndarray:
    """The SIGPROC slab guard, shared by every ``.fil`` append path
    (FilWriter here and blit.pipeline.ResumableFilWriter): SIGPROC derives
    nsamps from file size, so a mis-shaped or mis-typed slab would write a
    valid-looking corrupt product nothing downstream can detect.  Shape
    must match exactly; dtype is coerced only within the same kind
    (float64→float32 fine; float→uint8 would silently wrap sample values
    and is refused)."""
    if slab.ndim != 3 or slab.shape[1:] != (nifs, nchans):
        raise ValueError(
            f"append: slab shape {slab.shape} does not extend "
            f"(*, {nifs}, {nchans})"
        )
    if slab.dtype != dtype:
        slab = slab.astype(dtype, casting="same_kind")
    return np.ascontiguousarray(slab)


class FilWriter:
    """Streaming ``.fil`` slab writer with ``.partial`` atomicity — the
    SIGPROC twin of :class:`blit.io.fbh5.FBH5Writer`'s append interface.
    SIGPROC derives nsamps from file size, so append-only streaming is
    exact; bytes land in a ``.partial`` sibling renamed on :meth:`close`
    (a crash mid-stream must not leave a valid-looking truncated product).
    Backs both ``RawReducer.reduce_to_file`` and the mesh scan writer
    (blit/parallel/scan.py) so the atomicity protocol lives in one place.
    """

    def __init__(self, path: str, header: Dict, nifs: int, nchans: int,
                 dtype=np.float32, timeline=None):
        import os as _os

        from blit import integrity

        self.final_path = path
        self.path = path + ".partial"
        self._os = _os
        self.nifs = nifs
        self.nchans = nchans
        self.dtype = np.dtype(dtype)
        write_fil(self.path, header, np.zeros((0, nifs, nchans), dtype))
        # Product manifest (ISSUE 13): per-window digests + whole-file
        # CRC, folded as slabs append (this runs on the write-behind
        # sink thread under the async plane — digesting rides the
        # thread that already owns the bytes: what the digest takes of
        # its `write` is the part `write.digest` of ``timeline``) and
        # published as a <product>.manifest.json sidecar at close.
        self._mf = integrity.ManifestWriter(
            self.final_path, "fil",
            row_bytes=nifs * nchans * self.dtype.itemsize,
            writer=type(self).__name__, timeline=timeline)
        self._mf.data_offset = _os.path.getsize(self.path)
        self._mf.fold_path(self.path)
        self._f = open(self.path, "ab")
        self.nsamps = 0

    def append(self, slab: np.ndarray) -> None:
        """Append ``(k, nifs, nchans)`` spectra (validated + same-kind
        dtype-coerced by :func:`validate_slab`)."""
        slab = validate_slab(slab, self.nifs, self.nchans, self.dtype)
        slab.tofile(self._f)
        self.nsamps += slab.shape[0]
        self._mf.fold(slab)
        self._mf.claim(self.nsamps)

    def flush(self) -> None:
        """Push appended bytes to the OS — the write-behind sink's flush
        barrier hook (:meth:`blit.outplane.AsyncSink.flush`).  Durability
        (fsync) stays the resumable writers' job; the atomic-publish
        rename on :meth:`close` is this writer's completion marker."""
        if self._f is not None:
            self._f.flush()

    def close(self) -> None:
        if self._f is None:
            return
        try:
            self._f.close()
            self._f = None
            self._os.replace(self.path, self.final_path)
        except BaseException:
            self.abort()
            raise
        # After the atomic publish: the manifest sidecar (best-effort —
        # a manifest-write failure must never un-publish the product).
        self._mf.publish()

    def abort(self) -> None:
        """Drop the partial product (crash/exception path)."""
        if self._f is not None:
            self._f.close()
            self._f = None
        if self._os.path.exists(self.path):
            self._os.unlink(self.path)

    def __enter__(self):
        return self

    def __exit__(self, etype, _e, _tb):
        if etype is None:
            self.close()
        else:
            self.abort()


def write_fil(path: str, header: Dict, data: np.ndarray) -> None:
    """Write a SIGPROC filterbank file.

    ``data`` must be shaped ``(nsamps, nifs, nchans)``; dtype determines
    ``nbits``.  Header keywords not in the SIGPROC keyword tables are ignored
    (so normalized headers round-trip).
    """
    if data.ndim != 3:
        raise ValueError("write_fil: data must be (nsamps, nifs, nchans)")
    nbits = {np.uint8: 8, np.uint16: 16, np.float32: 32}[data.dtype.type]
    hdr = dict(header)
    hdr["nbits"] = nbits
    hdr["nchans"] = data.shape[2]
    hdr["nifs"] = data.shape[1]
    with open(path, "wb") as f:
        _write_string(f, "HEADER_START")
        for key, val in hdr.items():
            if key in _STRING_KEYS:
                _write_string(f, key)
                _write_string(f, str(val))
            elif key in _INT_KEYS:
                _write_string(f, key)
                f.write(struct.pack("<i", int(val)))
            elif key in _DOUBLE_KEYS:
                _write_string(f, key)
                f.write(struct.pack("<d", float(val)))
            # silently skip computed/unknown keys (nsamps, nfpc, data_size, ...)
        _write_string(f, "HEADER_END")
        np.ascontiguousarray(data).tofile(f)
