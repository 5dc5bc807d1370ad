"""Product kind ``hits``: the drift search's ``.hits`` table, the format
``blit/io/hits.py`` writes today — JSON lines: a header record (``kind``
``blit.hits``, ``version``, the search's header), then one line a hit, in
window order; written as ``.partial`` and renamed on success; a manifest
sidecar with the size, the windows claimed (its ``rows``) and the CRC of
exactly the file's bytes.  A RAGGED product: what a pass lands depends on
the data, so the plan states the MOST it can hold (``top_k`` hits a coarse
channel a window) and it never sizes a cut under a file cap.  Its rows are
search WINDOWS: ``rows`` of the inner ``nfft`` / ``nint`` product //
``window_spectra``.

A traffic file's entry: ``{"name", "kind": "hits", "nfft", "nint",
"window_spectra", "snr", "top_k", "max_drift_bins" (optional),
"tolerance", "guard", "path" (optional)}``.

Its own reader, a copy in spirit and no import of ``blit.io.hits`` or
``blit.search.hits``, as ``reference.py`` is of ``channelize_np``.

The comparison (``against_reference``), on the CHECKED coarse channels (a
hit belongs to the coarse channel its fine channel lies in): with a guard
band ``g`` about the threshold,

  hits_missing      reference cells of S/N >= thr (1 + g) that the product
                    does not hold at the same window, fine channel and
                    drift                                        limit 0
  hits_unexplained  product hits whose reference S/N is under
                    thr (1 - g)                                  limit 0
  snr_rel_err       the worst |S/N - reference's| / reference's over the
                    hits both hold                               limit tolerance

Traffic keeps a checked channel's hits under ``top_k`` (tones a few tens
of sigma strong), so that the cut never decides membership: a checked
channel that lands ``top_k`` hits in a window is refused as incorrect by
name, whatever they are.

The reference (``compute``, a child a coarse channel; NumPy float64): from
``reference.stokes_i``'s rows, window by window, ``reference.drift_sums``
one path at a time, then the search's own normalisation: a drift row's
mean and standard deviation over ALL the band's fine channels, which is
why this kind asks for every channel's stream (``ALL_CHANNELS``) — two
sums a drift row a channel are kept of the unchecked ones.  Departures
from ``blit/ops/pallas_dedoppler.py``'s docstring, each on purpose:

- no tree: every path is summed on its own (``reference.drift_sums``);
- no zero-padded band: a child sees one coarse channel, so it sums its
  INTERIOR cells (``T - 1`` from either edge, where no path leaves the
  channel) and keeps its ``2 (T - 1)`` edge columns; the comparison sums
  the strips across each boundary from the two neighbours' columns, zeros
  beyond the band's two ends as the program pads them.  Every cell of the
  band is counted once, exactly;
- no top-k: every cell of a checked channel over half of thr (1 - g) by
  the channel's own statistics is kept as a candidate and judged by the
  band's once all children have ended;
- float64 throughout (the program: float32 power, float32 sums).
"""

from __future__ import annotations

import collections
import json
import os

import numpy as np

import reference
from check import Incorrect, manifest

RAGGED = True           # what lands depends on the data
ALL_CHANNELS = True     # a drift row is normalised over the whole band
KIND, VERSION = "blit.hits", 1
HEADER_ROOM = 1 << 13   # the header record is 1-2 KB
HIT_LINE_MOST = 320     # a hit's line is 220-260 B today
CANDIDATE_SHARE = 0.5   # of thr (1 - g), by the channel's own statistics


# -- sizing --------------------------------------------------------------------

def sized(spec: dict, samples: int, *, nslots: int, ntap: int) -> dict:
    """The plan's entry: ``rows`` are search windows, ``row_bytes`` the
    most a window's lines can hold."""
    spectra = (samples // spec["nfft"] - (ntap - 1)) // spec["nint"]
    return {"name": spec["name"], "kind": "hits", "nfft": spec["nfft"],
            "nint": spec["nint"], "window_spectra": spec["window_spectra"],
            "snr": spec["snr"], "top_k": spec["top_k"],
            "max_drift_bins": spec.get("max_drift_bins"),
            "tolerance": spec["tolerance"], "guard": spec["guard"],
            "row_bytes": nslots * spec["top_k"] * HIT_LINE_MOST,
            "rows": max(0, spectra) // spec["window_spectra"]}


def nothing(p: dict):
    if p["rows"] < 1:
        return (f"no window of product {p['name']!r} at nfft {p['nfft']}, "
                f"nint {p['nint']}, window_spectra {p['window_spectra']}")
    return None


def bytes_at(p: dict, rows=None) -> int:
    """The most the file can hold at ``rows`` windows."""
    return (p["rows"] if rows is None else rows) * p["row_bytes"] \
        + HEADER_ROOM


def rows_under(p: dict, cap: int) -> int:
    return (cap - HEADER_ROOM) // p["row_bytes"]


def frames(p: dict, rows: int) -> int:
    return rows * p["window_spectra"] * p["nint"]


def samples_for(p: dict, rows: int, ntap: int) -> int:
    return (frames(p, rows) + ntap - 1) * p["nfft"]


def least_bytes(p: dict) -> int:
    """A hit table is kilobytes: nothing beside the input the search
    reads."""
    return 0


# -- the file ------------------------------------------------------------------

def read_hits(path: str):
    """-> (the header record's ``header``, the hits as dicts, the first
    line's bytes)."""
    with open(path, "rb") as f:
        first = f.readline()
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    try:
        head = json.loads(first)
        hits = [json.loads(ln) for ln in lines]
    except ValueError as e:
        raise Incorrect(f"{path}: not JSON lines: {e}") from None
    if head.get("kind") != KIND or head.get("version") != VERSION:
        raise Incorrect(f"{path}: header record kind {head.get('kind')!r} "
                        f"version {head.get('version')!r}, want {KIND!r} "
                        f"{VERSION}")
    return head["header"], hits, first


def landed(path: str) -> bool:
    """A hit's line is in the ``.partial``, or the table is at its final
    path (a quiet window lands nothing before that)."""
    if os.path.exists(path):
        return True
    try:
        with open(path + ".partial", "rb") as f:
            return f.read(1 << 16).count(b"\n") > 1
    except OSError:
        return False


# -- checked -------------------------------------------------------------------

def guarantees(path: str, p: dict, want_rows: int, read_all: bool) -> dict:
    """No ``.partial`` left; the header record's kind and version; the
    search's knobs as the plan states them; ``search_windows`` what the
    plan implies — the manifest's ``rows`` are the windows the writer
    claimed, and the header's own count is held to it where the header
    states one (today's one-shot writer publishes the header record before
    the counts are known); the hit count the header states = the lines,
    and no hit names a window the search did not make; manifest complete,
    its size and CRC against the bytes (always whole: the table is
    small)."""
    if os.path.exists(path + ".partial"):
        raise Incorrect(f"{path}.partial left behind")
    hdr, hits, first = read_hits(path)
    for key, want in (("search_window_spectra", p["window_spectra"]),
                      ("search_top_k", p["top_k"]),
                      ("search_snr_threshold", p["snr"])):
        if hdr.get(key) != want:
            raise Incorrect(f"{path}: header {key}={hdr.get(key)!r}, the "
                            f"plan says {want!r}")
    doc = manifest(path, want_rows, True)
    if hdr.get("search_windows", want_rows) != want_rows:
        raise Incorrect(f"{path}: header search_windows="
                        f"{hdr['search_windows']}, want {want_rows}")
    if hdr.get("search_nhits", len(hits)) != len(hits):
        raise Incorrect(f"{path}: header search_nhits={hdr['search_nhits']} "
                        f"but {len(hits)} hit lines")
    stray = [h["window"] for h in hits if not 0 <= h["window"] < want_rows]
    if stray:
        raise Incorrect(f"{path}: hits in windows {sorted(set(stray))}, the "
                        f"search made {want_rows}")
    return {"bytes": os.path.getsize(path),
            "crc32": str(doc["crc32"]).lower(), "rows": want_rows,
            "header": first, "read_all": True, "hits": len(hits)}


def reference_tasks(p: dict, slices, *, ntap: int, despike: bool) -> list:
    """One task a coarse channel of the band, checked or not."""
    return [(s["slot"], p["nfft"] * (2 if s["checked"] else 1),
             {"nfft": p["nfft"], "ntap": ntap, "nint": p["nint"],
              "despike": despike, "window_spectra": p["window_spectra"],
              "checked": s["checked"],
              "keep_over": CANDIDATE_SHARE * p["snr"] * (1 - p["guard"])})
            for s in slices]


def compute(volt, args: dict) -> dict:
    """The child's work, one coarse channel: its rows, then window by
    window and drift by drift the sums over its interior cells —
    ``sum`` and ``sum_sq`` ``(windows, drifts)``, the cells counted
    (``ncells``) — its edge columns (``lo``, ``hi``: ``(windows, T, 2 (T -
    1))``) and, of a checked channel, the candidates ``cand`` ``(n, 4)``:
    window, drift row, fine channel, drift sum."""
    nspectra, edge = args["window_spectra"], args["window_spectra"] - 1
    rows = reference.stokes_i(volt, nfft=args["nfft"], ntap=args["ntap"],
                              nint=args["nint"],
                              despike=bool(args["despike"]))
    nfft = rows.shape[1]
    if nfft < 4 * edge:
        raise ValueError(f"hits: a coarse channel of {nfft} fine channels "
                         f"has no interior at window_spectra {nspectra}")
    windows = rows.shape[0] // nspectra
    ndrift = 2 * nspectra - 1
    total = np.zeros((windows, ndrift))
    total_sq = np.zeros((windows, ndrift))
    lo = np.zeros((windows, nspectra, 2 * edge))
    hi = np.zeros((windows, nspectra, 2 * edge))
    cand = []
    for w in range(windows):
        x = rows[w * nspectra:(w + 1) * nspectra]
        lo[w], hi[w] = x[:, :2 * edge], x[:, nfft - 2 * edge:]
        for i in range(ndrift):
            d = i - edge
            row = np.zeros(nfft - 2 * edge)   # the interior cells' sums
            for t in range(nspectra):
                s = reference.tree_shift(abs(d), t, nspectra)
                s = s if d >= 0 else -s
                row += x[t, edge + s:nfft - edge + s]
            total[w, i], total_sq[w, i] = row.sum(), (row * row).sum()
            if args["checked"]:
                over = np.flatnonzero(
                    row >= row.mean() + args["keep_over"] * row.std())
                cand += [(w, i, edge + int(f), row[f]) for f in over]
    return {"sum": total, "sum_sq": total_sq,
            "ncells": np.array(nfft - 2 * edge), "lo": lo, "hi": hi,
            "cand": np.array(cand, np.float64).reshape(-1, 4)}


def band_statistics(kept_of, nslots: int, checked_slots):
    """Every cell of the band counted once: the children's interior sums
    plus the strips across each of the ``nslots + 1`` boundaries (zeros
    beyond the band's ends) -> ``(mean, std)`` ``(windows, drifts)``, and
    the strips' cells that lie in a checked channel, as candidates
    ``{slot: [(window, drift row, fine channel, drift sum)]}``."""
    kept = [kept_of(slot) for slot in range(nslots)]
    total = sum(k["sum"] for k in kept)
    total_sq = sum(k["sum_sq"] for k in kept)
    ncells = sum(int(k["ncells"]) for k in kept)
    windows, nspectra, two_edge = kept[0]["lo"].shape
    edge = two_edge // 2
    nfft = int(kept[0]["ncells"]) + two_edge
    none = np.zeros((nspectra, two_edge))
    cand = {slot: [] for slot in checked_slots}
    for b in range(nslots + 1):
        # of a strip, cells [edge, 2 edge) are channel b-1's last and
        # [2 edge, 3 edge) channel b's first; beyond the band are none
        a, z = (edge if b > 0 else two_edge), \
            (3 * edge if b < nslots else two_edge)
        ncells += z - a
        for w in range(windows):
            below = kept[b - 1]["hi"][w] if b > 0 else none
            above = kept[b]["lo"][w] if b < nslots else none
            dd = reference.drift_sums(np.concatenate([below, above], axis=1))
            total[w] += dd[:, a:z].sum(axis=1)
            total_sq[w] += (dd[:, a:z] ** 2).sum(axis=1)
            for j in range(a, z):
                slot, f = (b - 1, nfft - two_edge + j) if j < two_edge \
                    else (b, j - two_edge)
                if slot in cand:
                    cand[slot] += [(w, i, f, dd[i, j])
                                   for i in range(dd.shape[0])]
    assert ncells == nslots * nfft, (ncells, nslots, nfft)
    mean, std = reference.snr_rows(total, total_sq, ncells)
    return mean, np.maximum(std, 1e-30), cand


def limits(p: dict) -> dict:
    return {f"hits_missing.{p['name']}": 0,
            f"hits_unexplained.{p['name']}": 0,
            f"snr_rel_err.{p['name']}": p["tolerance"]}


def against_reference(path: str, p: dict, slices, kept_of, *, rows: int,
                      nslots: int):
    """The module docstring's comparison -> ``(said, compared)``."""
    nfft, nspectra, thr, g = p["nfft"], p["window_spectra"], p["snr"], \
        p["guard"]
    name = p["name"]
    hdr, hits, _ = read_hits(path)
    first = min(slices, key=lambda s: s["slot"])
    want = reference.product_header(first["raw_hdr"], nfft=nfft,
                                    nint=p["nint"])
    want["fch1"] -= (first["slot"] - first["chan"]) * nfft * want["foff"]
    want["nchans"] = nslots * nfft
    for k, v in want.items():
        if abs(hdr[k] - v) > 1e-9 * max(1.0, abs(v)):
            raise Incorrect(f"product header {k}={hdr[k]}, want {v}")
    checked = sorted(s["slot"] for s in slices if s["checked"])
    mean, std, cand = band_statistics(kept_of, nslots, checked)
    looked_at = reference.drift_mask(nspectra, p["max_drift_bins"])
    ref = {}   # (window, fine channel of the band, drift) -> reference S/N
    for slot in checked:
        for w, i, f, dd in [*kept_of(slot)["cand"], *cand[slot]]:
            w, i = int(w), int(i)
            if looked_at[i] and w < rows:
                ref[w, slot * nfft + int(f), i - (nspectra - 1)] = \
                    (dd - mean[w, i]) / std[w, i]
    got = {(h["window"], h["chan"], h["drift_bins"]): h["snr"]
           for h in hits if h["chan"] // nfft in checked}
    landed_in = collections.Counter((c // nfft, w) for w, c, _ in got)
    full = sorted((slot, w, n) for (slot, w), n in landed_in.items()
                  if n >= p["top_k"])
    if full:
        raise Incorrect(
            f"checked coarse channels landed top_k={p['top_k']} hits in a "
            f"window (slot, window, hits) {full}: the cut decided what the "
            "table holds, so membership cannot be judged — the traffic's "
            "tones are too strong for this top_k")
    missing = sorted(k for k, s in ref.items()
                     if s >= thr * (1 + g) and k not in got)
    unexplained = sorted(k for k in got
                         if ref.get(k, -np.inf) < thr * (1 - g))
    both = [k for k in got if k in ref]
    errs = [abs(got[k] - ref[k]) / abs(ref[k]) for k in both]
    numbers = {f"hits_missing.{name}": len(missing),
               f"hits_unexplained.{name}": len(unexplained)}
    if errs:
        numbers[f"snr_rel_err.{name}"] = float(max(errs))
    said = {"windows": rows, "checked_slots": checked,
            "hits": len(hits), "hits_in_checked": len(got),
            "reference_over_threshold": sum(
                bool(s >= thr) for s in ref.values()),
            "in_guard_band": sum(bool(thr * (1 - g) <= s < thr * (1 + g))
                                 for s in ref.values()),
            "strongest": max(got.values(), default=None),
            "weakest": min(got.values(), default=None),
            "snr_rel_err": numbers.get(f"snr_rel_err.{name}"),
            "tolerance": p["tolerance"], "guard": g}
    problems = []
    if missing:
        problems.append(f"{len(missing)} reference hits of S/N >= "
                        f"{thr * (1 + g):g} are not in the table, the first "
                        f"(window, chan, drift) {missing[0]} at "
                        f"{ref[missing[0]]:.4g}")
    if unexplained:
        k = unexplained[0]
        problems.append(f"{len(unexplained)} hits of the table have a "
                        f"reference S/N under {thr * (1 - g):g}, the first "
                        f"(window, chan, drift) {k}: table {got[k]:.4g}, "
                        f"reference {ref.get(k, float('nan')):.4g}")
    if errs and max(errs) > p["tolerance"]:
        k = both[int(np.argmax(errs))]
        problems.append(f"S/N of (window, chan, drift) {k}: table "
                        f"{got[k]:.6g}, reference {ref[k]:.6g}, rel err "
                        f"{max(errs):.3g} > {p['tolerance']}")
    if problems:
        raise Incorrect("; ".join(problems), numbers)
    return said, numbers


def sample(path: str, facts: dict, seed: int) -> dict:
    """The verified table's bytes, whole: it is small."""
    with open(path, "rb") as f:
        return {0: f.read()}


def same_product(path: str, facts: dict, golden: dict, seed: int) -> None:
    """The same bytes in must give the same table out, byte for byte."""
    for k in ("bytes", "rows", "header", "crc32", "hits"):
        if facts[k] != golden[k]:
            raise Incorrect(f"{path}: {k} differs from the verified "
                            f"product's ({facts[k]!r:.80} / {golden[k]!r:.80})")
    if sample(path, facts, seed) != golden["sample"]:
        raise Incorrect(f"{path}: bytes differ from the verified product's")
