"""The traffic generator: seeded GUPPI RAW recordings at recorder geometry.

One general generator reads a traffic file's parameters (blocks, tone,
pool size) and a configuration's geometry (channels, block size, band
plan).  It is the benchmark's own: the card format below is a copy of
what ``blit.io.guppi.write_raw`` writes, so a later PR cannot move the
generator and the reader under test together.

Noise is seeded Gaussian int8 (rms 8), drawn for a POOL of distinct
128 MiB blocks per bank and cycled; ``blit.testing.voltage_blocks`` draws
every block afresh, which costs a run several seconds of set-up and buys
nothing here: no branch of the reduction looks at the samples' values.
The pool size is odd so that a PFB frame (two blocks at nfft 2^20) does
not repeat with the pool: spectra repeat only every ``pool_blocks``
frames, and a product row written in the wrong place fails the
reference check.  A tone is added per block from its exact integer
phase, so it is continuous across blocks whatever the pool does.

A bank's ``tones[]`` entry is one tone (``chan``, ``fine_offset``) and may
carry ``amp`` (``TONE_AMP`` where absent), ``drift`` (fine channels of the
finest product per spectrum of it, a whole number or ``[num, den]``: a
chirp, whose phase is still built from integers) and ``more``, a list of
further tones of the same bank, each with keys of its own.  An entry with
none of the three writes the bytes it wrote before PR 42.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from scratch import RAW_HEADER_ROOM

CARD_LEN = 80
NOISE_RMS = 8.0
TONE_AMP = 20.0


def _card(key: str, value) -> bytes:
    if isinstance(value, str):
        vs = f"'{value:<8s}'"
    elif isinstance(value, float):
        vs = f"{value:.12G}"
    else:
        vs = str(value)
    card = f"{key:<8s}= {vs}"
    if len(card) > CARD_LEN:
        raise ValueError(f"guppi card too long: {card!r}")
    return card.ljust(CARD_LEN).encode("ascii")


def raw_header(geom: dict, *, obsfreq: float, obsbw: float,
               src_name: str = "SYNTH") -> dict:
    """One bank's RAW header at the configuration's geometry, critically
    sampled (TBIN = OBSNCHAN / |OBSBW|)."""
    nchan = geom["obsnchan"]
    return {
        "SRC_NAME": src_name, "TELESCOP": "GBT",
        "OBSFREQ": float(obsfreq), "OBSBW": float(obsbw),
        "OBSNCHAN": nchan, "NPOL": 4, "NBITS": geom["nbits"],
        "TBIN": abs(nchan / (obsbw * 1e6)), "OVERLAP": 0,
        "STT_IMJD": 59897, "STT_SMJD": 21221, "PKTIDX": 0,
        "CHAN_BW": obsbw / nchan,
        "BLOCSIZE": geom["block_samples"] * nchan * geom["npol"] * 2,
        "DIRECTIO": 0,
    }


def _quantize(v: np.ndarray) -> np.ndarray:
    np.rint(v, out=v)
    np.clip(v, -128, 127, out=v)
    return v.astype(np.int8)


def noise_pool(geom: dict, seed, nblocks: int, tone_chans, workers: int):
    """``nblocks`` distinct int8 blocks ``(nchan, ntime, npol, 2)`` and, for
    each, the tone channels' float noise ``{chan: noise}`` (a tone is added
    before the rounding, as a recorder's quantiser sees it)."""
    shape = (geom["obsnchan"], geom["block_samples"], geom["npol"], 2)

    def one(p: int):
        rng = np.random.default_rng([*seed, p])
        v = rng.standard_normal(shape, dtype=np.float32)
        v *= np.float32(NOISE_RMS)
        tone_noise = {c: v[c].copy() for c in tone_chans}
        return _quantize(v), tone_noise

    with ThreadPoolExecutor(max_workers=max(1, workers)) as ex:
        return list(ex.map(one, range(nblocks)))


def tone_block(b: int, nsamp: int, nfft: int, fine_offset: int, *,
               amp: float = TONE_AMP, drift=0, nint: int = 1) -> np.ndarray:
    """The tone's samples in block ``b``: ``(nsamp, 1, 2)`` float32 at
    ``fine_offset / nfft`` cycles per sample, from the exact integer
    phase.  ``drift`` (``num`` or ``[num, den]``) moves it ``num / den``
    fine channels per spectrum of ``nfft * nint`` samples: at sample ``n``
    it stands at ``fine_offset + (num / den) n / (nfft nint)`` channels,
    the phase is the sum of that over the samples so far, ``2 pi
    (fine_offset n / nfft + num n^2 / (2 den nfft^2 nint))``, and its
    numerator over ``m = 2 den nfft^2 nint`` is taken modulo ``m`` in whole
    numbers (its second difference is the constant ``2 num``)."""
    n = b * nsamp + np.arange(nsamp, dtype=np.int64)
    if not drift:
        ph = (2 * np.pi / nfft) * ((fine_offset * n) % nfft)
    else:
        num, den = drift if isinstance(drift, (list, tuple)) else (drift, 1)
        m = 2 * den * nfft * nfft * nint
        if nsamp * (b + 1) >= 1 << 31 or m * max(2, abs(num)) >= 1 << 62:
            raise ValueError("tone_block: the chirp's whole-number phase "
                             f"does not fit 64 bits (n {n[-1]}, m {m})")
        # n < 2^31: the square fits; reduce it before the small factor
        sq = (((n * n) % m) * (abs(num) % m)) % m
        lin = ((fine_offset * n) % nfft) * (2 * den * nfft * nint)
        ph = (2 * np.pi / m) * ((lin + (sq if num > 0 else m - sq)) % m)
    return (amp * np.stack([np.cos(ph), np.sin(ph)], axis=-1)
            ).astype(np.float32)[:, None, :]


def tones_of(entry: dict) -> list:
    """Every tone of a bank's ``tones[]`` entry, the entry's own first."""
    return [entry, *entry.get("more", [])]


def write_recording(stem: str, geom: dict, hdr: dict, nblocks: int,
                    file_cap: int, *, seed, nfft: int, tones,
                    pool_blocks: int, keep_chans, workers: int,
                    nint: int = 1):
    """Write ``nblocks`` blocks as ``<stem>.0000.raw``, ``.0001.raw``, … —
    the recorder's own sequence convention, each member at most
    ``file_cap`` bytes.  Returns ``(member paths, kept)`` where ``kept[c]``
    is coarse channel ``c``'s whole gap-free stream ``(ntime, npol, 2)``
    int8: the plain reference's input, taken from the generator and not
    read back through the reader under test.  ``tones``: ``tones_of`` a
    bank's entry; ``nfft`` and ``nint`` the finest product's, whose grid
    a tone's ``fine_offset`` and ``drift`` count on."""
    nsamp = geom["block_samples"]
    block_bytes = hdr["BLOCSIZE"]
    per_file = min(nblocks, file_cap // (block_bytes + RAW_HEADER_ROOM))
    if per_file < 1:
        raise RuntimeError(f"one RAW block is {block_bytes} B and the "
                           f"largest file allowed here is {file_cap} B")
    tone_chans = sorted({t["chan"] for t in tones})
    pool = noise_pool(geom, seed, min(pool_blocks, nblocks), tone_chans,
                      workers)
    kept = {c: np.empty((nblocks * nsamp, geom["npol"], 2), np.int8)
            for c in keep_chans}
    paths = []
    f = None
    try:
        for b in range(nblocks):
            if b % per_file == 0:
                if f is not None:
                    f.close()
                paths.append(f"{stem}.{len(paths):04d}.raw")
                f = open(paths[-1], "wb")
            blk, tone_noise = pool[b % len(pool)]
            for c in tone_chans:
                v = tone_noise[c]
                for t in tones:
                    if t["chan"] == c:
                        v = v + tone_block(
                            b, nsamp, nfft, t["fine_offset"],
                            amp=t.get("amp", TONE_AMP),
                            drift=t.get("drift", 0), nint=nint)
                blk[c] = _quantize(v)
            for c in keep_chans:
                kept[c][b * nsamp:(b + 1) * nsamp] = blk[c]
            cards = b"".join(_card(k, v) for k, v in
                             {**hdr, "PKTIDX": b * nsamp}.items())
            f.write(cards + "END".ljust(CARD_LEN).encode("ascii"))
            f.write(memoryview(blk).cast("B"))
    finally:
        if f is not None:
            f.close()
    return paths, kept
