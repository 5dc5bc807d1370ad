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
reference check.  The tone is added per block from its exact integer
phase, so it is continuous across blocks whatever the pool does.
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


def noise_pool(geom: dict, seed, nblocks: int, tone_chan: int, workers: int):
    """``nblocks`` distinct int8 blocks ``(nchan, ntime, npol, 2)`` and, for
    each, the tone channel's float noise (the tone is added before the
    rounding, as a recorder's quantiser sees it)."""
    shape = (geom["obsnchan"], geom["block_samples"], geom["npol"], 2)

    def one(p: int):
        rng = np.random.default_rng([*seed, p])
        v = rng.standard_normal(shape, dtype=np.float32)
        v *= np.float32(NOISE_RMS)
        tone_noise = v[tone_chan].copy()
        return _quantize(v), tone_noise

    with ThreadPoolExecutor(max_workers=max(1, workers)) as ex:
        return list(ex.map(one, range(nblocks)))


def tone_block(b: int, nsamp: int, nfft: int, fine_offset: int) -> np.ndarray:
    """The tone's samples in block ``b``: ``(nsamp, 1, 2)`` float32 at
    ``fine_offset / nfft`` cycles per sample, from the exact integer
    phase."""
    n = b * nsamp + np.arange(nsamp, dtype=np.int64)
    ph = (2 * np.pi / nfft) * ((fine_offset * n) % nfft)
    return (TONE_AMP * np.stack([np.cos(ph), np.sin(ph)], axis=-1)
            ).astype(np.float32)[:, None, :]


def write_recording(stem: str, geom: dict, hdr: dict, nblocks: int,
                    file_cap: int, *, seed, nfft: int, tone_chan: int,
                    tone_fine_offset: int, pool_blocks: int, keep_chans,
                    workers: int):
    """Write ``nblocks`` blocks as ``<stem>.0000.raw``, ``.0001.raw``, … —
    the recorder's own sequence convention, each member at most
    ``file_cap`` bytes.  Returns ``(member paths, kept)`` where ``kept[c]``
    is coarse channel ``c``'s whole gap-free stream ``(ntime, npol, 2)``
    int8: the plain reference's input, taken from the generator and not
    read back through the reader under test."""
    nsamp = geom["block_samples"]
    block_bytes = hdr["BLOCSIZE"]
    per_file = min(nblocks, file_cap // (block_bytes + RAW_HEADER_ROOM))
    if per_file < 1:
        raise RuntimeError(f"one RAW block is {block_bytes} B and the "
                           f"largest file allowed here is {file_cap} B")
    pool = noise_pool(geom, seed, min(pool_blocks, nblocks), tone_chan,
                      workers)
    kept = {c: [] for c in keep_chans}
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
            blk[tone_chan] = _quantize(
                tone_noise + tone_block(b, nsamp, nfft, tone_fine_offset))
            for c in keep_chans:
                kept[c].append(blk[c].copy())
            cards = b"".join(_card(k, v) for k, v in
                             {**hdr, "PKTIDX": b * nsamp}.items())
            f.write(cards + "END".ljust(CARD_LEN).encode("ascii"))
            f.write(memoryview(blk).cast("B"))
    finally:
        if f is not None:
            f.close()
    return paths, {c: np.concatenate(v, axis=0) for c, v in kept.items()}
