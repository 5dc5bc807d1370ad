"""Synthetic BL@GBT data generators (test fixtures + benchmark inputs).

The reference ships no fixtures at all (SURVEY.md §4); these generators are
the foundation of blit's far larger test surface: round-trip tests for every
codec, fake observation trees for the inventory crawl, and deterministic
voltage streams with injected tones for end-to-end pipeline validation.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from blit.config import COARSE_MHZ, nfpc_from_foff
from blit.io import write_fbh5, write_fil, write_raw


def signal_ready(outdir: str, tag) -> str:
    """Atomically drop a readiness marker ``<outdir>/.ready<tag>`` — the
    multi-process test harness's bring-up barrier (tests/
    test_multiprocess.py): a pod child writes it the moment its
    distributed runtime is up, so the parent can time the WORK phase
    separately from coordinator/collective bring-up (which legitimately
    runs long on loaded CI machines; ISSUE 8 satellite — the barrier
    replaced a blanket flaky-rerun)."""
    path = os.path.join(outdir, f".ready{tag}")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(str(os.getpid()))
    os.replace(tmp, path)
    return path


def make_fil_header(
    nchans: int = 64,
    nifs: int = 1,
    fch1: float = 8437.5,
    foff: Optional[float] = None,
    tsamp: float = 1.0e-3,
    tstart: float = 59897.0,
    source_name: str = "SYNTH",
) -> Dict:
    """A plausible GBT filterbank header; ``foff`` defaults to one coarse
    channel per fine channel bank slice (nfpc computes cleanly)."""
    if foff is None:
        foff = -COARSE_MHZ / max(nchans // 64, 1)
    return {
        "telescope_id": 6,  # GBT
        "machine_id": 0,
        "data_type": 1,
        "source_name": source_name,
        "barycentric": 0,
        "pulsarcentric": 0,
        "az_start": 0.0,
        "za_start": 0.0,
        "src_raj": 120000.0,
        "src_dej": 450000.0,
        "tstart": tstart,
        "tsamp": tsamp,
        "fch1": fch1,
        "foff": foff,
        "nchans": nchans,
        "nifs": nifs,
    }


def make_spectra(
    nsamps: int = 16,
    nifs: int = 1,
    nchans: int = 64,
    seed: int = 0,
    dtype=np.float32,
) -> np.ndarray:
    """Deterministic positive 'power' spectra shaped (nsamps, nifs, nchans)."""
    rng = np.random.default_rng(seed)
    base = rng.chisquare(4, size=(nsamps, nifs, nchans))
    ramp = 1.0 + np.arange(nchans) / nchans
    return (base * ramp).astype(dtype)


def synth_fil(path: str, nsamps=16, nifs=1, nchans=64, seed=0, **hdrkw) -> Tuple[Dict, np.ndarray]:
    hdr = make_fil_header(nchans=nchans, nifs=nifs, **hdrkw)
    data = make_spectra(nsamps, nifs, nchans, seed)
    write_fil(path, hdr, data)
    return hdr, data


def synth_fbh5(
    path: str, nsamps=16, nifs=1, nchans=64, seed=0, compression=None, **hdrkw
) -> Tuple[Dict, np.ndarray]:
    hdr = make_fil_header(nchans=nchans, nifs=nifs, **hdrkw)
    hdr["nfpc"] = nfpc_from_foff(hdr["foff"])
    data = make_spectra(nsamps, nifs, nchans, seed)
    write_fbh5(path, hdr, data, compression=compression)
    return hdr, data


def make_raw_header(
    obsnchan: int = 64,
    npol: int = 2,
    obsfreq: float = 8437.5,
    obsbw: float = 187.5,
    tbin: Optional[float] = None,
    overlap: int = 0,
    src_name: str = "SYNTH",
    stt_imjd: int = 59897,
    stt_smjd: int = 21221,
) -> Dict:
    if tbin is None:
        tbin = abs(obsnchan / (obsbw * 1e6))  # critically sampled
    return {
        "SRC_NAME": src_name,
        "TELESCOP": "GBT",
        "OBSFREQ": obsfreq,
        "OBSBW": obsbw,
        "OBSNCHAN": obsnchan,
        "NPOL": 4 if npol == 2 else npol,
        "NBITS": 8,
        "TBIN": tbin,
        "OVERLAP": overlap,
        "STT_IMJD": stt_imjd,
        "STT_SMJD": stt_smjd,
        "PKTIDX": 0,
        "CHAN_BW": obsbw / obsnchan,
    }


def tone_drift_for(nfft: int, nspectra: int, drift_bins: float) -> float:
    """The ``tone_drift`` (cycles/sample²) that drifts a tone by
    ``drift_bins`` FINE channels (bin width ``1/nfft`` cycles/sample)
    over ``nspectra`` consecutive nfft-point spectra — the known-ḟ
    injection for drift-search recovery tests (ISSUE 6 satellite):
    inject with this, search with ``window_spectra=nspectra``, and the
    top hit's ``drift_bins`` must match within one drift step."""
    return drift_bins / (nfft * nspectra * nfft)


def make_voltages(
    obsnchan: int,
    ntime: int,
    npol: int = 2,
    seed: int = 0,
    tone_chan: Optional[int] = None,
    tone_freq: float = 0.25,
    tone_amp: float = 20.0,
    noise_rms: float = 8.0,
    tone_drift: float = 0.0,
) -> np.ndarray:
    """Quantized complex voltages (obsnchan, ntime, npol, 2) int8: Gaussian
    noise plus an optional complex tone in one coarse channel (a
    'technosignature' for end-to-end detection tests).  ``tone_drift``
    chirps the tone linearly — instantaneous frequency
    ``tone_freq + tone_drift·t`` cycles/sample (phase integrates the
    chirp: ``2π(f₀·t + ½·ḟ·t²)``); :func:`tone_drift_for` maps a target
    fine-bin drift to this unit."""
    rng = np.random.default_rng(seed)
    v = rng.normal(0.0, noise_rms, size=(obsnchan, ntime, npol, 2))
    if tone_chan is not None:
        t = np.arange(ntime, dtype=np.float64)
        ph = 2 * np.pi * (tone_freq * t + 0.5 * tone_drift * t * t)
        v[tone_chan, :, :, 0] += tone_amp * np.cos(ph)[:, None]
        v[tone_chan, :, :, 1] += tone_amp * np.sin(ph)[:, None]
    return np.clip(np.round(v), -128, 127).astype(np.int8)


def voltage_blocks(
    nblocks: int,
    obsnchan: int,
    ntime_per_block: int,
    *,
    seed: int,
    nfft: int,
    tone_chan: int,
    tone_fine: int,
    npol: int = 2,
    tone_amp: float = 20.0,
    noise_rms: float = 8.0,
    workers: int = 4,
):
    """Yield ``nblocks`` int8 voltage blocks ``(obsnchan, ntime_per_block,
    npol, 2)`` ONE AT A TIME — the recorder-size counterpart of
    :func:`make_voltages`, which builds the whole stream in RAM through
    float64: here at most ``workers`` blocks (plus their f32 scratch) are
    alive, so a multi-GB recording streams straight into
    :func:`blit.io.write_raw`.

    Each block is seeded Gaussian noise (its own ``[seed, block]``
    generator, so the bytes do not depend on ``workers``) plus one complex
    tone in coarse channel ``tone_chan``, phase-continuous across blocks,
    centred on fine channel ``tone_fine`` of an ``nfft``-point fftshifted
    product (``(tone_fine - nfft/2) / nfft`` cycles/sample)."""
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    k = tone_fine - nfft // 2

    def block(b: int) -> np.ndarray:
        rng = np.random.default_rng([seed, b])
        v = rng.standard_normal((obsnchan, ntime_per_block, npol, 2),
                                dtype=np.float32)
        v *= np.float32(noise_rms)
        n = b * ntime_per_block + np.arange(ntime_per_block, dtype=np.int64)
        ph = (2 * np.pi / nfft) * ((k * n) % nfft)  # exact integer phase
        v[tone_chan, :, :, 0] += (tone_amp * np.cos(ph)).astype(
            np.float32)[:, None]
        v[tone_chan, :, :, 1] += (tone_amp * np.sin(ph)).astype(
            np.float32)[:, None]
        np.rint(v, out=v)
        np.clip(v, -128, 127, out=v)
        return v.astype(np.int8)

    with ThreadPoolExecutor(max_workers=max(1, workers)) as pool:
        ahead: deque = deque()
        for b in range(nblocks):
            ahead.append(pool.submit(block, b))
            if len(ahead) >= max(1, workers):
                yield ahead.popleft().result()
        while ahead:
            yield ahead.popleft().result()


def synth_raw(
    path: str,
    nblocks: int = 2,
    obsnchan: int = 64,
    ntime_per_block: int = 1024,
    npol: int = 2,
    overlap: int = 0,
    directio: bool = False,
    seed: int = 0,
    tone_chan: Optional[int] = None,
    tone_drift: float = 0.0,
    tone_freq: float = 0.25,
    tone_amp: float = 20.0,
    **hdrkw,
) -> Tuple[Dict, List[np.ndarray]]:
    """Write a synthetic GUPPI RAW file.  With ``overlap`` > 0, consecutive
    blocks share their trailing/leading ``overlap`` samples, as on disk at
    GBT.  ``tone_drift`` chirps the injected tone (a drifting
    technosignature — :func:`tone_drift_for`)."""
    hdr = make_raw_header(obsnchan=obsnchan, npol=npol, overlap=overlap, **hdrkw)
    step = ntime_per_block - overlap
    total = step * (nblocks - 1) + ntime_per_block
    stream = make_voltages(obsnchan, total, npol, seed=seed,
                           tone_chan=tone_chan, tone_drift=tone_drift,
                           tone_freq=tone_freq, tone_amp=tone_amp)
    blocks = [stream[:, i * step : i * step + ntime_per_block] for i in range(nblocks)]
    write_raw(path, hdr, blocks, directio=directio)
    return hdr, blocks


def synth_raw_sequence(
    stem: str,
    nfiles: int = 2,
    blocks_per_file: int = 2,
    obsnchan: int = 64,
    ntime_per_block: int = 1024,
    npol: int = 2,
    overlap: int = 0,
    seed: int = 0,
    tone_chan: Optional[int] = None,
    tone_drift: float = 0.0,
    **hdrkw,
) -> Tuple[List[str], np.ndarray]:
    """Write a multi-file ``.NNNN.raw`` scan sequence carrying ONE contiguous
    voltage stream (the on-disk GBT recording layout: the block stream —
    including the OVERLAP convention — continues across file boundaries).

    Returns ``(paths, stream)`` where ``stream`` is the full gap-free
    voltage stream the sequence encodes.
    """
    nblocks = nfiles * blocks_per_file
    hdr = make_raw_header(obsnchan=obsnchan, npol=npol, overlap=overlap, **hdrkw)
    step = ntime_per_block - overlap
    total = step * (nblocks - 1) + ntime_per_block
    stream = make_voltages(obsnchan, total, npol, seed=seed,
                           tone_chan=tone_chan, tone_drift=tone_drift)
    blocks = [
        stream[:, i * step : i * step + ntime_per_block] for i in range(nblocks)
    ]
    paths = []
    for f in range(nfiles):
        p = f"{stem}.{f:04d}.raw"
        fhdr = dict(hdr)
        # PKTIDX continues across files (write_raw advances it per block).
        fhdr["PKTIDX"] = f * blocks_per_file * step
        write_raw(p, fhdr, blocks[f * blocks_per_file : (f + 1) * blocks_per_file])
        paths.append(p)
    return paths, stream


def build_observation_tree(
    root: str,
    session: str = "AGBT22B_999_01",
    scans: Tuple[str, ...] = ("0011",),
    players: Tuple[Tuple[int, int], ...] = ((0, 0), (0, 1)),
    nsamps: int = 16,
    nchans: int = 64,
    kind: str = "fbh5",
    nfiles: int = 1,
    raw_ntime: int = 1024,
) -> List[str]:
    """A fake BL@GBT data tree: ``<root>/<session>/GUPPI/BLPbb/<guppi name>``
    with real, readable product files.  Returns created paths.

    ``kind="raw"`` writes per-player ``.NNNN.raw`` sequences (``nfiles``
    members, ``raw_ntime`` samples per block) whose bank frequencies tile
    contiguously across each band (bank k owns the k-th 187.5/8 MHz slice,
    descending GBT sign) — so a tree feeds
    :func:`blit.inventory.scan_grid` / ``load_scan_mesh`` directly."""
    paths = []
    for band, bank in players:
        player = f"BLP{band}{bank}"
        host = f"blc{band}{bank}"
        d = os.path.join(root, session, "GUPPI", player)
        os.makedirs(d, exist_ok=True)
        for scan in scans:
            base = f"{host}_guppi_59897_21221_HD_84406_{scan}"
            if kind == "fbh5":
                p = os.path.join(d, base + ".rawspec.0002.h5")
                synth_fbh5(p, nsamps=nsamps, nchans=nchans, seed=band * 8 + bank)
            elif kind == "fil":
                p = os.path.join(d, base + ".rawspec.0002.fil")
                synth_fil(p, nsamps=nsamps, nchans=nchans, seed=band * 8 + bank)
            elif kind == "raw":
                bank_bw = -187.5 / 8
                ps, _ = synth_raw_sequence(
                    os.path.join(d, base),
                    nfiles=nfiles,
                    blocks_per_file=2,
                    obsnchan=nchans,
                    ntime_per_block=raw_ntime,
                    seed=band * 8 + bank,
                    tone_chan=bank % nchans,
                    obsbw=bank_bw,
                    obsfreq=8000.0 + band * 500.0 + (bank + 0.5) * bank_bw,
                )
                paths.extend(ps)
                continue
            else:
                raise ValueError(f"unknown kind {kind!r}")
            paths.append(p)
    return paths
