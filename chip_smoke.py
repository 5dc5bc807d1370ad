"""chip_smoke.py — the standing proof that blit still starts on the chip.

    python chip_smoke.py            the witness: one process, needs a TPU
    python chip_smoke.py mesh       only the four-chip `blit scan` leg
                                    (needs >= 4 devices; the default run
                                    takes it too when it finds them)
    python chip_smoke.py rehearse   toy sizes on the CPU, to debug this
                                    script; proves nothing of the chip

The default run, in ONE process (a chip belongs to one process at a time):

1. refuses to go on unless JAX's first device is a TPU — there is no CPU
   continuation; prints platform, device kind and count, versions, the
   compile-cache directory and the tuning-profile directory;
2. builds ``blit/native`` from the committed sources on THIS machine (the
   Makefile compiles ``-march=native``; a library built elsewhere may not
   run here);
3. prints three rig facts the design notes need: whether
   ``block_until_ready`` blocks, one H2D and one D2H bandwidth on 256 MiB,
   and whether complex64 ``device_put`` / ``jnp.fft.fft`` run;
4. writes, block by block from a seed, one GUPPI RAW recording at the GBT
   recorder's geometry (OBSNCHAN 64, 8 bit, dual-pol complex, 128 MiB
   blocks; MacMahon+ 2018) outside the checkout, cut in DURATION only —
   as one file where the machine allows a file that large, else as the
   recorder's own ``.0000.raw``, ``.0001.raw``, … sequence; a cap on the
   size of one file also caps the spectra the product may hold, which
   cuts frames again and is printed;
5. reduces it through the CLI's own ``main()`` —
   ``blit reduce <raw> -o <out>.fil --product 0000`` — and checks the
   product: header geometry, the injected tone in the fine channel the
   header predicts, and two coarse channels x all spectra against
   ``channelize_np`` on the same bytes;
6. compiles (never interprets) and checks the per-chip kernels that are
   not on the 0000 path, at array-scale shapes: fused beamform, packed
   X-engine at nant 64, the drift-search tree at 64 x 2^20, and
   ``channelize`` with ``fqav_by=16`` and ``stokes="IQUV"``.

Any step that raises ends the run with a traceback and a non-zero exit;
nothing is caught and carried past.  The last stdout line of a passing run
is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import contextlib
import errno
import io
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20260926

# The recorder's geometry (PAPERS.md: MacMahon+ 2018; ROADMAP B2).  Width is
# not negotiable; duration is.
FULL = dict(
    nchan=64, nfft=1 << 20, block_samples=1 << 19,  # 128 MiB blocks
    frames=19,             # two full 8-frame chunks + the 3-frame filter tail
    mesh_frames=11,        # one full 8-frame window + the filter tail
    scan_seconds=300.0,    # what a real scan lasts
    kernels=dict(
        beamform=dict(nant=64, nbeam=64, nchan=64, ntime=8192, nint=8),
        xengine=dict(nant=64, nchan=16, nfft=512, nblk=64),
        dedoppler=dict(T=64, F=1 << 20),
        # At 48 channels XLA's own account for these two is 15.6 of
        # the chip's 15.75 GiB: nothing else may be resident.
        # Same kernels and per-channel grid at a third of the batch.
        channelize=dict(nchan=16, frames=8, dtype="bfloat16"),
    ),
)
# The rehearsal's sizes: small enough for the CPU and the Pallas interpreter.
TOY = dict(
    nchan=4, nfft=1 << 10, block_samples=1 << 9, frames=19, mesh_frames=11,
    scan_seconds=300.0,
    kernels=dict(
        beamform=dict(nant=4, nbeam=8, nchan=2, ntime=256, nint=2),
        xengine=dict(nant=64, nchan=1, nfft=8, nblk=8),
        dedoppler=dict(T=8, F=1 << 10),
        channelize=dict(nchan=2, frames=8, dtype="bfloat16"),
    ),
)


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + json.dumps(facts, default=str), flush=True)


# -- set-up -------------------------------------------------------------------

def require_tpu(rehearse: bool) -> dict:
    """Step 1.  Returns the device facts; exits 2 before any work when the
    first device is not a TPU (unless this is the named rehearsal)."""
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: no accelerator — JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}); this script proves the "
              "chip path and has no CPU continuation", file=sys.stderr)
        sys.exit(2)
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version label only
        libtpu = "unknown"
    from blit.device import use_compile_cache

    cache = use_compile_cache()
    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, python=sys.version.split()[0],
        compile_cache=cache,
        compile_cache_entries=len(os.listdir(cache))
        if os.path.isdir(cache) else 0,
        JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS"),
        cpus=os.cpu_count())
    if rehearse:
        say("rehearse", note="toy sizes on " + dev.platform + ": this run "
            "proves nothing of the chip")
    return device


def build_native() -> None:
    """Step 2: always rebuild (-B) — a stale library copied from another
    machine would look up to date to make."""
    t0 = time.perf_counter()
    subprocess.run(["make", "-B", "-C", os.path.join(HERE, "blit", "native")],
                   check=True, stdout=subprocess.DEVNULL)
    from blit.io.bshuf import available as bshuf_available
    from blit.io.native import guppi_lib

    if guppi_lib() is None or not bshuf_available():
        raise RuntimeError("blit/native built but its libraries do not load")
    say("native", built_s=round(time.perf_counter() - t0, 2))


def rig_facts() -> None:
    """Step 3: facts, not metrics."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    # Does block_until_ready block?  A ~0.5 s matmul chain: if it blocks,
    # the wait lands in block_until_ready and the fetch after it is short.
    n = 4096 if jax.devices()[0].platform == "tpu" else 256
    a = jnp.full((n, n), 1e-3, jnp.bfloat16)
    chain = jax.jit(lambda x: jax.lax.fori_loop(
        0, 400, lambda i, y: jnp.tanh(y @ x), x))
    jax.block_until_ready(chain(a))  # compile
    t0 = time.perf_counter()
    y = chain(a)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready(y)
    t_block = time.perf_counter() - t0 - t_dispatch
    t1 = time.perf_counter()
    np.asarray(y)
    t_fetch = time.perf_counter() - t1
    say("rig.block_until_ready", dispatch_s=round(t_dispatch, 4),
        block_s=round(t_block, 4), fetch_after_s=round(t_fetch, 4),
        blocks=bool(t_block > 4 * t_fetch))

    # One H2D and one D2H on 256 MiB (second pass: buffers faulted in).
    host = np.ones(1 << 26, np.float32)
    for _ in range(2):
        t0 = time.perf_counter()
        d = jax.block_until_ready(jax.device_put(host))
        h2d = host.nbytes / (time.perf_counter() - t0)
        d2 = jax.block_until_ready(d + 1)  # a buffer with no host copy
        t0 = time.perf_counter()
        np.asarray(d2)
        d2h = host.nbytes / (time.perf_counter() - t0)
        del d, d2
    say("rig.links", bytes=host.nbytes, h2d_GBps=round(h2d / 1e9, 3),
        d2h_GBps=round(d2h / 1e9, 3))

    # Complex dtypes on this backend (the design notes once said none run).
    facts = {}
    z = (np.arange(16) + 1j).astype(np.complex64)
    for name, fn in (
        ("complex64_device_put", lambda: np.asarray(jax.device_put(z))),
        ("jnp_fft_fft", lambda: np.asarray(jnp.fft.fft(jnp.asarray(z)))),
    ):
        try:
            fn()
            facts[name] = "runs"
        except Exception as e:  # noqa: BLE001 — the fact IS the outcome
            facts[name] = f"{type(e).__name__}: {str(e)[:160]}"
    say("rig.complex", **facts)


# -- the recording ------------------------------------------------------------

FIL_HEADER_ROOM = 4096   # a SIGPROC header is a few hundred bytes
RAW_HEADER_ROOM = 4096   # so is one RAW block's card header


def host_facts() -> dict:
    """What this machine lets one process write, said before anything is
    written: the file-size limit (the soft one raised to the hard one — the
    script asks for nothing the machine's owner withheld) and the room in
    the places a recording may go."""
    import resource

    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != hard:
        try:
            resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
        except (ValueError, OSError):
            pass
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)

    def show(v):
        return "unlimited" if v == resource.RLIM_INFINITY else v

    facts = {"RLIMIT_FSIZE": [show(soft), show(hard)]}
    for root in ("/dev/shm", "/tmp"):
        if os.path.isdir(root):
            facts["free " + root] = shutil.disk_usage(root).free
    return facts


def max_file_bytes(directory: str, want: int) -> int:
    """The largest single file, up to ``want`` bytes, this process may
    write in ``directory`` — asked of the machine, not assumed.  A sparse
    ``ftruncate`` meets the checks a ``write`` at that offset meets
    (RLIMIT_FSIZE, the filesystem's own maximum) and moves no data."""
    with tempfile.TemporaryFile(dir=directory) as f:

        def allowed(n: int) -> bool:
            try:
                os.ftruncate(f.fileno(), n)
            except OSError as e:
                if e.errno not in (errno.EFBIG, errno.EINVAL):
                    raise
                return False
            os.ftruncate(f.fileno(), 0)
            return True

        if allowed(want):
            return want
        lo, hi = 0, want  # allowed(lo), not allowed(hi)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if allowed(mid) else (lo, mid)
        return lo


def scratch_dir(need_bytes: int, file_bytes: int,
                prefer=("/dev/shm", "/tmp")):
    """A fresh directory OUTSIDE the checkout with ``need_bytes`` free (a
    multi-GB tree inside the repo breaks the chip tool's copy), and the
    largest single file, up to ``file_bytes``, that may be written there.
    The first root that allows ``file_bytes`` wins; failing that, the one
    that allows most."""
    best = None
    for root in prefer:
        if not (os.path.isdir(root)
                and shutil.disk_usage(root).free > need_bytes):
            continue
        cap = max_file_bytes(root, file_bytes)
        if best is None or cap > best[1]:
            best = (root, cap)
        if cap >= file_bytes:
            break
    if best is None:
        raise RuntimeError(f"no scratch with {need_bytes} B free in {prefer}: "
                           f"{host_facts()}")
    return tempfile.mkdtemp(prefix="blit-smoke-", dir=best[0]), best[1]


def product_dir(frames: int, spectrum_bytes: int, copies: int = 1):
    """Where the product goes, and how many frames it may cover.  A
    machine that caps the size of one file caps the spectra one product
    holds: that cuts DURATION (fewer frames), never width, and is said.
    Returns ``(directory, frames)``."""
    want = (frames - 3) * spectrum_bytes + FIL_HEADER_ROOM
    outdir, cap = scratch_dir(copies * want + (1 << 30), want,
                              prefer=("/tmp", "/dev/shm"))
    spectra = min(frames - 3, (cap - FIL_HEADER_ROOM) // spectrum_bytes)
    if spectra < 1:
        shutil.rmtree(outdir, ignore_errors=True)
        raise RuntimeError(
            f"one spectrum at this width is {spectrum_bytes} B and the "
            f"largest file this machine allows is {cap} B: {host_facts()}")
    if spectra < frames - 3:
        say("reduced", what="duration", frames_was=frames,
            frames_now=spectra + 3,
            why=f"the largest file this machine allows is {cap} B; the "
                f"product would be {want} B", **host_facts())
    return outdir, spectra + 3


def write_recording(stem: str, size: dict, nframes: int, file_cap: int, *,
                    seed: int, tone_chan: int, tone_fine: int, **hdrkw):
    """One RAW recording of exactly ``nframes`` PFB frames' worth of
    samples (so the last chunk is full and no second compile triggers),
    streamed block by block into ``<stem>.0000.raw``, ``.0001.raw``, … —
    the recorder's own sequence convention, each member at most
    ``file_cap`` bytes.  Returns ``(RAW header, member paths)``."""
    import itertools

    from blit.io import write_raw
    from blit.testing import make_raw_header, voltage_blocks

    nsamples = nframes * size["nfft"]
    nblocks, rem = divmod(nsamples, size["block_samples"])
    if rem:
        raise ValueError("frames do not fill whole blocks")
    block_bytes = size["block_samples"] * size["nchan"] * 4
    per_file = min(nblocks, file_cap // (block_bytes + RAW_HEADER_ROOM))
    if per_file < 1:
        raise RuntimeError(
            f"one RAW block is {block_bytes} B and the largest file this "
            f"machine allows is {file_cap} B: {host_facts()}")
    hdr = make_raw_header(obsnchan=size["nchan"], npol=2, **hdrkw)
    blocks = voltage_blocks(
        nblocks, size["nchan"], size["block_samples"], seed=seed,
        nfft=size["nfft"], tone_chan=tone_chan, tone_fine=tone_fine,
        workers=min(8, os.cpu_count() or 1))
    paths = []
    for first in range(0, nblocks, per_file):
        paths.append(f"{stem}.{len(paths):04d}.raw")
        write_raw(paths[-1],
                  {**hdr, "PKTIDX": first * size["block_samples"]},
                  itertools.islice(blocks, per_file))
    blocks.close()  # ends the generator's worker threads
    return hdr, paths


def read_channels(raw_paths, chans) -> "np.ndarray":
    """The recording's bytes for a few coarse channels, gap-free:
    ``(len(chans), ntime, npol, 2)`` int8 — the reference's input."""
    import numpy as np

    from blit.io.guppi import open_raw

    raw = open_raw(raw_paths)
    return np.concatenate(
        [np.ascontiguousarray(raw.read_block(i)[list(chans)])
         for i in range(raw.nblocks)], axis=1)


def open_fil(path: str):
    import numpy as np

    from blit.io.sigproc import read_fil_header

    hdr, off = read_fil_header(path)
    data = np.memmap(path, np.float32, "r", offset=off,
                     shape=(hdr["nsamps"], hdr["nifs"], hdr["nchans"]))
    return hdr, data


def run_cli(argv) -> dict:
    """Call the CLI's own ``main()`` in-process; echo what it printed and
    return its JSON lines (last one under ``"last"``)."""
    from blit.__main__ import main as blit_main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = blit_main(argv)
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    for ln in lines:
        print("  blit> " + (ln if len(ln) < 2000 else ln[:2000] + " …"),
              flush=True)
    if rc != 0:
        raise RuntimeError(f"blit {' '.join(argv)} exited {rc}")
    docs = [json.loads(ln) for ln in lines if ln.startswith("{")]
    return {"lines": docs, "last": docs[-1]}


@contextlib.contextmanager
def compile_account():
    """What JAX's compiler did while the block ran: seconds inside the
    backend compile step (a persistent-cache retrieval counts as one, a
    short one) and the cache's hits and misses — the evidence that a warm
    run did not recompile."""
    import jax

    acct = {"backend_compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_secs(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            acct["backend_compile_s"] = round(
                acct["backend_compile_s"] + secs, 2)

    def on_event(name, **_):
        for key in ("cache_hits", "cache_misses"):
            if name == "/jax/compilation_cache/" + key:
                acct[key] += 1

    jax.monitoring.register_event_duration_secs_listener(on_secs)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield acct
    finally:
        jax.monitoring.unregister_event_duration_listener(on_secs)
        jax.monitoring.unregister_event_listener(on_event)


def rel_err(got, want) -> float:
    """max|got - want| / max|want| — the scale-relative error
    tests/test_channelize.py pins for MXU-grade arithmetic."""
    import numpy as np

    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


# The tolerance tests/test_channelize.py pins for bf16-grade stages
# (test_bfloat16_stage_dtype_close_to_golden); the MXU's default precision
# makes "float32" stages bf16-grade multiplies too.
TOL = 2e-2


def check_tone_and_reference(fil: str, raw: list, rawhdr: dict, size: dict,
                             tone_chan: int, tone_fine: int,
                             other_chan: int, nspectra: int) -> None:
    import numpy as np

    from blit.ops.channelize import channelize_np, output_header, pfb_coeffs

    nfft = size["nfft"]
    hdr, data = open_fil(fil)
    want_hdr = output_header(rawhdr, nfft=nfft, nint=1)
    geometry = dict(nchans=size["nchan"] * nfft, nifs=1, nbits=32,
                    nsamps=nspectra)
    for k, v in geometry.items():
        if hdr[k] != v:
            raise AssertionError(f"product header {k}={hdr[k]}, want {v}")
    for k in ("fch1", "foff", "tsamp"):
        if abs(hdr[k] - want_hdr[k]) > 1e-9 * max(1.0, abs(want_hdr[k])):
            raise AssertionError(f"product header {k}={hdr[k]}, "
                                 f"want {want_hdr[k]}")
    # The tone's sky frequency from the RAW header and what was injected,
    # mapped through the PRODUCT header to a channel index.
    chan_bw = rawhdr["OBSBW"] / rawhdr["OBSNCHAN"]
    f_sky = (rawhdr["OBSFREQ"] - rawhdr["OBSBW"] / 2
             + (tone_chan + 0.5) * chan_bw
             + (tone_fine - nfft // 2) * chan_bw / nfft)
    predicted = int(round((f_sky - hdr["fch1"]) / hdr["foff"]))
    lo = tone_chan * nfft
    found = [lo + int(np.argmax(data[t, 0, lo:lo + nfft]))
             for t in range(nspectra)]
    if set(found) != {predicted}:
        raise AssertionError(f"tone found in channels {sorted(set(found))}, "
                             f"header predicts {predicted}")
    # Two coarse channels x all spectra against the NumPy reference on the
    # same bytes (host-side, outside any timed region).
    chans = (tone_chan, other_chan)
    v = read_channels(raw, chans)
    want = channelize_np(v, pfb_coeffs(4, nfft), nfft=nfft)
    errs = {}
    for j, c in enumerate(chans):
        got = data[:, :, c * nfft:(c + 1) * nfft]
        ref = want[:, :, j * nfft:(j + 1) * nfft]
        if not np.isfinite(got).all():
            raise AssertionError(f"non-finite product in coarse channel {c}")
        errs[c] = rel_err(got, ref)
        if errs[c] > TOL:
            raise AssertionError(
                f"coarse channel {c}: rel err {errs[c]:.3g} > {TOL}")
    say("reduce.check", header=geometry, tone_channel=predicted,
        tone_power=float(data[0, 0, predicted]),
        rel_err_by_coarse_channel=errs, tolerance=TOL,
        spectra=nspectra)


def reduce_leg(size: dict, on_tpu: bool) -> None:
    """Steps 4-5: the main path, one chip, full width."""
    import jax

    from blit.io.guppi import open_raw
    from blit.pipeline import RawReducer

    nfft, nchan = size["nfft"], size["nchan"]
    tbin = nchan / 187.5e6
    outdir, frames = product_dir(size["frames"], nchan * nfft * 4)
    raw_bytes = frames * nfft * nchan * 4
    rawdir = None
    try:
        rawdir, raw_cap = scratch_dir(
            raw_bytes + (1 << 30),
            raw_bytes + (raw_bytes // (size["block_samples"] * nchan * 4))
            * RAW_HEADER_ROOM)
        say("reduce.plan", OBSNCHAN=nchan, NBITS=8, npol=2,
            BLOCSIZE=size["block_samples"] * nchan * 4, nfft=nfft,
            frames=frames, raw_bytes=raw_bytes,
            reduced=f"duration {size['scan_seconds']:.0f} s -> "
                    f"{frames * nfft * tbin:.1f} s "
                    f"({frames} frames; width uncut)")
        stem = os.path.join(rawdir, "blc00_guppi_59897_21221_SMOKE_0001")
        tone_chan, other_chan = nchan // 3, nchan - 1
        tone_fine = nfft // 2 + nfft // 5 + 3
        t0 = time.perf_counter()
        rawhdr, raws = write_recording(stem, size, frames, raw_cap, seed=SEED,
                                       tone_chan=tone_chan,
                                       tone_fine=tone_fine)
        say("reduce.synth", files=[os.path.basename(p) for p in raws],
            directory=rawdir, seconds=round(time.perf_counter() - t0, 1),
            scratch_free=shutil.disk_usage(rawdir).free)

        out = os.path.join(outdir, "smoke.rawspec.0000.fil")
        argv = (["reduce", *raws, "-o", out, "--product", "0000"] if on_tpu
                else ["reduce", *raws, "-o", out, "--nfft", str(nfft)])
        first = {}
        done = threading.Event()

        def watch():  # seconds to the first product bytes on disk
            t = time.perf_counter()
            while not done.wait(0.02):
                for p in (out + ".partial", out):
                    if os.path.exists(p) and os.path.getsize(p) > 1 << 16:
                        first["s"] = time.perf_counter() - t
                        return

        watcher = threading.Thread(target=watch, daemon=True)
        watcher.start()
        t0 = time.perf_counter()
        try:
            with compile_account() as compiles:
                res = run_cli(argv)["last"]
        finally:
            done.set()
            watcher.join()
        wall = time.perf_counter() - t0
        stats = jax.devices()[0].memory_stats() or {}
        probe = RawReducer(nfft=nfft)
        say("reduce.run", argv=argv, first_product_s=round(first.get("s", wall), 2),
            total_wall_s=round(wall, 2), **compiles,
            peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            bytes_limit=stats.get("bytes_limit"),
            kernel_plan=res["kernel_plan"],
            raw_native=open_raw(raws).native,
            tuning=probe.tuning_provenance())
        if res["platform"] != jax.devices()[0].platform:
            raise AssertionError(f"CLI reports {res['platform']}")
        if on_tpu:
            plan = res["kernel_plan"]
            if (plan.get("pfb_kernel"), plan.get("tail_kernel")) != (
                    "fused1", "tail2_detect"):
                raise AssertionError(
                    f"'auto' did not resolve to the fused plan: {plan}")
        check_tone_and_reference(out, raws, rawhdr, size, tone_chan,
                                 tone_fine, other_chan, frames - 3)
    finally:
        if rawdir:
            shutil.rmtree(rawdir, ignore_errors=True)
        shutil.rmtree(outdir, ignore_errors=True)


# -- the kernels that are not on the 0000 path --------------------------------

def kernel_legs(size: dict, on_tpu: bool) -> None:
    """Step 6.  On the chip every kernel below is COMPILED (the plan hooks
    are asserted); the rehearsal runs the same checks through whatever the
    CPU resolves to."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from blit.ops.channelize import (
        channelize, channelize_np, last_kernel_plan, pfb_coeffs)
    from blit.ops.fqav import fqav
    from blit.ops.pallas_beamform import pack_voltages, pack_weights
    from blit.ops.pallas_dedoppler import (
        brute_force_dedoppler, dedoppler_hits, last_dedoppler_plan,
        taylor_tree, tree_path_shift, unpack_hits)
    from blit.parallel import beamform as B
    from blit.parallel import correlator as C
    from blit.parallel import mesh as M

    rng = np.random.default_rng(SEED)
    mesh = M.make_mesh(1, 1)
    K = size["kernels"]

    def ints(shape):  # int8-valued voltages: exact in bf16
        return rng.integers(-40, 40, shape).astype(np.float32)

    # Fused beamform + detect, bf16 planes.
    k = K["beamform"]
    t0 = time.perf_counter()
    vr = ints((k["nant"], k["nchan"], k["ntime"], 2))
    vi = ints((k["nant"], k["nchan"], k["ntime"], 2))
    wr, wi = B.delay_weights_planar(
        jnp.asarray(rng.uniform(0, 1e-9, (k["nbeam"], k["nant"]))),
        jnp.asarray(np.linspace(1e9, 1.1e9, k["nchan"])))
    wr, wi = np.asarray(wr), np.asarray(wi)
    kv = tuple(np.asarray(a.astype(jnp.bfloat16)) for a in pack_voltages(
        jnp.asarray(vr), jnp.asarray(vi)))
    kw = tuple(np.asarray(a) for a in pack_weights(
        jnp.asarray(wr), jnp.asarray(wi)))
    power = np.asarray(B.beamform(
        jax.device_put(kv, NamedSharding(mesh, P(None, "bank"))),
        jax.device_put(kw, NamedSharding(mesh, P(None, None, "bank"))),
        mesh=mesh, nint=k["nint"], layout="chan"))
    plan = B.last_beamform_plan()
    if on_tpu and not plan.get("fused"):
        raise AssertionError(f"beamform fell back to einsums: {plan}")
    sub = slice(0, min(4, k["nchan"]))  # reference on a few channels
    want = B.beamform_np((vr + 1j * vi)[:, sub], (wr + 1j * wi)[:, :, sub],
                         nint=k["nint"])
    err = rel_err(np.transpose(power[sub], (1, 0, 3, 2)), want)
    if not np.isfinite(power).all() or err > TOL:
        raise AssertionError(f"fused beamform rel err {err:.3g}")
    say("kernel.beamform", **k, plan=plan, rel_err=err,
        seconds=round(time.perf_counter() - t0, 1))
    del vr, vi, kv, power

    # Packed X-engine at nant 64.
    k = K["xengine"]
    t0 = time.perf_counter()
    ntime = k["nblk"] * k["nfft"]
    cr = ints((k["nant"], k["nchan"], ntime, 2))
    ci = ints((k["nant"], k["nchan"], ntime, 2))
    h = pfb_coeffs(4, k["nfft"])
    cvp = jax.device_put((cr, ci), C.correlator_sharding(mesh))
    pr, pi = C.correlate(cvp, jnp.asarray(h), mesh=mesh, nfft=k["nfft"],
                         ntap=4, vis_layout="packed")
    plan = C.last_xengine_plan()
    if on_tpu and plan.get("engine") != "pallas":
        raise AssertionError(f"X-engine fell back to einsums: {plan}")
    pr, pi = np.asarray(pr), np.asarray(pi)
    want = C.correlate_np((cr + 1j * ci)[:, :1].astype(np.complex64), h,
                          nfft=k["nfft"], ntap=4).transpose(2, 3, 0, 4, 1, 5)
    scale = np.abs(want).max()
    err = max(np.abs(pr[:1] - want.real).max(),
              np.abs(pi[:1] - want.imag).max()) / scale
    if not (np.isfinite(pr).all() and np.isfinite(pi).all()) or err > TOL:
        raise AssertionError(f"packed X-engine rel err {err:.3g}")
    say("kernel.xengine", **k, plan=plan, rel_err=float(err),
        seconds=round(time.perf_counter() - t0, 1))
    del cr, ci, cvp, pr, pi, want

    # Drift search: the tree kernel against
    # the lax reference bitwise and a NumPy brute force on a window of
    # columns; then the full search step must recover the injected drift.
    k = K["dedoppler"]
    t0 = time.perf_counter()
    T, F = k["T"], k["F"]
    x = rng.normal(100.0, 10.0, (T, F)).astype(np.float32)
    f0, db = F // 3, min(7, T - 1)
    for t in range(T):
        x[t, f0 + tree_path_shift(db, t, T)] += 400.0
    xj = jnp.asarray(x)
    interp = not on_tpu  # the rehearsal interprets; the chip never does
    pal = jax.jit(functools.partial(taylor_tree, kernel="pallas",
                                    interpret=interp))(xj)
    ref = jax.jit(functools.partial(taylor_tree, kernel="reference"))(xj)
    if not bool(jnp.array_equal(pal, ref)):
        raise AssertionError("tree kernel differs from the lax reference")
    w0, w1 = f0 - min(f0, 256), min(F, f0 + 256)
    brute = brute_force_dedoppler(x[:, w0:w1 + T])[:, :w1 - w0]
    err = rel_err(np.asarray(pal[:, w0:w1]), brute)
    if err > 1e-5:
        raise AssertionError(f"tree kernel vs brute force: {err:.3g}")
    packed = dedoppler_hits(xj, jnp.float32(8.0), top_k=4,
                            nbands=max(1, F >> 14), kernel="auto",
                            interpret=interp)
    plan = last_dedoppler_plan()
    if on_tpu and plan.get("kernel") != "pallas":
        raise AssertionError(f"drift search took the lax path: {plan}")
    snr, _, drift, chan, _ = unpack_hits(np.asarray(packed))
    top = int(np.argmax(snr))
    if (int(drift[top]), int(chan[top])) != (db, f0):
        raise AssertionError(
            f"top hit (drift {drift[top]}, chan {chan[top]}), injected "
            f"(drift {db}, chan {f0})")
    say("kernel.dedoppler", **k, plan=plan, bitwise_equals_lax=True,
        brute_force_rel_err=err, top_hit=[int(drift[top]), int(chan[top])],
        seconds=round(time.perf_counter() - t0, 1))
    del x, xj, pal, ref, packed

    # channelize with the fqav epilogue and with full Stokes, against
    # channelize_np on two channels.
    k = K["channelize"]
    t0 = time.perf_counter()
    nfft = size["nfft"]
    v = rng.integers(-40, 40, (k["nchan"], (k["frames"] + 3) * nfft, 2, 2),
                     dtype=np.int8)
    coeffs = pfb_coeffs(4, nfft)
    cj = jnp.asarray(coeffs)
    vj = jnp.asarray(v)
    two = [0, k["nchan"] - 1]
    kw = dict(nfft=nfft, dtype=k["dtype"])
    plans = {}
    for name, extra, ref_fn in (
        ("fqav16", dict(fqav_by=16),
         lambda: np.asarray(fqav(channelize_np(v[two], coeffs, nfft=nfft), 16))),
        ("iquv", dict(stokes="IQUV"),
         lambda: channelize_np(v[two], coeffs, nfft=nfft, stokes="IQUV")),
    ):
        got = channelize(vj, cj, **kw, **extra)  # stays on the device
        plans[name] = last_kernel_plan()
        if on_tpu and plans[name]["pfb_kernel"] != "fused1":
            raise AssertionError(f"{name}: not the fused plan {plans[name]}")
        want = ref_fn()
        per = got.shape[-1] // k["nchan"]
        sel = np.concatenate(
            [np.asarray(got[..., c * per:(c + 1) * per]) for c in two],
            axis=-1)
        plans[name + "_rel_err"] = rel_err(sel, want)
        if (not bool(jnp.isfinite(got).all())
                or plans[name + "_rel_err"] > TOL):
            raise AssertionError(
                f"channelize {name}: rel err {plans[name + '_rel_err']:.3g}")
        del got
    say("kernel.channelize", **k, **plans,
        seconds=round(time.perf_counter() - t0, 1))


# -- the same path on four chips ----------------------------------------------

def mesh_leg(size: dict, on_tpu: bool) -> None:
    """`blit scan` over one band, banks BLP00-BLP03 on the (1, 4) mesh:
    the default window loop, the --pool oracle, --sharded, and --search."""
    import filecmp
    import gc

    import jax
    import numpy as np

    from blit.config import default_window_frames

    nfft, nchan = size["nfft"], size["nchan"]
    session, scan, nbank = "AGBT22B_999_01", "0011", 4
    if len(jax.devices()) < nbank:
        raise RuntimeError(f"the mesh leg needs {nbank} devices, "
                           f"found {len(jax.devices())}")
    outdir, frames = product_dir(size["mesh_frames"],
                                 nbank * nchan * nfft * 4, copies=4)
    bank_bytes = frames * nfft * nchan * 4
    root = None
    try:
        root, raw_cap = scratch_dir(
            nbank * bank_bytes + (1 << 30),
            bank_bytes + (bank_bytes // (size["block_samples"] * nchan * 4))
            * RAW_HEADER_ROOM)
        t0 = time.perf_counter()
        bank_bw = -187.5 / 8
        for k in range(nbank):  # build_observation_tree's layout and tiling
            d = os.path.join(root, session, "GUPPI", f"BLP0{k}")
            os.makedirs(d)
            write_recording(
                os.path.join(d, f"blc0{k}_guppi_59897_21221_HD_84406_{scan}"),
                size, frames, raw_cap, seed=SEED + 1 + k, tone_chan=k,
                tone_fine=nfft // 2 + 17 + k, obsbw=bank_bw,
                obsfreq=8000.0 + (k + 0.5) * bank_bw)
        say("mesh.synth", banks=nbank, bank_bytes=bank_bytes,
            seconds=round(time.perf_counter() - t0, 1))

        def scan_cli(tag, *extra):
            out = os.path.join(outdir, tag)
            os.makedirs(out, exist_ok=True)
            t = time.perf_counter()
            res = run_cli(["scan", root, session, scan, "-o", out,
                           "--nfft", str(nfft), *extra])
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in jax.devices()[:nbank]]
            say("mesh." + tag, args=list(extra),
                wall_s=round(time.perf_counter() - t, 1),
                peak_bytes_in_use_by_device=peaks,
                platform=res["last"].get("platform"),
                device_count=res["last"].get("device_count"))
            return out, res, peaks

        def scan_fitting(tag, *extra, wf=None):
            """Run a scan; if the device refuses the window, halve it with
            the lever the CLI already has (--window-frames) and say so.
            Only an out-of-memory refusal is retried."""
            while True:
                window = [] if wf is None else ["--window-frames", str(wf)]
                try:
                    out, res, peaks = scan_cli(tag, *extra, *window)
                    return out, res, peaks, res["last"]["window_frames"]
                except Exception as e:  # noqa: BLE001 — re-raised unless OOM
                    msg = str(e)
                    if ("RESOURCE_EXHAUSTED" not in msg
                            and "Ran out of memory" not in msg):
                        raise
                    was = wf or default_window_frames(nfft)
                    wf = was // 2
                    if wf < 1:
                        raise
                    at = max(msg.find("Ran out of memory"), 0)
                    say("mesh.reduced", scan=tag, lever="--window-frames",
                        was=was, now=wf, allocator=msg[at:at + 400])
                gc.collect()  # drop the failed attempt's device arrays

        # The default path first, exactly as a user types it.
        mesh_out, res, peaks, wf = scan_fitting("mesh")
        if on_tpu and not all(p for p in peaks):
            raise AssertionError(f"a device did no work: peaks {peaks}")
        band = os.path.join(mesh_out, "band0.fil")
        hdr, data = open_fil(band)
        if (hdr["nchans"], hdr["nsamps"]) != (nbank * nchan * nfft,
                                             frames - 3):
            raise AssertionError(f"band product geometry {hdr}")
        for k in range(nbank):  # each bank's tone, where its slice sits
            lo = (k * nchan + k) * nfft
            at = int(np.argmax(data[0, 0, lo:lo + nfft]))
            if at != nfft // 2 + 17 + k:
                raise AssertionError(f"bank {k}: tone at fine {at}")
        del data

        # The oracle: per-bank `RawReducer`s laid side by side.
        pool_out, _, _ = scan_cli("pool", "--pool", "--window-frames", str(wf))
        same = filecmp.cmp(band, os.path.join(pool_out, "band0.fil"),
                           shallow=False)
        if not same:
            _, a = open_fil(band)
            _, b = open_fil(os.path.join(pool_out, "band0.fil"))
            err = max(rel_err(a[t], np.asarray(b[t], np.float64))
                      for t in range(a.shape[0]))
            if err > TOL:
                raise AssertionError(f"mesh vs pool oracle rel err {err:.3g}")
            say("mesh.oracle", byte_identical=False, rel_err=err)
        else:
            say("mesh.oracle", byte_identical=True)
        shutil.rmtree(pool_out)

        # The sharded plane and the search plane, once each.
        sh_out, _, _, sh_wf = scan_fitting("sharded", "--sharded", wf=wf)
        say("mesh.sharded_vs_mesh", window_frames=sh_wf,
            byte_identical=filecmp.cmp(
                band, os.path.join(sh_out, "band0.fil"), shallow=False))
        shutil.rmtree(sh_out)
        # Default window_spectra is 64 spectra of 64 Mi channels per chip
        # (16 GiB): the search runs at the smallest window the CLI allows.
        say("mesh.reduced", lever="--window-spectra", was=64, now=2,
            why="a 64-spectra window of 64 x 2^20 channels is 16 GiB/chip")
        s_out, sres, _ = scan_cli("search", "--search", "--window-spectra",
                                  "2", "--window-frames", "2")
        nhits = len([p for p in os.listdir(s_out) if p.endswith(".hits")])
        if nhits != nbank:
            raise AssertionError(f"{nhits} .hits products, want {nbank}")
        say("mesh.search.check", products=nhits,
            dedoppler_plan=sres["last"].get("dedoppler_plan"))
    finally:
        if root:
            shutil.rmtree(root, ignore_errors=True)
        shutil.rmtree(outdir, ignore_errors=True)


# -- main ---------------------------------------------------------------------

def main(argv) -> int:
    mode = argv[1] if len(argv) > 1 else "witness"
    if mode not in ("witness", "mesh", "rehearse") or len(argv) > 2:
        print(__doc__, file=sys.stderr)
        return 2
    rehearse = mode == "rehearse"
    if rehearse:  # the named CPU rehearsal: a virtual 4-device mesh
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    sys.path.insert(0, HERE)
    import logging

    # The reducer says how many coarse channels it puts in one dispatch.
    logging.basicConfig(stream=sys.stdout, format="  log> %(message)s")
    logging.getLogger("blit.pipeline").setLevel(logging.DEBUG)
    t0 = time.perf_counter()
    device = require_tpu(rehearse)
    on_tpu = device["platform"] == "tpu"
    size = TOY if rehearse else FULL
    build_native()
    say("host", **host_facts())
    if mode != "mesh":
        rig_facts()
        reduce_leg(size, on_tpu)
        kernel_legs(size, on_tpu)
    if mode == "mesh" or device["count"] >= 4:
        mesh_leg(size, on_tpu)
    else:
        say("mesh", skipped=f"{device['count']} device(s); run "
            "`chiprun --chips 4 -- python chip_smoke.py mesh`")
    say("done", mode=mode, wall_s=round(time.perf_counter() - t0, 1))
    if rehearse:
        print("rehearsal finished on the CPU: no result", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main(sys.argv))
    except Exception as e:
        # Still a failure (re-raised): only the END of stderr comes back
        # from a sealed machine, so what that machine allows goes last.
        e.add_note(f"chip_smoke: this machine allows {host_facts()}")
        raise
