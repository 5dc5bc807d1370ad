"""blit benchmark — the driver-tracked metric (BASELINE.json).

Measures sustained single-chip GUPPI RAW → hi-res filterbank reduction:
int8 dual-pol complex voltages through dequant → 4-tap PFB → 1M-point
matmul-DFT channelization → Stokes-I detect (blit.ops.channelize, the
rawspec-equivalent hi-res "0000" product).

Prints ONE JSON line:
  {"metric": ..., "value": GB/s/chip of net RAW input, "unit": "GB/s",
   "vs_baseline": real-time factor vs one bank's 0.75 GB/s recording rate}

The north-star target is >= 4x real-time for a full bank (BASELINE.json:
>= 3 GB/s/chip).  "Net" input counts each voltage sample once (the PFB
overlap re-processing is not credited).

Methodology: data device-resident, K dispatches enqueued back-to-back, one
final sync — steady-state streaming with dispatch latency amortized, matching
how blit.pipeline overlaps host IO with device work.  The record names the
platform, device kind and device count it ran on at its top level.

One config per platform: the orchestrator finds the platform in a
subprocess (it never touches JAX itself — a parent that did would hold the
chip its ``--single`` child needs), runs that platform's config once, and
exits non-zero when the run or any of its legs raised (the line still
prints, with the failures under ``errors``).  The CPU config runs only
when the caller set ``JAX_PLATFORMS=cpu``: a chip that is not found is an
error, not a smaller benchmark under the same metric name.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time

import numpy as np

# Per-bank recording rate: 187.5 Msamp/s x 2 pol x 2 bytes (SURVEY.md §6).
REALTIME_BANK_GBPS = 0.750

# Ingest-inclusive leg: (nfft, nchan, chunk_frames, nblocks, ntime_per_block)
# — synthetic RAW file -> streamed filterbank product via RawReducer, i.e.
# file read + host->device + channelize + host readback, the reference's
# whole worker-side data path (src/gbtworkerfunctions.jl:171-189 analog).
# The file length leaves exactly the (ntap-1)*nfft filter tail after the
# last chunk, so no flush-shape compile triggers (total samples =
# n_chunks*frames*nfft + 3*nfft).
_INGEST_CONFIGS = {
    # 48 channels x 8 frames: the primary leg's chunk (the reducer splits
    # it into as many channel groups as the device holds).
    "tpu_bf16": (1 << 20, 48, 8, 4, 19 * (1 << 18)),
    "cpu": (1 << 14, 4, 4, 4, 11 * (1 << 12)),
}

# (nfft, ntap, nint, nchan, frames, K calls, dtype).  K is large enough
# that K x call-time dwarfs the one closing fetch.
_CONFIGS = {
    # Hi-res product, bf16 stages + fused pallas dequant+PFB: the gross
    # dequant planes never hit HBM, so 48 coarse channels x 8 frames fit
    # per dispatch (64 do not).  Accuracy bound: DESIGN.md §8.
    "tpu_bf16": (1 << 20, 4, 1, 48, 8, 24, "bfloat16"),
    # JAX_PLATFORMS=cpu runs (development): keep runtime sane.
    "cpu": (1 << 14, 4, 1, 4, 4, 4, "float32"),
}

# The config each platform runs.
_PLATFORM_CONFIG = {"tpu": "tpu_bf16", "cpu": "cpu"}

# Budget for the one --single subprocess, cold compile cache included.
_SINGLE_TIMEOUT_S = 2400.0


def run_single(config_name: str) -> int:
    """One measurement in this process; prints the JSON line and returns
    the exit code (non-zero when any leg raised)."""
    import jax
    import jax.numpy as jnp

    from blit.device import device_facts, use_compile_cache

    use_compile_cache()

    # Live monitoring (ISSUE 11): with BLIT_MONITOR_SPOOL / _PORT set,
    # the bench publishes its stage/hist telemetry while it measures —
    # `blit top` watches a long TPU bench exactly like a production run.
    from blit import monitor

    monitor.ensure_publisher()

    from blit.ops.channelize import (
        channelize,
        last_kernel_plan as _last_kernel_plan,
        pfb_coeffs,
    )

    facts = device_facts()
    nfft, ntap, nint, nchan, frames, K, dtype = _CONFIGS[config_name]
    errors = {}

    def leg(name, fn):
        """Run one secondary leg.  A failure is recorded and the line
        still prints; the exit code says a leg failed."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — reported, and fails the run
            errors[name] = f"{type(e).__name__}: {e}"
            return {}

    ntime = (ntap - 1 + frames) * nfft
    rng = np.random.default_rng(0)
    v = rng.integers(-40, 40, size=(nchan, ntime, 2, 2), dtype=np.int8)
    coeffs = jnp.asarray(pfb_coeffs(ntap, nfft))
    vj = jax.block_until_ready(jnp.asarray(v))

    # NOTE: the kwarg set here matches RawReducer._channelize_kw EXACTLY
    # (jax.jit caches per call signature, so an extra/missing kwarg — even
    # at its default value — forces a recompile and would poison the ingest
    # leg's warm-cache assumption).  RawReducer adds dtype= only when not
    # float32; mirror that.
    kw = dict(nfft=nfft, ntap=ntap, nint=nint, stokes="I", fft_method="auto")
    if dtype != "float32":
        kw["dtype"] = dtype

    def step(x):
        out = channelize(x, coeffs, **kw)
        # Tiny on-device reduction: forces execution while keeping the
        # sync payload scalar (the host readback is not the DUT here).
        return jnp.sum(out)

    # Warmup / compile.
    float(step(vj))

    # Methodology: enqueue all K dispatches, then ONE final sync — the
    # device queue is in-order, so the last scalar materializing implies
    # every dispatch executed.  The K checksums are fetched outside the
    # timed window (the compute being timed is genuinely done).
    t0 = time.perf_counter()
    acc = [step(vj) for _ in range(K)]
    float(acc[-1])
    elapsed = time.perf_counter() - t0
    total = float(jnp.sum(jnp.stack(acc)))
    del acc
    net_bytes_per_call = frames * nfft * nchan * 2 * 2  # int8 re/im, 2 pol

    def timed_variant(**extra):
        """The same reduction with ``extra`` channelize kwargs: compile,
        then K dispatches under one closing sync -> net GB/s."""
        kwv = dict(kw, **extra)

        def stepv(x):
            return jnp.sum(channelize(x, coeffs, **kwv))

        float(stepv(vj))  # compile (persistent-cached)
        t0 = time.perf_counter()
        accv = [stepv(vj) for _ in range(K)]
        float(accv[-1])
        return round(
            net_bytes_per_call * K / (time.perf_counter() - t0) / 1e9, 3)

    # fqav epilogue leg (VERDICT r3 item 7): the on-device
    # reduce-before-the-wire fold active.  Full-Stokes leg (VERDICT r4
    # item 5): stokes="IQUV" — nif=4, 4x the product bytes through the
    # fused detect path.
    extras = {}
    extras.update(leg("fqav16", lambda: {
        "fqav16_gbps": timed_variant(fqav_by=16)}))
    extras.update(leg("stokes_iquv", lambda: {
        "stokes_iquv_gbps": timed_variant(stokes="IQUV")}))

    # Free the primary leg's device residents (up to GBs) before the
    # secondary legs — they have their own working sets and OOM otherwise.
    del vj

    gbps = net_bytes_per_call * K / elapsed / 1e9

    result = {
        "metric": "guppi_raw_to_hires_filterbank_GBps_per_chip",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / REALTIME_BANK_GBPS, 2),
        # What ran this: a record without a device is not a record.
        **facts,
        "config": {
            "backend": facts["platform"],
            "name": config_name,
            "nfft": nfft,
            "ntap": ntap,
            "nint": nint,
            "nchan": nchan,
            "frames_per_call": frames,
            "calls": K,
            "stokes": "I",
            "dtype": dtype,
            "checksum": total,
            # What 'auto' dispatch resolved to (ADVICE r3: silent default
            # changes must be attributable in the recorded numbers).
            "kernel_plan": _last_kernel_plan(),
        },
    }
    result.update(extras)
    result.update(leg("ingest", lambda: _run_ingest(config_name, leg)))
    result.update(leg("config1", _run_config1))
    result.update(leg("dedoppler", lambda: _run_dedoppler(config_name)))
    result.update(leg("collectives", _run_collectives))
    result.update(leg("mesh_collectives", _run_mesh_collectives))

    # Telemetry surfacing (ISSUE 5): span/flight-event counts plus any
    # process-timeline histograms ride the bench line, and the full fleet
    # report lands wherever BLIT_TELEMETRY_OUT points (the CI-artifact
    # hook; no-op when unset).
    def telemetry():
        from blit import observability

        observability.maybe_write_report()
        return {"telemetry": {
            "spans": len(observability.tracer().spans()),
            "flight_events": len(observability.flight_recorder().events()),
            "hists": observability.process_timeline().report().get(
                "hists", {}),
        }}

    result.update(leg("telemetry", telemetry))

    # Perf-regression self-check (ISSUE 11): with BLIT_BENCH_BASELINE_DIR
    # pointing at the checked-in BENCH_*.json trajectory, this run diffs
    # itself against the noise bands and records the verdict in its own
    # line.  Advisory here; CI runs `blit bench-diff` as the gating step.
    def self_diff():
        import glob
        import os

        bdir = os.environ.get("BLIT_BENCH_BASELINE_DIR")
        if not bdir:
            return {}
        baselines = []
        for p in sorted(glob.glob(os.path.join(bdir, "BENCH_*.json"))):
            try:
                baselines.append(monitor.load_bench_json(p))
            except ValueError:
                # A failed round with no record line thins the
                # trajectory; it must not break the self-check.
                continue
        if not baselines:
            return {}
        diff = monitor.bench_diff(result, baselines)
        return {"bench_diff": {
            "verdict": diff["verdict"],
            "regressed": diff["regressed"],
            "baselines": diff["baselines"],
        }}

    result.update(leg("bench_diff", self_diff))
    # Sub-legs that keep their siblings running report as "<name>_error".
    for k in [k for k in result if k.endswith("_error")]:
        errors[k[: -len("_error")]] = result.pop(k)
    if errors:
        result["errors"] = errors
    print(json.dumps(result))
    return 1 if errors else 0


def _run_ingest(config_name: str, leg) -> dict:
    """File→product throughput: synthetic RAW on a ram-backed dir, streamed
    through :class:`blit.pipeline.RawReducer` (native threaded reads + ring
    buffer + jitted channelize + full host readback of the product)."""
    import os
    import shutil
    import tempfile

    from blit.io.guppi import GuppiRaw, write_raw
    from blit.outplane import INGEST_HISTS
    from blit.pipeline import RawReducer
    from blit.testing import make_raw_header

    nfft, nchan, chunk_frames, nblocks, ntime = _INGEST_CONFIGS[config_name]
    # Same working dtype as the primary leg (keeps the jit cache shared).
    *_, dtype = _CONFIGS[config_name]
    rng = np.random.default_rng(1)
    tmp = tempfile.mkdtemp(
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None
    )
    try:
        path = os.path.join(tmp, "bench.raw")
        hdr = make_raw_header(obsnchan=nchan, npol=2)
        blocks = [
            rng.integers(-40, 40, (nchan, ntime, 2, 2)).astype(np.int8)
            for _ in range(nblocks)
        ]
        write_raw(path, hdr, blocks)
        file_bytes = sum(b.nbytes for b in blocks)

        # BLIT_BENCH_TRACE=<logdir> wraps the streaming run in a JAX
        # profiler trace (TensorBoard/Perfetto) without touching the metric.
        red = RawReducer(nfft=nfft, nint=1, stokes="I",
                         chunk_frames=chunk_frames, dtype=dtype,
                         trace_logdir=os.environ.get("BLIT_BENCH_TRACE") or None)
        raw = GuppiRaw(path)
        # Producer-only read pass FIRST: measures the host read leg clean of
        # device interference (best of 2 — the host's cores are shared),
        # and doubles as steady-state warmup
        # (page cache + buffer first-touch faults) for the timed run below,
        # matching the compute leg's compile warmup.
        host_read_gbps = 0.0
        for _ in range(2):
            t0 = time.perf_counter()
            for c in red._chunks(raw):
                c.release()
            host_read_gbps = max(
                host_read_gbps,
                file_bytes / (time.perf_counter() - t0) / 1e9,
            )
        # Discard warmup passes IN PLACE (Timeline.reset) — NOT
        # stages.clear(): clear() orphans any StageStats object a thread
        # or captured local still holds, so later byte/second updates
        # land in objects the report never sees.  That identity bug is
        # how the seed-era rig reported BENCH_r05's
        # "stream": {"s": 350.3, "bytes": 0} (ISSUE 4 satellite;
        # tests/test_outplane.py pins this exact warmup→reset→drain
        # sequence).
        red.timeline.reset()
        t0 = time.perf_counter()
        checksum = red.drain(raw)
        elapsed = time.perf_counter() - t0

        # Rig characterization: device→host bandwidth (NOT part of the
        # metric — the drain keeps the product device-side, while the
        # framework's own write path is bounded by this link, so the
        # per-rig number is reported alongside).
        import jax
        import jax.numpy as jnp

        y = jax.block_until_ready(jnp.zeros((1 << 21,), jnp.float32))  # 8 MB
        t1 = time.perf_counter()
        np.asarray(y)
        readback_gbps = y.nbytes / (time.perf_counter() - t1) / 1e9

        # Product leg (ISSUE 4): the SAME recording reduced to an actual
        # on-disk product through the asynchronous output plane — host
        # read → H2D → compute → D2H readback → write-behind .fil append
        # all overlapped (blit/outplane.py).  fqav_by=16 is the paper's
        # reduce-before-the-wire lever: the product (hence the readback)
        # shrinks 16x.  The stage table carries the readback/write stages
        # and the overlap gauge (sum of device+readback+write seconds per
        # stream-wall second; ~1 = serialized, higher = hidden).
        def product_legs() -> dict:
            def product_leg(async_output: bool, name: str) -> dict:
                # tune_online=False: with BLIT_TUNE_ONLINE=1 the async
                # leg could persist a profile mid-bench that the sync
                # leg then loads — the A/B must compare ONE knob set
                # (same reason ingest-bench pins it).
                redp = RawReducer(nfft=nfft, nint=1, stokes="I",
                                  chunk_frames=chunk_frames, dtype=dtype,
                                  fqav_by=16, async_output=async_output,
                                  tune_online=False)
                t2 = time.perf_counter()
                redp.reduce_to_file(raw, os.path.join(tmp, name))
                elp = time.perf_counter() - t2
                return {
                    "async_output": async_output,
                    "wall_s": round(elp, 3),
                    "gbps": round(file_bytes / elp / 1e9, 3),
                    "overlap_efficiency": round(
                        redp.timeline.overlap_efficiency(), 3
                    ),
                    "stages": {
                        k: {"s": round(v.seconds, 3), "bytes": v.bytes}
                        for k, v in redp.timeline.stages.items()
                    },
                    # Stage TAILS from the telemetry hists (ISSUE 8):
                    # p50/p99 readback lag / write / chunk service.
                    "stage_quantiles": redp.timeline.hist_quantiles(
                        INGEST_HISTS),
                }

            # Before/after --sync-compare table ON the bench artifact
            # (ISSUE 8 acceptance): the same recording through the async
            # plane and the serialized path, byte-identity checked.
            pa = product_leg(True, "bench.0000.fil")
            ps = product_leg(False, "bench.sync.0000.fil")
            from blit.testing import sync_compare_verdict

            return {
                "rig_product_gbps": pa["gbps"],
                "product_config": {
                    "fqav_by": 16,
                    "sink": ".fil (async output plane)",
                    "overlap_efficiency": pa["overlap_efficiency"],
                    "stages": pa["stages"],
                    "stage_quantiles": pa["stage_quantiles"],
                    "sync_compare": ps,
                    **sync_compare_verdict(
                        os.path.join(tmp, "bench.0000.fil"),
                        os.path.join(tmp, "bench.sync.0000.fil"),
                        async_wall_s=pa["wall_s"],
                        sync_wall_s=ps["wall_s"]),
                },
            }

        from blit import hostmem

        return {
            **leg("rig_product", product_legs),
            # "rig_" prefix: an end-to-end figure that includes this
            # machine's host read and host<->device links (see the stage
            # table and rig_readback_gbps).
            "rig_ingest_gbps": round(file_bytes / elapsed / 1e9, 3),
            "ingest_config": {
                "nfft": nfft,
                "nchan": nchan,
                "chunk_frames": chunk_frames,
                "dtype": dtype,
                "prefetch_depth": red.prefetch_depth,
                "host_read_gbps": round(host_read_gbps, 3),
                "file_bytes": file_bytes,
                "out_frames": red.stats.output_frames,
                "checksum": checksum,
                "native_reader": raw.native,
                "sink": "device (see DESIGN.md §8)",
                "rig_readback_gbps": round(readback_gbps, 4),
                # Which ingest knobs ran and where they came from
                # (explicit bench pin / per-rig tuning profile / default
                # — blit/tune.py; ISSUE 8 satellite).
                "tuning": red.tuning_provenance(),
                "staging_pool": hostmem.slab_pool().stats(),
                "stages": {
                    k: {"s": round(v.seconds, 3), "bytes": v.bytes}
                    for k, v in red.timeline.stages.items()
                },
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _run_collectives() -> dict:
    """BASELINE configs 4-5: coherent beamform and FX correlator throughput
    on the real chip (1x1 mesh — the per-chip math plus the collective code
    path; ICI scaling is validated separately on the virtual mesh).
    Reported as GB/s of planar antenna voltages consumed.

    The inputs are REAL per-antenna GUPPI RAW files on a ram-backed dir,
    loaded through the WINDOWED antenna data plane
    (blit/parallel/antenna.py streams — the collective legs consume the
    same bytes a recording would provide, not rng arrays; VERDICT r3
    item 4).  Device residents for the K-dispatch chip numbers come from
    a one-window feed; the ``*_stream_*`` legs then run genuinely
    multi-window (ingest/pack/transfer overlapping compute at
    ``prefetch_depth`` windows of host memory — recording length no
    longer bounds host RSS) and report per-window stage timings with
    byte counts (``rig_*_feed`` — "rig_" because the host and transfer
    legs depend on the machine); the chip numbers are the headline.
    """
    import os
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from blit.observability import Timeline
    from blit.ops.channelize import pfb_coeffs
    from blit.parallel import antenna as A
    from blit.parallel import beamform as B
    from blit.parallel import correlator as C
    from blit.parallel import mesh as M
    from blit.testing import synth_raw

    mesh = M.make_mesh(1, 1)
    rng = np.random.default_rng(3)
    out = {}

    def stage_table(tl: Timeline) -> dict:
        """ONE serializer for every collective stage table (s/bytes per
        stage + the byte_free marker, so each report can be checked
        against the nonzero-seconds ⇒ nonzero-bytes-or-byte-free
        invariant).  list(): feed producer threads may still be
        inserting stage keys."""
        return {
            k: {"s": round(v.seconds, 3), "bytes": v.bytes,
                **({"byte_free": True} if v.byte_free else {})}
            for k, v in sorted(list(tl.stages.items()))
        }

    def feed_report(tl: Timeline, seconds: float) -> dict:
        """A feed Timeline as the JSON report block."""
        return {"seconds": round(seconds, 3), "stages": stage_table(tl)}

    tmp = tempfile.mkdtemp(
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None
    )
    try:

        def ant_files(tag, nant, nchan, ntime):
            paths = []
            for a in range(nant):
                p = os.path.join(tmp, f"{tag}{a}.raw")
                synth_raw(p, nblocks=2, obsnchan=nchan,
                          ntime_per_block=ntime // 2, seed=300 + a,
                          tone_chan=a % nchan)
                paths.append(p)
            return paths

        # Beamform: 64 antennas -> 64 beams, detect+integrate.
        nant, nbeam, nchan, ntime, npol, nint = 64, 64, 64, 8192, 2, 8
        # Fixture synthesis happens OUTSIDE the timed load window — the
        # feed legs measure the antenna data plane (file read + dequant +
        # device_put), not rng writes a real recording never incurs.
        paths = ant_files("bf", nant, nchan, ntime)
        # Device residents via a ONE-WINDOW feed (the windowed data plane
        # is the only load path now); the window stays unreleased for the
        # whole K-loop — its arrays may alias the slot's host buffers.
        tl_bf = Timeline()
        t0 = time.perf_counter()
        bf_wins = list(A.AntennaStream(
            paths, mesh=mesh, window_samples=ntime, max_samples=ntime,
            timeline=tl_bf,
        ))
        jax.block_until_ready(bf_wins[0].arrays)
        out["rig_beamform_feed"] = feed_report(
            tl_bf, time.perf_counter() - t0
        )
        vp = bf_wins[0].arrays
        wr, wi = B.delay_weights_planar(
            jnp.asarray(rng.uniform(0, 1e-9, (nbeam, nant))),
            jnp.asarray(np.linspace(1e9, 1.1e9, nchan)),
        )
        wp = jax.device_put((np.asarray(wr), np.asarray(wi)),
                            B.weight_sharding(mesh))
        jax.block_until_ready(wp)

        # bf16-resident planes: lossless for 8-bit RAW voltages, half the
        # HBM reads (measured +26%, DESIGN.md §9 r5; ~1e-2 max rel err on
        # detected power from weight rounding + bf16 partial sums).
        vp16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), vp)
        jax.block_until_ready(vp16)

        def bstep():
            return jnp.sum(B.beamform(vp, wp, mesh=mesh, nint=nint))

        def bstep16():
            return jnp.sum(B.beamform(vp16, wp, mesh=mesh, nint=nint))

        float(bstep())  # compile
        float(bstep16())
        # These calls are short; 48 reps keep the one closing fetch a
        # small share of the timed window.
        K = 48
        # In-order queue: sync the last dispatch only (see run_single).
        t0 = time.perf_counter()
        acc = [bstep() for _ in range(K)]
        float(acc[-1])
        el = time.perf_counter() - t0
        nbytes = vp[0].nbytes + vp[1].nbytes
        out["beamform_gbps"] = round(nbytes * K / el / 1e9, 3)
        out["beamform_config"] = {
            "nant": nant, "nbeam": nbeam, "nchan": nchan, "ntime": ntime,
            "npol": npol, "nint": nint, "input_bytes": nbytes,
            "source": "raw_files",
        }
        # Same voltages, bf16-resident: GB/s in f32-equivalent bytes so
        # the two legs compare like-for-like (the bf16 planes MOVE half).
        t0 = time.perf_counter()
        acc = [bstep16() for _ in range(K)]
        float(acc[-1])
        el = time.perf_counter() - t0
        out["beamform_bf16_gbps"] = round(nbytes * K / el / 1e9, 3)
        del vp16

        # Fused beamform+detect (round 5): packed chan-major bf16 planes
        # from the SAME recordings through the VMEM-resident kernel
        # (beamform(layout="chan") — beam planes never touch HBM;
        # measured 2.1x the einsum path, DESIGN.md §9 r5 addendum).
        from jax.sharding import NamedSharding, PartitionSpec as P

        from blit.ops.pallas_beamform import pack_weights

        chan_wins = list(A.AntennaStream(
            paths, mesh=mesh, window_samples=ntime, max_samples=ntime,
            dtype="bfloat16", layout="chan",
        ))
        vpc = chan_wins[0].arrays
        kwr, kwi = pack_weights(jnp.asarray(np.asarray(wr)),
                                jnp.asarray(np.asarray(wi)))
        kwp = jax.device_put(
            (np.asarray(kwr), np.asarray(kwi)),
            NamedSharding(mesh, P(None, None, "bank")),
        )
        jax.block_until_ready((vpc, kwp))

        def bstep_fused():
            return jnp.sum(B.beamform(vpc, kwp, mesh=mesh, nint=nint,
                                      layout="chan"))

        float(bstep_fused())
        # The number is only honest if the pallas path dispatched: a
        # silent einsum fallback must not masquerade as "fused" — the
        # fallback is an explicit error field (which fails the run, see
        # run_single) and the number is skipped; the later collective
        # legs still run.
        if B.last_beamform_plan().get("fused"):
            float(bstep_fused())  # absorb the one-off first-call alloc
            t0 = time.perf_counter()
            acc = [bstep_fused() for _ in range(K)]
            float(acc[-1])
            el = time.perf_counter() - t0
            out["beamform_fused_gbps"] = round(nbytes * K / el / 1e9, 3)
        elif jax.default_backend() == "tpu":  # off it the kernel never fuses
            out["beamform_fused_error"] = (
                f"fell back to einsums: {B.last_beamform_plan()}"
            )
        del vpc
        for w_ in chan_wins:
            w_.release()
        del chan_wins

        # WINDOWED streaming beamform leg: the same recordings through a
        # genuinely multi-window feed + beamform_stream — end-to-end
        # file→beam-power at prefetch_depth-bounded host memory, with
        # per-window stage timings (the mesh analog of rig_ingest_gbps;
        # acceptance: ingest/transfer/compute each carry bytes or are
        # declared byte-free).
        tl_s = Timeline()
        wsamp = ntime // 4
        feed = A.AntennaStream(
            paths, mesh=mesh, window_samples=wsamp, max_samples=ntime,
            timeline=tl_s,
        )
        per_window = []
        snap = tl_s.snapshot()
        t0 = time.perf_counter()
        for _slab in B.beamform_stream(feed, wp, mesh=mesh, nint=nint,
                                       timeline=tl_s):
            if len(per_window) < 3:
                per_window.append(tl_s.since(snap))
            snap = tl_s.snapshot()
        el = time.perf_counter() - t0
        fed = nant * nchan * ntime * npol * 2  # int8 RAW bytes consumed
        out["rig_beamform_stream_gbps"] = round(fed / el / 1e9, 3)
        out["rig_beamform_stream"] = {
            "windows": feed.nwindows,
            "window_samples": wsamp,
            "prefetch_depth": feed.prefetch_depth,
            "seconds": round(el, 3),
            "stages": stage_table(tl_s),
            "per_window": per_window,
        }
        del vp
        for w_ in bf_wins:
            w_.release()
        del bf_wins

        # FX correlator: 8 antennas, PFB+DFT F-engine + full visibility matrix.
        nant, nchan, nfft, ntap, npol = 8, 64, 512, 4, 2
        ntime = 64 * nfft
        paths = ant_files("fx", nant, nchan, ntime)
        tl_fx = Timeline()
        t0 = time.perf_counter()
        fx_wins = list(A.CorrelatorStream(
            paths, mesh=mesh, nfft=nfft, ntap=ntap,
            window_frames=ntime // nfft - ntap + 1, max_samples=ntime,
            timeline=tl_fx,
        ))
        jax.block_until_ready(fx_wins[0].arrays)
        out["rig_correlator_feed"] = feed_report(
            tl_fx, time.perf_counter() - t0
        )
        cvp = fx_wins[0].arrays
        h = jnp.asarray(pfb_coeffs(ntap, nfft))

        def cstep():
            visr, visi = C.correlate(cvp, h, mesh=mesh, nfft=nfft, ntap=ntap)
            return jnp.sum(visr) + jnp.sum(visi)

        float(cstep())
        t0 = time.perf_counter()
        acc = [cstep() for _ in range(K)]
        float(acc[-1])
        el = time.perf_counter() - t0
        nbytes = cvp[0].nbytes + cvp[1].nbytes
        out["correlator_gbps"] = round(nbytes * K / el / 1e9, 3)
        out["correlator_config"] = {
            "nant": nant, "nchan": nchan, "nfft": nfft, "ntap": ntap,
            "ntime": ntime, "npol": npol, "input_bytes": nbytes,
            "source": "raw_files",
        }
        del cvp
        for w_ in fx_wins:
            w_.release()
        del fx_wins

        # FX correlator at ARRAY SCALE (VERDICT r4 item 1): 64 antennas —
        # (nant*npol)^2 = 128^2 baseline tiles, exactly MXU-sized — through
        # the packed-layout pallas X-engine (correlate(vis_layout="packed"),
        # blit/ops/pallas_xengine.py; measured +19% over the einsum
        # X-engine at this shape, DESIGN.md §9 r5 addendum).  nchan=16
        # keeps visibilities + spectra + inputs comfortably inside HBM.
        nant, nchan, nfft, ntap, npol = 64, 16, 512, 4, 2
        ntime = 64 * nfft
        h = jnp.asarray(pfb_coeffs(ntap, nfft))  # local: don't lean on the
        # nant=8 section happening to share (ntap, nfft)
        paths = ant_files("fx64", nant, nchan, ntime)
        tl_fx64 = Timeline()
        t0 = time.perf_counter()
        fx64_wins = list(A.CorrelatorStream(
            paths, mesh=mesh, nfft=nfft, ntap=ntap,
            window_frames=ntime // nfft - ntap + 1, max_samples=ntime,
            timeline=tl_fx64,
        ))
        jax.block_until_ready(fx64_wins[0].arrays)
        out["rig_correlator64_feed"] = feed_report(
            tl_fx64, time.perf_counter() - t0
        )
        cvp = fx64_wins[0].arrays

        cvp16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), cvp)
        jax.block_until_ready(cvp16)

        def c64step():
            visr, visi = C.correlate(cvp, h, mesh=mesh, nfft=nfft,
                                     ntap=ntap, vis_layout="packed")
            return jnp.sum(visr) + jnp.sum(visi)

        def c64step16():
            visr, visi = C.correlate(cvp16, h, mesh=mesh, nfft=nfft,
                                     ntap=ntap, vis_layout="packed")
            return jnp.sum(visr) + jnp.sum(visi)

        float(c64step())
        # Provenance follows the ACTUAL dispatch: _xengine_packed records
        # its trace-time gate decision (last_xengine_plan, the
        # last_beamform_plan convention) — the gate runs on per-shard
        # LOCAL shapes, so re-deriving it here from global shapes would
        # drift (ADVICE r5 low).  Read it right after the f32 warmup
        # trace (the bf16 warmup below re-traces with itemsize 2).
        plan = C.last_xengine_plan()
        xe = (
            "pallas" if plan.get("engine") == "pallas" else "einsum-packed"
        )
        float(c64step16())
        K64 = 24  # enough calls to amortize the closing fetch
        t0 = time.perf_counter()
        acc = [c64step() for _ in range(K64)]
        float(acc[-1])
        el = time.perf_counter() - t0
        nbytes = cvp[0].nbytes + cvp[1].nbytes
        out["correlator64_gbps"] = round(nbytes * K64 / el / 1e9, 3)
        out["correlator64_config"] = {
            "nant": nant, "nchan": nchan, "nfft": nfft, "ntap": ntap,
            "ntime": ntime, "npol": npol, "input_bytes": nbytes,
            "vis_layout": "packed", "x_engine": xe,
            "source": "raw_files",
        }
        # bf16-staged (f32-equivalent bytes; measured +25% in the
        # controlled A/B — DESIGN.md §9 r5 addendum).
        t0 = time.perf_counter()
        acc = [c64step16() for _ in range(K64)]
        float(acc[-1])
        el = time.perf_counter() - t0
        out["correlator64_bf16_gbps"] = round(nbytes * K64 / el / 1e9, 3)
        del cvp, cvp16
        for w_ in fx64_wins:
            w_.release()
        del fx64_wins

        # WINDOWED streaming correlator leg: the nant=8 recordings through
        # a multi-window CorrelatorStream + correlate_stream — file→
        # integrated visibilities with the PFB tail carried between
        # windows and the accumulator folded on-device, at
        # prefetch_depth-bounded host memory.
        nant, nchan, nfft, ntap, npol = 8, 64, 512, 4, 2
        ntime = 64 * nfft
        h = jnp.asarray(pfb_coeffs(ntap, nfft))
        paths = ant_files("fxs", nant, nchan, ntime)
        tl_cs = Timeline()
        wf = (ntime // nfft - ntap + 1) // 4  # 4 windows + remainder
        feed = A.CorrelatorStream(
            paths, mesh=mesh, nfft=nfft, ntap=ntap, window_frames=wf,
            max_samples=ntime, timeline=tl_cs,
        )
        per_window = []
        snap = tl_cs.snapshot()
        t0 = time.perf_counter()

        def _fx_windows():
            nonlocal snap
            for win in feed:
                if len(per_window) < 3:
                    per_window.append(tl_cs.since(snap))
                snap = tl_cs.snapshot()
                yield win

        visr, visi = C.correlate_stream(
            _fx_windows(), h, mesh=mesh, nfft=nfft, ntap=ntap,
            timeline=tl_cs,
        )
        checksum = float(jnp.sum(visr) + jnp.sum(visi))
        el = time.perf_counter() - t0
        fed = nant * nchan * feed.seg * feed.nband * npol * 2
        out["rig_correlator_stream_gbps"] = round(fed / el / 1e9, 3)
        out["rig_correlator_stream"] = {
            "windows": feed.nwindows,
            "window_frames": wf,
            "prefetch_depth": feed.prefetch_depth,
            "seconds": round(el, 3),
            "checksum": checksum,
            "stages": stage_table(tl_cs),
            "per_window": per_window,
        }
        return out
    finally:
        # RAM-backed fixtures must not outlive the run, success or
        # not — repeated failed attempts would exhaust /dev/shm.
        shutil.rmtree(tmp, ignore_errors=True)

def _run_mesh_collectives() -> dict:
    """The sharded plane's collective probe (ISSUE 9): pure all_gather
    and psum programs over whatever mesh THIS rig's devices form,
    reporting per-chip vs aggregate ICI GB/s and the ``mesh.gather_s`` /
    ``mesh.psum_s`` p50/p99 quantiles through the PR 5 histogram
    machinery — the same hists the sharded scan's probe windows feed, so
    a bench artifact and a production scan report read alike.

    On a 1-chip rig the gather leg degenerates (no ICI; recorded as
    such) — the multi-device numbers come from pods and from the CI
    virtual mesh.  The provenance block also records the (2, n/2)
    band-axis dryrun parity result (``__graft_entry__.dryrun_multichip``
    run in a SUBPROCESS pinned to a virtual CPU pod: this process holds
    the chip, and a child that asked for it would fail or hang)."""
    import os
    import subprocess

    import jax

    from blit.observability import Timeline
    from blit.parallel import mesh as M

    devs = jax.devices()
    n = len(devs)
    nbank = max(k for k in (1, 2, 4, 8) if k <= n)
    mesh = M.make_mesh(1, nbank, devices=devs)
    tl = Timeline()
    rng = np.random.default_rng(7)
    K = 24
    out = {"mesh_collectives": {}}
    cfg = out["mesh_collectives"]

    # all_gather leg: a bank-sharded filterbank block through the scan
    # plane's own stitch program (blit/parallel/mesh.stitch_despike).
    t, F = 16, nbank * 4096
    x = jax.device_put(
        rng.standard_normal((1, t, 1, F)).astype(np.float32),
        M.sharding_for(mesh, "filterbank_sharded"),
    )
    jax.block_until_ready(x)
    shard_bytes = x.nbytes // nbank
    ici = M.gather_ici_bytes(shard_bytes, nbank)
    y = M.stitch_despike(x, mesh=mesh, despike_nfpc=0)  # compile
    jax.block_until_ready(y)
    for _ in range(K):
        t0 = time.perf_counter()
        y = M.stitch_despike(x, mesh=mesh, despike_nfpc=0)
        jax.block_until_ready(y)
        M.record_ici(tl, "gather", ici, time.perf_counter() - t0)
    g = tl.hists["mesh.gather_s"]
    p50 = g.percentile(50) or float("inf")
    cfg["gather"] = {
        "mesh": [1, nbank],
        "operand_bytes": x.nbytes,
        "ici_bytes_per_chip": ici,
        "per_chip_gbps": round(ici / p50 / 1e9, 3),
        "aggregate_gbps": round(ici * nbank / p50 / 1e9, 3),
    }

    # psum leg: the correlator's closing collective — a band-axis psum
    # over a (2, n/2) mesh when the rig has one.
    if n >= 4 and n % 2 == 0:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh2 = M.make_mesh(2, n // 2, devices=devs)
        rows = 64
        v = jax.device_put(
            rng.standard_normal((2 * rows, 4096)).astype(np.float32),
            NamedSharding(mesh2, P("band", None)),
        )
        jax.block_until_ready(v)

        @jax.jit
        def pfn(v):
            return jax.shard_map(
                lambda b: jax.lax.psum(b, "band"), mesh=mesh2,
                in_specs=P("band", None), out_specs=P(None, None),
                check_vma=False,
            )(v)

        w = pfn(v)
        jax.block_until_ready(w)
        per_chip = v.nbytes // 2  # the per-chip band block
        ici_p = M.psum_ici_bytes(per_chip, 2)
        for _ in range(K):
            t0 = time.perf_counter()
            w = pfn(v)
            jax.block_until_ready(w)
            M.record_ici(tl, "psum", ici_p, time.perf_counter() - t0)
        p = tl.hists["mesh.psum_s"]
        p50p = p.percentile(50) or float("inf")
        cfg["psum"] = {
            "mesh": [2, n // 2],
            "operand_bytes": per_chip,
            "ici_bytes_per_chip": ici_p,
            "per_chip_gbps": round(ici_p / p50p / 1e9, 3),
            "aggregate_gbps": round(ici_p * n / p50p / 1e9, 3),
        }
    else:
        cfg["psum"] = {"skipped": f"{n} device(s): no (2, n/2) band axis"}

    # The p50/p99 tails (MESH_HISTS) + per-collective ICI byte hists —
    # the acceptance's provenance block.
    cfg["quantiles"] = tl.hist_quantiles()
    cfg["ici_stage"] = {
        "calls": tl.stages["mesh.ici"].calls,
        "bytes": tl.stages["mesh.ici"].bytes,
    }

    # Band-axis dryrun parity (the (2, n/2) pass of dryrun_multichip,
    # incl. the sharded-vs-per-chip byte-identity assertion).  The child
    # is pinned to JAX_PLATFORMS=cpu and must stay so: one process per
    # chip, and this parent has it.
    entry = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "__graft_entry__.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); "
         "from __graft_entry__ import dryrun_multichip; "
         "import json; print(json.dumps(dryrun_multichip(8)))",
         os.path.dirname(entry)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    lines = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and lines:
        cfg["band_axis_dryrun"] = json.loads(lines[-1])
    else:
        tail = proc.stderr.strip().splitlines()
        out["band_axis_dryrun_error"] = (
            tail[-1] if tail else f"rc={proc.returncode}")
    return out


def _run_config1() -> dict:
    """BASELINE config 1: single-bank ``0002.h5`` read → integrated power
    spectrum — the reference's core read path (worker ``getdata`` +
    post-read ``fqav``, src/gbtworkerfunctions.jl:179-189) over a
    bitshuffle-compressed FBH5 file on a ram-backed dir.  Host-side only;
    reported as GB/s of decompressed filterbank payload."""
    import os
    import shutil
    import tempfile

    from blit import workers
    from blit.io.bshuf import available as bshuf_available
    from blit.io.fbh5 import write_fbh5
    from blit.testing import make_fil_header, make_spectra

    nsamps, nifs, nchans, fqav_by = 256, 1, 1 << 20, 16
    compression = "bitshuffle" if bshuf_available() else None
    tmp = tempfile.mkdtemp(
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None
    )
    try:
        path = os.path.join(tmp, "bench.rawspec.0002.h5")
        hdr = make_fil_header(nchans=nchans, nifs=nifs, foff=-187.5 / nchans)
        hdr["nfpc"] = nchans // 64
        data = make_spectra(nsamps, nifs, nchans, seed=2)
        write_fbh5(path, hdr, data, compression=compression,
                   chunks=(nsamps, nifs, nchans // 64))
        payload = data.nbytes

        # Warm the reader once (h5py/libhdf5 init), then time the measured
        # read: full-file hyperslab read + worker-side fqav to the
        # integrated spectrum (the bytes that would otherwise cross the
        # wire shrink by fqav_by).  Best of 2 — the host's cores are
        # shared (same rule as the ingest leg's host_read).
        workers.get_data(path, (slice(0, 1), slice(None), slice(None)))
        elapsed = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            spec = workers.get_data(path, fqav_by=fqav_by)
            elapsed = min(elapsed, time.perf_counter() - t0)
        assert spec.shape == (nsamps, nifs, nchans // fqav_by)
        return {
            "config1_gbps": round(payload / elapsed / 1e9, 3),
            "config1_config": {
                "nsamps": nsamps,
                "nifs": nifs,
                "nchans": nchans,
                "fqav_by": fqav_by,
                "payload_bytes": payload,
                "compression": compression or "none",
                "checksum": float(spec.sum()),
            },
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


# Search-plane science leg shapes: (window_spectra T, channels F, reps K).
# The metric is drift-rate trials/s/chip — (2T-1) drift rows × F channels
# × K windows scored per second by the on-device tree + SNR + per-band
# top-k step (blit/ops/pallas_dedoppler), device-resident with a single
# closing fetch like the primary leg.
_DEDOPPLER_CONFIGS = {
    "tpu_bf16": (64, 1 << 20, 8),
    "cpu": (16, 1 << 14, 4),
}


def _run_dedoppler(config_name: str) -> dict:
    """The search plane's science metric (ISSUE 6 / ROADMAP item 4):
    sustained drift-rate trials per second of the jitted on-device
    dedoppler step over synthetic windows with an injected drifting
    tone (which doubles as a liveness check: the tone must surface as
    the strongest hit)."""
    import functools

    import jax
    import jax.numpy as jnp

    from blit.ops.pallas_dedoppler import dedoppler_hits, unpack_hits

    T, F, K = _DEDOPPLER_CONFIGS[config_name]
    nbands = max(1, F >> 14)  # ~one band per 16k channels
    rng = np.random.default_rng(3)
    x = rng.normal(100.0, 10.0, size=(T, F)).astype(np.float32)
    # A clean drifting tone along the tree's own drift-7 path.
    from blit.ops.pallas_dedoppler import tree_path_shift

    f0, db = F // 3, min(7, T - 1)
    for t in range(T):
        x[t, f0 + tree_path_shift(db, t, T)] += 400.0
    # dedoppler_hits is module-level jitted (knobs static); binding the
    # knobs is enough.
    fn = functools.partial(dedoppler_hits, top_k=4, nbands=nbands,
                           kernel="auto")
    xj = jax.block_until_ready(jnp.asarray(x))
    thr = jnp.float32(8.0)
    packed = jax.block_until_ready(fn(xj, thr))  # warmup / compile
    snr, _, drift, chan, _ = unpack_hits(np.asarray(packed))
    top = int(np.argmax(snr)) if len(snr) else -1
    t0 = time.perf_counter()
    acc = [fn(xj, thr) for _ in range(K)]
    jax.block_until_ready(acc[-1])
    elapsed = time.perf_counter() - t0
    trials = (2 * T - 1) * F * K
    return {
        "dedoppler_drift_rates_per_s": round(trials / elapsed, 1),
        "dedoppler_config": {
            "window_spectra": T,
            "nchans": F,
            "nbands": nbands,
            "calls": K,
            "seconds": round(elapsed, 3),
            "tone_recovered": bool(
                top >= 0 and int(drift[top]) == db and int(chan[top]) == f0
            ),
        },
    }


def _probe_platform() -> str:
    """Platform name, probed in a SUBPROCESS — the orchestrator must never
    initialize JAX itself, or it would hold the chip for its whole lifetime
    and starve the ``--single`` child of the device."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.devices()[0].platform)"],
        capture_output=True, text=True, timeout=180,
    )
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        tail = proc.stderr.strip().splitlines()
        raise RuntimeError(tail[-1] if tail else "platform probe failed")
    return lines[-1]


def _failed(error: str, **extra) -> int:
    """The parseable record of a run that produced no number."""
    print(json.dumps({
        "metric": "guppi_raw_to_hires_filterbank_GBps_per_chip",
        "value": 0.0,
        "unit": "GB/s",
        "vs_baseline": 0.0,
        **extra,
        "errors": {"run": error},
    }))
    return 1


def main() -> int:
    import os

    if len(sys.argv) >= 3 and sys.argv[1] == "--single":
        return run_single(sys.argv[2])

    try:
        platform = _probe_platform()
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        return _failed(f"platform probe: {e}")
    config_name = _PLATFORM_CONFIG.get(platform)
    if config_name is None:
        return _failed(f"no bench config for platform {platform!r}",
                       platform=platform)
    if platform == "cpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        return _failed(
            "no accelerator found; the CPU config runs only when the "
            "caller sets JAX_PLATFORMS=cpu", platform=platform)

    try:
        proc = subprocess.run(
            [sys.executable, __file__, "--single", config_name],
            capture_output=True, text=True, timeout=_SINGLE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        return _failed(f"{config_name}: timeout after {_SINGLE_TIMEOUT_S:.0f} s",
                       platform=platform)
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            json.loads(line)
        except ValueError:
            continue
        print(line)
        # The child's code: non-zero when any leg raised.
        return proc.returncode
    tail = proc.stderr.strip().splitlines() or ["no stderr"]
    return _failed(f"{config_name} rc={proc.returncode}: {tail[-1]}",
                   platform=platform)


if __name__ == "__main__":
    sys.exit(main())
