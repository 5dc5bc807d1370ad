"""Command-line interface: ``python -m blit <command>``.

The reference is a library driven from the Julia REPL; the tool it
replaces on the recording nodes — rawspec — is a CLI.  blit ships both:
the library (:mod:`blit.gbt` et al.) and this thin command layer over it.

Commands:
  reduce     GUPPI RAW (file, .NNNN.raw sequence stem, or member list)
             → filterbank product (.fil streams to disk; .h5 = FBH5).
  search     GUPPI RAW → .hits drift-rate search product: the on-device
             Taylor-tree dedoppler over windowed spectra (ISSUE 6) —
             only hit records ever cross the readback link.
  stream     LIVE reduction (ISSUE 7): follow a RAW file the recorder is
             still appending to (or replay a completed one at wall-clock
             / accelerated rate) and produce the .fil/.h5 — or, with
             --search, .hits — product *during* the session, with
             watermark lateness masking and p50/p99 chunk→product
             latency in the report.  Byte-identical to the batch path
             for a completed stream.
  scan       Whole (session, scan) across the device mesh: crawl the
             tree, map every player's RAW sequence onto the (band, bank)
             mesh, stream each stitched band to a per-band product —
             the reference's ``loadscan`` (src/gbt.jl:99) as a command.
  inventory  Crawl a data tree (reference getinventory semantics) and
             print records as JSON lines or a table.
  info       Print the normalized header of a .fil / .h5 / .raw file.
  fleet-peer Run ONE serving peer of the fleet (ISSUE 14): a
             ProductService over stdlib HTTP (/product /warm /stats
             /healthz /metrics /drain) beating a heartbeat lease;
             SIGTERM drains gracefully — refuse new, finish in-flight,
             release live capacity holds.
  telemetry  Fleet telemetry (ISSUE 5): harvest per-worker Timelines,
             fault counters and spans into one per-host report (text /
             Prometheus exposition / JSON), render a saved report, or
             run a multi-worker demo reduction that also exports a
             Perfetto-loadable trace.
  trace-view Render a flight-recorder dump (written automatically when a
             stall watchdog trips, a breaker opens, or an agent dies)
             into a readable incident summary.
  chaos      Crash-recovery drill (ISSUE 12): run a seeded kill/hang
             schedule against a real supervised sharded scan or live
             stream and assert detection, degrade-and-resume (reshaped
             mesh or pool fallback / session rejoin) and product
             byte-identity against an uninterrupted oracle.  The
             ``--fault corrupt`` leg (ISSUE 13) instead corrupts a
             delivered RAW frame under a digest sidecar and asserts
             masked-not-garbage: the product must be byte-identical to
             a zero-filled oracle with ``integrity.bad_block`` >= 1.
  fsck       Archive integrity check (ISSUE 13): walk a tree of
             products / disk-cache entries verifying every manifest
             and content digest; mismatches are QUARANTINED
             (``.quarantine/`` sibling) and exit != 0.  ``--repair``
             re-derives quarantined cache entries from their recorded
             recipes and retires corpses superseded by a verified
             replacement.
  top        Live terminal dashboard (ISSUE 11): tail a monitor spool
             dir or poll a publisher endpoint during an in-progress
             reduce/scan/stream/serve — per-stage throughput, stage-tail
             p50/p99, SLO burn, host health.  ``--once`` renders one
             frame (tests/scripts).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def _ran_on() -> dict:
    """What a device command ran on, printed with its result so a CPU run
    and a chip run never give the same line: platform, device kind and
    count as JAX reports them, and the kernel plan ``auto`` resolved to."""
    from blit.device import device_facts
    from blit.ops.channelize import last_kernel_plan

    return {**device_facts(), "kernel_plan": last_kernel_plan()}


def _int_list(text: str) -> List[int]:
    """``1048576,8,1024`` -> [1048576, 8, 1024] (rawspec's ``-f`` / ``-t``
    spelling; one number is a list of one)."""
    try:
        return [int(w) for w in text.split(",")]
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"{text!r} is not a comma list of whole numbers") from None


def rawspec_product_path(stem: str, k: int) -> str:
    """Where product ``k`` of several lands: rawspec's own naming."""
    return f"{stem}.rawspec.{k:04d}.fil"


def _product_list(args: argparse.Namespace, cmd: str, alone) -> List[tuple]:
    """The ``(nfft, nint)`` list of a ``blit reduce`` / ``blit scan``
    (rawspec's ``-f`` / ``-t`` pairing: one ``--nint`` per ``--nfft``);
    refuses by flag, before any byte is read, what cannot hold for every
    product alike.  ``alone``: ``(flag, given)`` of the flags that go
    with ONE product only."""
    if len(args.nfft) != len(args.nint):
        raise SystemExit(
            f"blit {cmd}: --nfft lists {len(args.nfft)} products and "
            f"--nint {len(args.nint)}: give one --nint per --nfft")
    products = list(zip(args.nfft, args.nint))
    for nfft, _ in products:
        if args.fqav > 1 and nfft % args.fqav:
            raise SystemExit(
                f"blit {cmd}: --fqav {args.fqav} does not divide --nfft "
                f"{nfft}: the averaging must hold for every product")
    if len(products) > 1:
        for flag, given in alone:
            if given:
                raise SystemExit(
                    f"blit {cmd}: {flag} with several products is not "
                    "supported (ROADMAP B1): run one product per command, "
                    "or all of them without it")
    return products


def _reduce_products(args: argparse.Namespace) -> List[tuple]:
    """The ``(nfft, nint)`` list of a ``blit reduce`` (a preset, or
    :func:`_product_list`); with several, ``-o`` is a stem."""
    if args.product is not None:
        from blit.pipeline import PRODUCT_PRESETS

        return [PRODUCT_PRESETS[args.product]]
    products = _product_list(args, "reduce", (
        ("--resume", args.resume), ("--compression", args.compression)))
    if len(products) > 1 and args.output.endswith((".h5", ".hdf5", ".fil")):
        raise SystemExit(
            "blit reduce: with several products -o is a STEM (product "
            "k lands at <stem>.rawspec.000k.fil), not a product path: "
            f"{args.output!r}")
    return products


def _cmd_reduce(args: argparse.Namespace) -> int:
    from blit.pipeline import RawReducer

    (nfft, nint), *also = _reduce_products(args)
    red = RawReducer(nfft=nfft, nint=nint, also=tuple(also),
                     stokes=args.stokes, fqav_by=args.fqav, dtype=args.dtype)
    src: object = args.raw[0] if len(args.raw) == 1 else args.raw
    doc = {"output": args.output}
    if also:
        paths = [rawspec_product_path(args.output, k)
                 for k in range(len(red.products))]
        hdrs = red.reduce_to_files(src, paths)
        hdr = hdrs[0]
        doc["products"] = [
            {"path": path, "nfft": f, "nint": t, "nsamps": h.get("nsamps"),
             "nchans": h.get("nchans")}
            for path, (f, t), h in zip(paths, red.products, hdrs)]
    elif args.resume:
        hdr = red.reduce_resumable(src, args.output,
                                   compression=args.compression)
    else:
        hdr = red.reduce_to_file(src, args.output,
                                 compression=args.compression)
    stats = red.stats
    print(
        json.dumps(
            {
                **doc,
                "nsamps": hdr.get("nsamps"),
                "nchans": hdr.get("nchans"),
                "nifs": hdr.get("nifs"),
                "input_bytes": stats.input_bytes,
                "gbps": round(stats.gbps, 3),
                # Per-stage seconds and bytes, every wait.* row included,
                # under the key `blit scan` uses.
                "stages": red.timeline.report(),
                **_ran_on(),
            }
        )
    )
    return 0


def _cmd_search(args: argparse.Namespace) -> int:
    from blit.ops.pallas_dedoppler import last_dedoppler_plan
    from blit.pipeline import PRODUCT_PRESETS
    from blit.search import DedopplerReducer

    nfft, nint = ((args.nfft, args.nint) if args.product is None
                  else PRODUCT_PRESETS[args.product])
    red = DedopplerReducer(
        nfft=nfft, nint=nint, dtype=args.dtype,
        window_spectra=args.window_spectra, top_k=args.top_k,
        snr_threshold=args.snr, max_drift_bins=args.max_drift_bins,
        kernel=args.kernel, interpret=args.interpret,
    )
    src: object = args.raw[0] if len(args.raw) == 1 else args.raw
    if args.resume:
        hdr = red.search_resumable(src, args.output)
    else:
        hdr = red.search_to_file(src, args.output)
    tl = red.timeline.report()
    print(
        json.dumps(
            {
                "output": args.output,
                "windows": hdr.get("search_windows"),
                "hits": hdr.get("search_nhits"),
                "nchans": hdr.get("nchans"),
                "window_spectra": hdr.get("search_window_spectra"),
                "snr_threshold": hdr.get("search_snr_threshold"),
                "top_k": hdr.get("search_top_k"),
                # The per-window tree latency / hits-per-window
                # distributions (sync path populates tree_s; the async
                # plane's equivalent is out.chunk_latency_s).
                "hists": tl.get("hists", {}),
                **_ran_on(),
                "dedoppler_plan": last_dedoppler_plan(),
            }
        )
    )
    return 0


def _cmd_stream(args: argparse.Namespace) -> int:
    """Live reduction front door (ISSUE 7).  Default mode FOLLOWS the
    file as the recorder appends (ending at the ``.done`` marker or the
    idle timeout); ``--replay-rate`` replays a completed recording
    through the same plane — the latency rig and the byte-identity
    drill."""
    from blit.observability import Timeline
    from blit.pipeline import PRODUCT_PRESETS
    from blit.stream import FileTailSource, ReplaySource

    # Live monitoring (ISSUE 11): a session that never pauses is what
    # the monitor plane exists for — the flags start the publisher, the
    # reducer's publishing hook streams the watermark/latency telemetry.
    pub = _monitor_from_flags(args)

    if args.replay_rate is not None:
        src = ReplaySource(args.raw, rate=args.replay_rate)
    else:
        src = FileTailSource(args.raw, poll_s=args.poll,
                             idle_timeout_s=args.idle_timeout,
                             done_path=args.done_file)
    nfft, nint = ((args.nfft, args.nint) if args.product is None
                  else PRODUCT_PRESETS[args.product])
    tl = Timeline()
    if args.search:
        from blit.stream import stream_search

        hdr = stream_search(
            src, args.output, lateness_s=args.lateness, nfft=nfft,
            nint=nint, dtype=args.dtype, timeline=tl,
            window_spectra=args.window_spectra, snr_threshold=args.snr,
            top_k=args.top_k, resume=args.resume,
        )
        body = {"hits": hdr.get("search_nhits"),
                "windows": hdr.get("search_windows")}
    else:
        from blit.stream import stream_reduce

        hdr = stream_reduce(
            src, args.output, lateness_s=args.lateness, nfft=nfft,
            nint=nint, stokes=args.stokes, fqav_by=args.fqav,
            dtype=args.dtype, compression=args.compression, timeline=tl,
            resume=args.resume,
        )
        body = {"nsamps": hdr.get("nsamps"), "nchans": hdr.get("nchans")}
    lat = tl.report().get("hists", {}).get("stream.chunk_to_product_s", {})
    out = {
        "output": args.output,
        **body,
        "stream_chunks": hdr.get("stream_chunks"),
        "late_chunks": hdr.get("stream_late_chunks"),
        "dup_chunks": hdr.get("stream_dup_chunks"),
        "masked_chunks": hdr.get("stream_masked_chunks"),
        "degraded_spectra": hdr.get("stream_degraded_spectra",
                                    hdr.get("stream_degraded_windows")),
        "chunk_to_product_p50_s": lat.get("p50"),
        "chunk_to_product_p99_s": lat.get("p99"),
    }
    if hdr.get("_masked_chunks"):
        out["masked_chunk_seqs"] = hdr["_masked_chunks"]
    if hdr.get("stream_flight_dump"):
        out["flight_dump"] = hdr["stream_flight_dump"]
    if pub is not None:
        pub.tick()
        out["monitor"] = {"port": pub.port, "spool": pub.spool_path,
                          "samples": pub.seq}
        from blit import monitor

        monitor.shutdown_publisher()
    print(json.dumps(out))
    return 0


def _scan_window(args: argparse.Namespace, mdef: dict, sharded: bool,
                 also=()):
    """``(wf, tuning, depths, sharded)`` of a ``blit scan``: the
    effective window, whose it is (``--window-frames``: ``explicit``,
    else ``default``), the sharded plane's rotation depths and whether
    that plane runs."""
    from blit.config import default_window_frames
    from blit.parallel.scan import scan_window_frames

    given = args.window_frames is not None
    tuning = {"source": "explicit" if given else "default"}
    if also:
        # One grid for every product, in frames of the largest --nfft;
        # every integration is folded across windows, so none sizes it.
        # The mesh loop alone makes several products.
        wf = scan_window_frames(max([args.nfft] + [f for f, _ in also]), 1,
                                args.window_frames)
        return wf, tuning, {}, False
    depths = {"prefetch_depth": mdef["prefetch_depth"],
              "out_depth": mdef["out_depth"]}
    # The library's own rule, so the stats line reports what executed:
    # nint sizes the window only where an integration fits one; where it
    # does not, the window stands and the integration is carried.
    wf = scan_window_frames(
        args.nfft, args.nint,
        args.window_frames if given else default_window_frames(args.nfft))
    if sharded and wf % args.nint and not args.search:
        if args.sharded:
            raise SystemExit(
                f"--sharded has no carry: an integration of {args.nint} "
                f"frames does not fit the {wf}-frame window; drop "
                "--sharded (the default mesh loop carries it across "
                "windows)")
        sharded = False  # the site's default plane cannot carry: mesh loop
    return wf, tuning, depths, sharded


def _cmd_scan(args: argparse.Namespace) -> int:
    from blit.config import mesh_defaults
    from blit.inventory import get_inventory
    from blit.observability import Timeline
    from blit.parallel.scan import (
        reduce_scan_mesh_to_files,
        reduce_scan_pool_to_files,
        scan_window_frames,
    )

    mdef = mesh_defaults()
    # Parallelism selection (ISSUE 9): --sharded = the fully-threaded
    # sharded reduction plane; --pool = the per-player pool fallback /
    # byte-identity oracle; neither = SiteConfig/BLIT_MESH_SHARDED picks
    # between the sharded plane and the serial mesh window loop.
    sharded = args.sharded or (mdef["sharded"] and not args.pool)

    # rawspec's spelling: a comma list makes every product from ONE read
    # and ONE upload of each mesh window (the mesh loop's legs).
    (args.nfft, args.nint), *also = _product_list(args, "scan", (
        ("--resume", args.resume), ("--compression", args.compression),
        ("--sharded", args.sharded), ("--pool", args.pool),
        ("--search", args.search)))
    invs = [get_inventory(args.file_re or r"\.raw$", root=args.root)]
    wf, tuning, depths, sharded = _scan_window(args, mdef, sharded, also)
    tl = Timeline()
    parallel = "sharded" if sharded else ("pool" if args.pool else "mesh")
    if args.search:
        if args.resume:
            # Whole-scan search has no resume machinery (the per-file
            # `blit search --resume` path does) — refuse loudly rather
            # than silently re-running a crashed pod search from frame 0.
            raise SystemExit(
                "--resume is not supported with scan --search; re-run "
                "fresh, or use `blit search --resume` per player"
            )
        # Filterbank-product knobs the search planes cannot honor
        # (DedopplerReducer searches Stokes-I unaveraged spectra; .hits
        # are JSON lines): refuse loudly, like --resume above, instead
        # of writing a product the flags pretend to have shaped.
        if args.stokes != "I":
            raise SystemExit("--stokes is not supported with --search "
                             "(drift search runs on Stokes I)")
        if args.fqav != 1:
            raise SystemExit("--fqav is not supported with --search "
                             "(the drift transform needs full-resolution "
                             "fine channels)")
        if args.compression is not None:
            raise SystemExit("--compression applies to .h5 filterbank "
                             "products, not .hits")
        # Effective window: whole search windows (window_spectra * nint
        # frames each), resolved through the SAME reducer knob path both
        # search planes use — so the stats line reports what actually
        # executed and the two paths dispatch at identical shapes.
        from blit.search import DedopplerReducer

        probe = DedopplerReducer(
            nfft=args.nfft, nint=args.nint, dtype=args.dtype,
            window_spectra=args.window_spectra,
        )
        unit = probe.window_spectra * args.nint
        wf = max((wf // unit) * unit, unit)
        if args.pool:
            if args.max_frames is not None:
                # DedopplerReducer searches whole recordings; silently
                # dropping the cap would also break the sharded-vs-pool
                # byte-identity diff this path exists to provide.
                raise SystemExit(
                    "--max-frames is not supported with --pool --search "
                    "(the per-player reducers search whole recordings)"
                )
            from blit.observability import profile_trace

            with profile_trace(args.trace_logdir):
                written = _pool_scan_search(args, invs, wf, tl)
        else:
            # The sharded search plane: every chip searches its own
            # frequency slice; per-player .hits products (ISSUE 9).
            from blit.parallel.sharded import search_scan_sharded_to_files

            parallel = "sharded"
            written = search_scan_sharded_to_files(
                args.session, args.scan, inventories=invs,
                out_dir=args.output_dir, nfft=args.nfft, nint=args.nint,
                dtype=args.dtype, window_spectra=args.window_spectra,
                top_k=args.top_k, snr_threshold=args.snr,
                max_drift_bins=args.max_drift_bins, kernel=args.kernel,
                interpret=args.interpret, window_frames=wf,
                max_frames=args.max_frames, timeline=tl,
                trace_logdir=args.trace_logdir, **depths,
            )
        for player, (path, hdr) in sorted(written.items()):
            print(json.dumps({
                "player": list(player), "output": path,
                "windows": hdr.get("search_windows"),
                "nchans": hdr.get("nchans"),
            }))
        from blit.ops.pallas_dedoppler import last_dedoppler_plan

        print(json.dumps({"window_frames": wf, "parallel": parallel,
                          "tuning": tuning, "stages": tl.report(),
                          **_ran_on(),
                          "dedoppler_plan": last_dedoppler_plan()}))
        return 0
    kw = dict(
        inventories=invs,
        out_dir=args.output_dir,
        nfft=args.nfft,
        nint=args.nint,
        stokes=args.stokes,
        fqav_by=args.fqav,
        despike=not args.no_despike,
        window_frames=wf,
        max_frames=args.max_frames,
        compression=args.compression,
        dtype=args.dtype,
        timeline=tl,
    )
    if args.pool:
        if args.resume:
            raise SystemExit(
                "--resume applies to the mesh/sharded paths; the pool "
                "fallback re-runs whole per-bank reductions"
            )
        # The pool oracle honors --trace-logdir like every other scan
        # path — wrapped here because the library call itself takes no
        # trace knob (it is plain host-looped reducers).
        from blit.observability import profile_trace

        with profile_trace(args.trace_logdir):
            written = reduce_scan_pool_to_files(args.session, args.scan,
                                                **kw)
    elif sharded:
        from blit.parallel.sharded import reduce_scan_sharded_to_files

        written = reduce_scan_sharded_to_files(
            args.session, args.scan, resume=args.resume,
            trace_logdir=args.trace_logdir, **depths, **kw,
        )
    else:
        written = reduce_scan_mesh_to_files(
            args.session, args.scan, resume=args.resume, also=tuple(also),
            trace_logdir=args.trace_logdir, **kw,
        )
    for band, made in sorted(written.items()):
        # (Of several products a line each, in the order asked for.)
        for path, hdr in made if also else [made]:
            print(
                json.dumps(
                    {
                        "band": band,
                        "output": path,
                        "nsamps": hdr.get("nsamps"),
                        "nchans": hdr.get("nchans"),
                        "fch1": hdr.get("fch1"),
                        "foff": hdr.get("foff"),
                    }
                )
            )
    # Per-stage throughput (read/device/readback/write), like blit reduce.
    print(json.dumps({"window_frames": wf, "parallel": parallel,
                      "tuning": tuning, "stages": tl.report(),
                      **_ran_on()}))
    return 0


def _pool_scan_search(args: argparse.Namespace, invs, wf: int, tl) -> dict:
    """The pool-path whole-scan search fallback/oracle: one
    :class:`blit.search.DedopplerReducer` per (band, bank) player, each
    writing its own ``.hits`` — the per-player twin of
    ``search_scan_sharded_to_files`` (same dispatch shapes via
    ``chunk_frames=window_frames``, so the products are byte-identical;
    tests/test_sharded.py).

    Oracle scope: each reducer searches its player's WHOLE recording,
    so byte-identity to the sharded path holds when the players share a
    common whole-window span (the recorded case).  Ragged recordings
    diverge by design — the sharded path truncates every player to the
    pod-agreed minimum span; ``--max-frames`` is rejected here for the
    same reason (the caller raises before dispatch)."""
    import os

    from blit.inventory import scan_grid
    from blit.search import DedopplerReducer

    band_ids, _, grid = scan_grid(invs, args.session, args.scan)
    # ``wf`` arrives already rounded to whole search windows by
    # _cmd_scan (the sharded path's own rounding), so chunk_frames
    # dispatches at the identical shapes byte-identity assumes.
    written = {}
    for b, row in enumerate(grid):
        for k, rp in enumerate(row):
            red = DedopplerReducer(
                nfft=args.nfft, nint=args.nint, dtype=args.dtype,
                window_spectra=args.window_spectra, top_k=args.top_k,
                snr_threshold=args.snr,
                max_drift_bins=args.max_drift_bins, kernel=args.kernel,
                interpret=args.interpret, chunk_frames=wf, timeline=tl,
            )
            out = os.path.join(
                args.output_dir, f"band{band_ids[b]}bank{k}.hits"
            )
            hdr = red.search_to_file(rp, out)
            written[(band_ids[b], k)] = (out, hdr)
    return written


def _cmd_inventory(args: argparse.Namespace) -> int:
    from blit.inventory import get_inventory, raw_sequences

    records = get_inventory(
        args.file_re,
        root=args.root,
        session_re=args.session_re,
        extra=args.extra,
    )
    if args.sequences:
        for rec, paths in raw_sequences(records):
            print(json.dumps({"stem_of": rec._asdict(), "files": paths}))
        return 0
    for rec in records:
        print(json.dumps(rec._asdict()))
    return 0


def _cmd_fleet_peer(args: argparse.Namespace) -> int:
    """``blit fleet-peer`` (ISSUE 14): one serving peer of the fleet —
    a ProductService behind the HTTP wire (``/product``, ``/warm``,
    ``/stats``, ``/healthz``, ``/metrics``, ``/drain``), beating a
    heartbeat lease the front door watches.  SIGTERM/SIGINT drain
    gracefully: refuse new work, finish in-flight (releasing live
    capacity holds), then exit.  ``--port 0`` binds an ephemeral port,
    published via ``--port-file`` (atomic write) for the spawner."""
    import os
    import threading

    from blit.config import DEFAULT
    from blit.observability import Timeline
    from blit.serve import ProductCache, ProductService, Scheduler
    from blit.serve.http import PeerServer, install_drain_handler

    tl = Timeline()
    # Archive plane (ISSUE 19): --catalog-root arms the peer's catalog
    # (kind="catalog" asks + local session=/scan= resolution);
    # --cold-dir/--disk-bytes arm the tiered store behind the hot disk
    # cache.  Flags override the env/config defaults.
    config = DEFAULT
    if args.catalog_root:
        config = config.with_(catalog_root=args.catalog_root)
    if args.cold_dir:
        config = config.with_(cache_cold_dir=args.cold_dir)
    from blit.config import archive_defaults

    service = ProductService(
        cache=ProductCache(args.cache_dir, ram_bytes=args.ram_bytes,
                           disk_bytes=args.disk_bytes,
                           cold_dir=archive_defaults(config)["cold_dir"],
                           timeline=tl),
        scheduler=Scheduler(max_concurrency=args.concurrency,
                            queue_depth=args.queue_depth, timeline=tl,
                            retry_seed=args.retry_seed),
        timeline=tl,
        config=config,
    )
    server = PeerServer(service, name=args.name, port=args.port,
                        host=args.host,
                        lease_dir=args.lease_dir, proc=args.proc,
                        beat_interval_s=args.beat_interval).start()
    stop = threading.Event()

    def _drain():
        server.drain(timeout=args.drain_timeout)
        stop.set()

    uninstall = install_drain_handler(_drain, exit_after=False)
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(server.port))
        os.replace(tmp, args.port_file)
    # --standby (ISSUE 17): the peer itself serves identically — it is
    # the FRONT DOOR that keeps a standby out of the ring until the
    # elastic controller admits it.  The flag rides the bring-up line
    # so spawners and operators see the role the process was given.
    print(json.dumps({"name": args.name, "url": server.url,
                      "pid": os.getpid(), "lease_dir": args.lease_dir,
                      "proc": args.proc,
                      "standby": bool(args.standby)}), flush=True)
    try:
        stop.wait()
    except KeyboardInterrupt:
        service.drain(timeout=args.drain_timeout)
    uninstall()
    server.close()
    service.close()
    return 0


def _spawn_fleet_peers(td: str, npeers: int, *, concurrency: int,
                       queue_depth: int, ram_bytes: int,
                       beat_interval_s: float = 0.2,
                       bringup_timeout_s: float = 120.0,
                       standbys: int = 0):
    """Bring up ``npeers`` REAL ``blit fleet-peer`` subprocesses (the
    chaos rig): per-peer cache dirs + one shared lease dir under
    ``td``, ephemeral ports published through port files.  Returns
    ``(procs, peers, lease_dir)`` with ``procs`` a list of
    ``(Popen, logfile)`` pairs and ``peers`` the name→url map the
    front door takes.

    ``standbys`` additionally spawns that many ``--standby`` peers
    (ISSUE 17): named ``standby{j}``, lease proc ``npeers + j``,
    appended to both ``procs`` and ``peers`` — the caller registers
    them via ``door.add_standby`` instead of the ring-seeding map."""
    import os
    import subprocess
    import time as _time

    from blit.serve.http import wait_http_ready

    lease_dir = os.path.join(td, "leases")
    names = [f"peer{i}" for i in range(npeers)]
    names += [f"standby{j}" for j in range(max(0, standbys))]
    procs, peers = [], {}
    for i, name in enumerate(names):
        port_file = os.path.join(td, f"{name}.port")
        cmd = [sys.executable, "-m", "blit", "fleet-peer",
               "--name", name,
               "--cache-dir", os.path.join(td, f"cache-{name}"),
               "--lease-dir", lease_dir, "--proc", str(i),
               "--port", "0", "--port-file", port_file,
               "--concurrency", str(concurrency),
               "--queue-depth", str(queue_depth),
               "--ram-bytes", str(ram_bytes),
               "--beat-interval", str(beat_interval_s),
               "--retry-seed", str(i)]
        if i >= npeers:
            cmd.append("--standby")
        env = dict(os.environ)
        # One process per chip, and these rigs start several peers per
        # host: unless the caller names a platform the peers derive on
        # the CPU.  Each peer reports its platform in /stats, so a CPU
        # fleet never reads as a chip.
        env.setdefault("JAX_PLATFORMS", "cpu")
        logf = open(os.path.join(td, f"{name}.log"), "w")
        procs.append((subprocess.Popen(cmd, stdout=logf, stderr=logf,
                                       env=env), logf))
    try:
        for i, name in enumerate(names):
            port_file = os.path.join(td, f"{name}.port")
            deadline = _time.monotonic() + bringup_timeout_s
            while not os.path.exists(port_file):
                if procs[i][0].poll() is not None:
                    raise RuntimeError(
                        f"{name} died at bring-up "
                        f"(rc={procs[i][0].returncode}; see {name}.log)")
                if _time.monotonic() > deadline:
                    raise TimeoutError(f"{name} port file never appeared")
                _time.sleep(0.05)
            with open(port_file) as f:
                url = f"http://127.0.0.1:{int(f.read().strip())}"
            wait_http_ready(url, timeout_s=bringup_timeout_s)
            peers[name] = url
    except BaseException:
        _reap_fleet_peers(procs)
        raise
    return procs, peers, lease_dir


def _reap_fleet_peers(procs) -> None:
    """Terminate (then kill) peer subprocesses and close their logs —
    every exit path of the chaos rigs."""
    for p, _ in procs:
        if p.poll() is None:
            p.terminate()
    for p, logf in procs:
        try:
            p.wait(timeout=10)
        except Exception:  # noqa: BLE001 — escalate to SIGKILL
            p.kill()
            try:
                p.wait(timeout=5)
            except Exception:  # noqa: BLE001 — nothing left to do
                pass
        try:
            logf.close()
        except OSError:
            pass


def _monitor_from_flags(args: argparse.Namespace):
    """Start the process-wide metrics publisher from ``--monitor-*``
    CLI flags (ISSUE 11) and install it as the singleton every
    ``publishing`` hook resolves (:func:`blit.monitor
    .install_publisher`) — so the reductions this command runs
    auto-publish exactly as an env-enabled deployment would, without
    mutating the environment.  Returns the publisher (caller shuts it
    down) or None when no flag was given."""
    if (getattr(args, "monitor_spool", None) is None
            and getattr(args, "monitor_port", None) is None):
        return None
    from blit import monitor

    pub = monitor.install_publisher(monitor.MetricsPublisher(
        interval_s=args.monitor_interval,
        spool_dir=args.monitor_spool,
        port=args.monitor_port).start())
    if pub.port is not None:
        print(f"# monitor: {pub.url}/metrics", file=sys.stderr)
    return pub


def _chaos_run(sup) -> dict:
    """Run a supervisor for the chaos drill, converting an exhausted
    recovery budget into a failed REPORT instead of a traceback — the
    --json-out artifact must exist exactly when the drill fails (that
    is the run CI needs to triage)."""
    try:
        rep = sup.run()
    except RuntimeError as e:
        rep = {"recovered": False, "error": str(e), "attempts": [],
               "attempts_tried": sup.state().get("attempt", 0) + 1}
    return rep


def _cmd_fsck(args: argparse.Namespace) -> int:
    """``blit fsck`` (ISSUE 13): verify an archive tree's manifests and
    cache-entry content digests, quarantine what fails, optionally
    repair.  Exit 0 = clean tree; 1 = corruption found (the report
    names every artifact, and everything bad is already quarantined
    unless ``--no-quarantine``)."""
    from blit import integrity

    rep = integrity.fsck(args.root, repair=args.repair,
                         quarantine=not args.no_quarantine)
    cold = getattr(args, "cold_dir", None)
    if cold:
        # The cold tier (ISSUE 19) shares the hot tier's sidecar
        # convention, so the SAME walk verifies/quarantines/repairs it
        # — one merged report, one exit verdict.
        crep = integrity.fsck(cold, repair=args.repair,
                              quarantine=not args.no_quarantine)
        rep = {
            "root": rep["root"], "cold_root": crep["root"],
            "checked": rep["checked"] + crep["checked"],
            "ok": rep["ok"] + crep["ok"],
            "unmanifested": (rep["unmanifested"]
                             + crep["unmanifested"]),
            "in_progress": rep["in_progress"] + crep["in_progress"],
            "bad": rep["bad"] + crep["bad"],
            "quarantined": rep["quarantined"] + crep["quarantined"],
            "repaired": rep["repaired"] + crep["repaired"],
            "repair_failed": (rep["repair_failed"]
                              + crep["repair_failed"]),
            "clean": rep["clean"] and crep["clean"],
        }
    body = json.dumps(rep)
    print(body)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(body)
    return 0 if rep["clean"] else 1


def _cmd_backfill(args: argparse.Namespace) -> int:
    """``blit backfill`` (ISSUE 19 tentpole #3): walk an archive root
    through the catalog crawl, derive + publish EVERY (session, scan,
    player) product into a hot(+cold) cache — the fleet then serves the
    archive day from warm tiers instead of recompute storms.

    Resumable by construction: a product's completion is recorded in an
    append-only fsync-per-line LEDGER only AFTER its cache publish
    lands, so a kill mid-derive leaves no entry and the product simply
    re-derives on resume, while completed products are never re-derived
    (the acceptance kill-drill).  Products are content-addressed, so an
    interrupted+resumed backfill finishes byte-identical to an
    uninterrupted one.

    Paced like the PR-12 Scrubber: after each product the walker sleeps
    off the debt ``max(0, input_bytes / bytes_per_s - elapsed)`` so a
    backfill sharing a host with foreground serving never starves it."""
    import os
    import time as _time

    from blit.config import DEFAULT, archive_defaults
    from blit.observability import Timeline
    from blit.serve.cache import ProductCache, fingerprint_for
    from blit.serve.catalog import CatalogIndex
    from blit.serve.service import ProductRequest

    config = DEFAULT
    if args.cold_dir:
        config = config.with_(cache_cold_dir=args.cold_dir)
    if args.bytes_per_s is not None:
        config = config.with_(backfill_bytes_per_s=args.bytes_per_s
                              if args.bytes_per_s > 0 else None)
    bps = archive_defaults(config)["backfill_bytes_per_s"]
    tl = Timeline()
    cache = ProductCache(args.cache_dir, ram_bytes=args.ram_bytes,
                         disk_bytes=args.disk_bytes,
                         cold_dir=archive_defaults(config)["cold_dir"],
                         timeline=tl)
    catalog = CatalogIndex(args.root, config=config, rescan_s=0.0,
                           timeline=tl)
    catalog.refresh(force=True)
    ledger_path = args.ledger or os.path.join(args.cache_dir,
                                              "backfill.ledger.jsonl")
    done: set = set()
    if os.path.exists(ledger_path):
        with open(ledger_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    done.add(json.loads(line)["fp"])
                except (ValueError, KeyError):
                    # A torn tail line (the crash wrote half a record):
                    # treat as not-completed — the product re-derives.
                    continue
    os.makedirs(os.path.dirname(os.path.abspath(ledger_path)),
                exist_ok=True)
    ledger = open(ledger_path, "a")
    if ledger.tell() > 0:
        with open(ledger_path, "rb") as f:
            f.seek(-1, os.SEEK_END)
            torn_tail = f.read(1) != b"\n"
        if torn_tail:
            # Terminate the crash's half-record so the claims appended
            # below never concatenate onto it (both would be lost on
            # the NEXT resume).
            ledger.write("\n")
            ledger.flush()

    def _claim(fp: str, session: str, scan: str, player: str) -> None:
        """fsync-before-claim: the ledger line is durable BEFORE the
        product counts as completed — a crash can lose work, never
        fake it."""
        ledger.write(json.dumps({"fp": fp, "session": session,
                                 "scan": scan, "player": player,
                                 "t": round(_time.time(), 3)}) + "\n")
        ledger.flush()
        os.fsync(ledger.fileno())
        done.add(fp)

    report = {"backfill": True, "root": os.path.abspath(args.root),
              "cache_dir": args.cache_dir,
              "cold_dir": archive_defaults(config)["cold_dir"],
              "ledger": ledger_path, "bytes_per_s": bps,
              "products_total": 0, "derived": 0, "skipped_ledger": 0,
              "skipped_cached": 0, "errors": []}
    t_start = _time.perf_counter()
    bytes_read = 0
    debt_s = 0.0
    stop = False
    try:
        with catalog._lock:
            sessions = {s: dict(e["scans"])
                        for s, e in catalog._sessions.items()}
        for session in sorted(sessions):
            if stop:
                break
            for scan in sorted(sessions[session]):
                if stop:
                    break
                seqs = sessions[session][scan]["sequences"]
                for (band, bank), members in sorted(seqs.items()):
                    if args.limit and report["products_total"] >= args.limit:
                        stop = True
                        break
                    report["products_total"] += 1
                    player = f"BLP{band}{bank}"
                    req = (ProductRequest(raw=tuple(members),
                                          product=args.product)
                           if args.product else
                           ProductRequest(raw=tuple(members),
                                          nfft=args.nfft,
                                          nint=args.nint))
                    reducer = req.reducer()
                    fp = fingerprint_for(reducer, req.raw_source)
                    if fp in done:
                        report["skipped_ledger"] += 1
                        continue
                    if cache.contains(fp):
                        # Published but the claim never landed (killed
                        # in the publish→claim window) — or a foreground
                        # serve beat us to it.  Completed either way.
                        _claim(fp, session, scan, player)
                        report["skipped_cached"] += 1
                        continue
                    t0 = _time.perf_counter()
                    nbytes = sum(os.path.getsize(m) for m in members)
                    try:
                        header, data = reducer.reduce(req.raw_source)
                        cache.put(fp, header, data, recipe=req.recipe())
                        cache.note_derive()
                    except Exception as e:  # noqa: BLE001 — reported
                        report["errors"].append(
                            f"{session}/{scan}/{player}: {e!r}")
                        continue
                    _claim(fp, session, scan, player)
                    report["derived"] += 1
                    bytes_read += nbytes
                    # The Scrubber debt discipline: pay for the bytes
                    # just read before touching the next product.
                    if bps:
                        dt = _time.perf_counter() - t0
                        debt_s = max(0.0, nbytes / bps - dt)
                        if debt_s > 0:
                            _time.sleep(debt_s)
    finally:
        ledger.close()
    report["wall_s"] = round(_time.perf_counter() - t_start, 3)
    report["bytes_read"] = bytes_read
    report["cache"] = cache.stats()
    body = json.dumps(report)
    print(body)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(body)
    return 1 if report["errors"] else 0


def _chaos_corrupt(args: argparse.Namespace, work: str,
                   report: dict) -> int:
    """The ``blit chaos --fault corrupt`` leg (ISSUE 13 satellite):
    seeded in-flight corruption of one delivered RAW block under a
    digest sidecar.  The contract asserted end to end: the mismatch is
    DETECTED (``integrity.bad_block`` >= 1), the block is MASKED to
    zero weight (never garbage), and the product is byte-identical to
    an oracle reduction of the same recording with that block zeroed.

    Geometry note: blocks are sized so the whole drill fits one device
    chunk — every block then arrives as ONE delivery, so "delivery k"
    is "block k" and the zero-filled oracle is exact."""
    import filecmp
    import os

    import numpy as np

    from blit import faults, integrity
    from blit.io.guppi import GuppiRaw, write_raw
    from blit.pipeline import RawReducer
    from blit.testing import synth_raw

    nblocks = max(2, args.chunks)
    per_block = max(4, args.window_frames) * args.nfft
    victim = min(max(0, args.after), nblocks - 1)
    in_dir = os.path.join(work, "input")
    oracle_dir = os.path.join(work, "oracle_input")
    os.makedirs(in_dir, exist_ok=True)
    os.makedirs(oracle_dir, exist_ok=True)
    raw = os.path.join(in_dir, "chaos.raw")
    synth_raw(raw, nblocks=nblocks, obsnchan=args.nchan,
              ntime_per_block=per_block, seed=args.seed)
    # The zero-filled oracle: the SAME recording with the victim block
    # zeroed (same basename so derived headers cannot differ).
    rdr0 = GuppiRaw(raw, native=False)
    blocks = [np.array(rdr0.read_block(i)) for i in range(nblocks)]
    blocks[victim][:] = 0
    write_raw(os.path.join(oracle_dir, "chaos.raw"),
              dict(rdr0.header(0)), blocks)
    integrity.write_raw_digests(raw)
    # One chunk spans the whole recording (its (ntap-1)-frame head and
    # chunk_frames new frames), so every block lands as one delivery —
    # but for block 0, whose first samples are the stream's head: a
    # delivery of its own, ahead of the rest of the block.
    cf = max(args.nint, (nblocks * per_block) // args.nfft - 3)
    kw = dict(nfft=args.nfft, nint=args.nint, chunk_frames=cf)
    oracle = os.path.join(work, "oracle.fil")
    RawReducer(**kw).reduce_to_file(
        os.path.join(oracle_dir, "chaos.raw"), oracle)
    out = os.path.join(work, "chaos.fil")
    faults.reset_counters()
    faults.install(faults.FaultRule(point="guppi.read", mode="corrupt",
                                    after=victim + (victim > 0), times=1))
    try:
        rdr = GuppiRaw(raw)  # arms the digest sidecar
        hdr = RawReducer(**kw).reduce_to_file(rdr, out)
    finally:
        faults.clear()
    counters = faults.counters()
    try:
        identical = filecmp.cmp(out, oracle, shallow=False)
    except OSError:
        identical = False
    bad_blocks = int(counters.get("integrity.bad_block", 0))
    report.update(
        recovered=bad_blocks >= 1,
        byte_identical=identical,
        victim_block=victim,
        masked_blocks=hdr.get("_masked_blocks", []),
        integrity={k: v for k, v in sorted(counters.items())
                   if k.startswith(("integrity.", "mask."))},
        work_dir=work,
    )
    body = json.dumps(report)
    print(body)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(body)
    return 0 if (identical and bad_blocks >= 1) else 1


def _chaos_fleet(args: argparse.Namespace, work: str, report: dict) -> int:
    """``blit chaos --fleet`` (ISSUE 14 tentpole): break a REAL
    multi-process serving fleet mid-replay and assert the front door's
    recovery contract end to end:

    - the failed peer (SIGKILL / SIGSTOP-wedge / SIGSTOP+SIGCONT
      partition) is DETECTED within the lease TTL and ejected,
    - its key range re-routes: every request completes,
    - every served product is BYTE-IDENTICAL to a single-process
      oracle reduction,
    - ``/healthz`` degrades honestly and (partition) recovers,
    - post-recovery hit-rate returns to within 10% of pre-kill.

    The victim is the OWNER of the hottest product — the worst case for
    the cache-warm replication story."""
    import math
    import os
    import random
    import signal
    import time as _time

    import numpy as np

    from blit.observability import Timeline
    from blit.serve import Overloaded, ProductRequest
    from blit.serve.cache import fingerprint_for
    from blit.serve.fleet import FleetError, FleetFrontDoor
    from blit.serve.http import http_json
    from blit.serve.scheduler import DeadlineExpired
    from blit.testing import synth_raw

    rng = random.Random(args.seed)
    nfft = args.nfft
    distinct = max(2, args.fleet_distinct)
    total = max(30, args.fleet_requests)
    ntime = (8 + 3) * nfft
    reqs, oracle = [], {}
    for i in range(distinct):
        path = os.path.join(work, f"prod{i:02d}.raw")
        synth_raw(path, nblocks=1, obsnchan=2, ntime_per_block=ntime,
                  seed=args.seed + i)
        req = ProductRequest(raw=path, nfft=nfft, nint=1)
        reqs.append(req)
        # The single-process oracle: the same reduction, no fleet.
        _, data = req.reducer().reduce(path)
        oracle[i] = np.asarray(data)
    procs, peers, lease_dir = _spawn_fleet_peers(
        work, args.peers, concurrency=2, queue_depth=32,
        ram_bytes=64 << 20, beat_interval_s=min(0.2, args.lease_ttl / 5))
    tl = Timeline()
    door = FleetFrontDoor(
        peers, lease_dir=lease_dir, timeline=tl, replicas=args.replicas,
        peer_ttl_s=args.lease_ttl, poll_s=args.poll,
        health_poll_s=max(args.poll, 0.5),
        hedge_floor_s=0.05, request_timeout_s=10.0).start()

    fp0 = fingerprint_for(reqs[0].reducer(), reqs[0].raw_source)
    victim = door.ring.owners(fp0)[0]
    victim_proc = procs[int(victim.removeprefix("peer"))][0]
    weights = [1.0 / math.pow(k + 1, 1.2) for k in range(distinct)]
    picks = rng.choices(range(distinct), weights=weights, k=total)
    third = total // 3

    def cache_totals() -> dict:
        out = {}
        for name, url in peers.items():
            try:
                _, _, s = http_json("GET", url, "/stats", timeout=2.0)
            except OSError:
                continue
            c = s.get("cache") or {}
            out[name] = (c.get("hit.ram", 0) + c.get("hit.disk", 0),
                         c.get("miss", 0))
        return out

    def window_hit_rate(before: dict, after: dict):
        """Hit rate of the interval, over peers alive in BOTH samples
        (a SIGKILLed peer's counters vanish mid-drill)."""
        dh = dm = 0
        for name, (h1, m1) in after.items():
            if name not in before:
                continue
            h0, m0 = before[name]
            dh += max(0, h1 - h0)
            dm += max(0, m1 - m0)
        return (dh / (dh + dm)) if dh + dm else None

    failed: list = []
    diffs: list = []

    def run_slice(idxs) -> None:
        for k in idxs:
            for _attempt in range(8):
                try:
                    _, d = door.get(reqs[k], client="chaos")
                except Overloaded as e:
                    _time.sleep(min(0.25, e.retry_after_s))
                    continue
                except (FleetError, DeadlineExpired, OSError):
                    # Transient while the failure is being detected:
                    # back off a beat and retry — a real client's loop.
                    _time.sleep(0.2)
                    continue
                if not np.array_equal(np.asarray(d), oracle[k]):
                    diffs.append(k)
                failed_here = False
                break
            else:
                failed_here = True
            if failed_here:
                failed.append(k)

    try:
        run_slice(picks[:third])                     # warm the fleet
        marks = {"warm": cache_totals()}
        health_pre = door.health()
        run_slice(picks[third:2 * third])            # pre-kill window
        marks["pre_kill"] = cache_totals()
        hit_pre = window_hit_rate(marks["warm"], marks["pre_kill"])

        sig = (signal.SIGKILL if args.fault == "kill" else signal.SIGSTOP)
        t_kill = _time.monotonic()
        victim_proc.send_signal(sig)
        # Detection: the lease goes stale, the door ejects within the
        # TTL (+ the watch cadence), traffic re-routes to the replicas.
        detect_budget = args.lease_ttl * 3 + 5.0
        while victim in door.ring and \
                _time.monotonic() - t_kill < detect_budget:
            _time.sleep(args.poll / 2)
        detect_s = _time.monotonic() - t_kill
        detected = victim not in door.ring
        health_after = door.health()

        tail = picks[2 * third:]
        run_slice(tail[:len(tail) // 2])             # recovery window
        marks["recovering"] = cache_totals()
        run_slice(tail[len(tail) // 2:])             # recovered window
        marks["recovered"] = cache_totals()
        hit_post = window_hit_rate(marks["recovering"],
                                   marks["recovered"])

        rejoined = None
        if args.fault == "partition":
            victim_proc.send_signal(signal.SIGCONT)
            budget = _time.monotonic() + args.lease_ttl * 4 + 5.0
            while victim not in door.ring and _time.monotonic() < budget:
                _time.sleep(args.poll / 2)
            rejoined = victim in door.ring
        health_final = door.health()

        fstats = door.stats()
        hit_recovered = (hit_pre is not None and hit_post is not None
                         and hit_post >= hit_pre - 0.10)
        report.update(
            peers=args.peers,
            replicas=args.replicas,
            requests=total,
            distinct=distinct,
            victim=victim,
            detected=detected,
            detect_s=round(detect_s, 3),
            lease_ttl_s=args.lease_ttl,
            recovered=detected and not failed,
            byte_identical=not diffs,
            differing_products=diffs[:8],
            failed_requests=len(failed),
            hit_rate_pre_kill=(round(hit_pre, 4)
                               if hit_pre is not None else None),
            hit_rate_post_recovery=(round(hit_post, 4)
                                    if hit_post is not None else None),
            hit_rate_recovered=hit_recovered,
            rejoined=rejoined,
            healthz={
                "pre": health_pre["status"],
                "after_detect": health_after["status"],
                "final": health_final["status"],
                "final_reasons": health_final["reasons"],
            },
            counters=fstats["counters"],
            work_dir=work,
        )
    finally:
        door.close()
        # A SIGSTOPped victim cannot be reaped until it runs again.
        if args.fault in ("hang", "partition") and \
                victim_proc.poll() is None:
            try:
                victim_proc.send_signal(signal.SIGCONT)
            except OSError:
                pass
        _reap_fleet_peers(procs)

    ok = (report["recovered"] and report["byte_identical"]
          and report["hit_rate_recovered"]
          and report["healthz"]["after_detect"] == "degraded"
          and (rejoined is None or rejoined))
    report["ok"] = ok
    body = json.dumps(report)
    print(body)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(body)
    return 0 if ok else 1


def _chaos_fleet_resize(args: argparse.Namespace, work: str,
                        report: dict) -> int:
    """``blit chaos --fleet --fault resize`` (ISSUE 17): SIGKILL a
    serving peer DURING the elastic warm handoff — the worst moment:
    the controller is mid-flip, the joiner is computing its incoming
    hot range, and a peer that was supposed to keep serving dies.
    Asserts the resize contract under fire:

    - the membership flip still COMPLETES (the standby is admitted;
      fail-open if the handoff deadline burns),
    - ``/healthz`` answers an honest ``"resizing"`` mid-flip,
    - the killed peer is detected within the lease TTL and ejected,
    - every request completes BYTE-IDENTICAL to a single-process
      oracle,
    - post-resize hit-rate is within 10% of pre-resize.

    The product set is EXTENDED until the joiner's incoming key range
    holds several hot products, so the handoff has real work to
    interrupt (otherwise the flip is sub-millisecond and the kill
    cannot land inside it)."""
    import math
    import os
    import random
    import signal
    import threading
    import time as _time

    import numpy as np

    from blit.observability import Timeline
    from blit.serve import Overloaded, ProductRequest
    from blit.serve.cache import fingerprint_for
    from blit.serve.elastic import FleetController
    from blit.serve.fleet import FleetError, FleetFrontDoor
    from blit.serve.http import http_json
    from blit.serve.scheduler import DeadlineExpired
    from blit.testing import synth_raw

    rng = random.Random(args.seed)
    nfft = args.nfft
    joiner = "standby0"
    total = max(30, args.fleet_requests)
    ntime = (8 + 3) * nfft
    reqs, oracle, fps = [], {}, []

    def add_product(i: int) -> None:
        path = os.path.join(work, f"prod{i:02d}.raw")
        synth_raw(path, nblocks=1, obsnchan=2, ntime_per_block=ntime,
                  seed=args.seed + i)
        req = ProductRequest(raw=path, nfft=nfft, nint=1)
        reqs.append(req)
        fps.append(fingerprint_for(req.reducer(), req.raw_source))
        # The single-process oracle: the same reduction, no fleet.
        _, data = req.reducer().reduce(path)
        oracle[i] = np.asarray(data)

    for i in range(max(2, args.fleet_distinct)):
        add_product(i)
    procs, peers, lease_dir = _spawn_fleet_peers(
        work, args.peers, concurrency=2, queue_depth=32,
        ram_bytes=64 << 20,
        beat_interval_s=min(0.2, args.lease_ttl / 5), standbys=1)
    tl = Timeline()
    door = FleetFrontDoor(
        {f"peer{i}": peers[f"peer{i}"] for i in range(args.peers)},
        lease_dir=lease_dir, timeline=tl, replicas=args.replicas,
        peer_ttl_s=args.lease_ttl, poll_s=args.poll,
        health_poll_s=max(args.poll, 0.5),
        hedge_floor_s=0.05, request_timeout_s=10.0).start()
    door.add_standby(joiner, peers[joiner], proc=args.peers)
    ctl = FleetController(door, None, hysteresis_s=0.0,
                          warm_timeout_s=30.0, min_peers=1,
                          warm_hints=64, timeline=tl)
    # Grow the mix until >= 3 products will MOVE to the joiner on
    # admit — the handoff then computes them on the cold joiner, a
    # window wide enough to kill a peer inside.
    while len(reqs) < 40 and \
            len(door.ring.incoming_keys(joiner, fps)) < 3:
        add_product(len(reqs))
    incoming = door.ring.incoming_keys(joiner, fps)

    victim = door.ring.owners(fps[0])[0]
    victim_proc = procs[int(victim.removeprefix("peer"))][0]
    weights = [1.0 / math.pow(k + 1, 1.2) for k in range(len(reqs))]
    picks = rng.choices(range(len(reqs)), weights=weights, k=total)
    third = total // 3

    def cache_totals() -> dict:
        out = {}
        for name, url in peers.items():
            try:
                _, _, s = http_json("GET", url, "/stats", timeout=2.0)
            except OSError:
                continue
            c = s.get("cache") or {}
            out[name] = (c.get("hit.ram", 0) + c.get("hit.disk", 0),
                         c.get("miss", 0))
        return out

    def window_hit_rate(before: dict, after: dict):
        dh = dm = 0
        for name, (h1, m1) in after.items():
            if name not in before:
                continue
            h0, m0 = before[name]
            dh += max(0, h1 - h0)
            dm += max(0, m1 - m0)
        return (dh / (dh + dm)) if dh + dm else None

    failed: list = []
    diffs: list = []

    def run_slice(idxs) -> None:
        for k in idxs:
            for _attempt in range(8):
                try:
                    _, d = door.get(reqs[k], client="chaos")
                except Overloaded as e:
                    _time.sleep(min(0.25, e.retry_after_s))
                    continue
                except (FleetError, DeadlineExpired, OSError):
                    _time.sleep(0.2)
                    continue
                if not np.array_equal(np.asarray(d), oracle[k]):
                    diffs.append(k)
                failed_here = False
                break
            else:
                failed_here = True
            if failed_here:
                failed.append(k)

    flip_completed = detected = False
    mid_handoff = False
    resizing_status = None
    detect_s = None
    hit_pre = hit_post = None
    out_rec: list = []
    try:
        # Warm every product once (so the door's hot map knows the
        # whole range), then the zipfian pre window.
        run_slice(list(range(len(reqs))) + picks[:third])
        marks = {"warm": cache_totals()}
        run_slice(picks[third:2 * third])
        marks["pre"] = cache_totals()
        hit_pre = window_hit_rate(marks["warm"], marks["pre"])
        health_pre = door.health()

        # The flip, in a thread — and the kill, INSIDE the handoff.
        t = threading.Thread(target=lambda: out_rec.append(
            ctl.scale_out(joiner)))
        t.start()
        gate = _time.monotonic() + 30.0
        while _time.monotonic() < gate:
            if door.resize_reason is not None:
                mid_handoff = True
                break
            _time.sleep(0.001)
        if mid_handoff:
            resizing_status = door.health()["status"]
        t_kill = _time.monotonic()
        victim_proc.send_signal(signal.SIGKILL)
        t.join(timeout=120.0)
        flip_completed = joiner in door.ring

        detect_budget = args.lease_ttl * 3 + 5.0
        while victim in door.ring and \
                _time.monotonic() - t_kill < detect_budget:
            _time.sleep(args.poll / 2)
        detect_s = _time.monotonic() - t_kill
        detected = victim not in door.ring

        tail = picks[2 * third:]
        run_slice(tail[:len(tail) // 2])             # recovery window
        marks["recovering"] = cache_totals()
        run_slice(tail[len(tail) // 2:])             # recovered window
        marks["recovered"] = cache_totals()
        hit_post = window_hit_rate(marks["recovering"],
                                   marks["recovered"])
        health_final = door.health()

        fstats = door.stats()
        hit_recovered = (hit_pre is not None and hit_post is not None
                         and hit_post >= hit_pre - 0.10)
        report.update(
            peers=args.peers,
            replicas=args.replicas,
            requests=total,
            distinct=len(reqs),
            joiner=joiner,
            joiner_incoming=len(incoming),
            victim=victim,
            killed_mid_handoff=mid_handoff,
            resizing_status=resizing_status,
            flip_completed=flip_completed,
            warm=(out_rec[0] if out_rec else None),
            detected=detected,
            detect_s=round(detect_s, 3),
            lease_ttl_s=args.lease_ttl,
            recovered=detected and not failed,
            byte_identical=not diffs,
            differing_products=diffs[:8],
            failed_requests=len(failed),
            hit_rate_pre_resize=(round(hit_pre, 4)
                                 if hit_pre is not None else None),
            hit_rate_post_resize=(round(hit_post, 4)
                                  if hit_post is not None else None),
            hit_rate_recovered=hit_recovered,
            healthz={
                "pre": health_pre["status"],
                "mid_flip": resizing_status,
                "final": health_final["status"],
                "final_reasons": health_final["reasons"],
            },
            counters=fstats["counters"],
            work_dir=work,
        )
    finally:
        ctl.close()
        door.close()
        _reap_fleet_peers(procs)

    ok = (flip_completed and mid_handoff
          and resizing_status == "resizing"
          and report.get("recovered", False)
          and report.get("byte_identical", False)
          and report.get("hit_rate_recovered", False))
    report["ok"] = ok
    body = json.dumps(report)
    print(body)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(body)
    return 0 if ok else 1


def _cmd_session(args: argparse.Namespace) -> int:
    """``blit session`` (ISSUE 18): run (or rejoin) a whole LIVE
    observing session from a spec file — one supervised stream consumer
    per recorder seat, fanned across this host, each crash-rejoinable
    through its StreamCursor.  The spec is JSON::

        {"seats": [{"name": "blc00", "out": "...", "raw": "...",
                    "source": {"kind": "packet", "port": 60000},
                    "knobs": {"nfft": 1024}}, ...],
         "work_dir": "...", "lease_ttl_s": 5.0}

    (seat/source fields: :class:`blit.stream.SessionSupervisor` /
    :func:`blit.stream.source_from_spec`).  Re-running the same spec
    after a host crash REJOINS every seat mid-product.  Prints the
    folded session report; exit 0 = every seat completed."""
    import tempfile

    from blit.observability import Timeline
    from blit.stream import SessionSupervisor

    with open(args.spec) as f:
        spec = json.load(f)
    tl = Timeline()
    pub = _monitor_from_flags(args)
    work = (args.work_dir or spec.get("work_dir")
            or tempfile.mkdtemp(prefix="blit-session-"))
    sup = SessionSupervisor(
        spec["seats"], work_dir=work,
        lease_ttl_s=(args.lease_ttl if args.lease_ttl is not None
                     else spec.get("lease_ttl_s")),
        poll_s=(args.poll if args.poll is not None
                else spec.get("poll_s")),
        max_attempts=(args.attempts if args.attempts is not None
                      else spec.get("max_attempts")),
        faults=spec.get("faults"), timeline=tl,
    )
    rep = sup.run()
    rep["work_dir"] = work
    if pub is not None:
        pub.tick()
        rep["monitor"] = {"port": pub.port, "spool": pub.spool_path}
        from blit import monitor

        monitor.shutdown_publisher()
    body = json.dumps(rep)
    print(body)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(body)
    return 0 if rep["ok"] else 1


def _cmd_chaos(args: argparse.Namespace) -> int:
    """``blit chaos`` (ISSUE 12): run a SEEDED kill/hang schedule
    against a real supervised workload — a multi-process sharded scan
    (``--workload scan`` / ``scan-search``) or a live stream consumer
    (``--workload stream``) — and assert the recovery contract end to
    end: the failure is DETECTED within the lease budget, the scan
    re-plans (reshaped mesh or pool fallback) / the consumer rejoins,
    and the final products are BYTE-IDENTICAL to an uninterrupted
    oracle run.  Prints (and optionally writes) the drill report JSON;
    exit 0 = recovered and identical."""
    import os
    import tempfile

    from blit.observability import Timeline
    from blit.recover import RECOVER_HISTS, ScanSupervisor, StreamSupervisor
    from blit.testing import synth_raw

    tl = Timeline()
    work = args.work_dir or tempfile.mkdtemp(prefix="blit-chaos-")
    os.makedirs(work, exist_ok=True)
    if args.fleet:
        if args.fault == "corrupt":
            print("chaos --fleet supports kill/hang/partition, "
                  "not corrupt", file=sys.stderr)
            return 2
        report = {"workload": "fleet", "fault": args.fault}
        if args.fault == "resize":
            return _chaos_fleet_resize(args, work, report)
        return _chaos_fleet(args, work, report)
    if args.fault == "partition":
        print("--fault partition requires --fleet (a network partition "
              "is a serving-fleet failure shape)", file=sys.stderr)
        return 2
    if args.fault == "resize":
        print("--fault resize requires --fleet (an elastic membership "
              "flip is a serving-fleet failure shape)", file=sys.stderr)
        return 2
    if args.fault == "reorder" and args.workload != "stream":
        print("--fault reorder requires --workload stream (wire "
              "reordering is a packet front-end failure shape)",
              file=sys.stderr)
        return 2
    use_packets = args.workload == "stream" and (
        args.packets or args.fault == "reorder")
    point = args.point or (
        "packet.recv" if args.fault == "reorder"
        else "stream.chunk" if args.workload == "stream"
        else "mesh.window")
    if args.fault == "corrupt":
        # The integrity leg (ISSUE 13) is its own drill shape: no
        # supervisor, no crash — a corrupted delivered frame must be
        # detected and MASKED, whatever the workload flag says.
        report = {"workload": "reduce",
                  "fault": f"guppi.read:corrupt:after={args.after}"}
        return _chaos_corrupt(args, work, report)
    fault = (f"{point}:{args.fault}:after={args.after}"
             + (f":hang={args.hang_s}" if args.fault == "hang" else ""))
    report = {"workload": args.workload, "fault": fault,
              "procs": args.procs}

    if args.workload == "stream":
        raw = os.path.join(work, "chaos.raw")
        nblocks = max(4, args.chunks)
        ntime = (args.chunks * args.window_frames + 3) * args.nfft
        hdr0, blocks = synth_raw(
            raw, nblocks=nblocks, obsnchan=args.nchan,
            ntime_per_block=-(-ntime // nblocks), seed=args.seed)
        out = os.path.join(work, "chaos.fil")
        oracle = os.path.join(work, "oracle.fil")
        from blit.pipeline import RawReducer

        source = None
        oracle_raw = raw
        if use_packets:
            # The packet drill's seeded schedule: with --packets, one
            # whole block is dropped off the wire — the oracle is then
            # the SAME recording with that block zero-filled (gap ≡
            # mask ≡ zero weight, the acceptance identity).  A plain
            # --fault reorder keeps every packet, so the clean batch
            # oracle stands.
            source = {"kind": "packet-replay", "raw": raw,
                      "rate": args.replay_rate,
                      "packet_ntime": args.packet_ntime,
                      "seed": args.seed}
            if args.packets:
                from blit.io.guppi import write_raw

                source.update(drop_blocks=[1], reorder=0.15, dup=0.05)
                report["gapped_blocks"] = [1]
                zb = [b.copy() for b in blocks]
                zb[1][:] = 0
                oracle_raw = os.path.join(work, "chaos_zeroed.raw")
                write_raw(oracle_raw, hdr0, zb)
        RawReducer(nfft=args.nfft, nint=args.nint,
                   chunk_frames=args.window_frames
                   ).reduce_to_file(oracle_raw, oracle)
        sup = StreamSupervisor(
            raw, out, kind="reduce",
            knobs=dict(nfft=args.nfft, nint=args.nint,
                       chunk_frames=args.window_frames),
            replay_rate=args.replay_rate, source=source, faults=fault,
            lease_ttl_s=args.lease_ttl, poll_s=args.poll,
            max_attempts=args.attempts, timeline=tl,
        )
        rep = _chaos_run(sup)
        products = [(out, oracle)]
    else:
        kind = "search" if args.workload == "scan-search" else "reduce"
        grid = []
        bank_bw = -187.5 / args.nbank
        for b in range(args.nband):
            row = []
            for k in range(args.nbank):
                p = os.path.join(work, f"blc{b}{k}.raw")
                synth_raw(
                    p, nblocks=2, obsnchan=args.nchan,
                    ntime_per_block=-(-(args.chunks * args.window_frames
                                        + 3) * args.nfft // 2),
                    seed=args.seed + b * 8 + k, tone_chan=k % args.nchan,
                    obsbw=bank_bw,
                    obsfreq=8000.0 + b * 500.0 + (k + 0.5) * bank_bw,
                )
                row.append(p)
            grid.append(row)
        out_dir = os.path.join(work, "products")
        oracle_dir = os.path.join(work, "oracle")
        os.makedirs(oracle_dir, exist_ok=True)
        search_kw = dict(window_spectra=args.window_spectra, top_k=4,
                         snr_threshold=2.0, max_drift_bins=None,
                         kernel="reference")
        sup = ScanSupervisor(
            grid, out_dir=out_dir, kind=kind, nfft=args.nfft,
            nint=args.nint, despike=False,
            window_frames=args.window_frames,
            search=(search_kw if kind == "search" else None),
            nprocs=args.procs,
            devices_per_proc=(
                args.devices_per_proc if args.devices_per_proc
                else (args.nband * args.nbank) // args.procs),
            lease_ttl_s=args.lease_ttl, poll_s=args.poll,
            max_attempts=args.attempts,
            faults={args.victim: fault}, timeline=tl,
        )
        rep = _chaos_run(sup)
        # The pool oracle over the identical scan, at the SAME window
        # granularity (dispatch shape is part of the identity contract).
        wf = sup.wf
        if kind == "search":
            from blit.search import DedopplerReducer

            products = []
            for b in range(args.nband):
                for k in range(args.nbank):
                    op = os.path.join(oracle_dir, f"band{b}bank{k}.hits")
                    DedopplerReducer(
                        nfft=args.nfft, nint=args.nint, chunk_frames=wf,
                        **search_kw,
                    ).search_to_file(grid[b][k], op)
                    products.append(
                        (os.path.join(out_dir, f"band{b}bank{k}.hits"),
                         op))
        else:
            from blit.parallel.scan import reduce_scan_pool_to_files

            written = reduce_scan_pool_to_files(
                grid, out_dir=oracle_dir, nfft=args.nfft,
                nint=args.nint, despike=False, window_frames=wf)
            products = [
                (os.path.join(out_dir, os.path.basename(path)), path)
                for _, (path, _) in sorted(written.items())
            ]

    import filecmp

    identical = True
    diffs = []
    for got, want in products:
        try:
            # filecmp, not read()==read(): constant memory over
            # realistically-sized products (the PR 8 compare rule).
            same = filecmp.cmp(got, want, shallow=False)
        except OSError:
            same = False
        if not same:
            identical = False
            diffs.append(got)
    hists = tl.report().get("hists", {})
    report.update(
        recovered=rep.get("recovered", False),
        error=rep.get("error"),
        byte_identical=identical,
        differing_products=diffs,
        attempts=rep.get("attempts"),
        result=rep.get("result"),
        recover={h: hists.get(h, {}) for h in RECOVER_HISTS},
        windows_recomputed=sum(
            a.get("windows_recomputed", 0)
            for a in (rep.get("attempts") or [])),
        work_dir=work,
    )
    body = json.dumps(report)
    print(body)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(body)
    # Only the process-grade faults demand a RECOVERY (a restart to
    # detect); a data-plane fault like reorder is absorbed in place —
    # there, "no error and byte-identical" IS the pass.
    crashy = args.fault in ("kill", "hang")
    ok = identical and (report["recovered"] if crashy
                        else not rep.get("error"))
    return 0 if ok else 1


def _cmd_telemetry(args: argparse.Namespace) -> int:
    """Fleet telemetry report (ISSUE 5 tentpole #3).  Three sources:
    ``--from`` renders a saved report JSON; ``--demo`` runs a real
    multi-worker ``reduce_to_file`` fan-out over synthetic recordings and
    harvests the pool (the end-to-end proof: every worker's stage table
    and fault counters in one per-host report, plus a Perfetto-loadable
    trace via ``--trace-out``); the default snapshots this process."""
    import json as _json

    from blit import observability

    if args.watch is not None and not args.demo:
        # Poor-man's live mode (ISSUE 11 satellite): periodic re-harvest
        # + re-render on `blit top`'s refresh path (monitor.watch_loop —
        # same ANSI frame loop, same cadence semantics).
        from blit import monitor

        def frame() -> str:
            if args.from_file:
                with open(args.from_file) as f:
                    rep = _json.load(f)
            else:
                rep = observability.local_fleet_report()
            if args.format == "prom":
                return observability.render_prometheus(rep)
            if args.format == "json":
                return _json.dumps(rep)
            return observability.render_fleet_text(rep)

        monitor.watch_loop(frame, args.watch, count=args.iterations)
        return 0
    if args.from_file:
        with open(args.from_file) as f:
            report = _json.load(f)
    elif args.demo:
        import os
        import tempfile

        from blit import workers
        from blit.parallel.pool import WorkerPool
        from blit.testing import synth_raw

        n = max(1, args.workers)
        with tempfile.TemporaryDirectory(prefix="blit-telemetry-") as td:
            argtuples = []
            for i in range(n):
                raw = os.path.join(td, f"demo{i}.raw")
                synth_raw(raw, nblocks=1, obsnchan=2,
                          ntime_per_block=(8 + 3) * args.nfft, seed=i)
                argtuples.append((raw, os.path.join(td, f"demo{i}.fil")))
            with WorkerPool([f"w{i + 1}" for i in range(n)],
                            backend=args.backend) as pool:
                with observability.span("telemetry-demo", workers=n):
                    pool.run_on(list(range(1, n + 1)), workers.reduce_raw,
                                argtuples, kwargs={"nfft": args.nfft})
                report = pool.harvest_telemetry()
    else:
        report = observability.local_fleet_report()
    if args.trace_out:
        # Works in every source mode: the tracer holds this process's
        # spans, the report carries any harvested (or saved) ones.
        observability.tracer().export_chrome(
            args.trace_out, extra=report.get("spans"))
        print(f"# trace written to {args.trace_out}", file=sys.stderr)
    if args.format == "prom":
        print(observability.render_prometheus(report), end="")
    elif args.format == "json":
        print(_json.dumps(report))
    else:
        print(observability.render_fleet_text(report))
    return 0


def _cmd_top(args: argparse.Namespace) -> int:
    """``blit top`` (ISSUE 11 tentpole): the live terminal dashboard.
    ``--spool DIR`` tails the per-process monitor spool (merging a pod's
    processes through ``merge_fleet``); ``--url`` polls one publisher's
    ``/snapshot`` endpoint.  Refreshes every ``--interval`` seconds with
    an ANSI clear; ``--once`` renders a single frame with no clear.
    ``--history DIR`` appends a sparkline panel per stored series from
    a durable history store (ISSUE 20: the last N finest-tier
    buckets)."""
    from blit import monitor, observability

    def fetch() -> str:
        if args.url:
            import urllib.request

            with urllib.request.urlopen(
                    args.url.rstrip("/") + "/snapshot", timeout=10) as r:
                sample = json.load(r)
            report = observability.merge_fleet([sample])
            samples = [sample]
        else:
            report, samples = monitor.merge_spool(args.spool)
        frame = monitor.render_top(report, samples)
        if args.history:
            from blit.history import HistoryStore, render_history_panel

            store = HistoryStore(args.history, create=False)
            frame += "\n" + render_history_panel(
                store, buckets=args.history_buckets)
        return frame

    if args.once:
        print(fetch())
        return 0
    monitor.watch_loop(fetch, args.interval, count=args.iterations)
    return 0


def _cmd_trace_view(args: argparse.Namespace) -> int:
    """Render a flight-recorder dump into an incident summary, or
    (``--fleet``, ISSUE 15) stitch span batches from many processes —
    monitor spools, saved snapshots, live ``/snapshot`` endpoints —
    into ONE trace view: a Perfetto export (``--out``), per-trace trees
    (``--trace``), and tail-bucket exemplar resolution
    (``--exemplar METRIC`` → the trace id behind the slowest bucket)."""
    import json as _json

    from blit.observability import render_flight_dump

    if args.fleet:
        return _trace_view_fleet(args)
    if not args.dump:
        raise SystemExit("trace-view needs a flight dump path "
                         "(or --fleet SOURCES)")
    with open(args.dump) as f:
        doc = _json.load(f)
    print(render_flight_dump(doc, tail=args.events))
    if args.trace or args.exemplar or args.out:
        # A flight dump is itself a span batch: reuse the fleet path so
        # `trace-view dump.json --trace <id>` follows the dump's trace.
        args.fleet = [args.dump]
        return _trace_view_fleet(args)
    return 0


def _trace_view_fleet(args: argparse.Namespace) -> int:
    """The fleet half of ``blit trace-view`` (ISSUE 15 tentpole #4)."""
    from blit import monitor, observability

    spans, hists = monitor.gather_trace_sources(args.fleet)
    summary = observability.trace_summary(spans)
    out = {"sources": list(args.fleet), **summary}
    if args.out:
        tr = observability.Tracer(max_spans=max(len(spans), 1),
                                  enabled=True)
        tr.ingest(spans)
        tr.export_chrome(args.out)
        out["out"] = args.out
    exemplar_trace = None
    if args.exemplar:
        h = hists.get(args.exemplar)
        ex = h.tail_exemplar() if h is not None else None
        if ex is None:
            print(json.dumps(out))
            print(f"# no exemplar recorded for {args.exemplar!r} "
                  f"({len(hists)} histogram(s) in the sources)",
                  file=sys.stderr)
            return 1
        out["exemplar"] = {"metric": args.exemplar, **ex}
        exemplar_trace = ex["trace"]
    print(json.dumps(out))
    for trace_id in ([args.trace] if args.trace else []) + (
            [exemplar_trace] if exemplar_trace else []):
        print(observability.render_trace_tree(spans, trace_id))
    return 0


def _cmd_requests(args: argparse.Namespace) -> int:
    """``blit requests`` (ISSUE 15 tentpole #2): tail, filter and
    aggregate a per-request access-record spool — the operator's "which
    requests were slow, and whose trace do I open" surface."""
    from blit import monitor

    since = until = None
    if args.since or args.until:
        import time

        from blit.history import parse_when

        now = time.time()
        since = parse_when(args.since, now) if args.since else None
        until = parse_when(args.until, now) if args.until else None
    records = monitor.read_requests(args.spool, tail=args.tail)
    records = monitor.filter_requests(
        records, slow_ms=args.slow_ms, status=args.status,
        client=args.client, role=args.role, since=since, until=until)
    if args.aggregate:
        agg = monitor.aggregate_requests(records)
        print(json.dumps(agg) if args.json
              else json.dumps(agg, indent=2))
        return 0
    if args.json:
        for r in records:
            print(json.dumps(r))
    else:
        print(monitor.render_requests(records))
    return 0


def _incident_dir(args: argparse.Namespace) -> str:
    from blit.config import history_defaults

    d = args.dir or history_defaults()["incident_dir"]
    if not d:
        raise SystemExit("no incident dir: pass --dir or set "
                         "BLIT_INCIDENT_DIR")
    return d


def _cmd_incidents(args: argparse.Namespace) -> int:
    """``blit incidents`` (ISSUE 20): list the self-contained forensics
    bundles under the incident dir, oldest first."""
    from blit.history import list_incidents, render_incidents

    manifests = list_incidents(_incident_dir(args))
    if args.json:
        for m in manifests:
            print(json.dumps(m))
    else:
        print(render_incidents(manifests))
    return 0


def _cmd_incident(args: argparse.Namespace) -> int:
    """``blit incident show BUNDLE`` (ISSUE 20): render one bundle's
    merged cross-source timeline — flight events, request records,
    trace spans and the triggering alert, wall-clock aligned via the
    stamped anchors.  ``--window`` narrows the timeline around the
    page using the shared grammar (``15m``, ``2h``, an epoch pair)."""
    import time

    from blit.history import load_incident, render_incident, window_seconds

    bundle = load_incident(args.bundle)
    window = None
    if args.window:
        t = float((bundle.get("manifest") or {}).get("t", time.time()))
        half = window_seconds(args.window)
        window = (t - half, t + half / 4.0)
    if args.json:
        print(json.dumps(bundle))
    else:
        print(render_incident(bundle, window))
    return 0


def _cmd_slo_report(args: argparse.Namespace) -> int:
    """``blit slo-report`` (ISSUE 20): attainment + error-budget spend
    per objective over day/week windows, straight from a durable
    history store — text for the operator, ``--json`` for CI (its
    flat ``metrics`` block carries one attainment scalar per
    objective)."""
    from blit.history import (
        HistoryStore,
        render_slo_report,
        slo_report,
        window_seconds,
    )

    store = HistoryStore(args.store, create=False)
    doc = slo_report(store, window_s=window_seconds(args.window))
    body = json.dumps(doc) if args.json else render_slo_report(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write((json.dumps(doc) if args.json else body) + "\n")
    print(body)
    return 0


def _cmd_info(args: argparse.Namespace) -> int:
    path = args.file
    if path.endswith(".raw") or _looks_like_raw(path):
        from blit.io.guppi import open_raw

        raw = open_raw(path)
        hdr = dict(raw.header(0))
        hdr["_nblocks"] = raw.nblocks
        hdr["_files"] = getattr(raw, "paths", [raw.path])
        hdr["_time_span_s"] = raw.time_span_s()
    else:
        from blit.workers import get_header

        hdr = get_header(path)
    print(json.dumps(hdr, indent=2, default=str))
    return 0


def _looks_like_raw(path: str) -> bool:
    import os

    from blit.io.guppi import scan_files

    return not os.path.exists(path) and bool(scan_files(path))


# rawspec's standard product presets (stable contract, mirrored from
# blit.pipeline.PRODUCT_PRESETS — not imported here so `blit info` /
# `blit inventory` never pay the jax import just to build --product
# choices; tests/test_cli.py pins the two lists equal).
_PRODUCTS = ("0000", "0001", "0002")

# Commands that read files and reports only: they never import jax, so they
# skip the compile-cache set-up (which does).
_HOST_ONLY = frozenset((
    "inventory", "info", "top", "trace-view", "requests",
    "incidents", "incident", "slo-report",
))


def _add_monitor_flags(parser: argparse.ArgumentParser) -> None:
    """The shared ``--monitor-*`` flag set (ISSUE 11): commands that run
    long enough to watch grow a live publisher switch."""
    parser.add_argument("--monitor-spool", default=None,
                        help="spool live telemetry samples (JSON lines) "
                             "into this dir; `blit top --spool` tails it")
    parser.add_argument("--monitor-port", type=int, default=None,
                        help="serve /metrics, /healthz and /snapshot on "
                             "this port while running (0 = ephemeral; "
                             "the chosen port prints to stderr)")
    parser.add_argument("--monitor-interval", type=float, default=0.25,
                        help="publisher snapshot cadence in seconds")


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="blit", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    pr = sub.add_parser("reduce", help="RAW → filterbank product")
    pr.add_argument("raw", nargs="+",
                    help="RAW file, .NNNN.raw sequence stem, or member list")
    pr.add_argument("-o", "--output", required=True,
                    help="output product path (.fil streams; .h5 = FBH5); "
                         "with several products a STEM: product k lands "
                         "at <stem>.rawspec.000k.fil, each with its own "
                         ".partial and .manifest.json")
    pr.add_argument("--product", choices=list(_PRODUCTS),
                    help="rawspec product preset (else --nfft/--nint)")
    pr.add_argument("--nfft", type=_int_list, default=[1024],
                    help="fine channels per coarse channel; a comma list "
                         "(rawspec's -f 1048576,8,1024) makes several "
                         "products from ONE read and ONE upload")
    pr.add_argument("--nint", type=_int_list, default=[1],
                    help="spectra per output row; one per --nfft entry "
                         "(rawspec's -t 51,128,3072)")
    pr.add_argument("--stokes", default="I")
    pr.add_argument("--fqav", type=int, default=1,
                    help="on-device frequency averaging factor (must "
                         "divide every --nfft)")
    pr.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    pr.add_argument("--compression", default=None,
                    choices=["gzip", "bitshuffle"],
                    help="codec for .h5 (FBH5) output")
    pr.add_argument("--resume", action="store_true",
                    help="crash-resumable streaming (cursor sidecar; "
                         ".fil and .h5; refused with several products)")
    pr.set_defaults(fn=_cmd_reduce)

    ph = sub.add_parser(
        "search",
        help="RAW → .hits drift-rate search product (on-device dedoppler)",
    )
    ph.add_argument("raw", nargs="+",
                    help="RAW file, .NNNN.raw sequence stem, or member list")
    ph.add_argument("-o", "--output", required=True,
                    help="output .hits product path (JSON lines)")
    ph.add_argument("--product", choices=list(_PRODUCTS),
                    help="rawspec product preset for the underlying "
                         "filterbank (else --nfft/--nint)")
    ph.add_argument("--nfft", type=int, default=1024)
    ph.add_argument("--nint", type=int, default=1)
    ph.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ph.add_argument("--window-spectra", type=int, default=None,
                    help="spectra per drift transform (power of two; "
                         "default SiteConfig/BLIT_SEARCH_WINDOW)")
    ph.add_argument("--snr", type=float, default=None,
                    help="device-side SNR threshold "
                         "(default SiteConfig/BLIT_SEARCH_SNR)")
    ph.add_argument("--top-k", type=int, default=None,
                    help="hits kept per band per window "
                         "(default SiteConfig/BLIT_SEARCH_TOP_K)")
    ph.add_argument("--max-drift-bins", type=int, default=None,
                    help="clamp the searched drift range (bins/window; "
                         "default the full ±(window-1))")
    ph.add_argument("--kernel", default="auto",
                    choices=["auto", "reference", "pallas"],
                    help="drift-transform backend")
    ph.add_argument("--interpret", action="store_true",
                    help="run the pallas kernel in interpreter mode "
                         "(CPU smoke tests)")
    ph.add_argument("--resume", action="store_true",
                    help="crash-resumable search (cursor sidecar; resumes "
                         "at the last durable window boundary)")
    ph.set_defaults(fn=_cmd_search)

    pl = sub.add_parser(
        "stream",
        help="LIVE reduction: follow (or replay) a recording and write "
             "the product during the session (ISSUE 7)",
    )
    pl.add_argument("raw",
                    help="RAW file (or growing .NNNN.raw member) to "
                         "follow, or the completed recording to replay")
    pl.add_argument("-o", "--output", required=True,
                    help="product path: .fil / .h5, or .hits with "
                         "--search")
    pl.add_argument("--product", choices=list(_PRODUCTS),
                    help="rawspec product preset (else --nfft/--nint)")
    pl.add_argument("--nfft", type=int, default=1024)
    pl.add_argument("--nint", type=int, default=1)
    pl.add_argument("--stokes", default="I")
    pl.add_argument("--fqav", type=int, default=1)
    pl.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    pl.add_argument("--compression", default=None,
                    choices=["gzip", "bitshuffle"],
                    help="codec for .h5 (FBH5) output")
    pl.add_argument("--search", action="store_true",
                    help="write a .hits drift-search product instead of "
                         "a filterbank")
    pl.add_argument("--window-spectra", type=int, default=None,
                    help="search window (with --search; default "
                         "SiteConfig/BLIT_SEARCH_WINDOW)")
    pl.add_argument("--snr", type=float, default=None,
                    help="search SNR threshold (with --search)")
    pl.add_argument("--top-k", type=int, default=None,
                    help="hits kept per band per window (with --search)")
    pl.add_argument("--replay-rate", type=float, default=None,
                    help="replay a COMPLETED recording at this multiple "
                         "of wall-clock recording rate instead of "
                         "tailing a growing one (1.0 = real time)")
    pl.add_argument("--lateness", type=float, default=None,
                    help="watermark allowed-lateness budget in seconds "
                         "(default SiteConfig/BLIT_STREAM_LATENESS); "
                         "chunks missing past it are masked to zero "
                         "weight, stragglers dropped")
    pl.add_argument("--poll", type=float, default=None,
                    help="growing-file poll cadence in seconds "
                         "(default SiteConfig/BLIT_STREAM_POLL)")
    pl.add_argument("--idle-timeout", type=float, default=None,
                    help="end the tail after this long without file "
                         "growth (default SiteConfig/"
                         "BLIT_STREAM_IDLE_TIMEOUT: wait forever)")
    pl.add_argument("--done-file", default=None,
                    help="end-of-session marker path (default "
                         "<stem>.done)")
    pl.add_argument("--resume", action="store_true",
                    help="rejoinable consumer (ISSUE 12): persist a "
                         ".stream-cursor sidecar so a restarted "
                         "consumer re-attaches to the still-recording "
                         "session mid-file, byte-identical to a "
                         "never-restarted one")
    _add_monitor_flags(pl)
    pl.set_defaults(fn=_cmd_stream)

    pv = sub.add_parser(
        "session",
        help="run (or rejoin) a whole LIVE observing session from a "
             "spec file: one supervised stream consumer per recorder "
             "seat, packet capture included (ISSUE 18)",
    )
    pv.add_argument("spec",
                    help="session spec JSON: {\"seats\": [{name, out, "
                         "source, knobs...}], ...} — see `blit.stream."
                         "SessionSupervisor`")
    pv.add_argument("--work-dir", default=None,
                    help="session lease/spec scratch dir (default: the "
                         "spec's work_dir, else a fresh temp dir); "
                         "re-use it to rejoin after a crash")
    pv.add_argument("--lease-ttl", type=float, default=None,
                    help="per-seat heartbeat lease TTL in seconds (the "
                         "seat-death detection budget)")
    pv.add_argument("--poll", type=float, default=None,
                    help="seat supervisor watch cadence")
    pv.add_argument("--attempts", type=int, default=None,
                    help="per-seat recovery attempt budget")
    pv.add_argument("--json-out", default=None,
                    help="also write the session report JSON here")
    _add_monitor_flags(pv)
    pv.set_defaults(fn=_cmd_session)

    ps = sub.add_parser(
        "scan", help="whole (session, scan) → per-band products via the mesh"
    )
    ps.add_argument("root", help="data tree root (as `blit inventory`)")
    ps.add_argument("session", help="e.g. AGBT22B_999_01")
    ps.add_argument("scan", help="4-digit scan number, e.g. 0011")
    ps.add_argument("-o", "--output-dir", required=True,
                    help="band <b> lands at <dir>/band<b>.fil (.h5 with "
                         "--compression); of several products, product k "
                         "at <dir>/band<b>.rawspec.000k.fil")
    ps.add_argument("--file-re", default=None,
                    help=r"inventory filename filter (default \.raw$)")
    ps.add_argument("--nfft", type=_int_list, default=[1024],
                    help="fine channels per coarse channel; a comma list "
                         "(rawspec's -f 1048576,8,1024) makes several band "
                         "products from ONE read and ONE upload of every "
                         "mesh window (not with --resume, --compression, "
                         "--sharded, --pool or --search)")
    ps.add_argument("--nint", type=_int_list, default=[1],
                    help="spectra per output row; one per --nfft entry "
                         "(rawspec's -t 51,128,3072)")
    ps.add_argument("--stokes", default="I")
    ps.add_argument("--fqav", type=int, default=1,
                    help="per-chip frequency averaging before the stitch")
    ps.add_argument("--no-despike", action="store_true")
    ps.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="per-chip channelizer stage dtype (bfloat16 = "
                         "the official bench's lever; product stays f32)")
    ps.add_argument("--window-frames", type=int, default=None,
                    help="PFB frames per device window (bounds HBM, host "
                         "RSS, and per-window readback, whatever the scan "
                         "length and --nint: an integration longer than "
                         "the window is carried across windows on the "
                         "mesh, and a window below --nint is kept as "
                         "given).  Default: 8*2^20 samples' worth of "
                         "frames — i.e. max(8, 2^23/nfft) — in whole "
                         "integrations where one fits; 8 frames at nfft "
                         "2^20 do NOT fit four 16 GB chips (pass 2).  Of "
                         "several products: frames of the largest --nfft, "
                         "one grid for all")
    ps.add_argument("--max-frames", type=int, default=None,
                    help="reduce this many frames at most (of several "
                         "products: frames of the largest --nfft, and each "
                         "product is what its own command makes of the "
                         "recording cut to them)")
    ps.add_argument("--trace-logdir", default=None,
                    help="write a device-only JAX profiler trace of the "
                         "window loop (.xplane.pb; host and Python "
                         "tracers off) and, beside it, blit-spans.json: "
                         "every stage and wait of the loop as a span on "
                         "the same epoch clock")
    ps.add_argument("--compression", default=None,
                    choices=["gzip", "bitshuffle"],
                    help="write .h5 (FBH5) band products with this codec")
    ps.add_argument("--resume", action="store_true",
                    help="crash-resumable streaming (cursor sidecar per "
                         "band; .fil and .h5, incl. --compression "
                         "bitshuffle)")
    par = ps.add_mutually_exclusive_group()
    par.add_argument("--sharded", action="store_true",
                     help="the sharded reduction plane (ISSUE 9): "
                          "pipelined per-shard chunk feeds, async "
                          "addressable-shard readback and write-behind "
                          "sinks around the same one-program SPMD "
                          "reduction; byte-identical products (default: "
                          "SiteConfig/BLIT_MESH_SHARDED)")
    par.add_argument("--pool", action="store_true",
                     help="the pool-path fallback: one RawReducer per "
                          "(band, bank) player + main-process stitch — "
                          "the reference's shape, and the sharded "
                          "plane's byte-identity oracle")
    ps.add_argument("--search", action="store_true",
                    help="write per-player .hits drift-search products "
                         "instead of per-band filterbanks (each chip "
                         "searches its own frequency slice)")
    ps.add_argument("--window-spectra", type=int, default=None,
                    help="search window (with --search; default "
                         "SiteConfig/BLIT_SEARCH_WINDOW)")
    ps.add_argument("--snr", type=float, default=None,
                    help="search SNR threshold (with --search)")
    ps.add_argument("--top-k", type=int, default=None,
                    help="hits kept per band per window (with --search)")
    ps.add_argument("--max-drift-bins", type=int, default=None,
                    help="clamp the searched drift range (with --search)")
    ps.add_argument("--kernel", default="auto",
                    choices=["auto", "reference", "pallas"],
                    help="drift-transform backend (with --search)")
    ps.add_argument("--interpret", action="store_true",
                    help="pallas interpreter mode (CPU smoke; with "
                         "--search)")
    ps.set_defaults(fn=_cmd_scan)

    pi = sub.add_parser("inventory", help="crawl a data tree")
    pi.add_argument("root")
    pi.add_argument("--file-re", default=None)
    pi.add_argument("--session-re", default=None)
    pi.add_argument("--extra", default=None)
    pi.add_argument("--sequences", action="store_true",
                    help="group .NNNN.raw members into scan sequences")
    pi.set_defaults(fn=_cmd_inventory)

    pf = sub.add_parser("info", help="print a file's normalized header")
    pf.add_argument("file")
    pf.set_defaults(fn=_cmd_info)

    pfp = sub.add_parser(
        "fleet-peer",
        help="run ONE serving peer of the fleet: a ProductService "
             "over HTTP with lease heartbeats; SIGTERM drains "
             "gracefully (ISSUE 14)",
    )
    pfp.add_argument("--name", default="peer")
    pfp.add_argument("--port", type=int, default=0,
                     help="bind port (0 = ephemeral; see --port-file)")
    pfp.add_argument("--host", default="127.0.0.1",
                     help="bind address (default loopback; a multi-host "
                          "fleet binds 0.0.0.0, or this host's fabric "
                          "address, which is then advertised in .url)")
    pfp.add_argument("--port-file", default=None,
                     help="publish the bound port here (atomic write) "
                          "so a spawner can find an ephemeral bind")
    pfp.add_argument("--cache-dir", default=None,
                     help="disk cache tier root (None = RAM-only)")
    pfp.add_argument("--lease-dir", default=None,
                     help="shared heartbeat-lease dir the front door "
                          "watches")
    pfp.add_argument("--proc", type=int, default=0,
                     help="this peer's lease proc index")
    pfp.add_argument("--ram-bytes", type=int, default=256 << 20)
    pfp.add_argument("--concurrency", type=int, default=2)
    pfp.add_argument("--queue-depth", type=int, default=64)
    pfp.add_argument("--retry-seed", type=int, default=None,
                     help="seed the jittered Retry-After spread")
    pfp.add_argument("--beat-interval", type=float, default=0.5,
                     help="lease heartbeat cadence (keep well under "
                          "the fleet's peer TTL)")
    pfp.add_argument("--drain-timeout", type=float, default=30.0)
    pfp.add_argument("--catalog-root", default=None,
                     help="archive tree to catalog (ISSUE 19): serves "
                          "kind='catalog' asks and resolves "
                          "session=/scan= logical addressing locally")
    pfp.add_argument("--cold-dir", default=None,
                     help="cold storage tier root (ISSUE 19): disk "
                          "evictees demote here; cold hits are "
                          "CRC-verified and promoted back")
    pfp.add_argument("--disk-bytes", type=int, default=None,
                     help="hot disk tier capacity (None = unbounded; "
                          "a bound is what forces demotion)")
    pfp.add_argument("--standby", action="store_true",
                     help="run as an elastic STANDBY (ISSUE 17): "
                          "process up and lease beating but NOT in the "
                          "ring — the front door's controller admits "
                          "it after a warm handoff when the SLO pages")
    pfp.set_defaults(fn=_cmd_fleet_peer)

    pc = sub.add_parser(
        "chaos",
        help="run a seeded kill/hang schedule against a supervised "
             "scan or live stream and assert recovery + byte-identity "
             "(ISSUE 12)",
    )
    pc.add_argument("--workload", default="scan",
                    choices=["scan", "scan-search", "stream"],
                    help="what to break: a supervised sharded scan, a "
                         "supervised sharded search, or a live consumer")
    pc.add_argument("--fault", default="kill",
                    choices=["kill", "hang", "corrupt", "partition",
                             "resize", "reorder"],
                    help="the injected failure mode (corrupt = the "
                         "ISSUE 13 integrity leg: a bit-flipped "
                         "delivered RAW frame under a digest sidecar "
                         "must be masked, not propagated; partition = "
                         "--fleet only: SIGSTOP then SIGCONT, the peer "
                         "must be ejected AND rejoin; resize = --fleet "
                         "only: SIGKILL a serving peer DURING the "
                         "elastic warm handoff, the flip must still "
                         "complete with byte-identical answers, "
                         "ISSUE 17; reorder = stream workload only, "
                         "ISSUE 18: hold packets back at the "
                         "packet.recv point — the assembler must "
                         "repair the order with the product "
                         "byte-identical and no crash)")
    pc.add_argument("--fleet", action="store_true",
                    help="break a SERVING fleet instead (ISSUE 14): "
                         "SIGKILL/SIGSTOP a real fleet-peer subprocess "
                         "mid-replay and assert detection within the "
                         "lease TTL, re-route, byte-identity vs a "
                         "single-process oracle, and hit-rate recovery")
    pc.add_argument("--peers", type=int, default=3,
                    help="fleet peer subprocess count (--fleet)")
    pc.add_argument("--replicas", type=int, default=2,
                    help="ring owner-set size R (--fleet)")
    pc.add_argument("--fleet-requests", type=int, default=150,
                    help="zipfian requests replayed across the drill "
                         "(--fleet)")
    pc.add_argument("--fleet-distinct", type=int, default=6,
                    help="distinct products in the fleet mix (--fleet)")
    pc.add_argument("--after", type=int, default=2,
                    help="fire after this many windows/chunks")
    pc.add_argument("--hang-s", type=float, default=60.0,
                    help="hang duration (must exceed --lease-ttl)")
    pc.add_argument("--point", default=None,
                    help="injection point override (default mesh.window "
                         "for scans, stream.chunk for streams)")
    pc.add_argument("--victim", type=int, default=0,
                    help="pod process the schedule targets (scan modes)")
    pc.add_argument("--procs", type=int, default=2,
                    help="pod size of the first scan attempt")
    pc.add_argument("--devices-per-proc", type=int, default=None,
                    help="chips per simulated host (default: exactly "
                         "the mesh share, so losing a host forces the "
                         "pool fallback; set it to the WHOLE mesh to "
                         "exercise the reshaped-mesh resume instead)")
    pc.add_argument("--nband", type=int, default=2)
    pc.add_argument("--nbank", type=int, default=2)
    pc.add_argument("--nchan", type=int, default=2)
    pc.add_argument("--nfft", type=int, default=32)
    pc.add_argument("--nint", type=int, default=1)
    pc.add_argument("--window-frames", type=int, default=4)
    pc.add_argument("--window-spectra", type=int, default=4,
                    help="search window (scan-search workload)")
    pc.add_argument("--chunks", type=int, default=6,
                    help="how many windows/chunks the synthetic scan "
                         "spans")
    pc.add_argument("--replay-rate", type=float, default=200.0,
                    help="stream workload replay speed")
    pc.add_argument("--packets", action="store_true",
                    help="feed the stream workload through the PACKET "
                         "front end (ISSUE 18): a PacketReplaySource "
                         "with a seeded whole-block drop + "
                         "reorder/dup schedule — the drill then also "
                         "asserts the gapped block is MASKED "
                         "(byte-identical to the zero-filled oracle), "
                         "and a --fault kill rejoins through the "
                         "packet source")
    pc.add_argument("--packet-ntime", type=int, default=64,
                    help="time samples per DATA packet (--packets)")
    pc.add_argument("--lease-ttl", type=float, default=3.0,
                    help="heartbeat lease TTL (the detection budget)")
    pc.add_argument("--poll", type=float, default=0.1,
                    help="supervisor watch cadence")
    pc.add_argument("--attempts", type=int, default=3,
                    help="recovery attempt budget")
    pc.add_argument("--seed", type=int, default=0)
    pc.add_argument("--work-dir", default=None,
                    help="keep the drill's inputs/products here "
                         "(default: a fresh temp dir)")
    pc.add_argument("--json-out", default=None,
                    help="also write the drill report JSON here "
                         "(the CI chaos-smoke artifact)")
    pc.set_defaults(fn=_cmd_chaos)

    pk = sub.add_parser(
        "fsck",
        help="verify an archive tree (manifests + cache content "
             "digests), quarantining corruption; exit 1 when any is "
             "found (ISSUE 13)",
    )
    pk.add_argument("root", help="tree to walk: product dirs and/or a "
                                 "serve disk-cache dir")
    pk.add_argument("--repair", action="store_true",
                    help="re-derive quarantined cache entries from "
                         "their recorded recipes (the serve layer's "
                         "miss path) and retire quarantined corpses "
                         "superseded by a verified replacement")
    pk.add_argument("--no-quarantine", action="store_true",
                    help="report only; leave corrupt artifacts in "
                         "place (default: move them to a .quarantine/ "
                         "sibling so they stop being served/resumed)")
    pk.add_argument("--cold-dir", default=None,
                    help="ALSO walk this cold storage tier (ISSUE 19): "
                         "cold entries share the hot tier's sidecar "
                         "convention, so quarantine and --repair "
                         "re-derivation apply unchanged")
    pk.add_argument("--json-out", default=None,
                    help="also write the fsck report JSON here "
                         "(the CI drill artifact)")
    pk.set_defaults(fn=_cmd_fsck)

    pbf = sub.add_parser(
        "backfill",
        help="derive+publish every product of an archive root into a "
             "hot(+cold) cache — resumable (fsync-per-line ledger), "
             "budget-paced (ISSUE 19)",
    )
    pbf.add_argument("root", help="archive tree to walk (the catalog "
                                  "crawl's session/GUPPI layout)")
    pbf.add_argument("--cache-dir", required=True,
                     help="hot disk cache tier to publish into")
    pbf.add_argument("--cold-dir", default=None,
                     help="cold tier behind the hot cache (evictees "
                          "demote here)")
    pbf.add_argument("--ledger", default=None,
                     help="completion ledger path (default: "
                          "<cache-dir>/backfill.ledger.jsonl)")
    pbf.add_argument("--product", default=None,
                     help="rawspec preset (0000/0001/0002); otherwise "
                          "--nfft/--nint configure the reduction")
    pbf.add_argument("--nfft", type=int, default=1024)
    pbf.add_argument("--nint", type=int, default=1)
    pbf.add_argument("--ram-bytes", type=int, default=64 << 20)
    pbf.add_argument("--disk-bytes", type=int, default=None,
                     help="hot tier capacity (a bound forces demotion "
                          "into --cold-dir)")
    pbf.add_argument("--bytes-per-s", type=float, default=None,
                     help="pacing budget over input bytes (the "
                          "Scrubber debt discipline; 0 = unpaced; "
                          "default SiteConfig.backfill_bytes_per_s)")
    pbf.add_argument("--limit", type=int, default=None,
                     help="stop after this many products (CI drills)")
    pbf.add_argument("--json-out", default=None,
                     help="also write the backfill report JSON here")
    pbf.set_defaults(fn=_cmd_backfill)

    pt = sub.add_parser(
        "telemetry",
        help="fleet telemetry report (harvest / render / demo run)",
    )
    pt.add_argument("--from", dest="from_file", default=None,
                    help="render a saved fleet report JSON instead of "
                         "harvesting")
    pt.add_argument("--demo", action="store_true",
                    help="run a multi-worker reduce_to_file fan-out over "
                         "synthetic recordings and harvest the pool")
    pt.add_argument("--workers", type=int, default=2,
                    help="demo pool size")
    pt.add_argument("--backend", default="thread",
                    choices=["local", "thread", "process"],
                    help="demo pool backend")
    pt.add_argument("--nfft", type=int, default=256)
    pt.add_argument("--trace-out", default=None,
                    help="also export the run's spans as Chrome-trace-"
                         "event JSON (Perfetto-loadable)")
    pt.add_argument("--format", default="text",
                    choices=["text", "prom", "json"],
                    help="report rendering: human text, Prometheus "
                         "exposition, or raw JSON")
    pt.add_argument("--watch", type=float, default=None, metavar="N",
                    help="re-harvest and re-render every N seconds "
                         "(`blit top`'s refresh loop; Ctrl-C to stop)")
    pt.add_argument("--iterations", type=int, default=None,
                    help="with --watch: stop after this many frames "
                         "(tests/scripts; default: until interrupted)")
    pt.set_defaults(fn=_cmd_telemetry)

    po = sub.add_parser(
        "top",
        help="live terminal dashboard over a monitor spool dir or a "
             "publisher endpoint (ISSUE 11)",
    )
    src = po.add_mutually_exclusive_group(required=True)
    src.add_argument("--spool",
                     help="monitor spool dir to tail (one JSONL file "
                          "per process; merged into one fleet view)")
    src.add_argument("--url",
                     help="publisher base URL to poll "
                          "(e.g. http://127.0.0.1:8080)")
    po.add_argument("--interval", type=float, default=1.0,
                    help="refresh cadence in seconds")
    po.add_argument("--once", action="store_true",
                    help="render one frame (no ANSI clear) and exit")
    po.add_argument("--iterations", type=int, default=None,
                    help="stop after this many frames (tests/scripts)")
    po.add_argument("--history", default=None, metavar="DIR",
                    help="append per-series sparklines from this "
                         "durable history store (BLIT_HISTORY_DIR; "
                         "ISSUE 20)")
    po.add_argument("--history-buckets", type=int, default=32,
                    help="how many finest-tier buckets each sparkline "
                         "spans")
    po.set_defaults(fn=_cmd_top)

    pv = sub.add_parser(
        "trace-view",
        help="render a flight-recorder dump into an incident summary, "
             "or stitch a fleet's span batches into one trace "
             "(--fleet; ISSUE 15)",
    )
    pv.add_argument("dump", nargs="?", default=None,
                    help="flight-recorder JSON "
                         "(blit-flight-<host>-<pid>-<t>-<n>.json)")
    pv.add_argument("--events", type=int, default=40,
                    help="how many trailing ring events to show")
    pv.add_argument("--fleet", nargs="+", default=None, metavar="SRC",
                    help="stitch spans from these sources into one "
                         "trace view: monitor spool dirs / .jsonl "
                         "files, saved *.snapshot.json batches, flight "
                         "dumps, or live http://host:port /snapshot "
                         "endpoints")
    pv.add_argument("--out", default=None,
                    help="write the stitched spans as Chrome-trace-"
                         "event JSON (Perfetto-loadable)")
    pv.add_argument("--trace", default=None, metavar="ID",
                    help="print one trace's span tree")
    pv.add_argument("--exemplar", default=None, metavar="METRIC",
                    help="resolve METRIC's tail-bucket exemplar to its "
                         "trace id (and print that trace's tree when "
                         "the spans are in the sources)")
    pv.set_defaults(fn=_cmd_trace_view)

    pq = sub.add_parser(
        "requests",
        help="tail / filter / aggregate a per-request access-record "
             "spool (BLIT_REQUEST_LOG; ISSUE 15)",
    )
    pq.add_argument("spool",
                    help="request-log spool dir (requests-*.jsonl) or "
                         "one log file")
    pq.add_argument("--tail", type=int, default=None,
                    help="keep only the newest N records")
    pq.add_argument("--slow-ms", type=float, default=None,
                    help="keep records at least this slow")
    pq.add_argument("--status", default=None,
                    help="keep one status (ok/overloaded/deadline/"
                         "timeout/error, or an HTTP code like 503)")
    pq.add_argument("--client", default=None,
                    help="keep one client's records")
    pq.add_argument("--role", default=None,
                    choices=["door", "peer", "serve"],
                    help="keep one component role's records")
    pq.add_argument("--since", default=None, metavar="WHEN",
                    help="keep records at/after WHEN — an epoch, "
                         "'15m'/'2h'/'1d'-style ago-windows, or 'now' "
                         "(the `blit incident show` window grammar)")
    pq.add_argument("--until", default=None, metavar="WHEN",
                    help="keep records at/before WHEN (same grammar)")
    pq.add_argument("--aggregate", action="store_true",
                    help="print one summary (counts by status/tier, "
                         "p50/p99, slowest records w/ trace ids) "
                         "instead of the record table")
    pq.add_argument("--json", action="store_true",
                    help="machine output: one JSON record per line "
                         "(or the compact aggregate)")
    pq.set_defaults(fn=_cmd_requests)

    pin = sub.add_parser(
        "incidents",
        help="list the self-contained incident bundles under the "
             "incident dir (BLIT_INCIDENT_DIR; ISSUE 20)",
    )
    pin.add_argument("--dir", default=None,
                     help="incident bundle dir (default: "
                          "BLIT_INCIDENT_DIR)")
    pin.add_argument("--json", action="store_true",
                     help="one manifest JSON per line")
    pin.set_defaults(fn=_cmd_incidents)

    pic = sub.add_parser(
        "incident",
        help="render one incident bundle's merged cross-source "
             "timeline (ISSUE 20)",
    )
    pic.add_argument("action", choices=["show"],
                     help="'show': render the bundle")
    pic.add_argument("bundle",
                     help="bundle directory (from `blit incidents`)")
    pic.add_argument("--window", default=None, metavar="SPAN",
                     help="narrow the timeline to SPAN around the page "
                          "('15m', '2h', '1d' — the shared window "
                          "grammar)")
    pic.add_argument("--json", action="store_true",
                     help="dump the loaded bundle as one JSON doc")
    pic.set_defaults(fn=_cmd_incident)

    psr = sub.add_parser(
        "slo-report",
        help="attainment + error-budget spend per objective over "
             "day/week windows from a durable history store "
             "(ISSUE 20; --json for CI)",
    )
    psr.add_argument("store",
                     help="history store dir (BLIT_HISTORY_DIR)")
    psr.add_argument("--window", default="1d",
                     help="report window: '1d', '1w', seconds, ... "
                          "(the shared window grammar; default 1d)")
    psr.add_argument("--json", action="store_true",
                     help="machine output (the 'metrics' block carries "
                          "slo.<name>_attained for CI gating)")
    psr.add_argument("--out", default=None,
                     help="also write the report to this file "
                          "(CI artifact)")
    psr.set_defaults(fn=_cmd_slo_report)

    args = p.parse_args(argv)
    if args.command not in _HOST_ONLY:
        # Every command that compiles device programs shares one
        # persistent cache (the hi-res channelizer is not recompiled on
        # each invocation); spawned `blit` peers pass through here too.
        from blit.device import use_compile_cache

        use_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
