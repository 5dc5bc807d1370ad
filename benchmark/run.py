"""benchmark/run.py — run ONE cell of BENCHMARK.json ONCE, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process (a chip belongs to one process at a time).  A *pass* is one
whole user command, the CLI's own ``blit.__main__.main(argv)`` called
here: seeded GUPPI RAW on RAM-backed scratch -> every finished product at
its final path, manifest published.  A pass makes a LIST of products (the
traffic file's ``products``; a file without the key is a list of one),
each of a KIND (``products/<kind>.py``; ``fil`` where the entry names
none), and everything below is done to each, by asking its kind.  The
pass's clock stops when this file's own ``os.fsync`` of the last product
has returned.

set-up   refuse without a TPU holding the cell's chips; fixed compile
         cache; empty tuning directory; ``make -B`` of blit/native; ask
         the machine what one file may hold and size the pass (``reduced``);
         write the recording from ``--seed``; start the plain reference's
         tasks in processes of their own (``refpool``); one warm-up pass
         beside them; join them, keep what they computed, check the
         warm-up's products.  All of it is ``setup_s``.
window   passes back to back; a pass starts only while the summed time of
         the passes so far is under ``--seconds``, and every started pass
         completes and counts.  ``reduce_rate`` is the RAW bytes one pass
         reads over the median pass's seconds (in a cell whose entry does
         not list it, the per-layer ``pass_rate``).  Checks run between
         passes, outside every timed interval, against what the reference
         kept: no reference arithmetic runs beside a pass.  A
         compile inside a pass makes the run incorrect.
traced   with ``--trace 1``, one more pass under ``jax.profiler``; the
         per-layer metrics come from it, from the window's rusage and
         from ``memory_stats``.

The harness holds no list of cells, traffic mixes, driver kinds, product
kinds or per-layer metrics: ``BENCHMARK.json`` names the first, the files
it names name the rest, and each is a file of its own (configs/,
traffic/, drivers/, products/, layer_metrics/, readers/, peaks.json).
What a run READS is GUPPI RAW from ``recording.py``, written here
(``write_inputs``): no command of blit reads anything else as a pass's
input yet.  A command's report may lack what another's has:
a pass whose JSON holds no stage table yields the device-trace and
host-clock readings only.
``--rehearse`` runs the same code at toy sizes on the CPU and prints no
metric; it proves nothing of the chip.  A run says what it took: ``[run]``
on standard error, ahead of the numbers compared, gives the seconds from
process start to the result line by phase.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
WATCH_POLL_S = 0.02
SMALL_PRODUCT_BYTES = 1 << 28   # read whole after every pass up to here
MEMORY_HEADROOM_SHARE = 0.3   # of the machine's memory
REFERENCE_SLACK_BYTES = 1 << 30   # kept over the guard's floor by the pool


def memory_facts() -> dict:
    """Host memory as this machine accounts it, for the log and the guard."""
    try:
        with open("/proc/meminfo") as f:
            info = {ln.split(":")[0]: int(ln.split()[1]) << 10 for ln in f}
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * os.sysconf("SC_PAGESIZE")
        return {"mem_total": info["MemTotal"], "mem_free": info["MemFree"],
                "mem_cached": info["Cached"], "mem_shmem": info["Shmem"],
                "rss": rss}
    except (OSError, KeyError, ValueError):
        return {}


def memory_guard(stop: threading.Event, seen: dict) -> None:
    """End the run in order (SIGTERM -> the clean-up in ``run``) before the
    machine's own limit ends it without one: a run that meets that limit
    loses the machine.  Free, not available: the chip tool's limit counts
    the page cache and ``/dev/shm``.  ``seen`` keeps the lowest reading
    and the floor, for the ``[run]`` line."""
    while not stop.wait(0.25):
        m = memory_facts()
        if not m:
            continue
        seen["floor"] = MEMORY_HEADROOM_SHARE * m["mem_total"]
        seen["lowest_mem_free"] = min(m["mem_free"],
                                      seen.get("lowest_mem_free", 1 << 62))
        if m["mem_free"] < seen["floor"]:
            print(f"benchmark refused: host memory nearly spent: {m}",
                  file=sys.stderr, flush=True)
            os.kill(os.getpid(), signal.SIGTERM)
            if not stop.wait(10):
                os._exit(3)
            return


class Refused(Exception):
    """The run cannot be made here; the message carries the numbers."""


def say(phase: str, **facts) -> None:
    print(f"[{phase}] " + json.dumps(facts, default=str), flush=True)


# -- what BENCHMARK.json names -------------------------------------------------

def load_cell(workload: str, rehearse: bool) -> dict:
    """The cell's entry, its configuration file, its traffic file, its
    driver module and the module of each product's kind, each found by the
    name ``BENCHMARK.json`` or one of those files gives."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(has {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if rehearse:  # toy sizes, named in the same files
        config["geometry"].update(config.get("rehearse", {}))
        traffic.update(traffic.get("rehearse", {}))
    # What a pass makes, in the order it is reported.  A traffic file
    # without the key makes one product: its top-level setting, at the
    # driver's own path.  Nothing below asks which it was.
    if "products" not in traffic:
        traffic["products"] = [{k: traffic[k]
                                for k in ("nfft", "nint", "tolerance")}
                               | {"name": "product"}]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    # A per-layer entry that lists no cell holds wherever the end-to-end
    # metric it should move is reported (a cell whose rate is too unsteady
    # to carry a bound reports it per layer, and the entries that move the
    # rate are not this cell's).
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moved = {m["name"] for m in e2e}
    return {
        "name": workload, "chips": cell["chips"], "config": config,
        "traffic": traffic,
        "driver": importlib.import_module("drivers." + traffic["driver"]),
        "kinds": product_kinds(traffic),
        "end_to_end": e2e,
        "per_layer": [m for m in bench["per_layer"]
                      if applies(m) and m["moves"] in moved],
    }


def product_kinds(traffic: dict) -> list:
    """The module of each product's kind, in the traffic file's order
    (``products/<kind>.py``; ``fil`` where the entry names none)."""
    return [importlib.import_module("products." + p.get("kind", "fil"))
            for p in traffic["products"]]


def plan_pass(cell: dict, out_cap: int) -> dict:
    """How long a pass is on this machine.  A cap on the size of one file
    caps the rows one product holds: that cuts duration (blocks), never
    width.  One read feeds every product, so all are cut to
    the same blocks: by the product with the most bytes among those whose
    size follows from the plan (a ragged kind never sizes the cut), which
    is the one the traffic file's ``align_rows`` (a chunk, a window) counts
    rows of, down to whole ``align_rows`` where it can.  What a row is,
    what it weighs and what holds none is each product's kind's to say."""
    g, t, kinds = cell["config"]["geometry"], cell["traffic"], cell["kinds"]
    ntap = t["ntap"]
    nslots = cell["config"]["banks"] * g["obsnchan"]

    def sized(blocks):
        return [k.sized(spec, blocks * g["block_samples"], nslots=nslots,
                        ntap=ntap)
                for k, spec in zip(kinds, t["products"])]

    def hold_rows(products, blocks, why=""):
        for k, p in zip(kinds, products):
            if said := k.nothing(p):
                raise Refused(f"{blocks} blocks of {g['block_samples']} "
                              f"samples{why} hold {said}")

    def fits(products):
        return all(k.bytes_at(p) <= out_cap
                   for k, p in zip(kinds, products))

    blocks, products = t["blocks"], sized(t["blocks"])
    hold_rows(products, blocks)
    for k, p in zip(kinds, products):
        if k.bytes_at(p, rows=1) > out_cap:
            raise Refused(
                f"one row of {cell['name']}'s product {p['name']!r} is "
                f"{p['row_bytes']} B and the largest file this machine "
                f"allows is {out_cap} B: this cell cannot run here")
    rows_wanted = [p["rows"] for p in products]
    fixed = [i for i, k in enumerate(kinds) if not k.RAGGED]
    b = max(fixed or range(len(kinds)),
            key=lambda i: products[i]["rows"] * products[i]["row_bytes"])
    kind, big = kinds[b], products[b]
    if not fits(products):
        rows = kind.rows_under(big, out_cap)
        if rows >= t["align_rows"]:
            rows -= rows % t["align_rows"]
        blocks = math.ceil(kind.samples_for(big, rows, ntap)
                           / g["block_samples"])
        while (products := sized(blocks))[b]["rows"] > rows \
                or not fits(products):
            blocks -= 1
        hold_rows(products, blocks, f" (all that a cap of {out_cap} B a "
                  f"file leaves of {t['blocks']})")
        big = products[b]
    # A cut warm-up (drivers' WARMUP_CUT) is one `align_rows` of that
    # product; of the others, what as many samples give.
    warm_rows = min(big["rows"], t["align_rows"])
    warm = [k.sized(spec, kind.samples_for(big, warm_rows, ntap),
                    nslots=nslots, ntap=ntap)
            for k, spec in zip(kinds, t["products"])]
    for p, want, w in zip(products, rows_wanted, warm):
        p.update(bytes=p["rows"] * p["row_bytes"], rows_wanted=want,
                 warm_rows=w["rows"])
    block_bytes = g["block_samples"] * g["obsnchan"] * g["npol"] * 2
    return {
        "blocks": blocks, "blocks_wanted": t["blocks"], "nslots": nslots,
        "raw_bytes": cell["config"]["banks"] * blocks * block_bytes,
        "products": products, "sized_by": big["name"],
        "warm_frames": kind.frames(big, warm_rows),
        "product_bytes": sum(p["bytes"] for p in products),
    }


def write_inputs(cell: dict, plan: dict, rawdir: str, raw_cap: int,
                 seed: int, *, whole_band: bool = False) -> dict:
    """The recordings of every bank, and the reference's input slices: one
    a checked coarse channel (a tone's, or one the entry lists under
    ``also``) and, with ``whole_band``, one of every other channel too
    (``checked`` false)."""
    import recording

    cfg, t, g = cell["config"], cell["traffic"], cell["config"]["geometry"]
    banks = cfg["banks"]
    # A tone's `fine_offset` counts channels of the finest product.
    finest = max(t["products"], key=lambda p: p["nfft"])
    tone_nfft = finest["nfft"]
    workers = max(1, min(t["pool_blocks"], 8,
                         ((os.cpu_count() or 2) - 1) // banks))

    def bank(k: int):
        hdr = recording.raw_header(
            g, obsfreq=cfg["first_bank_obsfreq_mhz"] + k * cfg["obsbw_mhz"],
            obsbw=cfg["obsbw_mhz"])
        tones = recording.tones_of(t["tones"][k])
        checked = {tone["chan"] for tone in tones} \
            | set(t["tones"][k].get("also", []))
        keep = sorted(range(g["obsnchan"]) if whole_band else checked)
        paths, kept = recording.write_recording(
            cell["driver"].stem(rawdir, k, t), g, hdr, plan["blocks"],
            raw_cap, seed=[seed, k], nfft=tone_nfft, tones=tones,
            pool_blocks=t["pool_blocks"], keep_chans=keep, workers=workers,
            nint=finest["nint"])
        slices = []
        for c, v in kept.items():
            mine = [tone for tone in tones if tone["chan"] == c]
            # where the channel holds ONE tone and it stands still, the
            # headers predict the fine channel it peaks in
            still = len(mine) == 1 and not mine[0].get("drift")
            slices.append({
                "volt": v, "chan": c, "slot": k * g["obsnchan"] + c,
                "raw_hdr": hdr, "tone_nfft": tone_nfft,
                "tone_fine_offset": mine[0]["fine_offset"] if still
                else None,
                "checked": c in checked})
        return paths, slices

    with ThreadPoolExecutor(max_workers=banks) as ex:
        done = list(ex.map(bank, range(banks)))
    return {"rawdir": rawdir, "raws": [p for p, _ in done],
            "slices": [s for _, ss in done for s in ss]}


# -- one pass ------------------------------------------------------------------

def product_paths(cell: dict, out: str) -> list:
    """Where a pass told to write ``out`` lands its products, in the traffic
    file's order: each entry's ``path`` pattern over ``{out}``, else the
    driver's ``product(out)``."""
    return [p["path"].format(out=out) if "path" in p
            else cell["driver"].product(out)
            for p in cell["traffic"]["products"]]


def run_cli(argv) -> list:
    """The CLI's own ``main()`` in this process; echoes what it printed and
    returns its JSON lines."""
    from blit.__main__ import main as blit_main

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = blit_main(argv)
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    for ln in lines:
        print("  blit> " + (ln if len(ln) < 1500 else ln[:1500] + " ..."),
              flush=True)
    if rc != 0:
        raise RuntimeError(f"blit {' '.join(argv)} exited {rc}")
    return [json.loads(ln) for ln in lines if ln.startswith("{")]


@contextlib.contextmanager
def compile_account():
    """What JAX's compiler did while the block ran (copied from
    chip_smoke.py): backend compile steps, their seconds, and the
    persistent cache's hits and misses."""
    import jax

    acct = {"backend_compiles": 0, "backend_compile_s": 0.0,
            "cache_hits": 0, "cache_misses": 0}

    def on_secs(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            acct["backend_compiles"] += 1
            acct["backend_compile_s"] += secs

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


def cpu_seconds() -> float:
    return sum(r.ru_utime + r.ru_stime
               for r in (resource.getrusage(resource.RUSAGE_SELF),
                         resource.getrusage(resource.RUSAGE_CHILDREN)))


def timed_pass(cell: dict, inputs: dict, outdir: str, tag: str, *,
               warm_frames=None) -> dict:
    """One pass: command entry -> this file's fsync of the last finished
    product.  A watcher thread (20 ms poll, from chip_smoke.py) notes, for
    every product, when its kind calls its first rows landed; the pass's
    ``first_product_s`` is the earliest, what a user tailing the directory
    sees."""
    drv, t = cell["driver"], cell["traffic"]
    out = drv.new_out(outdir, tag)
    products, kinds = product_paths(cell, out), cell["kinds"]
    first, done = {}, threading.Event()

    def watch(t0):
        while len(first) < len(products) and not done.wait(WATCH_POLL_S):
            for p, kind in zip(products, kinds):
                if p not in first and kind.landed(p):
                    first[p] = time.perf_counter() - t0

    res = {"tag": tag, "out": out, "products": products}
    cpu0 = cpu_seconds()
    t0 = time.perf_counter()
    watcher = threading.Thread(target=watch, args=(t0,), daemon=True)
    watcher.start()
    try:
        with compile_account() as res["compiles"]:
            res["cli"] = drv.run_pass(t, inputs, out, run_cli, warm_frames)
            res["cli_s"] = time.perf_counter() - t0
            for p in products:
                fd = os.open(p, os.O_RDONLY)
                try:
                    os.fsync(fd)
                finally:
                    os.close(fd)
            res["wall_s"] = time.perf_counter() - t0
    finally:
        done.set()
        watcher.join()
    res["cpu_s"] = cpu_seconds() - cpu0
    res["first_by_product"] = {
        spec["name"]: first.get(p, res["wall_s"])
        for spec, p in zip(t["products"], products)}
    res["first_product_s"] = min(res["first_by_product"].values())
    return res


def profiled_pass(cell: dict, inputs: dict, outdir: str):
    """One pass under ``jax.profiler``, device ops only -> (the pass, the
    ``.xplane.pb`` files written).  The host tracer (level 1 and up) makes
    a 1.5 s pass take 45 s and grow this process by over 10 GB on this
    machine (PERF.md section 3), so no host event or annotation is recorded
    and the traced window is the pass's host-clock time."""
    import jax

    trace_dir = os.path.join(outdir, "trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        tp = timed_pass(cell, inputs, outdir, "traced")
    finally:
        jax.profiler.stop_trace()
    return tp, sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))


def discard(res: dict) -> None:
    """Remove a pass's products (and their sidecars) once checked."""
    if os.path.isdir(res["out"]):
        shutil.rmtree(res["out"], ignore_errors=True)
    for p in res["products"]:
        for suffix in ("", ".manifest.json", ".partial"):
            with contextlib.suppress(FileNotFoundError):
                os.remove(p + suffix)


# -- the run -------------------------------------------------------------------

def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None, *, compared: dict) -> str:
    """The contract's last line: its five keys, ``breakdown`` on a traced
    run, and last of all ``compared``, which the contract asks for under a
    key of its own: each number that decided ``correct`` beside its limit,
    ``{name: [number, limit]}``."""
    doc = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    doc["compared"] = compared
    return json.dumps(doc)


def layer_metrics(cell: dict, evidence: dict) -> dict:
    """Each per-layer metric of the cell through its own file
    (``layer_metrics/<name>.json`` names the reader and its arguments).  A
    reader that finds nothing to read returns nothing and the metric is
    left out.  A file with ``same_as`` reads what the file it names reads
    (its reader, its arguments): the same quantity in the cells where it
    moves another end-to-end metric, under a name of its own."""
    def spec_of(name):
        with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
            return json.load(f)

    out = {}
    for m in cell["per_layer"]:
        spec = spec_of(m["name"])
        if "same_as" in spec:
            spec = spec_of(spec["same_as"])
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(spec.get("args", {}), evidence)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, memory_seen=None) -> int:
    import check
    import reference
    import refpool
    import scratch

    rehearse = args.rehearse
    cell = load_cell(args.workload, rehearse)
    cfg, t, drv = cell["config"], cell["traffic"], cell["driver"]
    kinds = cell["kinds"]
    if not os.path.exists(os.path.join(ROOT, "blit", "__main__.py")):
        raise Refused("the system under test (blit/) is not in this "
                      f"checkout: {ROOT}")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # Single-threaded Eigen: the CPU backend's threaded contractions
        # round in an order that depends on timing (two float32 variants
        # 1.8e-6 apart, about one toy pass in ten), which the byte-for-byte
        # comparison of passes would report.  Nothing of this reaches a chip.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}"
            + " --xla_cpu_multi_thread_eigen=false"
        ).strip()
    # A fixed path inside the checkout: the path is part of the cache's
    # key.  blit.device.use_compile_cache takes what the variable says.
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  os.path.join(ROOT, ".jax_cache"))
    state = tempfile.mkdtemp(prefix="blit-bench-state-")
    os.environ["BLIT_TUNE_DIR"] = os.path.join(state, "tune-empty")
    os.makedirs(os.environ["BLIT_TUNE_DIR"])
    made = [state]
    parts = {}
    pool = None
    try:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        if not rehearse and (device["platform"] != "tpu"
                             or device["count"] < cell["chips"]):
            raise Refused(f"{cell['name']} needs {cell['chips']} TPU "
                          f"chip(s); JAX reports {device}.  There is no CPU "
                          "continuation (--rehearse is the toy-size debug "
                          "run and prints no metric)")
        import jaxlib

        say("device", **device, jax=jax.__version__,
            jaxlib=jaxlib.__version__, python=sys.version.split()[0],
            compile_cache=cache,
            compile_cache_entries=len(os.listdir(cache))
            if os.path.isdir(cache) else 0,
            JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS"))
        if rehearse:
            say("rehearse", note="toy sizes on the CPU: this run proves "
                "nothing of the chip and prints no metric")
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)["by_device_kind"]
        if not rehearse and device["kind"] not in peaks:
            raise Refused(f"no peaks recorded for device kind "
                          f"{device['kind']!r}; add it to benchmark/"
                          "peaks.json with its source")
        say("peaks", **peaks.get(device["kind"], {}))
        parts["start_s"] = time.perf_counter() - T_START

        # blit/native is built on the machine that runs (-march=native).
        t0 = time.perf_counter()
        subprocess.run(["make", "-B", "-C", os.path.join(ROOT, "blit",
                                                          "native")],
                       check=True, stdout=subprocess.DEVNULL)
        parts["build_s"] = time.perf_counter() - t0

        # Size the pass from what this machine lets one file hold.
        scratch.raise_file_limit()
        raw_roots = ["/dev/shm", tempfile.gettempdir()]
        out_roots = [tempfile.gettempdir(), "/dev/shm"]
        say("host", **scratch.host_facts(sorted(set(raw_roots + out_roots))))
        unbounded = [k.bytes_at(p) for k, p in zip(
            kinds, plan_pass(cell, 1 << 62)["products"])]
        want_file = max(unbounded)
        outdir, out_cap = scratch.scratch_dir(
            out_roots, 2 * sum(unbounded) + (1 << 30), want_file)
        made.append(outdir)
        plan = plan_pass(cell, out_cap)
        raw_file = plan["raw_bytes"] // cfg["banks"] \
            + plan["blocks"] * scratch.RAW_HEADER_ROOM
        rawdir, raw_cap = scratch.scratch_dir(
            raw_roots, cfg["banks"] * raw_file + (1 << 30), raw_file)
        made.append(rawdir)
        say("plan", **plan, largest_product_file=out_cap,
            largest_raw_file=raw_cap, rawdir=rawdir, outdir=outdir,
            geometry=cfg["geometry"], banks=cfg["banks"])
        say("reduced", config=cfg["reduced"],
            blocks=[plan["blocks_wanted"], plan["blocks"]],
            rows={p["name"]: [p["rows_wanted"], p["rows"]]
                  for p in plan["products"]},
            bytes={p["name"]: p["bytes"] for p in plan["products"]},
            why="as the traffic file asks" if plan["blocks"]
            == plan["blocks_wanted"] else
            f"the largest file this machine allows is {out_cap} B; product "
            f"{plan['sized_by']!r} would be {want_file} B.  Duration is "
            "cut, width never")

        t0 = time.perf_counter()
        # A kind whose reference reads the WHOLE band (`ALL_CHANNELS`) is
        # given every channel's slice, any other the checked ones; asked
        # here and nowhere else.
        whole_band = any(k.ALL_CHANNELS for k in kinds)
        inputs = write_inputs(cell, plan, rawdir, raw_cap, args.seed,
                              whole_band=whole_band)
        checked = [s for s in inputs["slices"] if s["checked"]]
        slices_of = [inputs["slices"] if k.ALL_CHANNELS else checked
                     for k in kinds]
        parts["synth_s"] = time.perf_counter() - t0
        say("synth", files=[[os.path.basename(p) for p in ps]
                            for ps in inputs["raws"]],
            seconds=parts["synth_s"], pool_blocks=t["pool_blocks"])

        # The plain reference, once a run: a child process per task of a
        # product's kind (for `fil` one a checked channel), started here,
        # where nothing is timed, and
        # joined before `setup_s` is taken.  No child is alive beside a
        # measured or traced pass.
        m = memory_facts()
        pool = refpool.ReferencePool(
            list(zip(plan["products"], kinds, slices_of)), ntap=t["ntap"],
            despike=t["despike"], workdir=os.path.join(rawdir, "reference"),
            mem_free=lambda: memory_facts().get("mem_free", 1 << 62),
            mem_floor=MEMORY_HEADROOM_SHARE * m.get("mem_total", 0)
            + REFERENCE_SLACK_BYTES)
        pool.start()
        # With the whole band's streams out, as many bytes again as the
        # recording, the children are joined before a pass takes memory of
        # its own, not beside it.
        t0 = time.perf_counter()
        if whole_band:
            pool.wait()
        early = time.perf_counter() - t0

        problems = []   # what made the run incorrect
        bad = set()     # the passes with a product that was wrong
        worst = {}      # name compared -> the largest number read under it

        def note(numbers):
            for name, got in numbers.items():
                worst[name] = max(worst.get(name, got), got)

        def verify(res, rows, *, read_all, against_reference=False,
                   golden=None):
            """Every product of the pass: guarantees, then the plain
            reference and/or the verified product (``golden``, one entry
            a product).  ``rows`` names the plan's count to hold them to.
            Outside every timed interval."""
            res["facts"] = []
            for i, (path, p, kind, slices) in enumerate(zip(
                    res["products"], plan["products"], kinds, slices_of)):
                try:
                    facts = kind.guarantees(
                        path, p, p[rows],
                        read_all or p["bytes"] <= SMALL_PRODUCT_BYTES)
                    res["facts"].append(facts)
                    if against_reference:
                        said, numbers = kind.against_reference(
                            path, p, slices,
                            lambda slot, name=p["name"]: pool.rows(name, slot),
                            rows=p[rows], nslots=plan["nslots"])
                        say("check.reference", pass_=res["tag"],
                            product=p["name"], **said)
                        note(numbers)
                    if golden is not None:
                        kind.same_product(path, facts, golden[i], args.seed)
                except (check.Incorrect, refpool.ReferenceFailed) as e:
                    note(getattr(e, "compared", {}))
                    problems.append(f"{res['tag']}, product {p['name']}: {e}")
                    bad.add(res["tag"])
                    say("INCORRECT", pass_=res["tag"], product=p["name"],
                        problem=str(e))
            return res["tag"] not in bad

        def keep_as_golden(res):
            """The verified products' facts and seeded byte samples stay;
            the products themselves go (memory is what a run is short
            of)."""
            g = [{**facts, "sample": kind.sample(path, facts, args.seed)}
                 for path, facts, kind in zip(res["products"], res["facts"],
                                              kinds)]
            discard(res)
            return g

        # Warm-up: compiles or loads this cell's own programs, faults the
        # staging pool in.
        t0 = time.perf_counter()
        whole_warmup = not (drv.WARMUP_CUT and any(
            p["warm_rows"] < p["rows"] for p in plan["products"]))
        warm = timed_pass(cell, inputs, outdir, "warmup",
                          warm_frames=None if whole_warmup
                          else plan["warm_frames"])
        parts["warmup_pass_s"] = time.perf_counter() - t0
        plan_got = (warm["cli"].get("kernel_plan") or {})
        say("warmup", wall_s=warm["wall_s"],
            first_product_s=warm["first_product_s"],
            first_by_product=warm["first_by_product"], **warm["compiles"],
            kernel_plan=plan_got, expected_plan=t.get("expect_plan"),
            plan_as_expected=None if rehearse or "expect_plan" not in t
            else all(plan_got.get(k) == v
                     for k, v in t["expect_plan"].items()),
            whole_pass=whole_warmup)
        t0 = time.perf_counter()
        from blit.integrity import verify_product

        # blit's own whole-file verification of the warm-up products (size
        # and CRC against the manifest): the one full read of set-up.
        for path, p in zip(warm["products"], plan["products"]):
            _, said = verify_product(path)
            if said:
                problems.append(f"warmup, product {p['name']}: blit's own "
                                f"verify_product: {said}")
        t1 = time.perf_counter()
        joined = pool.wait()
        late = time.perf_counter() - t1
        parts["reference_wait_s"] = early + late
        say("reference", **joined, started_at_s=pool.started_at - T_START,
            joined_at_s=pool.joined_at - T_START,
            note="every child has ended; the rows are kept for the run")
        golden = None
        if whole_warmup:
            if verify(warm, "rows", read_all=False, against_reference=True):
                golden = keep_as_golden(warm)
        else:
            verify(warm, "warm_rows", read_all=False)
        discard(warm)
        parts["other_checks_s"] = time.perf_counter() - t0 - late
        try:
            from blit.pipeline import RawReducer

            tuning = {p["name"]: RawReducer(
                nfft=p["nfft"], nint=p["nint"]).tuning_provenance()
                for p in plan["products"]}
        except Exception as e:  # noqa: BLE001 — a label for the log only
            tuning = f"{type(e).__name__}: {e}"
        say("tuning", BLIT_TUNE_DIR=os.environ["BLIT_TUNE_DIR"],
            provenance=tuning)
        setup_s = time.perf_counter() - T_START
        say("setup", setup_s=setup_s, **parts)

        # The window.
        passes = []
        last = None   # the newest pass's product is read whole at the end
        while not passes or sum(p["wall_s"] for p in passes) \
                < args.seconds:
            if last is not None:
                discard(last)
            p = last = timed_pass(cell, inputs, outdir, f"pass{len(passes)}")
            passes.append(p)
            say("pass", n=len(passes) - 1, wall_s=p["wall_s"],
                cli_s=p["cli_s"], fsync_s=p["wall_s"] - p["cli_s"],
                first_product_s=p["first_product_s"],
                first_by_product=p["first_by_product"], cpu_s=p["cpu_s"],
                **p["compiles"], **memory_facts())
            if p["compiles"]["backend_compiles"]:
                problems.append(f"{p['tag']}: {p['compiles']} — a compile "
                                "inside the measured window")
            if golden is not None:
                verify(p, "rows", read_all=False, golden=golden)
            elif verify(p, "rows", read_all=False, against_reference=True):
                # The first whole products (the warm-up was cut): the plain
                # reference has checked them here, between passes.
                golden, last = keep_as_golden(p), None
        if last is not None:
            verify(last, "rows", read_all=True, golden=golden)
            discard(last)
        measured_s = sum(p["wall_s"] for p in passes)
        window_raw = plan["raw_bytes"] * len(passes)
        took = dict(parts, setup_s=setup_s, window_s=measured_s,
                    between_pass_checks_s=time.perf_counter() - T_START
                    - setup_s - measured_s)
        t0 = time.perf_counter()

        # The traced pass and the per-layer metrics.
        breakdown = None
        if args.trace:
            tp, found = profiled_pass(cell, inputs, outdir)
            # (against the kept rows where no pass has been verified)
            verify(tp, "rows", read_all=False, golden=golden,
                   against_reference=golden is None)
            discard(tp)
            from readers import xplane

            trace = xplane.reduce_trace(found[-1], tp["wall_s"]) if found \
                else None
            # A command's report may lack what another's has: without a
            # stage table the readers of stages find nothing to read.
            stages = tp["cli"].get("stages") or {}
            say("traced", **memory_facts(), wall_s=tp["wall_s"],
                first_product_s=tp["first_product_s"],
                first_by_product=tp["first_by_product"],
                stages=stages or "absent",
                trace_file_bytes=os.path.getsize(found[-1]) if found else 0,
                chips=trace and trace["chips"],
                busy_s_by_chip=trace and trace["busy_s_by_chip"],
                collective_s=trace and trace["collective_s"],
                note="the stage table is host-side busy/wait seconds per "
                "thread under the profiler; its 'device' row is a wait on "
                "a dispatch, not device busy time")
            if args.keep_trace and found:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(found[-1], os.path.join(
                    args.keep_trace, cell["name"] + ".xplane.pb"))
            if trace:
                device["busy_s"] = trace["busy_s"]
                device["window_s"] = trace["window_s"]

                def top(d):
                    return [[k, v] for k, v in sorted(
                        d.items(), key=lambda kv: -kv[1])[:10]]

                breakdown = {"device_ops": top(trace["per_op_s"]),
                             "idle_gaps": top(trace["idle_gaps_s"])}
                stage_s = {k: v["seconds"] for k, v in stages.items()
                           if isinstance(v, dict) and "seconds" in v
                           and k not in drv.WRAPPER_STAGES}
                say("overlap", pass_wall_s=tp["wall_s"], stage_s=stage_s,
                    largest_stage_s=max(stage_s.values(), default=None),
                    sum_of_stages_s=sum(stage_s.values()),
                    note="the stages run on threads of their own: with "
                    "perfect overlap the pass takes the largest, with none "
                    "their sum")

        took["traced_pass_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
                   for d in devs)
        device["memory_peak_bytes"] = peak
        if args.trace:
            metrics = layer_metrics(cell, {
                "stages": stages, "trace": trace,
                "traced_raw_bytes": plan["raw_bytes"],
                "traced_least_bytes": reference.least_bytes(
                    plan["raw_bytes"], sum(
                        k.least_bytes(p)
                        for k, p in zip(kinds, plan["products"]))),
                "window_raw_bytes": window_raw,
                "window_cpu_s": sum(p["cpu_s"] for p in passes),
                "window_first_product_s": [p["first_product_s"]
                                           for p in passes],
                "window_wall_s": [p["wall_s"] for p in passes],
                "memory_peak_bytes": peak, "device_kind": device["kind"],
                "peaks": peaks})
        else:
            own = {
                # Every pass reduces the same bytes: the median pass, so
                # that one stalled pass on a shared host (3 s in a 1.5 s
                # pass was seen) does not set the run's number.  The mean
                # over the window is on the [window] line.
                "reduce_rate": plan["raw_bytes"] / statistics.median(
                    p["wall_s"] for p in passes) / 1e9,
                "first_product_s": statistics.median(
                    p["first_product_s"] for p in passes),
                "setup_s": setup_s,
            }
            metrics = {m["name"]: {"value": own[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell["end_to_end"]}
        say("window", passes=len(passes), measured_s=measured_s,
            raw_bytes=window_raw, mean_rate_GBps=window_raw / measured_s / 1e9,
            problems=problems,
            wall_s_by_pass=[p["wall_s"] for p in passes])
        took["metrics_s"] = time.perf_counter() - t0
        # What the run leaves goes now, so that the seconds it takes are on
        # the `[run]` line (the `finally` below is for a run that ends
        # early).
        t0 = time.perf_counter()
        pool.close()
        for d in made:
            shutil.rmtree(d, ignore_errors=True)
        took["cleanup_s"] = time.perf_counter() - t0
        took["start_to_result_s"] = time.perf_counter() - T_START
        took["reference"] = {
            "tasks": joined["tasks"], "workers": joined["workers"],
            "pool_s": joined["pool_s"],
            "longest_child_s": max(joined["child_s"].values(), default=None),
            "last_child_joined_at_s": pool.joined_at - T_START}
        took.update(memory_seen or {})
        print("[run] " + json.dumps(took), file=sys.stderr, flush=True)
        # Every number that decided `correct`, beside its limit: the last
        # lines of stderr, and the last key of the result line.
        compiled = [p["compiles"]["backend_compiles"] for p in passes]
        compared = {
            **{name: [worst.get(name), limit]
               for k, p in zip(kinds, plan["products"])
               for name, limit in k.limits(p).items()},
            "wrong_products": [len(problems) - sum(map(bool, compiled)), 0],
            "compiles_in_window": [sum(compiled), 0]}
        for name, (got, limit) in compared.items():
            print(f"compared {name} {got} limit {limit}", file=sys.stderr,
                  flush=True)
        failed = len(bad - {"warmup", "traced"})
        if rehearse:
            print(json.dumps({"rehearsal": True, "platform":
                              device["platform"], "correct": not problems,
                              "attempted": len(passes), "failed": failed,
                              "metric_names": sorted(metrics),
                              "breakdown": breakdown is not None}),
                  flush=True)
            return 0 if not problems else 1
        print(result_line(not problems, len(passes), failed, metrics, device,
                          breakdown, compared=compared), flush=True)
        return 0
    finally:
        if pool is not None:
            pool.close()
        for d in made:
            shutil.rmtree(d, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU; prints no metric")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced pass's .xplane.pb to DIR")
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, ROOT]
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    guard_stop, memory_seen = threading.Event(), {}
    threading.Thread(target=memory_guard, args=(guard_stop, memory_seen),
                     daemon=True).start()
    import logging

    logging.basicConfig(stream=sys.stdout, format="  log> %(message)s")
    logging.getLogger("blit.pipeline").setLevel(logging.INFO)
    try:
        return run(args, memory_seen)
    except Refused as e:
        # No result line; the reason goes LAST on stderr, which is all a
        # sealed machine hands back.
        print(f"benchmark refused: {e}", file=sys.stderr, flush=True)
        return 2
    finally:
        guard_stop.set()


if __name__ == "__main__":
    sys.exit(main())
