"""Site configuration with BL@GBT defaults.

The reference scatters its site defaults across keyword arguments
(``root="/datax/dibas"``, ``extra="GUPPI"``, regexes — src/gbt.jl:48-53;
ssh options — src/gbt.jl:12-18).  Here they live in one dataclass, and every
API function accepts an optional ``config=`` override (SURVEY.md §5 "Config").
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Pattern, Tuple

from blit import naming


def datahosts(prefix: str = "") -> List[str]:
    """The 64 default BL@GBT host names ``blc00``..``blc77`` — 8 racks
    (bands) x 8 nodes (banks), optionally prefixed for ssh aliases.

    Reference: ``GBT.datahosts`` (src/gbt.jl:8-10).
    """
    return [f"{prefix}blc{band}{bank}" for band in range(8) for bank in range(8)]


# GBT BL backend constants (reference: README.md:17-27, src/gbtworkerfunctions.jl:134)
BAND_MHZ = 1500.0          # one band (8 banks) covers a 1500 MHz IF signal
BANK_MHZ = BAND_MHZ / 8    # each bank owns a contiguous 187.5 MHz slice
COARSE_PER_BANK = 64       # coarse channels recorded per bank (src/gbt.jl:101)
COARSE_MHZ = BANK_MHZ / COARSE_PER_BANK  # ~2.93 MHz coarse channel width


def nfpc_from_foff(foff_mhz: float) -> int:
    """Fine channels per coarse channel implied by a filterbank's channel
    width: ``round(187.5/64/|foff|)`` (reference: src/gbtworkerfunctions.jl:134).
    Returned as int; reference stores Int32 for FBH5 parity."""
    return int(round(COARSE_MHZ / abs(foff_mhz)))


@dataclass
class SiteConfig:
    """Everything site-specific, with BL@GBT defaults.

    Reference keyword defaults: src/gbt.jl:48-53 (inventory) and
    src/gbt.jl:12-18 (worker bring-up).
    """

    root: str = "/datax/dibas"
    extra: str = "GUPPI"
    session_re: Pattern = naming.SESSION_RE
    player_re: Pattern = naming.PLAYER_RE
    file_re: Pattern = naming.DEFAULT_FILE_RE
    # hosts=None derives the default 64-host list from host_prefix (the
    # reference's `prefix` ssh-alias kwarg, src/gbt.jl:14).
    hosts: Optional[List[str]] = None
    host_prefix: str = ""
    # Logical mesh shape (bands, banks) mapped onto the TPU device mesh.
    mesh_shape: Tuple[int, int] = (8, 8)
    # Worker-pool backend: "local" | "thread" | "process" (plugin boundary per
    # BASELINE.json: a backend flag swaps the worker pool implementation).
    backend: str = "thread"
    # Worker liveness deadlines (remote backend; SURVEY.md §5 "health-checked
    # worker pool"): per-call reply deadline and the agent-reuse ping
    # deadline.  The call deadline is OPT-IN (ADVICE r4): no finite default
    # sits safely above every legitimate single call — a whole-scan
    # reduce_raw can run hours, and a deadline that fires on healthy work
    # kills the agent mid-write.  None = block forever (the reference's
    # fetch behavior); sites that want kill-on-deadline liveness set it
    # above their largest sanctioned workload.  The reuse-time ping below
    # still bounds committing NEW work to a wedged agent either way.
    call_timeout: Optional[float] = None
    ping_timeout: Optional[float] = 30.0
    # Transient-failure recovery (blit/faults.py; ISSUE 2).  io_retries is
    # the TOTAL attempts for worker-side file I/O (guppi/fbh5/filterbank
    # reads — flaky NFS weather); call_retries is the number of
    # RE-dispatches of a WorkerPool remote call after AgentDied/CallTimeout
    # (each re-dispatch rides the pool's existing agent respawn).  Backoff
    # is jittered-exponential; retry_seed pins the jitter for
    # deterministic tests.
    io_retries: int = 3
    io_backoff_s: float = 0.05
    io_backoff_max_s: float = 2.0
    call_retries: int = 2
    call_backoff_s: float = 0.5
    call_backoff_max_s: float = 10.0
    retry_jitter: float = 0.5
    retry_seed: Optional[int] = None
    # Per-worker circuit breaker: breaker_threshold CONSECUTIVE remote-call
    # failures trip the host into a "degraded" state (calls fail fast with
    # RemoteError(etype="HostDegraded") instead of hammering it); after
    # breaker_cooldown_s one probe call may re-close the circuit.
    breaker_threshold: int = 3
    breaker_cooldown_s: float = 60.0
    # Product service layer (blit/serve; ISSUE 3).  cache_ram_bytes bounds
    # the in-RAM tier of the content-addressed product cache (LRU by byte
    # budget); cache_dir, when set, enables the disk tier (completed
    # FBH5 products indexed by reduction fingerprint).  serve_max_concurrency
    # is the scheduler's base concurrency budget (shrunk proportionally by
    # degraded hosts when a WorkerPool is attached) and serve_queue_depth
    # bounds each priority's queue — excess submissions are REJECTED with
    # Overloaded(retry_after_s) instead of growing the queue without bound.
    cache_ram_bytes: int = 1 << 30
    cache_dir: Optional[str] = None
    serve_max_concurrency: int = 4
    serve_queue_depth: int = 64
    # Search plane (blit/search; ISSUE 6).  search_window_spectra is the
    # Taylor-tree integration window (spectra per drift transform, power
    # of two — the drift resolution is one bin per window);
    # search_top_k bounds the hits extracted per band per window on
    # device; search_snr_threshold is the device-side SNR cut; and
    # search_max_drift_bins clamps the searched drift range (None = the
    # full ±(window-1) bins the tree computes).  Per-process overrides:
    # BLIT_SEARCH_WINDOW / BLIT_SEARCH_TOP_K / BLIT_SEARCH_SNR /
    # BLIT_SEARCH_MAX_DRIFT (see :func:`search_defaults`).
    search_window_spectra: int = 64
    search_top_k: int = 8
    search_snr_threshold: float = 10.0
    search_max_drift_bins: Optional[int] = None
    # Streaming ingest plane (blit/stream; ISSUE 7).  stream_lateness_s is
    # the watermark's allowed-lateness budget: a missing chunk is masked
    # (zero weight, the PR 2 antenna discipline) once the watermark —
    # newest arrival + this budget — passes it, and a chunk arriving
    # after its seat was masked is counted late and dropped.
    # stream_poll_s is the growing-file tailer's poll cadence;
    # stream_idle_timeout_s ends a tailed session when the recorder
    # neither grows the file nor writes the done marker for that long
    # (None = wait for the marker forever); stream_stall_timeout_s arms
    # the live feed's producer-progress watchdog (flight-dump + raise
    # instead of a silent wedge; None = unarmed).  Per-process overrides:
    # BLIT_STREAM_LATENESS / BLIT_STREAM_POLL / BLIT_STREAM_IDLE_TIMEOUT /
    # BLIT_STREAM_STALL_TIMEOUT (see :func:`stream_defaults`).
    stream_lateness_s: float = 2.0
    stream_poll_s: float = 0.05
    stream_idle_timeout_s: Optional[float] = None
    stream_stall_timeout_s: Optional[float] = None
    # Recorder packet front end (blit/stream/packet.py; ISSUE 18).
    # packet_host/packet_port is where a PacketSource listens (port 0 =
    # ephemeral, read it back from the source); packet_rcvbuf_bytes
    # sizes SO_RCVBUF — a recorder never pauses, so the kernel buffer
    # is the only back-pressure before packets shed as gaps;
    # packet_ntime is the framer's time samples per DATA packet (all
    # channels per packet: with nchan=64 npol=2 that is 8 KiB of
    # payload at the default — under the common 9000-byte jumbo MTU);
    # packet_horizon_blocks is the assembler's reorder horizon — a
    # partial block is abandoned (masked downstream) once packets
    # arrive that many blocks past it.  Per-process overrides:
    # BLIT_PACKET_HOST / BLIT_PACKET_PORT / BLIT_PACKET_RCVBUF /
    # BLIT_PACKET_NTIME / BLIT_PACKET_HORIZON (:func:`packet_defaults`).
    packet_host: str = "127.0.0.1"
    packet_port: int = 60000
    packet_rcvbuf_bytes: int = 32 << 20
    packet_ntime: int = 64
    packet_horizon_blocks: int = 2
    # Ingest staging (blit/hostmem.py; ISSUE 8): staging_pool_bytes is
    # a fixed byte cap on the process-wide staging-slab pool (env
    # BLIT_STAGING_BYTES wins; 0 disables pooling; None = the pool keeps
    # what one stretch of work held at its peak, blit/hostmem.py).
    staging_pool_bytes: Optional[int] = None
    # Sharded reduction plane (blit/parallel/sharded.py; ISSUE 9).
    # mesh_sharded makes `blit scan` default to the fully-threaded
    # sharded plane (pipelined per-shard feeds + async addressable-shard
    # readback) instead of the serial window loop; the pool path stays
    # the explicit fallback either way.  mesh_probe_windows is how many
    # leading windows of a sharded scan time the stitch collective
    # honestly (they serialize compute vs gather to sample
    # ``mesh.gather_s``; 0 disables the probe — steady-state windows
    # only account ICI bytes).  mesh_prefetch_depth / mesh_out_depth
    # size the feed rotation and readback/write-behind planes (None =
    # the ingest-plane defaults).  Per-process overrides:
    # BLIT_MESH_SHARDED / BLIT_MESH_PROBE / BLIT_MESH_PREFETCH /
    # BLIT_MESH_OUT_DEPTH (:func:`mesh_defaults`).
    mesh_sharded: bool = False
    mesh_probe_windows: int = 2
    mesh_prefetch_depth: Optional[int] = None
    mesh_out_depth: Optional[int] = None
    # Live monitoring & SLO plane (blit/monitor.py; ISSUE 11).  The
    # publisher is OFF unless a spool dir or an HTTP port is configured
    # (monitor_port=0 binds an ephemeral port; None = no endpoint) —
    # monitoring must cost nothing when nobody is watching.
    # monitor_interval_s is the snapshot cadence (delta-based: each
    # sample carries only the interval's stage/histogram increments plus
    # the cumulative state for fleet merges).  Per-process overrides:
    # BLIT_MONITOR_INTERVAL / BLIT_MONITOR_PORT / BLIT_MONITOR_SPOOL
    # (:func:`monitor_defaults`).
    monitor_interval_s: float = 1.0
    monitor_port: Optional[int] = None
    monitor_spool_dir: Optional[str] = None
    # Service-level objectives evaluated continuously over the live
    # histogram deltas (multi-window burn rate, blit/monitor.py).  Each
    # enabled objective pages when the error budget (slo_budget: the
    # allowed bad-sample fraction) burns faster than slo_fast_burn over
    # the last slo_fast_window samples AND faster than slo_slow_burn
    # over the last slo_slow_window samples (the SRE multi-window rule:
    # fast to catch an outage, slow to stop flapping).  None disables an
    # objective.  Per-process overrides: BLIT_SLO_SERVE_WAIT_P99 /
    # BLIT_SLO_STREAM_P99 / BLIT_SLO_INGEST_GBPS_FLOOR
    # (:func:`slo_defaults`); slo_objectives appends raw extra objective
    # dicts ({"name","kind","metric","threshold"[,"budget"]}).
    slo_serve_wait_p99_s: Optional[float] = None
    slo_stream_latency_p99_s: Optional[float] = None
    slo_ingest_gbps_floor: Optional[float] = None
    # Sustained-capture objective (ISSUE 18): ceiling on packet block
    # assembly p99 (first packet → complete block) — burning it means
    # the wire is reordering/dropping harder than the horizon absorbs.
    # Env: BLIT_SLO_PACKET_P99.
    slo_packet_assembly_p99_s: Optional[float] = None
    slo_budget: float = 0.01
    slo_fast_burn: float = 14.0
    slo_slow_burn: float = 2.0
    slo_fast_window: int = 5
    slo_slow_window: int = 30
    slo_objectives: Optional[List[Dict]] = None
    # Crash-recovery plane (blit/recover.py; ISSUE 12).  Supervised
    # sharded scans refresh a per-process heartbeat lease between
    # windows; a peer whose lease goes stale for recover_lease_ttl_s is
    # DETECTED (dead via SIGKILL, or wedged in a collective — either
    # way it stopped making window progress) and the supervisor aborts
    # the attempt, re-plans on the survivors, and resumes from the
    # cursors.  recover_poll_s is the supervisor's watch cadence;
    # recover_max_attempts bounds the abort→re-plan→resume loop;
    # recover_grace_s is the bring-up budget before a child's FIRST
    # lease beat (jax import + distributed init — lease staleness is
    # only judged after a process has beaten once).  Per-process
    # overrides: BLIT_RECOVER_LEASE_TTL / BLIT_RECOVER_POLL /
    # BLIT_RECOVER_MAX_ATTEMPTS / BLIT_RECOVER_GRACE
    # (:func:`recover_defaults`).
    recover_lease_ttl_s: float = 10.0
    recover_poll_s: float = 0.2
    recover_max_attempts: int = 3
    recover_grace_s: float = 120.0
    # Data-integrity plane (blit/integrity.py; ISSUE 13).  The
    # background scrubber is OFF unless scrub_interval_s is set —
    # verification between requests must be a deliberate choice; when
    # on, it verifies one disk-tier entry per interval and paces itself
    # so verified bytes/s stays under scrub_bytes_per_s (big entries
    # buy longer pauses — scrubbing samples the archive, it never
    # competes with a request burst).  Per-process overrides:
    # BLIT_SCRUB_INTERVAL / BLIT_SCRUB_BYTES_PER_S
    # (:func:`scrub_defaults`); BLIT_VERIFY_INGEST=0 /
    # BLIT_VERIFY_CACHE=0 are the verification escape hatches
    # (blit.integrity.ingest_verify_enabled / cache_verify_enabled).
    scrub_interval_s: Optional[float] = None
    scrub_bytes_per_s: float = 64e6
    # Fleet serve plane (blit/serve/fleet.py; ISSUE 14).  fleet_replicas
    # is the owner-set size R on the consistent-hash ring (owner + R-1
    # failover/hedge replicas); fleet_vnodes the virtual nodes per peer
    # (load-spread smoothness); fleet_peer_ttl_s the heartbeat-lease TTL
    # after which a silent peer is EJECTED from the ring (the detection
    # budget — the recover-plane lease discipline applied to serving
    # peers); fleet_poll_s the front door's lease-watch cadence;
    # fleet_health_poll_s how often the door refreshes each peer's
    # /healthz body for the aggregated fleet health document.
    # fleet_hedge_floor_s is the hedged-read delay before a peer has
    # enough latency history (fleet_hedge_min_n samples) for its live
    # p99 to drive the hedge; fleet_hot_hits is the per-fingerprint hit
    # count at which the door cache-warms the replicas (losing the
    # owner then degrades hit-rate, not correctness).  Per-process
    # overrides: BLIT_FLEET_* (:func:`fleet_defaults`).
    fleet_replicas: int = 2
    fleet_vnodes: int = 128
    fleet_peer_ttl_s: float = 3.0
    fleet_poll_s: float = 0.25
    fleet_health_poll_s: float = 1.0
    fleet_hedge_floor_s: float = 0.05
    fleet_hedge_min_n: int = 16
    fleet_hot_hits: int = 3
    # Hot-path data plane (blit/serve/http.py; ISSUE 16).  fleet_wire
    # selects the door→peer product encoding: "binary" is the
    # application/x-blit-product frame (no base64 tax, zero-copy
    # decode), "json" the legacy base64 wire — products are
    # bit-identical either way.  fleet_pool_conns bounds the per-peer
    # keep-alive connection pool; fleet_wire_deflate adds whole-frame
    # deflate when the client advertises it (off by default: float
    # spectra compress poorly and the CPU lands on the hot path).
    fleet_wire: str = "binary"
    fleet_pool_conns: int = 4
    fleet_wire_deflate: bool = False
    # Elastic fleet plane (blit/serve/elastic.py; ISSUE 17).  The
    # FleetController scales OUT (admits a lease-fresh standby after a
    # warm handoff bounded by elastic_warm_timeout_s, streaming up to
    # elastic_warm_hints hot recipes from the joiner's incoming key
    # range) when the burn-rate evaluator pages, and scales IN (drains
    # the coldest peer, bounded by elastic_drain_timeout_s, never below
    # elastic_min_peers) after elastic_idle_windows consecutive
    # observation ticks under elastic_idle_rps requests/s.  Any resize
    # arms a flap guard: no further action for elastic_hysteresis_s, so
    # a page→idle→page cycle cannot thrash membership.
    # elastic_poll_s is the controller's observation cadence.
    # Per-process overrides: BLIT_ELASTIC_* (:func:`elastic_defaults`).
    elastic_idle_rps: float = 0.1
    elastic_idle_windows: int = 6
    elastic_hysteresis_s: float = 60.0
    elastic_warm_timeout_s: float = 30.0
    elastic_warm_hints: int = 32
    elastic_min_peers: int = 1
    elastic_poll_s: float = 1.0
    elastic_drain_timeout_s: float = 30.0
    # Fleet request observability (blit/observability.py RequestLog +
    # histogram exemplars; ISSUE 15).  request_log_dir, when set, makes
    # every serving component (ProductService, fleet front door, peer
    # HTTP handler) append one bounded JSON-lines access record per
    # request under that dir (`blit requests` tails/aggregates the
    # spool); request_log_max_bytes/request_log_files bound each
    # component's log by size rotation.  exemplars keeps the
    # most-recent-trace-id-per-bucket exemplars on every histogram
    # (OpenMetrics exemplar syntax on /metrics; `blit trace-view
    # --exemplar` resolves a tail bucket to its trace).  Per-process
    # overrides: BLIT_REQUEST_LOG / BLIT_REQUEST_LOG_MAX_BYTES /
    # BLIT_REQUEST_LOG_FILES / BLIT_EXEMPLARS
    # (:func:`request_log_defaults`).
    request_log_dir: Optional[str] = None
    request_log_max_bytes: int = 8 << 20
    request_log_files: int = 4
    exemplars: bool = True
    # Archive plane (blit/serve/catalog.py + the cold cache tier;
    # ISSUE 19).  catalog_root, when set, enables the session/scan/
    # product catalog: an in-RAM index over the inventory crawl, held
    # by peers (served as ProductRequest(kind="catalog")) and by the
    # fleet front door (which resolves by-(session, scan) asks into
    # explicit member-path recipes BEFORE ring routing, so logical and
    # explicit asks dedupe onto the same owner).  catalog_rescan_s
    # bounds how often a lookup may re-stat the tree for the
    # mtime-invalidated incremental rescan; catalog_negative_ttl_s /
    # catalog_negative_max bound the negative-lookup cache so repeated
    # misses cannot hammer the crawl.  cache_cold_dir enables the COLD
    # storage tier behind the hot disk tier: content-addressed
    # (sharded by fingerprint prefix), filled by demotion of hot-tier
    # evictees, promoted back on hit under the PR-12 CRC manifest
    # rules.  backfill_bytes_per_s paces `blit backfill` derivations
    # (the Scrubber debt discipline) so a backfill never starves
    # foreground serving.  Per-process overrides: BLIT_CATALOG_ROOT /
    # BLIT_CATALOG_RESCAN / BLIT_CATALOG_NEG_TTL / BLIT_CATALOG_NEG_MAX
    # / BLIT_CACHE_COLD_DIR / BLIT_BACKFILL_BYTES_PER_S
    # (:func:`catalog_defaults` / :func:`archive_defaults`).
    catalog_root: Optional[str] = None
    catalog_rescan_s: float = 2.0
    catalog_negative_ttl_s: float = 30.0
    catalog_negative_max: int = 4096
    cache_cold_dir: Optional[str] = None
    backfill_bytes_per_s: float = 256e6
    # History & incident forensics plane (blit/history.py; ISSUE 20).
    # history_dir, when set, makes every MetricsPublisher tick fold its
    # interval delta into an RRD-style tiered ring store (raw →
    # minutes → hours buckets, fixed on-disk budget, oldest-bucket
    # overwrite) that `blit top --history`, `blit slo-report` and the
    # peer/door ``/history`` endpoints read.  The tier knobs fix each
    # ring's bucket width and slot count (disk budget ≈ Σ slots ×
    # history_slot_bytes, paid up front at creation).  history_anomaly
    # layers a rolling median/MAD baseline over every stored series —
    # a robust z-score past history_anomaly_z for
    # history_anomaly_consecutive ticks pages through the flight-dump
    # machinery (the creep static SLO thresholds miss);
    # history_anomaly_overrides maps metric name → per-metric z.
    # incident_dir enables one-artifact incident bundles on any page
    # (SLO breach, anomaly, fleet eject, recover abort), rate-limited
    # by incident_cooldown_s per incident kind, each bundling an
    # incident_window_s history window.  Per-process overrides:
    # BLIT_HISTORY_DIR / BLIT_HISTORY_RAW_S / BLIT_HISTORY_RAW_SLOTS /
    # BLIT_HISTORY_MID_S / BLIT_HISTORY_MID_SLOTS / BLIT_HISTORY_SLOW_S
    # / BLIT_HISTORY_SLOW_SLOTS / BLIT_HISTORY_SLOT_BYTES /
    # BLIT_HISTORY_ANOMALY / BLIT_HISTORY_ANOMALY_Z /
    # BLIT_HISTORY_ANOMALY_WINDOW / BLIT_HISTORY_ANOMALY_MIN_N /
    # BLIT_HISTORY_ANOMALY_CONSEC / BLIT_HISTORY_SENSITIVITY /
    # BLIT_INCIDENT_DIR / BLIT_INCIDENT_WINDOW / BLIT_INCIDENT_COOLDOWN
    # (:func:`history_defaults`).
    history_dir: Optional[str] = None
    history_raw_s: float = 10.0
    history_raw_slots: int = 720          # 2 h of raw buckets
    history_mid_s: float = 60.0
    history_mid_slots: int = 1440         # 1 day of minute buckets
    history_slow_s: float = 3600.0
    history_slow_slots: int = 336         # 2 weeks of hour buckets
    history_slot_bytes: int = 16384
    history_anomaly: bool = True
    history_anomaly_z: float = 6.0
    history_anomaly_window: int = 120
    history_anomaly_min_n: int = 30
    history_anomaly_consecutive: int = 3
    history_anomaly_overrides: Optional[Dict[str, float]] = None
    incident_dir: Optional[str] = None
    incident_window_s: float = 900.0
    incident_cooldown_s: float = 300.0

    def io_retry_policy(self):
        """The :class:`blit.faults.RetryPolicy` for worker-side file I/O —
        install it process-wide with ``faults.set_io_policy(...)``."""
        from blit import faults

        return faults.RetryPolicy(
            attempts=max(1, self.io_retries), base_s=self.io_backoff_s,
            max_s=self.io_backoff_max_s, jitter=self.retry_jitter,
            seed=self.retry_seed,
        )

    def call_retry_policy(self):
        """The :class:`blit.faults.RetryPolicy` for WorkerPool remote-call
        re-dispatch (``attempts = call_retries + 1``)."""
        from blit import faults

        return faults.RetryPolicy(
            attempts=max(0, self.call_retries) + 1,
            base_s=self.call_backoff_s, max_s=self.call_backoff_max_s,
            jitter=self.retry_jitter, seed=self.retry_seed,
        )

    def __post_init__(self):
        if self.hosts is None:
            self.hosts = datahosts(self.host_prefix)

    def with_(self, **kw) -> "SiteConfig":
        from dataclasses import replace

        if "host_prefix" in kw and "hosts" not in kw:
            kw["hosts"] = None  # re-derive from the new prefix in __post_init__
        return replace(self, **kw)


DEFAULT = SiteConfig()

# Default device-window budget in SAMPLES per chip for windowed mesh
# reductions: 8 PFB frames at the hi-res preset (nfft=2^20) — the
# production dispatch size the kernel pipeline was measured HBM-safe at
# (DESIGN.md §3) — scaled to whole frames at other nfft.  Lives here (not
# blit.parallel.scan) so the CLI can derive it without importing jax.
WINDOW_SAMPLES = 8 << 20


def search_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective search-plane knob set: ``config``'s values with
    per-process ``BLIT_SEARCH_*`` environment overrides applied — the
    faults-layer pattern (``BLIT_IO_RETRIES``) for the search knobs, so
    a deployment can retune a worker fleet without code changes.
    Resolved at reducer construction, not import, so tests and drills
    can flip the env per run."""
    max_drift = os.environ.get("BLIT_SEARCH_MAX_DRIFT")
    max_drift = int(max_drift) if max_drift else config.search_max_drift_bins
    if max_drift is not None and max_drift < 0:
        # Headers/cursors encode "no limit" as -1 (JSON has no None-safe
        # int); feeding that back in must mean unlimited again, not a
        # drift mask that silently rejects every row.
        max_drift = None
    return {
        "window_spectra": int(os.environ.get(
            "BLIT_SEARCH_WINDOW", config.search_window_spectra)),
        "top_k": int(os.environ.get(
            "BLIT_SEARCH_TOP_K", config.search_top_k)),
        "snr_threshold": float(os.environ.get(
            "BLIT_SEARCH_SNR", config.search_snr_threshold)),
        "max_drift_bins": max_drift,
    }


def stream_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective streaming-ingest knob set: ``config``'s values with
    per-process ``BLIT_STREAM_*`` environment overrides applied (the
    :func:`search_defaults` pattern) — resolved at stream construction,
    not import, so drills and deployments retune per run."""

    def opt_s(env: str, fallback: Optional[float]) -> Optional[float]:
        v = os.environ.get(env)
        if v is None:
            return fallback
        # "" / "none" / negative all mean "unarmed" (the -1 encoding of
        # the search knobs: JSON/env have no None-safe float).
        if not v or v.lower() == "none":
            return None
        f = float(v)
        return None if f < 0 else f

    return {
        "lateness_s": float(os.environ.get(
            "BLIT_STREAM_LATENESS", config.stream_lateness_s)),
        "poll_s": float(os.environ.get(
            "BLIT_STREAM_POLL", config.stream_poll_s)),
        "idle_timeout_s": opt_s(
            "BLIT_STREAM_IDLE_TIMEOUT", config.stream_idle_timeout_s),
        "stall_timeout_s": opt_s(
            "BLIT_STREAM_STALL_TIMEOUT", config.stream_stall_timeout_s),
    }


def packet_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective packet-capture knob set (ISSUE 18): ``config``'s
    values with per-process ``BLIT_PACKET_*`` environment overrides
    applied — the :func:`stream_defaults` pattern, resolved when a
    packet source/assembler is constructed so drills retune per run."""
    return {
        "host": os.environ.get("BLIT_PACKET_HOST", config.packet_host),
        "port": int(os.environ.get(
            "BLIT_PACKET_PORT", config.packet_port)),
        "rcvbuf_bytes": int(os.environ.get(
            "BLIT_PACKET_RCVBUF", config.packet_rcvbuf_bytes)),
        "ntime": int(os.environ.get(
            "BLIT_PACKET_NTIME", config.packet_ntime)),
        "horizon_blocks": int(os.environ.get(
            "BLIT_PACKET_HORIZON", config.packet_horizon_blocks)),
    }


def mesh_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective sharded-plane knob set (ISSUE 9): ``config``'s
    values with per-process ``BLIT_MESH_*`` environment overrides
    applied — the :func:`search_defaults` pattern, resolved at scan
    construction so tests and deployments retune per run."""

    def opt_int(env: str, fallback: Optional[int]) -> Optional[int]:
        v = os.environ.get(env)
        if v is None or v == "":
            return fallback
        i = int(v)
        return None if i < 0 else i

    sharded = os.environ.get("BLIT_MESH_SHARDED")
    return {
        "sharded": (
            config.mesh_sharded if sharded is None
            else sharded not in ("", "0", "false", "False")
        ),
        "probe_windows": int(os.environ.get(
            "BLIT_MESH_PROBE", config.mesh_probe_windows)),
        "prefetch_depth": opt_int(
            "BLIT_MESH_PREFETCH", config.mesh_prefetch_depth),
        "out_depth": opt_int(
            "BLIT_MESH_OUT_DEPTH", config.mesh_out_depth),
    }


def monitor_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective monitoring knob set (ISSUE 11): ``config``'s values
    with per-process ``BLIT_MONITOR_*`` environment overrides applied —
    the :func:`stream_defaults` pattern, resolved when a publisher (or
    the process-wide auto-publisher, :func:`blit.monitor.ensure_publisher`)
    is constructed.  ``enabled`` is derived: monitoring is on only when a
    spool dir or an HTTP port is configured."""
    port_env = os.environ.get("BLIT_MONITOR_PORT")
    port = (int(port_env) if port_env not in (None, "")
            else config.monitor_port)
    if port is not None and port < 0:
        port = None  # the -1 "disabled" encoding of the other planes
    spool = os.environ.get("BLIT_MONITOR_SPOOL")
    if spool is None:
        spool = config.monitor_spool_dir
    elif not spool:
        spool = None
    return {
        "interval_s": float(os.environ.get(
            "BLIT_MONITOR_INTERVAL", config.monitor_interval_s)),
        "port": port,
        "spool_dir": spool,
        # Span batches on each spool sample (ISSUE 15 tentpole #4):
        # every tick ships the spans finished since the last one, so a
        # spool is a stitchable trace source (`blit trace-view --fleet`).
        "spans": os.environ.get(
            "BLIT_MONITOR_SPANS", "").lower() not in ("", "0", "false",
                                                      "off"),
        "enabled": port is not None or spool is not None,
    }


def slo_defaults(config: SiteConfig = DEFAULT) -> List[Dict]:
    """The effective SLO objective list (ISSUE 11): the three built-in
    site objectives (serve queue-wait p99 ceiling, live chunk→product
    p99 ceiling, ingest GB/s floor), each enabled by its SiteConfig
    field or ``BLIT_SLO_*`` env override, plus any raw extras from
    ``config.slo_objectives``.  Returned as plain dicts —
    :class:`blit.monitor.SLObjective` adopts them — so declaring an
    objective never imports the monitoring plane."""

    def opt_f(env: str, fallback: Optional[float]) -> Optional[float]:
        v = os.environ.get(env)
        if v is None:
            return fallback
        if not v or v.lower() == "none":
            return None
        f = float(v)
        return None if f < 0 else f

    objs: List[Dict] = []
    wait = opt_f("BLIT_SLO_SERVE_WAIT_P99", config.slo_serve_wait_p99_s)
    if wait is not None:
        objs.append({"name": "serve-queue-wait", "kind": "latency",
                     "metric": "sched.wait_s", "threshold": wait,
                     "budget": config.slo_budget})
    lat = opt_f("BLIT_SLO_STREAM_P99", config.slo_stream_latency_p99_s)
    if lat is not None:
        objs.append({"name": "stream-latency", "kind": "latency",
                     "metric": "stream.chunk_to_product_s",
                     "threshold": lat, "budget": config.slo_budget})
    floor = opt_f("BLIT_SLO_INGEST_GBPS_FLOOR",
                  config.slo_ingest_gbps_floor)
    if floor is not None:
        objs.append({"name": "ingest-throughput", "kind": "throughput",
                     "metric": "ingest", "threshold": floor,
                     "budget": config.slo_budget})
    asm = opt_f("BLIT_SLO_PACKET_P99", config.slo_packet_assembly_p99_s)
    if asm is not None:
        objs.append({"name": "packet-assembly", "kind": "latency",
                     "metric": "packet.assembly_s", "threshold": asm,
                     "budget": config.slo_budget})
    objs.extend(config.slo_objectives or [])
    return objs


def recover_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective crash-recovery knob set (ISSUE 12): ``config``'s
    values with per-process ``BLIT_RECOVER_*`` environment overrides
    applied — the :func:`stream_defaults` pattern, resolved at
    supervisor construction so drills retune per run."""
    return {
        "lease_ttl_s": float(os.environ.get(
            "BLIT_RECOVER_LEASE_TTL", config.recover_lease_ttl_s)),
        "poll_s": float(os.environ.get(
            "BLIT_RECOVER_POLL", config.recover_poll_s)),
        "max_attempts": int(os.environ.get(
            "BLIT_RECOVER_MAX_ATTEMPTS", config.recover_max_attempts)),
        "grace_s": float(os.environ.get(
            "BLIT_RECOVER_GRACE", config.recover_grace_s)),
    }


def scrub_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective integrity-scrubber knob set (ISSUE 13): ``config``'s
    values with per-process ``BLIT_SCRUB_*`` environment overrides
    applied — the :func:`stream_defaults` pattern, resolved at service
    construction so drills and deployments retune per run.  ``enabled``
    is derived: scrubbing is on only when an interval is configured."""
    v = os.environ.get("BLIT_SCRUB_INTERVAL")
    if v is None:
        interval = config.scrub_interval_s
    elif not v or v.lower() == "none" or float(v) <= 0:
        # "", "none", 0 and negatives all DISABLE (the health_port=0
        # convention) — 0 must never mean a busy verification loop.
        interval = None
    else:
        interval = float(v)
    return {
        "interval_s": interval,
        "bytes_per_s": float(os.environ.get(
            "BLIT_SCRUB_BYTES_PER_S", config.scrub_bytes_per_s)),
        "enabled": interval is not None,
    }


def fleet_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective fleet-serve knob set (ISSUE 14): ``config``'s
    values with per-process ``BLIT_FLEET_*`` environment overrides
    applied — the :func:`stream_defaults` pattern, resolved at front
    door construction so drills and deployments retune per run."""
    return {
        "replicas": int(os.environ.get(
            "BLIT_FLEET_REPLICAS", config.fleet_replicas)),
        "vnodes": int(os.environ.get(
            "BLIT_FLEET_VNODES", config.fleet_vnodes)),
        "peer_ttl_s": float(os.environ.get(
            "BLIT_FLEET_PEER_TTL", config.fleet_peer_ttl_s)),
        "poll_s": float(os.environ.get(
            "BLIT_FLEET_POLL", config.fleet_poll_s)),
        "health_poll_s": float(os.environ.get(
            "BLIT_FLEET_HEALTH_POLL", config.fleet_health_poll_s)),
        "hedge_floor_s": float(os.environ.get(
            "BLIT_FLEET_HEDGE_FLOOR", config.fleet_hedge_floor_s)),
        "hedge_min_n": int(os.environ.get(
            "BLIT_FLEET_HEDGE_MIN_N", config.fleet_hedge_min_n)),
        "hot_hits": int(os.environ.get(
            "BLIT_FLEET_HOT_HITS", config.fleet_hot_hits)),
        "wire": str(os.environ.get(
            "BLIT_FLEET_WIRE", config.fleet_wire)).strip().lower(),
        "pool_conns": int(os.environ.get(
            "BLIT_FLEET_POOL_CONNS", config.fleet_pool_conns)),
        "wire_deflate": str(os.environ.get(
            "BLIT_FLEET_WIRE_DEFLATE",
            config.fleet_wire_deflate)) not in (
                "0", "false", "False"),
    }


def elastic_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective elastic-fleet knob set (ISSUE 17): ``config``'s
    values with per-process ``BLIT_ELASTIC_*`` environment overrides
    applied — the :func:`stream_defaults` pattern, resolved at
    FleetController construction so the diurnal bench and chaos drills
    retune per run."""
    return {
        "idle_rps": float(os.environ.get(
            "BLIT_ELASTIC_IDLE_RPS", config.elastic_idle_rps)),
        "idle_windows": int(os.environ.get(
            "BLIT_ELASTIC_IDLE_WINDOWS", config.elastic_idle_windows)),
        "hysteresis_s": float(os.environ.get(
            "BLIT_ELASTIC_HYSTERESIS", config.elastic_hysteresis_s)),
        "warm_timeout_s": float(os.environ.get(
            "BLIT_ELASTIC_WARM_TIMEOUT", config.elastic_warm_timeout_s)),
        "warm_hints": int(os.environ.get(
            "BLIT_ELASTIC_WARM_HINTS", config.elastic_warm_hints)),
        "min_peers": int(os.environ.get(
            "BLIT_ELASTIC_MIN_PEERS", config.elastic_min_peers)),
        "poll_s": float(os.environ.get(
            "BLIT_ELASTIC_POLL", config.elastic_poll_s)),
        "drain_timeout_s": float(os.environ.get(
            "BLIT_ELASTIC_DRAIN_TIMEOUT",
            config.elastic_drain_timeout_s)),
    }


def request_log_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective request-observability knob set (ISSUE 15):
    ``config``'s values with per-process ``BLIT_REQUEST_LOG*`` /
    ``BLIT_EXEMPLARS`` environment overrides applied — the
    :func:`stream_defaults` pattern, resolved when a serving component
    constructs its :class:`blit.observability.RequestLog`.  ``dir`` is
    None when request logging is disabled (the default — disabled must
    cost one dict lookup per request)."""
    d = os.environ.get("BLIT_REQUEST_LOG")
    if d is None:
        d = config.request_log_dir
    elif not d:
        d = None
    ex = os.environ.get("BLIT_EXEMPLARS")
    return {
        "dir": d,
        "max_bytes": int(os.environ.get(
            "BLIT_REQUEST_LOG_MAX_BYTES", config.request_log_max_bytes)),
        "files": int(os.environ.get(
            "BLIT_REQUEST_LOG_FILES", config.request_log_files)),
        "exemplars": (config.exemplars if ex is None
                      else ex.lower() not in ("", "0", "false", "off")),
    }


def catalog_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective archive-catalog knob set (ISSUE 19): ``config``'s
    values with per-process ``BLIT_CATALOG_*`` environment overrides
    applied — the :func:`stream_defaults` pattern, resolved when a
    :class:`blit.serve.catalog.CatalogIndex` is constructed so peers,
    the front door and drills retune per run.  ``enabled`` is derived:
    the catalog is on only when a root is configured."""
    root = os.environ.get("BLIT_CATALOG_ROOT")
    if root is None:
        root = config.catalog_root
    elif not root:
        root = None
    return {
        "root": root,
        "rescan_s": float(os.environ.get(
            "BLIT_CATALOG_RESCAN", config.catalog_rescan_s)),
        "negative_ttl_s": float(os.environ.get(
            "BLIT_CATALOG_NEG_TTL", config.catalog_negative_ttl_s)),
        "negative_max": int(os.environ.get(
            "BLIT_CATALOG_NEG_MAX", config.catalog_negative_max)),
        "enabled": root is not None,
    }


def archive_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective archive-storage knob set (ISSUE 19): the cold
    cache tier's root and the backfill pacing budget, with per-process
    ``BLIT_CACHE_COLD_DIR`` / ``BLIT_BACKFILL_BYTES_PER_S`` overrides
    — resolved at cache / backfill construction."""
    cold = os.environ.get("BLIT_CACHE_COLD_DIR")
    if cold is None:
        cold = config.cache_cold_dir
    elif not cold:
        cold = None
    v = os.environ.get("BLIT_BACKFILL_BYTES_PER_S")
    bps = float(v) if v else config.backfill_bytes_per_s
    if bps is not None and bps <= 0:
        bps = None  # unpaced (the scrubber's "no budget" encoding)
    return {"cold_dir": cold, "backfill_bytes_per_s": bps}


def history_defaults(config: SiteConfig = DEFAULT) -> Dict:
    """The effective history/forensics knob set (ISSUE 20): ``config``'s
    values with per-process ``BLIT_HISTORY_*`` / ``BLIT_INCIDENT_*``
    environment overrides applied — the :func:`stream_defaults` pattern,
    resolved when a :class:`blit.history.HistoryStore` /
    :class:`blit.history.AnomalyDetector` / bundler is constructed.
    ``enabled`` is derived: the store is on only when a dir is
    configured; ``anomaly`` is additionally gated by its kill switch
    (``BLIT_HISTORY_ANOMALY=0`` silences the baseline pager without
    touching the store).  ``BLIT_HISTORY_SENSITIVITY`` is a
    ``metric=z,metric=z`` list of per-metric z overrides folded over
    ``config.history_anomaly_overrides``."""

    def opt_dir(env: str, fallback: Optional[str]) -> Optional[str]:
        v = os.environ.get(env)
        if v is None:
            return fallback
        return v or None

    d = opt_dir("BLIT_HISTORY_DIR", config.history_dir)
    inc = opt_dir("BLIT_INCIDENT_DIR", config.incident_dir)
    an = os.environ.get("BLIT_HISTORY_ANOMALY")
    anomaly = (config.history_anomaly if an is None
               else an.lower() not in ("", "0", "false", "off"))
    overrides: Dict[str, float] = dict(config.history_anomaly_overrides
                                       or {})
    for part in os.environ.get("BLIT_HISTORY_SENSITIVITY", "").split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        k, _, v = part.partition("=")
        try:
            overrides[k.strip()] = float(v)
        except ValueError:
            continue
    return {
        "dir": d,
        "raw_s": float(os.environ.get(
            "BLIT_HISTORY_RAW_S", config.history_raw_s)),
        "raw_slots": int(os.environ.get(
            "BLIT_HISTORY_RAW_SLOTS", config.history_raw_slots)),
        "mid_s": float(os.environ.get(
            "BLIT_HISTORY_MID_S", config.history_mid_s)),
        "mid_slots": int(os.environ.get(
            "BLIT_HISTORY_MID_SLOTS", config.history_mid_slots)),
        "slow_s": float(os.environ.get(
            "BLIT_HISTORY_SLOW_S", config.history_slow_s)),
        "slow_slots": int(os.environ.get(
            "BLIT_HISTORY_SLOW_SLOTS", config.history_slow_slots)),
        "slot_bytes": int(os.environ.get(
            "BLIT_HISTORY_SLOT_BYTES", config.history_slot_bytes)),
        "anomaly": anomaly,
        "anomaly_z": float(os.environ.get(
            "BLIT_HISTORY_ANOMALY_Z", config.history_anomaly_z)),
        "anomaly_window": int(os.environ.get(
            "BLIT_HISTORY_ANOMALY_WINDOW", config.history_anomaly_window)),
        "anomaly_min_n": int(os.environ.get(
            "BLIT_HISTORY_ANOMALY_MIN_N", config.history_anomaly_min_n)),
        "anomaly_consecutive": int(os.environ.get(
            "BLIT_HISTORY_ANOMALY_CONSEC",
            config.history_anomaly_consecutive)),
        "anomaly_overrides": overrides,
        "incident_dir": inc,
        "incident_window_s": float(os.environ.get(
            "BLIT_INCIDENT_WINDOW", config.incident_window_s)),
        "incident_cooldown_s": float(os.environ.get(
            "BLIT_INCIDENT_COOLDOWN", config.incident_cooldown_s)),
        "enabled": d is not None,
    }


def default_window_frames(nfft: int) -> int:
    """HBM-bounded default ``window_frames`` for a given ``nfft``: the
    scan's device windows hold ~``WINDOW_SAMPLES`` samples per chip, with
    a floor of 8 whole frames.  ``nint`` rounds it to whole integrations
    only where one fits a dispatch
    (:func:`blit.parallel.scan.scan_window_frames`); it never grows the
    window past this bound — a longer integration is carried across
    windows."""
    return max(8, WINDOW_SAMPLES // nfft)


def _compile(p) -> Pattern:
    """Accept str or compiled pattern for all regex-valued options."""
    return re.compile(p) if isinstance(p, str) else p
