"""Live monitoring & SLO plane (blit/monitor.py; ISSUE 11).

Covers the tentpole end to end — interval publisher (delta sampling,
spool, HTTP endpoints), native Prometheus histogram exposition
(round-trip parse), the multi-window burn-rate SLO evaluator with its
breach actions (alert + forced flight dump + scheduler shed), the
deterministic SLO drill (BLIT_FAULTS latency injection → alert → dump →
measurable shed → recovery), dump rate-limiting under an alert storm,
and `blit top` / `blit telemetry --watch`."""

import json
import math
import os
import threading
import time
import urllib.request

import pytest

from blit import faults, monitor, observability
from blit.monitor import (
    BurnRateEvaluator,
    MetricsPublisher,
    SLObjective,
    bad_fraction,
    parse_prometheus,
)
from blit.observability import (
    FlightRecorder,
    HistogramStats,
    Timeline,
    hist_bucket_edges,
    merge_fleet,
    render_prometheus,
    telemetry_snapshot,
)


@pytest.fixture(autouse=True)
def clean_monitor(monkeypatch, tmp_path):
    """Hermetic monitoring env: no leaked publisher, faults, or flight
    dumps between tests."""
    for var in ("BLIT_MONITOR_SPOOL", "BLIT_MONITOR_PORT",
                "BLIT_MONITOR_INTERVAL", "BLIT_SLO_SERVE_WAIT_P99",
                "BLIT_SLO_STREAM_P99", "BLIT_SLO_INGEST_GBPS_FLOOR"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path / "flight"))
    (tmp_path / "flight").mkdir()
    faults.clear()
    faults.reset_counters()
    monitor.shutdown_publisher()
    yield
    monitor.shutdown_publisher()
    faults.clear()
    faults.reset_counters()


def _flight_dumps(tmp_path):
    return sorted((tmp_path / "flight").glob("blit-flight-*.json"))


def wait_for(pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.02)


# -- native Prometheus histograms (satellite 1) ------------------------------


class TestPrometheusNative:
    def _report_for(self, values, name="lat.s"):
        tl = Timeline()
        for v in values:
            tl.observe(name, v)
        snap = {"host": "h", "pid": 1, "worker": 0,
                "timeline": tl.state(), "faults": {}, "spans": []}
        return tl, merge_fleet([snap])

    def test_bucket_series_round_trip(self):
        """The pinned satellite contract: cumulative ``_bucket`` counts
        at the log2 edges reconstruct the EXACT HistogramStats bucket
        counts, and ``_sum``/``_count`` are exact."""
        values = [2e-6, 5e-6, 5e-6, 0.03, 0.5, 0.5, 12.0]
        tl, report = self._report_for(values)
        text = render_prometheus(report)
        samples = parse_prometheus(text)  # raises on unparseable lines
        edges = hist_bucket_edges()
        cum = {}
        for name, labels, value in samples:
            if (name == "blit_latency_seconds_bucket"
                    and labels["name"] == "lat.s"
                    and labels["le"] != "+Inf"):
                cum[float(labels["le"])] = int(value)
        # Cumulative counts must be non-decreasing in le and reconstruct
        # the per-bucket counts by differencing.
        les = sorted(cum)
        counts = {}
        prev = 0
        for le in les:
            assert cum[le] >= prev
            counts[le] = cum[le] - prev
            prev = cum[le]
        h = tl.hists["lat.s"]
        expect = {edges[i]: c for i, c in enumerate(h.counts) if c}
        got = {le: c for le, c in counts.items() if c}
        assert {round(math.log2(le / 1e-6)) for le in got} == \
            {round(math.log2(le / 1e-6)) for le in expect}
        assert sorted(got.values()) == sorted(expect.values())
        inf = [v for n, la, v in samples
               if n == "blit_latency_seconds_bucket"
               and la["name"] == "lat.s" and la["le"] == "+Inf"]
        assert inf == [float(len(values))]
        count = [v for n, la, v in samples
                 if n == "blit_latency_seconds_count"
                 and la["name"] == "lat.s"]
        assert count == [float(len(values))]
        total = [v for n, la, v in samples
                 if n == "blit_latency_seconds_sum"
                 and la["name"] == "lat.s"]
        assert total[0] == pytest.approx(sum(values))

    def test_help_and_type_lines(self):
        _, report = self._report_for([0.1])
        text = render_prometheus(report)
        assert "# TYPE blit_latency_seconds histogram" in text
        assert "# HELP blit_latency_seconds " in text
        assert "# TYPE blit_latency_quantile gauge" in text
        # The pre-existing families keep their heads (tests elsewhere
        # pin them too).
        assert "# TYPE blit_stage_seconds_total counter" in text

    def test_label_value_escaping_round_trips(self):
        nasty = 'we"ird\\name\nwith newline'
        _, report = self._report_for([0.25], name=nasty)
        text = render_prometheus(report)
        samples = parse_prometheus(text)
        names = {la.get("name") for n, la, _ in samples
                 if n == "blit_latency_seconds_count"}
        assert nasty in names

    def test_legacy_report_without_raw_state_still_renders(self):
        """A saved pre-ISSUE-11 fleet report (quantile block only) must
        render its quantile gauges without bucket series or a crash."""
        _, report = self._report_for([0.1])
        for e in report["hosts"].values():
            e.pop("hist_state")
        text = render_prometheus(report)
        samples = parse_prometheus(text)
        names = {n for n, _, _ in samples}
        assert "blit_latency_quantile" in names
        assert "blit_latency_seconds_bucket" not in names


# -- SLO math ----------------------------------------------------------------


class TestBadFraction:
    def test_counts_only_buckets_fully_above_threshold(self):
        h = HistogramStats()
        for v in (0.001, 0.001, 0.2, 0.9):
            h.observe(v)
        bad, total = bad_fraction(h, 0.05)
        assert (bad, total) == (2, 4)
        # Conservative: a sample in the bucket straddling the threshold
        # is not bad.
        bad, _ = bad_fraction(h, 0.15)  # 0.2 lands in (0.131, 0.262]
        assert bad == 1  # only 0.9's bucket lies fully above 0.15


class TestBurnRate:
    def _delta(self, values, metric="sched.wait_s"):
        d = Timeline()
        for v in values:
            d.observe(metric, v)
        return d

    def test_breach_fires_alert_and_dump_and_shed(self, tmp_path):
        rec = FlightRecorder(min_interval_s=60.0)
        ev = BurnRateEvaluator(
            [SLObjective(name="w", metric="sched.wait_s",
                         threshold=0.01, budget=0.01)],
            fast_window=3, slow_window=6, fast_burn=14.0, slow_burn=2.0,
            recorder=rec)
        shed_calls = []
        ev.add_shed_hook(shed_calls.append)
        alerts = ev.observe(self._delta([0.5] * 10), 1.0)
        assert len(alerts) == 1
        a = alerts[0]
        assert a["objective"] == "w" and a["burn_fast"] >= 14.0
        assert a.get("flight_dump") and os.path.exists(a["flight_dump"])
        assert shed_calls == [0.5]
        assert ev.breached() == ["w"]
        assert ev.report()["w"]["breached"] is True

    def test_within_budget_never_breaches(self):
        ev = BurnRateEvaluator(
            [SLObjective(name="w", metric="m", threshold=0.01,
                         budget=0.5)],
            fast_window=2, slow_window=4, fast_burn=2.0, slow_burn=2.0)
        for _ in range(10):
            assert ev.observe(self._delta([0.001, 0.001, 0.5], "m"),
                              1.0) == []
        assert ev.breached() == []

    def test_multi_window_confirmation_stops_flapping(self, tmp_path):
        """A one-round spike on a long good history trips the FAST
        window but not the SLOW one — no page (the multi-window rule)."""
        ev = BurnRateEvaluator(
            [SLObjective(name="w", metric="m", threshold=0.01,
                         budget=0.5)],
            fast_window=1, slow_window=8, fast_burn=2.0, slow_burn=2.0,
            recorder=FlightRecorder(min_interval_s=60.0))
        for _ in range(7):
            ev.observe(self._delta([0.001], "m"), 1.0)
        alerts = ev.observe(self._delta([0.5], "m"), 1.0)
        st = ev.report()["w"]
        assert st["burn_fast"] >= 2.0  # the spike alone torches fast
        assert st["burn_slow"] < 2.0   # 1 bad of 8 — budget holds
        assert alerts == []

    def test_throughput_floor_objective(self, tmp_path):
        rec = FlightRecorder(min_interval_s=60.0)
        ev = BurnRateEvaluator(
            [SLObjective(name="gbps", metric="ingest", kind="throughput",
                         threshold=1.0, budget=0.01)],
            fast_window=1, slow_window=2, fast_burn=2.0, slow_burn=2.0,
            recorder=rec)
        # Idle interval: the stage never ran — no observation, no breach.
        assert ev.observe(Timeline(), 1.0) == []
        slow = Timeline()
        with slow.stage("ingest", nbytes=1000):
            time.sleep(0.002)
        assert len(ev.observe(slow, 1.0)) == 1  # ~0.0005 GB/s < 1.0

    def test_recovery_releases_the_shed(self, tmp_path):
        ev = BurnRateEvaluator(
            [SLObjective(name="w", metric="m", threshold=0.01,
                         budget=0.01)],
            fast_window=2, slow_window=2, fast_burn=2.0, slow_burn=2.0,
            recorder=FlightRecorder(min_interval_s=60.0))
        shed_calls = []
        ev.add_shed_hook(shed_calls.append)
        ev.observe(self._delta([0.5] * 4, "m"), 1.0)
        assert shed_calls == [0.5]
        for _ in range(3):  # clean intervals: no samples at all
            ev.observe(Timeline(), 1.0)
        assert shed_calls == [0.5, 0.0]

    def test_alert_storm_rate_limits_dumps_and_stays_fast(
            self, tmp_path):
        """ISSUE 11 satellite: repeated breaches must not spam flight
        dumps (first breach forces one file; the rest ride the
        recorder's rate limit) or block the hot path."""
        rec = FlightRecorder(min_interval_s=3600.0)
        ev = BurnRateEvaluator(
            [SLObjective(name="w", metric="m", threshold=0.01,
                         budget=0.01)],
            fast_window=1, slow_window=2, fast_burn=2.0, slow_burn=2.0,
            recorder=rec)
        t0 = time.perf_counter()
        fired = 0
        for _ in range(50):
            fired += len(ev.observe(self._delta([0.5] * 3, "m"), 1.0))
        elapsed = time.perf_counter() - t0
        assert fired == 50  # every breach alerts...
        assert len(_flight_dumps(tmp_path)) == 1  # ...ONE dump file
        assert elapsed < 5.0  # and the loop never blocked
        assert len(ev.alerts) == 50


# -- the publisher -----------------------------------------------------------


class TestMetricsPublisher:
    def test_delta_sampling_and_spool(self, tmp_path):
        tl = Timeline()
        spool = tmp_path / "spool"
        pub = MetricsPublisher(interval_s=999.0, spool_dir=str(spool),
                               timeline=tl)
        with tl.stage("ingest", nbytes=1000):
            pass
        tl.observe("lat.s", 0.5)
        s1 = pub.tick()
        assert s1["delta"]["stages"]["ingest"]["bytes"] == 1000
        assert s1["delta"]["hists"]["lat.s"]["n"] == 1
        # Second interval: only the NEW work appears in the delta.
        tl.observe("lat.s", 0.5)
        tl.observe("lat.s", 0.5)
        s2 = pub.tick()
        assert "ingest" not in s2["delta"]["stages"]
        assert s2["delta"]["hists"]["lat.s"]["n"] == 2
        # The cumulative state still carries everything (fleet merges).
        assert s2["timeline"]["hists"]["lat.s"]["n"] == 3
        pub.close()
        report, samples = monitor.merge_spool(str(spool))
        assert len(samples) == 1  # newest line per process file
        assert samples[0]["seq"] == 1
        host = observability.hostname()
        assert report["hosts"][host]["stages"]["ingest"]["calls"] == 1

    def test_http_endpoints(self, tmp_path):
        tl = Timeline()
        with tl.stage("ingest", nbytes=512):
            pass
        tl.observe("lat.s", 0.1)
        with MetricsPublisher(interval_s=999.0, port=0,
                              timeline=tl) as pub:
            assert pub.port
            health = json.load(urllib.request.urlopen(
                pub.url + "/healthz", timeout=10))
            assert health["ok"] is True
            text = urllib.request.urlopen(
                pub.url + "/metrics", timeout=10).read().decode()
            samples = parse_prometheus(text)  # CI contract: parseable
            names = {n for n, _, _ in samples}
            assert "blit_stage_bytes_total" in names
            assert "blit_latency_seconds_bucket" in names
            snap = json.load(urllib.request.urlopen(
                pub.url + "/snapshot", timeout=10))
            assert snap["host"] == observability.hostname()
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(pub.url + "/nope", timeout=10)

    def test_background_loop_ticks(self, tmp_path):
        tl = Timeline()
        with tl.stage("ingest", nbytes=1):
            pass
        pub = MetricsPublisher(interval_s=0.05,
                               spool_dir=str(tmp_path / "s"),
                               timeline=tl).start()
        wait_for(lambda: pub.seq >= 2)
        pub.close()

    def test_watch_unwatch_refcount(self, tmp_path):
        pub = MetricsPublisher(interval_s=999.0)
        tl = Timeline()
        with tl.stage("x", nbytes=1, byte_free=True):
            pass
        pub.watch(tl)
        pub.watch(tl)  # nested publishing scopes
        pub.unwatch(tl)
        assert "x" in pub.merged_timeline().stages  # still watched once
        pub.unwatch(tl)
        assert "x" not in pub.merged_timeline().stages
        pub.close()

    def test_device_gauges_never_crash(self):
        import jax

        jax.devices()  # jax is imported + initialized in the suite
        tl = Timeline()
        monitor.device_gauges(tl)  # CPU: usually no memory_stats — ok

    def test_ensure_publisher_env_gated(self, monkeypatch, tmp_path):
        assert monitor.ensure_publisher() is None  # disabled: no-op
        monkeypatch.setenv("BLIT_MONITOR_SPOOL", str(tmp_path / "sp"))
        monkeypatch.setenv("BLIT_MONITOR_INTERVAL", "900")
        pub = monitor.ensure_publisher()
        assert pub is not None
        assert monitor.ensure_publisher() is pub  # singleton
        monitor.shutdown_publisher()

    def test_reduce_auto_publishes_when_enabled(
            self, monkeypatch, tmp_path):
        """Flipping BLIT_MONITOR_SPOOL makes a plain reduce_to_file
        spool at least one sample carrying its stage table — the
        ``_pump`` publishing hook (pipeline.py)."""
        from blit.pipeline import RawReducer
        from blit.testing import synth_raw

        spool = tmp_path / "spool"
        monkeypatch.setenv("BLIT_MONITOR_SPOOL", str(spool))
        monkeypatch.setenv("BLIT_MONITOR_INTERVAL", "900")
        raw = tmp_path / "r.raw"
        synth_raw(str(raw), nblocks=1, obsnchan=2,
                  ntime_per_block=(8 + 3) * 256)
        RawReducer(nfft=256).reduce_to_file(
            str(raw), str(tmp_path / "r.fil"))
        monitor.shutdown_publisher()
        report, samples = monitor.merge_spool(str(spool))
        assert samples, "no spool sample published"
        host = observability.hostname()
        assert report["hosts"][host]["stages"]["ingest"]["bytes"] > 0


# -- the SLO drill (acceptance) ----------------------------------------------


class TestSLODrill:
    def test_injected_latency_breaches_dumps_and_sheds(self, tmp_path):
        """Acceptance (ISSUE 11): a deterministic BLIT_FAULTS latency
        injection breaches a configured objective → burn-rate alert +
        forced flight dump + a MEASURABLE scheduler shed; recovery
        releases the shed."""
        from blit.serve.scheduler import Scheduler

        # The BLIT_FAULTS drill grammar, armed through the same parser
        # the env hook uses (docs/WORKFLOWS.md).
        faults.install_spec("sched.dispatch:delay:times=-1:delay=0.03")
        s = Scheduler(max_concurrency=1, queue_depth=64)
        jobs = [s.submit(lambda: None, client=f"c{i}") for i in range(6)]
        for j in jobs:
            j.result(timeout=30)
        pub = MetricsPublisher(
            interval_s=999.0, timeline=s.timeline,
            objectives=[SLObjective(name="serve-queue-wait",
                                    metric="sched.wait_s",
                                    threshold=0.01, budget=0.01)])
        pub.slo.attach_scheduler(s)
        base = 4
        s.max_concurrency = base
        sample = pub.tick()
        # Burn-rate alert...
        assert sample["slo"]["serve-queue-wait"]["breached"] is True
        assert sample["alerts"] and \
            sample["alerts"][0]["burn_fast"] >= 14.0
        # ...forced flight dump...
        dump = sample["alerts"][0].get("flight_dump")
        assert dump and os.path.exists(dump)
        doc = json.load(open(dump))
        assert "SLO breach: serve-queue-wait" in doc["reason"]
        # ...and a measurable scheduler shed.
        assert s.shed_level() == 0.5
        assert s.effective_budget() == base // 2
        # Recovery: the fault cleared, clean intervals drain the burn
        # windows, the shed releases.
        faults.clear()
        for _ in range(pub.slo.slow_window + 1):
            pub.tick()
        assert s.shed_level() == 0.0
        assert s.effective_budget() == base
        pub.close()

    def test_service_attaches_publisher_and_shed(
            self, monkeypatch, tmp_path):
        """ProductService wires the env-enabled publisher: its timeline
        is watched and SLO breaches shed ITS scheduler."""
        from blit.serve import ProductService

        monkeypatch.setenv("BLIT_MONITOR_SPOOL", str(tmp_path / "sp"))
        monkeypatch.setenv("BLIT_MONITOR_INTERVAL", "900")
        monkeypatch.setenv("BLIT_SLO_SERVE_WAIT_P99", "0.01")
        svc = ProductService()
        pub = monitor.ensure_publisher()
        assert pub is not None and svc._publisher is pub
        assert any(o.name == "serve-queue-wait"
                   for o in pub.slo.objectives)
        # A breach sheds the service's scheduler through the hook.
        delta = Timeline()
        for _ in range(50):
            delta.observe("sched.wait_s", 1.0)
        pub.slo.observe(delta, 1.0)
        assert svc.scheduler.shed_level() == 0.5
        assert svc.stats()["shed"] == 0.5
        svc.close()
        monitor.shutdown_publisher()


# -- blit top / telemetry --watch --------------------------------------------


class TestTopCli:
    def test_top_once_renders_spool(self, tmp_path, capsys):
        from blit.__main__ import main

        tl = Timeline()
        with tl.stage("ingest", nbytes=10 ** 6):
            pass
        tl.observe("out.chunk_latency_s", 0.01)
        spool = tmp_path / "spool"
        pub = MetricsPublisher(
            interval_s=999.0, spool_dir=str(spool), timeline=tl,
            objectives=[SLObjective(name="lat",
                                    metric="out.chunk_latency_s",
                                    threshold=10.0)])
        pub.tick()
        pub.close()
        assert main(["top", "--once", "--spool", str(spool)]) == 0
        out = capsys.readouterr().out
        assert "blit top" in out
        assert "ingest" in out
        assert "tail out.chunk_latency_s" in out
        assert "slo" in out and "lat" in out

    def test_top_once_renders_url(self, tmp_path, capsys):
        from blit.__main__ import main

        tl = Timeline()
        with tl.stage("ingest", nbytes=4096):
            pass
        with MetricsPublisher(interval_s=999.0, port=0,
                              timeline=tl) as pub:
            assert main(["top", "--once", "--url", pub.url]) == 0
        out = capsys.readouterr().out
        assert "ingest" in out

    def test_top_during_live_stream(self, tmp_path, capsys):
        """Acceptance (ISSUE 11): `blit top --once` renders a live
        snapshot DURING `blit stream` — the session publishes to a spool
        on an interval; top reads it mid-run."""
        from blit.__main__ import main
        from blit.testing import synth_raw

        raw = str(tmp_path / "live.raw")
        nfft = 256
        # A 3 s session at --replay-rate 1: TBIN sized to the recording.
        per_block = (8 + 3) * nfft
        synth_raw(raw, nblocks=4, obsnchan=2, ntime_per_block=per_block,
                  overlap=nfft, tone_chan=1, tbin=3.0 / (4 * per_block))
        spool = tmp_path / "spool"
        rc = {}

        def session():
            rc["rc"] = main([
                "stream", raw, "-o", str(tmp_path / "live.fil"),
                "--nfft", str(nfft), "--replay-rate", "1",
                "--monitor-spool", str(spool),
                "--monitor-interval", "0.05",
            ])

        t = threading.Thread(target=session, daemon=True)
        t.start()
        try:
            wait_for(lambda: monitor.read_spool(str(spool)), timeout=120)
            assert t.is_alive()
            assert main(["top", "--once", "--spool", str(spool)]) == 0
            out = capsys.readouterr().out
            assert "blit top" in out
        finally:
            t.join(timeout=300)
        assert rc.get("rc") == 0
        report = json.loads(capsys.readouterr().out.strip()
                            .splitlines()[-1])
        assert report["monitor"]["samples"] >= 1
        assert report["stream_chunks"] > 0

    def test_telemetry_watch_shares_refresh_loop(self, capsys):
        """Satellite: `blit telemetry --watch N` re-harvests and
        re-renders on `blit top`'s frame loop (ANSI clear per frame)."""
        from blit.__main__ import main

        with observability.process_timeline().stage("probe.watch",
                                                    nbytes=1):
            pass
        rc = main(["telemetry", "--watch", "0.01", "--iterations", "2"])
        assert rc == 0
        out = capsys.readouterr().out
        assert out.count(monitor.ANSI_CLEAR) == 2
        assert "probe.watch" in out


# -- packaging / config ------------------------------------------------------


class TestPlumbing:
    def test_monitor_is_a_lazy_blit_submodule(self):
        import blit

        assert blit.monitor.MetricsPublisher is MetricsPublisher

    def test_monitor_defaults_env_overrides(self, monkeypatch):
        from blit.config import monitor_defaults

        assert monitor_defaults()["enabled"] is False
        monkeypatch.setenv("BLIT_MONITOR_PORT", "0")
        d = monitor_defaults()
        assert d["enabled"] is True and d["port"] == 0
        monkeypatch.setenv("BLIT_MONITOR_PORT", "-1")
        assert monitor_defaults()["port"] is None

    def test_slo_defaults_env_and_extras(self, monkeypatch):
        from blit.config import DEFAULT, slo_defaults

        assert slo_defaults() == []
        monkeypatch.setenv("BLIT_SLO_STREAM_P99", "0.25")
        objs = slo_defaults()
        assert objs == [{"name": "stream-latency", "kind": "latency",
                         "metric": "stream.chunk_to_product_s",
                         "threshold": 0.25, "budget": 0.01}]
        cfg = DEFAULT.with_(slo_ingest_gbps_floor=0.5, slo_objectives=[
            {"name": "x", "kind": "latency", "metric": "m",
             "threshold": 1.0}])
        names = [o["name"] for o in slo_defaults(cfg)]
        assert names == ["stream-latency", "ingest-throughput", "x"]

    def test_publisher_snapshot_merges_into_fleet(self):
        """The publisher's wire snapshot folds its whole watch set into
        ONE merge_fleet entry — two reducer timelines from one process
        must not dedupe each other away."""
        # A quiet base timeline (not the process one — other tests'
        # stages must not leak into the byte assertions below).
        pub = MetricsPublisher(interval_s=999.0, timeline=Timeline())
        a, b = Timeline(), Timeline()
        with a.stage("ingest", nbytes=10):
            pass
        with b.stage("write", nbytes=20):
            pass
        pub.watch(a)
        pub.watch(b)
        report = pub.fleet_report()
        host = observability.hostname()
        stages = report["hosts"][host]["stages"]
        assert stages["ingest"]["bytes"] == 10
        assert stages["write"]["bytes"] == 20
        pub.close()

    def test_fleet_report_still_merges_snapshots(self):
        # The hist_state addition must not disturb merge_fleet's shape.
        report = merge_fleet([telemetry_snapshot()])
        host = observability.hostname()
        assert "hist_state" in report["hosts"][host]


class TestHonestHealthz:
    """/healthz degrades honestly (ISSUE 12 satellite): "degraded" with
    machine-readable reasons when breakers are not closed, a recovery
    supervisor is mid-flight, or an SLO is in fast-burn — and the JSON
    shape is pinned."""

    _SHAPE = {"ok", "status", "reasons", "t", "host", "pid", "seq",
              "interval_s", "watching", "breached", "alerts"}

    def test_clean_process_is_ok_with_pinned_shape(self):
        pub = MetricsPublisher(interval_s=60, spool_dir=None, port=None)
        try:
            h = pub.health()
            assert self._SHAPE <= set(h)
            assert h["status"] == "ok" and h["ok"] is True
            assert h["reasons"] == []
        finally:
            pub.close()

    def test_tripped_breaker_degrades(self, monkeypatch):
        from blit.parallel import pool as pool_mod
        from blit.parallel.pool import WorkerPool

        pub = MetricsPublisher(interval_s=60, spool_dir=None, port=None)
        wp = WorkerPool(["h0"], backend="local")
        try:
            br = wp.workers[0].breaker
            for _ in range(br.threshold):
                br.record_failure()
            monkeypatch.setattr(pool_mod, "_current", wp)
            h = pub.health()
            assert h["status"] == "degraded" and h["ok"] is False
            assert any(r.startswith("breaker-open:") for r in h["reasons"])
            br.record_success()
            h = pub.health()
            assert h["status"] == "ok"
        finally:
            wp.shutdown()
            pub.close()

    def test_slo_fast_burn_degrades(self):
        pub = MetricsPublisher(
            interval_s=60, spool_dir=None, port=None,
            objectives=[{"name": "lat", "metric": "m.s",
                         "threshold": 0.01, "budget": 0.01}])
        try:
            tl = Timeline()
            for _ in range(50):
                tl.observe("m.s", 1.0)  # every sample is bad
            for _ in range(6):
                pub.slo.observe(
                    monitor._delta_timeline(tl, None), 1.0)
            assert pub.slo.breached() == ["lat"]
            h = pub.health()
            assert h["status"] == "degraded"
            assert "slo-fast-burn:lat" in h["reasons"]
        finally:
            pub.close()

    def test_recover_hook_degrades(self):
        from blit.recover import _register, _unregister

        pub = MetricsPublisher(interval_s=60, spool_dir=None, port=None)
        try:
            key = _register({"kind": "reduce", "phase": "recovering",
                             "attempt": 2, "plan": "sharded"})
            try:
                h = pub.health()
                assert h["status"] == "degraded"
                assert any(r.startswith("recover:") for r in h["reasons"])
            finally:
                _unregister(key)
            assert pub.health()["status"] == "ok"
        finally:
            pub.close()
