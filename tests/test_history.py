"""Fleet history & incident forensics plane (blit/history.py; ISSUE 20).

Covers the tentpole end to end — the tiered ring store (downsampling
exactness across tier boundaries, fixed disk budget under a simulated
week, restart re-adoption, concurrent read-while-write, fleet merge of
two peers' stores), the median/MAD anomaly baseline (fires on an
injected step, quiet on a seeded steady baseline, kill switch +
per-metric sensitivity), incident bundles (self-contained: the
exemplar trace id resolves into the bundle's own request records),
`blit slo-report` against a hand-computed oracle (and its JSON's flat
`metrics` block), the shared window grammar, the wall-clock anchor
satellite, and the torn-tail drill (a writer SIGKILLed mid-line heals
and counts on every monitor-path reader)."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from blit import history as H
from blit import monitor, observability
from blit.config import SiteConfig, history_defaults
from blit.history import (
    AnomalyDetector,
    HistoryStore,
    IncidentBundler,
    TierSpec,
    bucket_point,
    list_incidents,
    load_incident,
    merge_buckets,
    parse_when,
    read_ring,
    render_incident,
    render_incidents,
    render_slo_report,
    slo_report,
    sparkline,
    window_seconds,
)
from blit.monitor import MetricsPublisher, SLObjective
from blit.observability import HistogramStats, Timeline, wall_anchor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T0 = 1_700_000_000.0  # aligned-enough epoch for bucket math


@pytest.fixture(autouse=True)
def clean_history(monkeypatch, tmp_path):
    """Hermetic history env: no leaked store/bundler/publisher state."""
    for var in ("BLIT_HISTORY_DIR", "BLIT_HISTORY_RAW_S",
                "BLIT_HISTORY_ANOMALY", "BLIT_HISTORY_SENSITIVITY",
                "BLIT_INCIDENT_DIR", "BLIT_REQUEST_LOG",
                "BLIT_MONITOR_SPOOL", "BLIT_MONITOR_PORT"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path / "flight"))
    (tmp_path / "flight").mkdir(exist_ok=True)
    H.reset_bundler()
    monitor.shutdown_publisher()
    yield
    H.reset_bundler()
    monitor.shutdown_publisher()


class FakeClock:
    def __init__(self, t=T0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _tick_delta(calls=2, nbytes=1 << 20, seconds=0.01, lat=0.02):
    """One synthetic per-tick Timeline delta: a stage with bytes, a
    byte-free counter, and a latency histogram sample."""
    tl = Timeline()
    s = tl.stages["ingest.chunks"]
    s.calls += calls
    s.seconds += seconds
    s.bytes += nbytes
    tl.count("ingest.retries", 1)
    tl.observe("serve.request_s", lat)
    return tl


def _small_tiers():
    return [TierSpec("raw", 1.0, 32), TierSpec("mid", 8.0, 32),
            TierSpec("slow", 64.0, 8)]


# -- window grammar ----------------------------------------------------------


class TestWindowGrammar:
    def test_window_seconds(self):
        assert window_seconds("90") == 90.0
        assert window_seconds("90s") == 90.0
        assert window_seconds("15m") == 900.0
        assert window_seconds("2h") == 7200.0
        assert window_seconds("1d") == 86400.0
        assert window_seconds("1w") == 604800.0
        assert window_seconds("1.5h") == 5400.0

    def test_parse_when(self):
        now = T0
        assert parse_when("now", now) == now
        assert parse_when("15m", now) == now - 900.0
        assert parse_when(str(T0 - 5.0), now) == T0 - 5.0
        assert parse_when("30", now) == now - 30.0

    def test_bad_spec_raises(self):
        with pytest.raises(ValueError):
            window_seconds("soon")


# -- the tiered ring store ---------------------------------------------------


class TestHistoryStore:
    def test_tier_downsampling_conserves_counts_and_sums(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=clock)
        nticks, calls, nbytes = 24, 3, 1 << 20
        for _ in range(nticks):
            store.append(clock(), 1.0, _tick_delta(calls, nbytes),
                         gauges={"sched.depth": 4.0},
                         burn={"api": (1, 10)})
            clock.advance(1.0)
        store.close()

        ro = HistoryStore(str(tmp_path / "h"), create=False)
        for tier in ("raw", "mid", "slow"):
            recs = ro.buckets(T0 - 1, clock(), tier=tier)
            assert recs, tier
            st = [r["stages"]["ingest.chunks"] for r in recs]
            assert sum(s["calls"] for s in st) == nticks * calls, tier
            assert sum(s["bytes"] for s in st) == nticks * nbytes, tier
            hs = [r["hists"]["serve.request_s"] for r in recs]
            assert sum(h["n"] for h in hs) == nticks, tier
            total = sum(h["total"] for h in hs)
            assert total == pytest.approx(nticks * 0.02), tier
            assert sum(r["n"] for r in recs) == nticks, tier
            burn = [r["burn"]["api"] for r in recs]
            assert sum(b["bad"] for b in burn) == nticks
            assert sum(b["total"] for b in burn) == nticks * 10
            # Byte-free counters conserve too (calls carry the count).
            assert sum(r["stages"]["ingest.retries"]["calls"]
                       for r in recs) == nticks

    def test_series_projection(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=clock)
        for _ in range(8):
            store.append(clock(), 1.0,
                         _tick_delta(nbytes=1_000_000_000, seconds=1.0),
                         gauges={"sched.depth": 7.0})
            clock.advance(1.0)
        pts = store.series("ingest.chunks", T0, clock(), tier="raw")
        assert pts and all(p["kind"] == "stage" for p in pts)
        assert pts[0]["gbps"] == pytest.approx(1.0, rel=0.01)
        lat = store.series("serve.request_s", T0, clock(), tier="raw")
        assert lat and lat[0]["kind"] == "hist" and lat[0]["n"] == 1
        g = store.series("sched.depth", T0, clock(), tier="raw")
        assert g and g[0]["value"] == 7.0
        assert "ingest.chunks" in store.metrics()
        store.close()

    def test_disk_budget_fixed_under_a_simulated_week(self, tmp_path):
        clock = FakeClock()
        tiers = [TierSpec("raw", 10.0, 60), TierSpec("mid", 300.0, 48),
                 TierSpec("slow", 3600.0, 48)]
        store = HistoryStore(str(tmp_path / "h"), tiers=tiers,
                             slot_bytes=4096, clock=clock)
        expected = sum(H._HDR_BYTES + t.slots * 4096 for t in tiers)
        sizes = []
        for day in range(7):
            for _ in range(288):  # one tick per 300 s
                store.append(clock(), 300.0, _tick_delta(),
                             burn={"api": (0, 10)})
                clock.advance(300.0)
            sizes.append(store.disk_usage())
        store.close()
        # The budget is claimed at creation and NEVER grows — day 1
        # equals day 7 equals the arithmetic of the tier spec.
        assert sizes == [expected] * 7
        for t in tiers:
            assert os.path.getsize(tmp_path / "h" / f"{t.name}.ring") \
                == H._HDR_BYTES + t.slots * 4096
        # And the rings still answer: the slow tier holds the tail of
        # the week.
        ro = HistoryStore(str(tmp_path / "h"), create=False, clock=clock)
        recs = ro.buckets(clock() - 47 * 3600.0, clock(), tier="slow")
        assert len(recs) >= 40

    def test_oldest_bucket_overwrite_wraps(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"),
                             tiers=[TierSpec("raw", 1.0, 4)],
                             slot_bytes=4096, clock=clock)
        for i in range(10):
            store.append(clock(), 1.0, _tick_delta(calls=i + 1))
            clock.advance(1.0)
        store.close()
        _, recs, _ = read_ring(str(tmp_path / "h" / "raw.ring"))
        assert len(recs) == 4  # the ring holds exactly `slots` buckets
        assert [r["stages"]["ingest.chunks"]["calls"] for r in recs] \
            == [7, 8, 9, 10]

    def test_restart_adopts_partial_bucket(self, tmp_path):
        clock = FakeClock()
        tiers = [TierSpec("raw", 60.0, 8)]
        store = HistoryStore(str(tmp_path / "h"), tiers=tiers,
                             slot_bytes=4096, clock=clock)
        store.append(clock(), 1.0, _tick_delta(calls=5))
        store.close()
        # Same bucket window, new process: the second store must FOLD
        # into the slot the first one wrote, not zero it.
        store2 = HistoryStore(str(tmp_path / "h"), tiers=tiers,
                              slot_bytes=4096, clock=clock)
        store2.append(clock.advance(1.0), 1.0, _tick_delta(calls=2))
        store2.close()
        _, recs, _ = read_ring(str(tmp_path / "h" / "raw.ring"))
        assert len(recs) == 1
        assert recs[0]["stages"]["ingest.chunks"]["calls"] == 7
        assert recs[0]["n"] == 2

    def test_reader_adopts_file_geometry_not_config(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"),
                             tiers=[TierSpec("raw", 2.0, 16)],
                             slot_bytes=4096, clock=clock)
        store.append(clock(), 1.0, _tick_delta())
        store.close()
        # Reopen under a DIFFERENT configured geometry: the on-disk
        # header wins, so old slots keep addressing correctly.
        store2 = HistoryStore(str(tmp_path / "h"),
                              tiers=[TierSpec("raw", 7.0, 99)],
                              slot_bytes=8192, clock=clock)
        store2.append(clock.advance(2.0), 1.0, _tick_delta())
        store2.close()
        hdr, recs, _ = read_ring(str(tmp_path / "h" / "raw.ring"))
        assert hdr["bucket_s"] == 2.0 and hdr["slots"] == 16
        assert os.path.getsize(tmp_path / "h" / "raw.ring") \
            == H._HDR_BYTES + 16 * 4096

    def test_torn_slot_heals_and_counts(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"),
                             tiers=[TierSpec("raw", 1.0, 8)],
                             slot_bytes=4096, clock=clock)
        for _ in range(4):
            store.append(clock(), 1.0, _tick_delta())
            clock.advance(1.0)
        store.close()
        path = tmp_path / "h" / "raw.ring"
        # Tear one occupied slot the way a dead writer would: garbage
        # over the front of the slot.
        i = int(T0 // 1.0) % 8
        with open(path, "r+b") as f:
            f.seek(H._HDR_BYTES + i * 4096)
            f.write(b"\xffGARBAGE\xff")
        ro = HistoryStore(str(tmp_path / "h"), create=False, clock=clock)
        recs = ro.buckets(T0 - 1, clock(), tier="raw")
        assert len(recs) == 3  # healed: the other buckets still read
        assert ro.torn_slots == 1

    def test_slot_overflow_sheds_hists_first(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"),
                             tiers=[TierSpec("raw", 60.0, 4)],
                             slot_bytes=2048, clock=clock)
        tl = Timeline()
        for i in range(200):  # enough distinct hists to bust 2 KB
            tl.observe(f"metric.{i:03d}_s", 0.01)
        tl.stages["ingest.chunks"].bytes += 5
        tl.stages["ingest.chunks"].calls += 1
        store.append(clock(), 1.0, tl)
        store.close()
        assert store.overflow_slots >= 1
        _, recs, torn = read_ring(str(tmp_path / "h" / "raw.ring"))
        assert torn == 0 and len(recs) == 1
        assert recs[0].get("overflow") is True
        # Stage accounting survives the shed; the hists were dropped.
        assert recs[0]["stages"]["ingest.chunks"]["calls"] == 1

    def test_concurrent_read_while_write(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=clock)
        stop = threading.Event()
        errors = []

        def reader():
            ro = HistoryStore(str(tmp_path / "h"), create=False,
                              clock=clock)
            while not stop.is_set():
                try:
                    for rec in ro.buckets(T0 - 1, clock() + 1):
                        assert "t0" in rec
                except Exception as e:  # noqa: BLE001 — the assertion
                    errors.append(e)
                    return

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        for _ in range(300):
            store.append(clock(), 0.1, _tick_delta())
            clock.advance(0.1)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        store.close()
        assert not errors

    def test_fleet_merge_of_two_peers_stores(self, tmp_path):
        clock = FakeClock()
        a = HistoryStore(str(tmp_path / "a"), tiers=_small_tiers(),
                         slot_bytes=4096, clock=clock)
        b = HistoryStore(str(tmp_path / "b"), tiers=_small_tiers(),
                         slot_bytes=4096, clock=clock)
        for _ in range(8):
            a.append(clock(), 1.0, _tick_delta(calls=1, nbytes=100),
                     burn={"api": (1, 5)})
            b.append(clock(), 1.0, _tick_delta(calls=2, nbytes=200),
                     burn={"api": (0, 5)})
            clock.advance(1.0)
        a.close()
        b.close()
        ra = HistoryStore(str(tmp_path / "a"), create=False,
                          clock=clock).buckets(T0 - 1, clock(), tier="raw")
        rb = HistoryStore(str(tmp_path / "b"), create=False,
                          clock=clock).buckets(T0 - 1, clock(), tier="raw")
        merged = merge_buckets([ra, rb])
        assert len(merged) == len(ra) == len(rb)
        st = [r["stages"]["ingest.chunks"] for r in merged]
        assert sum(s["calls"] for s in st) == 8 * 3
        assert sum(s["bytes"] for s in st) == 8 * 300
        hs = [r["hists"]["serve.request_s"] for r in merged]
        assert sum(h["n"] for h in hs) == 16
        burn = [r["burn"]["api"] for r in merged]
        assert sum(x["bad"] for x in burn) == 8
        assert sum(x["total"] for x in burn) == 80
        # Commutative: the other order folds identically.
        assert merge_buckets([rb, ra]) == merged

    def test_merge_in_materializes_peer_buckets(self, tmp_path):
        clock = FakeClock()
        a = HistoryStore(str(tmp_path / "a"), tiers=_small_tiers(),
                         slot_bytes=4096, clock=clock)
        a.append(clock(), 1.0, _tick_delta(calls=4))
        recs = a.buckets(T0 - 1, clock() + 1, tier="raw")
        a.close()
        door = HistoryStore(str(tmp_path / "door"), tiers=_small_tiers(),
                            slot_bytes=4096, clock=clock)
        assert door.merge_in(recs) == len(recs)
        got = door.buckets(T0 - 1, clock() + 1, tier="raw")
        door.close()
        assert got[0]["stages"]["ingest.chunks"]["calls"] == 4

    def test_bucket_point_slo_projection(self):
        rec = {"t0": T0, "bucket_s": 60.0, "burn": {"api":
                                                    {"bad": 3,
                                                     "total": 12}}}
        p = bucket_point(rec, "slo.api")
        assert p["kind"] == "slo" and p["value"] == 0.25
        assert bucket_point(rec, "nope") is None


# -- anomaly baselines -------------------------------------------------------


def _an(**kw):
    kw.setdefault("z", 5.0)
    kw.setdefault("window", 40)
    kw.setdefault("min_n", 10)
    kw.setdefault("consecutive", 3)
    clock = kw.pop("clock", FakeClock())
    rec = observability.FlightRecorder()
    return AnomalyDetector(recorder=rec, clock=clock, **kw), clock


class TestAnomaly:
    def test_quiet_on_seeded_steady_baseline(self):
        import random

        rng = random.Random(20)
        det, clock = _an()
        fired = []
        for _ in range(300):
            fired += det.observe(
                {"serve.request_s.p99_s": rng.gauss(0.050, 0.004)},
                clock.advance(1.0))
        assert fired == []
        assert det.breached() == []

    def test_injected_step_fires_within_window(self):
        import random

        rng = random.Random(7)
        det, clock = _an()
        for _ in range(60):
            det.observe({"serve.request_s.p99_s": rng.gauss(0.050, 0.004)},
                        clock.advance(1.0))
        fired = []
        for i in range(10):
            fired += det.observe({"serve.request_s.p99_s": 0.250},
                                 clock.advance(1.0))
        # Exactly one page (consecutive=3 → tick 3), then latched.
        assert len(fired) == 1
        a = fired[0]
        assert a["class"] == "anomaly"
        assert a["metric"] == "serve.request_s.p99_s"
        assert a["z"] >= 5.0
        assert a.get("flight_dump")  # first breach forces the dump
        assert det.breached() == ["serve.request_s.p99_s"]
        # Recovery re-arms: back at baseline, the latch clears.
        for _ in range(3):
            det.observe({"serve.request_s.p99_s": 0.050},
                        clock.advance(1.0))
        assert det.breached() == []

    def test_one_noisy_sample_never_pages(self):
        det, clock = _an(consecutive=3)
        for _ in range(30):
            det.observe({"g": 1.0}, clock.advance(1.0))
        assert det.observe({"g": 100.0}, clock.advance(1.0)) == []
        assert det.observe({"g": 1.0}, clock.advance(1.0)) == []
        assert det.breached() == []

    def test_throughput_pages_on_drop_not_rise(self):
        det, clock = _an(consecutive=1)
        for _ in range(30):
            det.observe({"ingest.chunks.gbps": 10.0}, clock.advance(1.0))
        assert det.observe({"ingest.chunks.gbps": 100.0},
                           clock.advance(1.0)) == []  # faster is fine
        fired = det.observe({"ingest.chunks.gbps": 0.5},
                            clock.advance(1.0))
        assert len(fired) == 1  # a drop is the page

    def test_per_metric_sensitivity_env(self, monkeypatch):
        monkeypatch.setenv("BLIT_HISTORY_SENSITIVITY",
                           "serve.request_s.p99_s=2.5, other=9")
        d = history_defaults(SiteConfig())
        assert d["anomaly_overrides"] == {
            "serve.request_s.p99_s": 2.5, "other": 9.0}
        det = AnomalyDetector(z=6.0,
                              overrides=d["anomaly_overrides"])
        assert det.threshold_for("serve.request_s.p99_s") == 2.5
        assert det.threshold_for("unknown") == 6.0

    def test_kill_switch_env(self, monkeypatch):
        monkeypatch.setenv("BLIT_HISTORY_ANOMALY", "0")
        assert history_defaults(SiteConfig())["anomaly"] is False
        pub = MetricsPublisher(
            interval_s=3600.0, spool_dir="", port=-1,
            config=SiteConfig(history_dir=None))
        assert pub.anomaly is None
        pub.close()

    def test_series_values_skips_idle(self):
        tl = _tick_delta(nbytes=2_000_000_000, seconds=1.0)
        vals = H.series_values(tl, {"sched.depth": 3.0})
        assert vals["ingest.chunks.gbps"] == pytest.approx(2.0)
        assert vals["serve.request_s.p99_s"] > 0
        assert vals["sched.depth"] == 3.0
        # ingest.retries is byte-free — no gbps series for it.
        assert not any(k.startswith("ingest.retries") for k in vals)
        assert H.series_values(Timeline()) == {}


# -- the publisher wiring ----------------------------------------------------


class TestPublisherIntegration:
    def test_tick_feeds_store_and_sample_carries_anchor(self, tmp_path):
        cfg = SiteConfig(history_dir=str(tmp_path / "h"),
                         history_raw_s=1.0,
                         slo_objectives=[{"name": "api",
                                          "metric": "serve.request_s",
                                          "threshold": 0.1,
                                          "kind": "latency"}])
        tl = Timeline()
        pub = MetricsPublisher(interval_s=0.05, spool_dir="", port=-1,
                               timeline=tl, config=cfg)
        assert pub.history is not None and pub.anomaly is not None
        for i in range(3):
            s = tl.stages["ingest.chunks"]
            s.calls += 1
            s.seconds += 0.01
            s.bytes += 1 << 20
            tl.observe("serve.request_s", 0.01)
            sample = pub.tick()
        anchor = sample["anchor"]
        assert set(anchor) == {"epoch", "mono"}
        assert anchor == wall_anchor()
        pub.close()
        ro = HistoryStore(str(tmp_path / "h"), create=False)
        now = time.time()
        recs = ro.buckets(now - 60, now + 60, tier="raw")
        total = sum(r["stages"]["ingest.chunks"]["calls"] for r in recs)
        assert total == 3
        # SLO burn observations ride the buckets (the default config
        # declares objectives).
        assert any(r.get("burn") for r in recs)

    def test_anomaly_breach_pages_and_bundles(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_REQUEST_LOG", str(tmp_path / "req"))
        cfg = SiteConfig(history_dir=str(tmp_path / "h"),
                         history_raw_s=1.0,
                         history_anomaly_window=16,
                         history_anomaly_min_n=5,
                         history_anomaly_consecutive=2,
                         history_anomaly_z=5.0,
                         incident_dir=str(tmp_path / "inc"))
        tl = Timeline()
        pub = MetricsPublisher(interval_s=0.05, spool_dir="", port=-1,
                               timeline=tl, config=cfg)
        rlog = observability.RequestLog(
            os.path.join(str(tmp_path / "req"),
                         "requests-peer.jsonl"))
        rlog.record(rid="r1", trace="tr-bundle", role="peer",
                    status="ok", duration_s=0.2, client="c1")
        rlog.close()
        import random

        rng = random.Random(3)
        for _ in range(10):  # steady baseline
            tl.hists["serve.request_s"].observe(rng.gauss(0.02, 0.001),
                                                trace_id="tr-bundle")
            pub.tick()
        assert pub.health()["ok"]
        alerts = []
        for _ in range(4):  # injected 20x latency step
            tl.hists["serve.request_s"].observe(0.4,
                                                trace_id="tr-bundle")
            alerts += pub.tick()["alerts"]
        anomaly_alerts = [a for a in alerts if a["class"] == "anomaly"]
        assert len(anomaly_alerts) == 1
        health = pub.health()
        assert not health["ok"]
        assert any(r.startswith("anomaly:serve.request_s")
                   for r in health["reasons"])
        pub.close()
        bundles = list_incidents(str(tmp_path / "inc"))
        assert len(bundles) == 1
        b = load_incident(bundles[0]["path"])
        # The self-containment contract: the bundle's exemplar trace
        # resolves into its OWN request records, no reach outside the
        # bundle dir.
        trace = b["manifest"]["trace"]
        assert trace == "tr-bundle"
        assert any(r.get("trace") == trace for r in b["requests"])
        assert b["flight"] is not None
        assert b["flight"]["anchor"] == wall_anchor()
        assert b["history"]["buckets"]
        assert b["healthz"]["reasons"]
        text = render_incident(b)
        assert "anomaly" in text and "tr-bundle" in text
        listing = render_incidents(bundles)
        assert "anomaly" in listing

    def test_quiet_baseline_means_zero_bundles(self, tmp_path):
        cfg = SiteConfig(history_dir=str(tmp_path / "h"),
                         history_raw_s=1.0,
                         history_anomaly_window=16,
                         history_anomaly_min_n=5,
                         history_anomaly_consecutive=2,
                         incident_dir=str(tmp_path / "inc"))
        tl = Timeline()
        pub = MetricsPublisher(interval_s=0.05, spool_dir="", port=-1,
                               timeline=tl, config=cfg)
        import random

        rng = random.Random(11)
        for _ in range(40):
            tl.hists["serve.request_s"].observe(rng.gauss(0.02, 0.001))
            sample = pub.tick()
            assert sample["alerts"] == []
        pub.close()
        assert list_incidents(str(tmp_path / "inc")) == []

    def test_incident_cooldown_one_bundle_per_storm(self, tmp_path):
        clock = FakeClock()
        b = IncidentBundler(str(tmp_path / "inc"), window_s=60.0,
                            cooldown_s=300.0, clock=clock)
        first = b.snapshot("slo:api", "breach 1")
        assert first is not None
        clock.advance(1.0)
        assert b.snapshot("slo:api", "breach 2") is None  # cooled down
        assert b.snapshot("anomaly:x", "other kind") is not None
        clock.advance(400.0)
        assert b.snapshot("slo:api", "breach 3") is not None
        assert len(list_incidents(str(tmp_path / "inc"))) == 3


# -- slo-report --------------------------------------------------------------


class TestSloReport:
    def test_attainment_matches_hand_computed_oracle(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=clock)
        # Hand oracle: 20 ticks × (bad=3, total=50) → 60/1000 bad;
        # attainment 0.94; budget 0.1 → spend 0.6.
        for _ in range(20):
            store.append(clock(), 1.0, _tick_delta(),
                         burn={"api": (3, 50)})
            clock.advance(1.0)
        objs = [SLObjective(name="api", metric="serve.request_s",
                            threshold=0.1, budget=0.1)]
        doc = slo_report(store, objectives=objs, window_s=120.0,
                         now=clock())
        store.close()
        o = doc["objectives"]["api"]
        assert o["bad"] == 60 and o["total"] == 1000
        assert o["attainment"] == pytest.approx(0.94)
        assert o["budget_spent"] == pytest.approx(0.6)
        assert doc["metrics"]["slo.api_attained"] == pytest.approx(0.94)
        assert "0.94" in render_slo_report(doc)

    def test_latency_fallback_recomputes_from_hist_state(self, tmp_path):
        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=clock)
        tl = Timeline()
        for v in [0.01] * 9 + [10.0]:  # one sample far above threshold
            tl.observe("serve.request_s", v)
        store.append(clock(), 1.0, tl)  # note: NO burn block stored
        objs = [SLObjective(name="api", metric="serve.request_s",
                            threshold=1.0, budget=0.5)]
        doc = slo_report(store, objectives=objs, window_s=60.0,
                         now=clock.advance(1.0))
        store.close()
        o = doc["objectives"]["api"]
        assert o["total"] == 10 and o["bad"] == 1
        assert o["attainment"] == pytest.approx(0.9)

    def test_empty_window_is_full_attainment(self, tmp_path):
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=FakeClock())
        objs = [SLObjective(name="api", metric="m", threshold=1.0)]
        doc = slo_report(store, objectives=objs, window_s=60.0, now=T0)
        store.close()
        assert doc["objectives"]["api"]["attainment"] == 1.0
        assert doc["objectives"]["api"]["budget_spent"] == 0.0


# -- torn-tail drills (satellite) --------------------------------------------


class TestTornTails:
    def test_read_spool_heals_and_counts(self, tmp_path):
        spool = tmp_path / "spool"
        spool.mkdir()
        good = {"t": T0, "seq": 1, "host": "h", "pid": 1,
                "timeline": {"stages": {}}}
        with open(spool / "h-1.jsonl", "w") as f:
            f.write(json.dumps(good) + "\n")
            f.write('{"t": 170')  # the SIGKILL tear: no newline
        tl = observability.process_timeline()
        before = tl.stages["monitor.torn_lines"].calls \
            if "monitor.torn_lines" in tl.stages else 0
        samples = monitor.read_spool(str(spool), tail=5)
        assert len(samples) == 1 and samples[0]["seq"] == 1
        assert tl.stages["monitor.torn_lines"].calls == before + 1

    def test_read_requests_heals_and_counts(self, tmp_path):
        d = tmp_path / "req"
        d.mkdir()
        with open(d / "requests-peer.jsonl", "w") as f:
            f.write(json.dumps({"t": T0, "rid": "a", "status": "ok"})
                    + "\n")
            f.write('{"t": 17, "rid": "tor')
        tl = observability.process_timeline()
        before = tl.stages["monitor.torn_lines"].calls \
            if "monitor.torn_lines" in tl.stages else 0
        recs = monitor.read_requests(str(d))
        assert [r["rid"] for r in recs] == ["a"]
        assert tl.stages["monitor.torn_lines"].calls == before + 1

    def test_kill_mid_write_drill(self, tmp_path):
        """A real SIGKILL mid-line: the child writes one whole record,
        then half a record with no newline, then blocks; every monitor-
        path reader over the spool must heal."""
        spool = tmp_path / "spool"
        spool.mkdir()
        child = subprocess.Popen(
            [sys.executable, "-c", f"""
import json, sys, time
f = open({str(spool / "h-9.jsonl")!r}, "w")
f.write(json.dumps({{"t": 1.0, "seq": 0, "host": "h", "pid": 9,
                     "timeline": {{"stages": {{}}}}}}) + "\\n")
f.write('{{"t": 2.0, "seq": 1, "host": "h"')  # torn: no newline
f.flush()
print("ready", flush=True)
time.sleep(60)
"""],
            stdout=subprocess.PIPE)
        try:
            assert child.stdout.readline().strip() == b"ready"
            child.send_signal(signal.SIGKILL)
            child.wait(timeout=30)
        finally:
            if child.poll() is None:
                child.kill()
        samples = monitor.read_spool(str(spool), tail=10)
        assert [s["seq"] for s in samples] == [0]
        report, latest = monitor.merge_spool(str(spool))
        assert len(latest) == 1  # blit top renders despite the tear

    def test_incident_ingest_heals_torn_request_lines(self, tmp_path):
        bundle = tmp_path / "incident-x"
        bundle.mkdir()
        with open(bundle / "incident.json", "w") as f:
            json.dump({"kind": "slo:api", "t": T0, "reason": "r"}, f)
        with open(bundle / "requests.jsonl", "w") as f:
            f.write(json.dumps({"t": T0, "trace": "tr1"}) + "\n")
            f.write('{"t": 17, "trace": "to')
        b = load_incident(str(bundle))
        assert len(b["requests"]) == 1
        assert b["torn_lines"] == 1
        assert "healed" in render_incident(b)


# -- wall-clock anchor (satellite) -------------------------------------------


class TestAnchor:
    def test_anchor_is_one_stable_pair(self):
        a = wall_anchor()
        assert set(a) == {"epoch", "mono"}
        assert a == wall_anchor()  # captured at import, not per call
        # The pair is coherent: epoch - mono is a plausible origin.
        assert a["epoch"] - a["mono"] <= time.time()

    def test_flight_dump_carries_and_renders_anchor(self, tmp_path):
        rec = observability.FlightRecorder()
        path = rec.dump("anchor test", path=str(tmp_path / "d.json"),
                        force=True)
        with open(path) as f:
            doc = json.load(f)
        assert doc["anchor"] == wall_anchor()
        text = observability.render_flight_dump(doc)
        assert "anchor" in text and "mono origin" in text

    def test_telemetry_snapshot_carries_anchor(self):
        snap = observability.telemetry_snapshot()
        assert snap["anchor"] == wall_anchor()


# -- CLI surface -------------------------------------------------------------


class TestCli:
    def _store(self, tmp_path, burn=None):
        # Near-now clock: the CLI windows anchor at real time.time().
        clock = FakeClock(time.time() - 15.0)
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=clock)
        for _ in range(10):
            store.append(clock(), 1.0, _tick_delta(),
                         burn=burn or {"api": (1, 10)})
            clock.advance(1.0)
        store.close()
        return str(tmp_path / "h")

    def test_slo_report_cli_json_and_artifact(self, tmp_path, capsys,
                                              monkeypatch):
        from blit.__main__ import main

        d = self._store(tmp_path)
        out = tmp_path / "slo.json"
        # The reader's config declares NO "api" objective — the burn
        # counts recorded in the store still report (the store
        # outranks the reader's config).
        rc = main(["slo-report", d, "--window", "1d", "--json",
                   "--out", str(out)])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["objectives"]["api"]["bad"] == 10
        assert doc["objectives"]["api"]["total"] == 100
        assert doc["metrics"]["slo.api_attained"] == pytest.approx(0.9)
        assert json.loads(out.read_text()) == doc

    def test_slo_report_json_metrics_block_is_flat(self, tmp_path, capsys):
        # What a CI step reads: one `slo.<name>_attained` scalar per
        # objective recorded in the store, nothing nested, each the
        # objective's own attainment.
        from blit.__main__ import main

        d = self._store(tmp_path, burn={"api": (1, 10), "ingest": (0, 4)})
        assert main(["slo-report", d, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["metrics"] == {"slo.api_attained": pytest.approx(0.9),
                                  "slo.ingest_attained": 1.0}
        for name, o in doc["objectives"].items():
            assert doc["metrics"][f"slo.{name}_attained"] == o["attainment"]

    def test_incident_cli_list_and_show(self, tmp_path, capsys):
        from blit.__main__ import main

        clock = FakeClock()
        b = IncidentBundler(str(tmp_path / "inc"), window_s=60.0,
                            cooldown_s=1.0, clock=clock)
        path = b.snapshot("slo:api", "drill", alert={
            "t": T0, "class": "slo", "objective": "api",
            "metric": "serve.request_s"})
        assert path
        rc = main(["incidents", "--dir", str(tmp_path / "inc")])
        assert rc == 0
        assert "slo:api" in capsys.readouterr().out
        rc = main(["incident", "show", path, "--window", "15m"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "slo:api" in out and "timeline" in out
        rc = main(["incident", "show", path, "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["manifest"]["kind"] == "slo:api"

    def test_incidents_cli_needs_a_dir(self, capsys):
        from blit.__main__ import main

        with pytest.raises(SystemExit):
            main(["incidents"])

    def test_top_history_sparklines(self, tmp_path, capsys):
        from blit.__main__ import main

        d = self._store(tmp_path)
        spool = tmp_path / "spool"
        spool.mkdir()
        sample = {"t": time.time(), "seq": 0, "host": "h", "pid": 1,
                  "timeline": {"stages": {}}, "delta": {"stages": {}},
                  "slo": {}}
        (spool / "h-1.jsonl").write_text(json.dumps(sample) + "\n")
        rc = main(["top", "--spool", str(spool), "--once",
                   "--history", d])
        assert rc == 0
        out = capsys.readouterr().out
        assert "history" in out and "ingest.chunks" in out
        # The sparkline glyphs actually render.
        assert any(ch in out for ch in "▁▂▃▄▅▆▇█")

    def test_requests_since_until_window(self, tmp_path, capsys):
        from blit.__main__ import main

        d = tmp_path / "req"
        d.mkdir()
        now = time.time()
        with open(d / "requests-x.jsonl", "w") as f:
            for dt, rid in [(-7200, "old"), (-60, "recent"),
                            (-1, "fresh")]:
                f.write(json.dumps({"t": now + dt, "rid": rid,
                                    "status": "ok",
                                    "duration_s": 0.01}) + "\n")
        rc = main(["requests", str(d), "--since", "15m", "--json"])
        assert rc == 0
        rids = [json.loads(line)["rid"] for line in
                capsys.readouterr().out.splitlines() if line]
        assert rids == ["recent", "fresh"]
        rc = main(["requests", str(d), "--since", "15m", "--until",
                   "30", "--json"])
        assert rc == 0
        rids = [json.loads(line)["rid"] for line in
                capsys.readouterr().out.splitlines() if line]
        assert rids == ["recent"]

    def test_sparkline_shapes(self):
        assert sparkline([]) == ""
        assert sparkline([1.0, 1.0, 1.0]) == "▁▁▁"
        s = sparkline([0, 1, 2, 3], width=4)
        assert s[0] == "▁" and s[-1] == "█"


# -- the serve-plane surface -------------------------------------------------


class TestServeSurface:
    def test_peer_history_doc_shape(self, tmp_path):
        from types import SimpleNamespace

        from blit.serve.http import _history_doc, history_query

        clock = FakeClock()
        store = HistoryStore(str(tmp_path / "h"), tiers=_small_tiers(),
                             slot_bytes=4096, clock=clock)
        store.append(clock(), 1.0, _tick_delta(calls=5))
        store.close()
        since, until, tier = history_query(
            f"/history?since={T0 - 10}&until={T0 + 10}&tier=raw")
        assert (since, until, tier) == (T0 - 10, T0 + 10, "raw")
        pub = SimpleNamespace(
            history=HistoryStore(str(tmp_path / "h"), create=False,
                                 clock=clock))
        doc = _history_doc(
            pub, f"/history?since={T0 - 10}&until={T0 + 10}&tier=raw")
        assert doc["enabled"] is True
        assert doc["buckets"][0]["stages"]["ingest.chunks"]["calls"] == 5
        off = _history_doc(SimpleNamespace(history=None), "/history")
        assert off["enabled"] is False and off["buckets"] == []

    def test_history_query_window_grammar(self):
        from blit.serve.http import history_query

        since, until, tier = history_query("/history?since=15m")
        assert until - since == pytest.approx(900.0, abs=5.0)
        assert tier is None

    def test_peer_route_and_door_merge_over_the_wire(self, tmp_path):
        from blit.serve.cache import ProductCache
        from blit.serve.fleet import FleetFrontDoor
        from blit.serve.http import PeerServer, http_json
        from blit.serve.scheduler import Scheduler
        from blit.serve.service import ProductService

        lease_dir = str(tmp_path / "leases")
        servers, peers = [], {}
        for i in range(2):
            tl = Timeline()
            cfg = SiteConfig(history_dir=str(tmp_path / f"hist{i}"),
                             history_raw_s=1.0,
                             history_anomaly=False)
            svc = ProductService(
                cache=ProductCache(str(tmp_path / f"cache{i}"),
                                   ram_bytes=1 << 24, timeline=tl),
                scheduler=Scheduler(max_concurrency=2, queue_depth=8,
                                    timeline=tl, retry_seed=i),
                timeline=tl, config=cfg)
            ps = PeerServer(svc, name=f"peer{i}",
                            lease_dir=lease_dir, proc=i,
                            beat_interval_s=0.05, config=cfg).start()
            # Land one known stage delta in each peer's ring.
            s = tl.stages["ingest.chunks"]
            s.calls += i + 1
            s.seconds += 0.01
            s.bytes += 1000
            ps._pub.tick()
            servers.append((ps, svc))
            peers[f"peer{i}"] = ps.url
        door = None
        try:
            # The peer-side route answers over the real wire.
            status, _, body = http_json(
                "GET", peers["peer0"], "/history?since=1h")
            assert status == 200 and body["enabled"]
            calls = sum(r["stages"]["ingest.chunks"]["calls"]
                        for r in body["buckets"])
            assert calls == 1
            # The door fans out and merges both peers' buckets.
            door = FleetFrontDoor(peers, lease_dir=lease_dir,
                                  peer_ttl_s=5.0, poll_s=0.05,
                                  health_poll_s=0.2)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                door.observe()
                if all(p.watch.seen for p in door._peers.values()):
                    break
                time.sleep(0.05)
            now = time.time()
            doc = door.history(now - 3600, now)
            assert sorted(doc["peers"]) == ["peer0", "peer1"]
            assert doc["skipped"] == []
            calls = sum(r["stages"]["ingest.chunks"]["calls"]
                        for r in doc["buckets"])
            assert calls == 3  # 1 + 2, folded by bucket
        finally:
            if door is not None:
                door.close()
            for ps, svc in servers:
                ps.close()
                svc.close(5)
