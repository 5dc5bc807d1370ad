"""Fleet request observability (blit ISSUE 15): cross-host trace
propagation over the serve HTTP wire, per-request access records
(RequestLog + `blit requests`), histogram exemplars (OpenMetrics
exposition + `blit trace-view --exemplar`), per-reason flight-dump rate
limiting, flight-dump trace correlation, and tracer thread-safety under
hedged/coalesced concurrency."""

import json
import os
import threading
import time

import pytest

pytest.importorskip("jax")

from blit import faults, monitor, observability  # noqa: E402
from blit.config import DEFAULT, request_log_defaults  # noqa: E402
from blit.faults import FaultRule  # noqa: E402
from blit.observability import (  # noqa: E402
    FlightRecorder,
    HistogramStats,
    RequestLog,
    Timeline,
    cross_process_pairs,
    render_flight_dump,
)
from blit.serve import (  # noqa: E402
    FleetFrontDoor,
    Overloaded,
    PeerServer,
    ProductCache,
    ProductRequest,
    ProductService,
    Scheduler,
)
from blit.serve.http import (  # noqa: E402
    SPAN_HEADER,
    TIER_HEADER,
    TRACE_HEADER,
    http_json,
    wire_request,
)
from blit.testing import synth_raw  # noqa: E402

NFFT = 128
NTIME = (8 + 3) * NFFT


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    faults.reset_counters()
    yield
    faults.clear()
    faults.reset_counters()


def make_req(tmp_path, i=0):
    p = str(tmp_path / f"r{i}.raw")
    synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=NTIME, seed=i)
    return ProductRequest(raw=p, nfft=NFFT, nint=1)


# -- RequestLog --------------------------------------------------------------


class TestRequestLog:
    def test_records_land_as_json_lines(self, tmp_path):
        rl = RequestLog(str(tmp_path / "r.jsonl"))
        rl.record(rid="a", status="ok", duration_s=0.5, tier=None)
        rl.close()
        recs = monitor.read_requests(str(tmp_path / "r.jsonl"))
        assert len(recs) == 1
        assert recs[0]["rid"] == "a" and recs[0]["status"] == "ok"
        assert "tier" not in recs[0]  # None-valued fields dropped
        assert recs[0]["t"] > 0

    def test_size_rotation_bounds_the_log(self, tmp_path):
        rl = RequestLog(str(tmp_path / "r.jsonl"), max_bytes=4096,
                        max_files=3)
        for i in range(3000):
            rl.record(rid=f"req-{i:06d}", status="ok", duration_s=0.001)
        rl.close()
        files = rl.files()
        assert 1 <= len(files) <= 3
        total = sum(os.path.getsize(f) for f in files)
        # Bounded forever: at most max_files * (max_bytes + one record).
        assert total < 3 * (4096 + 512)
        # The NEWEST records survive rotation.
        recs = monitor.read_requests(str(tmp_path))
        assert recs[-1]["rid"] == "req-002999"

    def test_concurrent_appends_never_tear(self, tmp_path):
        rl = RequestLog(str(tmp_path / "r.jsonl"), max_bytes=1 << 20)

        def hammer(k):
            for i in range(200):
                rl.record(rid=f"t{k}-{i}", status="ok")

        threads = [threading.Thread(target=hammer, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rl.close()
        recs = monitor.read_requests(str(tmp_path / "r.jsonl"))
        assert len(recs) == 800  # every line parseable — no torn writes

    def test_defaults_resolve_env_over_config(self, monkeypatch,
                                              tmp_path):
        monkeypatch.setenv("BLIT_REQUEST_LOG", str(tmp_path))
        monkeypatch.setenv("BLIT_REQUEST_LOG_MAX_BYTES", "1234")
        d = request_log_defaults(DEFAULT)
        assert d["dir"] == str(tmp_path) and d["max_bytes"] == 1234
        monkeypatch.setenv("BLIT_REQUEST_LOG", "")
        assert request_log_defaults(
            DEFAULT.with_(request_log_dir="/x"))["dir"] is None


# -- histogram exemplars -----------------------------------------------------


class TestExemplars:
    def test_observe_under_a_span_retains_the_trace(self):
        h = HistogramStats()
        with observability.span("probe") as sp:
            h.observe(0.25)
        ex = h.tail_exemplar()
        assert ex is not None and ex["trace"] == sp.trace_id
        assert ex["value"] == 0.25 and ex["le"] >= 0.25

    def test_kill_switch(self):
        observability.set_exemplars(False)
        try:
            h = HistogramStats()
            with observability.span("probe"):
                h.observe(0.25)
            assert h.tail_exemplar() is None
        finally:
            observability.set_exemplars(True)

    def test_no_ambient_span_no_exemplar(self):
        h = HistogramStats()
        h.observe(0.25)
        assert h.tail_exemplar() is None

    def test_state_roundtrip_and_merge_keeps_newest(self):
        a = HistogramStats()
        a.observe(0.25, trace_id="old")
        a.exemplars[list(a.exemplars)[0]][2] = 100.0  # age it
        b = HistogramStats.from_state(a.state())
        assert b.tail_exemplar()["trace"] == "old"
        c = HistogramStats()
        c.observe(0.25, trace_id="new")
        b.merge(c)
        assert b.tail_exemplar()["trace"] == "new"
        # reset clears them (identity-preserving zero).
        b.reset()
        assert b.tail_exemplar() is None

    def test_prometheus_exposition_and_parse(self):
        tl = Timeline()
        with observability.span("probe") as sp:
            tl.observe("sched.wait_s", 0.25)
        snap = {"host": "h", "pid": 1, "worker": 0,
                "timeline": tl.state(), "faults": {}, "spans": []}
        report = observability.merge_fleet([snap])
        # The DEFAULT text exposition stays exemplar-free — the legacy
        # Prometheus text parser would reject the suffix.
        plain = observability.render_prometheus(report)
        assert "# {" not in plain and "# EOF" not in plain
        # The negotiated OpenMetrics exposition carries them + # EOF.
        text = observability.render_prometheus(report, openmetrics=True)
        assert "# {" in text
        assert text.rstrip().endswith("# EOF")
        # The plain parser tolerates (and drops) exemplar suffixes...
        samples = monitor.parse_prometheus(text)
        assert any(n == "blit_latency_seconds_bucket"
                   for n, _, _ in samples)
        # ...and the exemplar parser reads them back.
        exes = monitor.parse_prometheus_exemplars(text)
        assert any(ex["labels"].get("trace_id") == sp.trace_id
                   and ex["value"] == 0.25 for _, _, ex in exes)

    def test_metrics_endpoint_negotiates_openmetrics(self, tmp_path):
        """Accept: application/openmetrics-text flips the /metrics body
        (and content type) into the exemplar-bearing exposition; a
        legacy scrape stays plain."""
        from blit.observability import OPENMETRICS_CTYPE

        tl = Timeline()
        svc = ProductService(
            cache=ProductCache(None, ram_bytes=1 << 24, timeline=tl),
            scheduler=Scheduler(timeline=tl), timeline=tl)
        peer = PeerServer(svc, name="om").start()
        try:
            svc.get(make_req(tmp_path), timeout=120)  # spans + hists
            status, hdrs, body = http_json("GET", peer.url, "/metrics")
            assert status == 200 and "# {" not in body
            assert hdrs["content-type"].startswith("text/plain")
            status, hdrs, body = http_json(
                "GET", peer.url, "/metrics",
                headers={"Accept": "application/openmetrics-text"})
            assert status == 200
            assert hdrs["content-type"] == OPENMETRICS_CTYPE
            assert body.rstrip().endswith("# EOF")
            assert monitor.parse_prometheus(body)
        finally:
            peer.close()
            svc.close(5)


# -- flight recorder satellites ----------------------------------------------


class TestFlightDumps:
    def test_rate_limit_is_per_reason(self, tmp_path, monkeypatch):
        """ISSUE 15 satellite (the two-reason pin): an SLO-breach dump
        must not starve a first-of-kind stall dump on the shared
        clock — but repeats of ONE reason still rate-limit."""
        monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path))
        rec = FlightRecorder(min_interval_s=60.0)
        assert rec.dump("SLO breach: w burning 14x") is not None
        # Same reason class, seconds later: rate-limited.
        assert rec.dump("SLO breach: w burning 20x") is None
        # A DIFFERENT reason class lands immediately.
        assert rec.dump("blit-feed: producer stalled — no progress") \
            is not None
        # And its own repeats rate-limit independently.
        assert rec.dump("blit-feed: producer stalled again") is None
        # force still overrides.
        assert rec.dump("SLO breach: w again", force=True) is not None
        assert len(list(tmp_path.glob("blit-flight-*.json"))) == 3

    def test_explicit_key_overrides_derivation(self, tmp_path,
                                               monkeypatch):
        monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path))
        rec = FlightRecorder(min_interval_s=60.0)
        assert rec.dump("one reason", key="k") is not None
        assert rec.dump("totally different reason", key="k") is None

    def test_key_table_is_bounded(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path))
        rec = FlightRecorder(min_interval_s=60.0)
        for i in range(2 * FlightRecorder._MAX_DUMP_KEYS):
            rec.dump(f"reason-{i}: x")
        assert len(rec._last_dump) <= FlightRecorder._MAX_DUMP_KEYS

    def test_dump_records_ambient_trace(self, tmp_path, monkeypatch):
        """ISSUE 15 satellite: a flight dump carries the trace that
        tripped it, and trace-view prints it."""
        monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path))
        rec = FlightRecorder(min_interval_s=0.0)
        with observability.span("incident") as sp:
            path = rec.dump("stall: drill")
        doc = json.load(open(path))
        assert doc["trace"] == sp.trace_id
        assert doc["span"]
        out = render_flight_dump(doc)
        assert f"trace  : {sp.trace_id}" in out
        # Outside any span: no trace keys, no trace line.
        path2 = rec.dump("stall: drill 2", force=True)
        doc2 = json.load(open(path2))
        assert "trace" not in doc2
        assert "trace  :" not in render_flight_dump(doc2)


# -- service-level access records --------------------------------------------


class TestServiceRecords:
    def _service(self, tmp_path, reqlog=True, **sched_kw):
        tl = Timeline()
        cfg = DEFAULT.with_(
            request_log_dir=str(tmp_path / "reqlog") if reqlog else None)
        return ProductService(
            cache=ProductCache(None, ram_bytes=1 << 24, timeline=tl),
            scheduler=Scheduler(timeline=tl, **sched_kw),
            timeline=tl, config=cfg)

    def test_disabled_writes_zero_records(self, tmp_path):
        svc = self._service(tmp_path, reqlog=False)
        try:
            assert svc.request_log is None
            svc.get(make_req(tmp_path), timeout=120)
        finally:
            svc.close(5)
        assert not list(tmp_path.rglob("requests-*.jsonl*"))

    def test_one_record_per_outcome(self, tmp_path):
        """Every get() — served, refused, deadline-dead — appends
        exactly one record with the right status/code."""
        from blit.serve.scheduler import DeadlineExpired

        svc = self._service(tmp_path)
        req = make_req(tmp_path)
        try:
            svc.get(req, timeout=120, client="a")       # ok (derived)
            svc.get(req, timeout=120, client="a")       # ok (ram hit)
            with pytest.raises(DeadlineExpired):
                # A burned deadline is rejected at admission → 504.
                svc.get(ProductRequest(raw=req.raw, nfft=NFFT, nint=4),
                        timeout=1, deadline_s=-1.0, client="dead")
            svc._draining = True
            with pytest.raises(Overloaded):              # refused → 503
                svc.get(req, timeout=1, client="shed")
            svc._draining = False
        finally:
            svc.close(30)
        recs = [r for r in monitor.read_requests(str(tmp_path / "reqlog"))
                if r["role"] == "serve"]
        assert len(recs) == 4
        ok = [r for r in recs if r["status"] == "ok"]
        assert len(ok) == 2
        assert ok[0]["tier"] == "derive" and ok[0]["code"] == 200
        assert ok[1]["tier"] == "ram" and ok[1]["bytes"] > 0
        dead = [r for r in recs if r["client"] == "dead"][0]
        assert dead["status"] == "deadline" and dead["code"] == 504
        assert dead["deadline_left_s"] < 0
        shed = [r for r in recs if r["client"] == "shed"][0]
        assert shed["status"] == "overloaded" and shed["code"] == 503

    def test_record_carries_ambient_trace_and_queue_wait(self, tmp_path):
        svc = self._service(tmp_path)
        try:
            with observability.span("caller") as sp:
                svc.get(make_req(tmp_path, 1), timeout=120)
        finally:
            svc.close(5)
        recs = monitor.read_requests(str(tmp_path / "reqlog"))
        assert recs and recs[0]["trace"] == sp.trace_id
        assert "queue_wait_s" in recs[0] and "duration_s" in recs[0]


# -- the in-process fleet rig ------------------------------------------------


class Fleet:
    """Two in-process peers + a door with request logging on and
    explicit observe() ticks — the ISSUE 14 test rig plus the ISSUE 15
    observability surface."""

    def __init__(self, tmp_path, npeers=2, **door_kw):
        self.reqlog = str(tmp_path / "reqlog")
        cfg = DEFAULT.with_(request_log_dir=self.reqlog)
        self.lease_dir = str(tmp_path / "leases")
        self.servers = []
        peers = {}
        for i in range(npeers):
            tl = Timeline()
            svc = ProductService(
                cache=ProductCache(str(tmp_path / f"cache{i}"),
                                   ram_bytes=1 << 24, timeline=tl),
                scheduler=Scheduler(max_concurrency=2, queue_depth=8,
                                    timeline=tl, retry_seed=i),
                timeline=tl)
            ps = PeerServer(svc, name=f"peer{i}",
                            lease_dir=self.lease_dir, proc=i,
                            beat_interval_s=0.05, config=cfg).start()
            self.servers.append(ps)
            peers[f"peer{i}"] = ps.url
        kw = dict(peer_ttl_s=5.0, poll_s=0.05, health_poll_s=0.5,
                  hedge_floor_s=5.0, request_timeout_s=60.0, config=cfg)
        kw.update(door_kw)
        self.timeline = Timeline()
        self.door = FleetFrontDoor(peers, lease_dir=self.lease_dir,
                                   timeline=self.timeline, **kw)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            self.door.observe()
            if all(p.watch.seen for p in self.door._peers.values()):
                break
            time.sleep(0.05)

    def close(self):
        self.door.close()
        for s in self.servers:
            try:
                s.close()
            except Exception:  # noqa: BLE001
                pass
            s.service.close(5)


@pytest.fixture
def fleet(tmp_path):
    f = Fleet(tmp_path)
    yield f
    f.close()


def spans_by_name(name):
    return [s for s in observability.tracer().span_dicts()
            if s["name"] == name]


class TestTracePropagation:
    def test_peer_spans_parent_onto_door_dispatch(self, fleet,
                                                  tmp_path):
        """Tentpole #1: the door's fleet.request → fleet.dispatch chain
        continues into serve.reduce THROUGH the HTTP wire (in-process
        servers)."""
        observability.tracer().reset()
        fleet.door.get(make_req(tmp_path), client="tp")
        fr = spans_by_name("fleet.request")
        fd = spans_by_name("fleet.dispatch")
        sr = spans_by_name("serve.reduce")
        assert len(fr) == 1 and len(fd) >= 1 and len(sr) == 1
        assert fd[0]["parent"] == fr[0]["span"]
        assert sr[0]["trace"] == fr[0]["trace"]
        assert sr[0]["parent"] in {d["span"] for d in fd}
        # The hedge verdict + routing outcome land on the parent span.
        assert fr[0]["attrs"]["peer"] in ("peer0", "peer1")
        assert fr[0]["attrs"]["tier"] == "derive"

    def test_wire_headers_reactivate_the_context(self, fleet,
                                                 tmp_path):
        """A raw HTTP caller's trace context is adopted by the peer:
        the peer-side spans join the CALLER's trace id."""
        observability.tracer().reset()
        req = make_req(tmp_path, 1)
        wire = wire_request(req)
        status, hdrs, body = http_json(
            "POST", fleet.servers[0].url, "/product", wire,
            timeout=60.0,
            headers={TRACE_HEADER: "cafe.1", SPAN_HEADER: "cafe.2"})
        assert status == 200
        assert hdrs.get(TIER_HEADER.lower()) == "derive"
        sr = spans_by_name("serve.reduce")
        assert sr and sr[0]["trace"] == "cafe.1"
        assert sr[0]["parent"] == "cafe.2"

    def test_hedge_appears_as_sibling_span_tagged(self, tmp_path):
        fleet = Fleet(tmp_path, hedge_floor_s=0.05)
        try:
            observability.tracer().reset()
            faults.install(FaultRule(point="peer.request", mode="delay",
                                     delay_s=0.6, times=-1))
            fleet.door.get(make_req(tmp_path, 2), client="hedger")
            # The losing dispatch's span lands when ITS thread finishes
            # (first-wins returned already) — wait for it.
            deadline = time.monotonic() + 10
            while (len(spans_by_name("fleet.dispatch")) < 2
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            fd = spans_by_name("fleet.dispatch")
            fr = spans_by_name("fleet.request")
            assert len(fd) == 2
            assert {d["attrs"]["hedge"] for d in fd} == {0, 1}
            # Siblings: both parent onto the one request span.
            assert {d["parent"] for d in fd} == {fr[0]["span"]}
            # The winner/loser outcome lands on the parent.
            assert fr[0]["attrs"]["hedged"] == 1
            assert fr[0]["attrs"]["hedge_won"] in (0, 1)
        finally:
            fleet.close()

    def test_concurrent_requests_never_cross_contaminate(self, fleet,
                                                         tmp_path):
        """ISSUE 15 satellite: hedged dispatch and coalesced followers
        run on shared threads — every span's trace_id must match its
        OWN request (assert per-trace consistency under concurrency)."""
        observability.tracer().reset()
        reqs = [make_req(tmp_path, 10 + i) for i in range(4)]
        errs = []

        def one(i):
            try:
                # Two callers per product: the second coalesces.
                fleet.door.get(reqs[i % len(reqs)], client=f"c{i}")
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        spans = observability.tracer().span_dicts()
        by_id = {s["span"]: s for s in spans}
        roots = {s["span"]: s for s in spans
                 if s["name"] == "fleet.request"}
        assert len(roots) == 8
        # Walk every span up its parent chain: the root it reaches must
        # belong to the SAME trace — a cross-contaminated thread-local
        # would parent a span onto another request's chain.
        for s in spans:
            cur = s
            while cur.get("parent") and cur["parent"] in by_id:
                parent = by_id[cur["parent"]]
                assert parent["trace"] == s["trace"], (s, parent)
                cur = parent
        # And each request's serve.reduce (when it ran) shares the
        # root's trace; a trace never holds two different roots.
        for s in spans:
            if s["name"] != "fleet.request":
                continue
            same_trace_roots = [r for r in roots.values()
                                if r["trace"] == s["trace"]]
            assert same_trace_roots == [s]


class TestDoorRecords:
    def test_exactly_one_record_per_200_503_504(self, fleet, tmp_path):
        req = make_req(tmp_path, 3)
        fleet.door.get(req, client="ok")                      # 200
        from blit.serve.scheduler import DeadlineExpired

        with pytest.raises(DeadlineExpired):                  # 504
            fleet.door.get(make_req(tmp_path, 4), client="dead",
                           deadline_s=-1.0)
        fleet.door._draining = True                           # 503
        with pytest.raises(Overloaded):
            fleet.door.get(req, client="shed")
        fleet.door._draining = False
        recs = monitor.filter_requests(
            monitor.read_requests(fleet.reqlog), role="door")
        assert len(recs) == 3
        by_status = {r["client"]: (r["status"], r["code"]) for r in recs}
        assert by_status["ok"] == ("ok", 200)
        assert by_status["dead"] == ("deadline", 504)
        assert by_status["shed"] == ("overloaded", 503)
        ok = [r for r in recs if r["client"] == "ok"][0]
        assert ok["peer"] in ("peer0", "peer1")
        assert ok["tier"] == "derive" and ok["bytes"] > 0
        assert ok["trace"] and ok["rid"]

    def test_peer_record_rides_the_doors_request_id(self, fleet,
                                                    tmp_path):
        fleet.door.get(make_req(tmp_path, 5), client="rid")
        recs = monitor.read_requests(fleet.reqlog)
        door = [r for r in recs if r["role"] == "door"
                and r["client"] == "rid"]
        peer = [r for r in recs if r["role"] == "peer"
                and r["client"] == "rid"]
        assert door and peer
        assert peer[0]["rid"] == door[0]["rid"]
        assert peer[0]["trace"] == door[0]["trace"]
        assert peer[0]["queue_wait_s"] >= 0

    def test_request_s_exemplar_resolves_to_the_request(self, fleet,
                                                        tmp_path):
        """Tentpole #3 acceptance shape: the fleet.request_s tail
        bucket's exemplar IS one of the logged requests' traces."""
        for i in range(3):
            fleet.door.get(make_req(tmp_path, 20 + i), client="ex")
        ex = fleet.timeline.hists["fleet.request_s"].tail_exemplar()
        assert ex is not None
        traces = {r["trace"] for r in monitor.filter_requests(
            monitor.read_requests(fleet.reqlog), role="door")}
        assert ex["trace"] in traces


class TestCrossProcessPairs:
    def test_edges_detected_from_id_prefixes(self):
        """Span ids embed a per-process prefix, so a cross-process
        parent/child edge is detectable from ids alone — but only
        counted when BOTH ends are present in the stitched set."""
        spans = [
            {"span": "aaa.1", "parent": None},
            {"span": "aaa.2", "parent": "aaa.1"},   # same process
            {"span": "bbb.1", "parent": "aaa.2"},   # cross process
            {"span": "ccc.1", "parent": "zzz.9"},   # parent not present
        ]
        assert cross_process_pairs(spans) == 1


# -- CLI surfaces ------------------------------------------------------------


class TestRequestsCLI:
    def _spool(self, tmp_path):
        rl = RequestLog(str(tmp_path / "requests-door-h-1.jsonl"))
        rl.record(rid="a", trace="t.1", role="door", client="c0",
                  status="ok", code=200, tier="ram", duration_s=0.004,
                  bytes=10)
        rl.record(rid="b", trace="t.2", role="door", client="c1",
                  status="overloaded", code=503, duration_s=0.5)
        rl.close()
        return str(tmp_path)

    def test_table_filter_and_aggregate(self, tmp_path, capsys):
        from blit.__main__ import main

        spool = self._spool(tmp_path)
        assert main(["requests", spool]) == 0
        out = capsys.readouterr().out
        assert "t.1" in out and "t.2" in out
        assert main(["requests", spool, "--slow-ms", "100"]) == 0
        out = capsys.readouterr().out
        assert "t.2" in out and "t.1" not in out
        assert main(["requests", spool, "--status", "503",
                     "--json"]) == 0
        out = capsys.readouterr().out
        assert json.loads(out.strip())["rid"] == "b"
        assert main(["requests", spool, "--aggregate", "--json"]) == 0
        agg = json.loads(capsys.readouterr().out)
        assert agg["records"] == 2
        assert agg["by_status"] == {"ok": 1, "overloaded": 1}
        assert agg["slowest"][0]["trace"] == "t.2"

    def test_aggregate_groups_by_session_scan(self, tmp_path, capsys):
        # ISSUE 19 satellite: door records for catalog-addressed asks
        # carry session/scan, and the aggregate groups on them — the
        # operator's "which scans are hot" view.
        from blit.__main__ import main

        rl = RequestLog(str(tmp_path / "requests-door-h-1.jsonl"))
        for i in range(3):
            rl.record(rid=f"s{i}", trace=f"s.{i}", role="door",
                      client="c", status="ok", code=200, tier="ram",
                      duration_s=0.002, bytes=5,
                      session="AGBT25A_999_01", scan="0001")
        rl.record(rid="x", trace="s.9", role="door", client="c",
                  status="ok", code=200, tier="ram", duration_s=0.9,
                  bytes=5, session="AGBT25A_999_01", scan="0002")
        rl.record(rid="y", trace="s.10", role="door", client="c",
                  status="ok", code=200, tier="derive",
                  duration_s=0.003, bytes=5)  # explicit-path ask
        rl.close()
        assert main(["requests", str(tmp_path), "--aggregate",
                     "--json"]) == 0
        agg = json.loads(capsys.readouterr().out)
        assert agg["by_scan"] == {"AGBT25A_999_01/0001": 3,
                                  "AGBT25A_999_01/0002": 1}
        slow = agg["slowest"][0]
        assert slow["session"] == "AGBT25A_999_01"
        assert slow["scan"] == "0002"


class TestTraceViewFleet:
    def _snapshot(self, tmp_path):
        # Two fake processes: door (aaa) and peer (bbb); the peer's
        # serve.reduce parents onto the door's dispatch span.
        spans = [
            {"name": "fleet.request", "span": "aaa.1", "trace": "aaa.9",
             "parent": None, "t0": 1.0, "duration_s": 0.5, "host": "h",
             "worker": 0, "tid": 1},
            {"name": "fleet.dispatch", "span": "aaa.2", "trace": "aaa.9",
             "parent": "aaa.1", "t0": 1.01, "duration_s": 0.4,
             "host": "h", "worker": 0, "tid": 1,
             "attrs": {"hedge": 1}},
            {"name": "serve.reduce", "span": "bbb.1", "trace": "aaa.9",
             "parent": "aaa.2", "t0": 1.02, "duration_s": 0.3,
             "host": "h", "worker": 0, "tid": 2},
        ]
        h = HistogramStats()
        h.observe(0.5, trace_id="aaa.9")
        path = str(tmp_path / "fleet.snapshot.json")
        with open(path, "w") as f:
            json.dump({"spans": spans,
                       "hists": {"fleet.request_s": h.state()}}, f)
        return path

    def test_stitch_summary_and_exemplar(self, tmp_path, capsys):
        from blit.__main__ import main

        snap = self._snapshot(tmp_path)
        out_path = str(tmp_path / "trace.json")
        assert main(["trace-view", "--fleet", snap, "--out", out_path,
                     "--exemplar", "fleet.request_s"]) == 0
        out = capsys.readouterr().out
        head = json.loads(out.splitlines()[0])
        assert head["spans"] == 3 and head["processes"] == 2
        assert head["cross_process_pairs"] == 1
        assert head["exemplar"]["trace"] == "aaa.9"
        # The exemplar's trace tree prints, hedge tag included.
        assert "serve.reduce" in out and "hedge=1" in out
        doc = json.load(open(out_path))
        assert len([e for e in doc["traceEvents"]
                    if e.get("ph") == "X"]) == 3

    def test_missing_exemplar_fails_loudly(self, tmp_path, capsys):
        from blit.__main__ import main

        snap = self._snapshot(tmp_path)
        assert main(["trace-view", "--fleet", snap,
                     "--exemplar", "no.such_metric"]) == 1

    def test_spool_dir_source(self, tmp_path, capsys):
        """A monitor spool with span batches is a stitchable source
        (tentpole #4's spool half)."""
        from blit.__main__ import main

        pub = monitor.MetricsPublisher(
            interval_s=3600.0, spool_dir=str(tmp_path / "spool"),
            port=-1, spans=True)
        observability.tracer().reset()
        with observability.span("spooled") as sp:
            observability.process_timeline().observe("sched.wait_s", 0.1)
        pub.tick()
        pub.close()
        assert main(["trace-view", "--fleet",
                     str(tmp_path / "spool")]) == 0
        head = json.loads(capsys.readouterr().out.splitlines()[0])
        assert head["spans"] >= 1
        spans, hists = monitor.gather_trace_sources(
            [str(tmp_path / "spool")])
        assert any(s["span"] == sp.span_id for s in spans)
        assert "sched.wait_s" in hists

    def test_trace_view_classic_dump_still_works(self, tmp_path,
                                                 capsys, monkeypatch):
        from blit.__main__ import main

        monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path))
        rec = FlightRecorder(min_interval_s=0.0)
        path = rec.dump("classic: drill")
        assert main(["trace-view", path]) == 0
        assert "classic: drill" in capsys.readouterr().out
