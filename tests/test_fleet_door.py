"""FleetFrontDoor (blit/serve/fleet.py; ISSUE 14 tentpole): ring
routing with cross-host dedupe, replica failover byte-identity, lease
ejection + rejoin, hedged reads off the live p99, the pinned
deadline-expired-at-the-door acceptance, cache-warm replication,
aggregated /healthz, and graceful drain with hot-entry hints."""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

pytest.importorskip("jax")

from blit import faults  # noqa: E402
from blit.faults import FaultRule  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.serve import (  # noqa: E402
    DeadlineExpired,
    FleetFrontDoor,
    FrontDoorServer,
    Overloaded,
    PeerServer,
    ProductCache,
    ProductRequest,
    ProductService,
    Scheduler,
)
from blit.serve.cache import fingerprint_for  # noqa: E402
from blit.serve.http import (  # noqa: E402
    decode_product,
    http_json,
    wire_request,
)
from blit.testing import synth_raw  # noqa: E402

NFFT = 128
NTIME = (8 + 3) * NFFT
TTL = 0.6


@pytest.fixture(autouse=True)
def clean_faults():
    faults.clear()
    faults.reset_counters()
    yield
    faults.clear()
    faults.reset_counters()


class Fleet:
    """Three in-process peers + a door driven by EXPLICIT observe()
    ticks (no background thread) — deterministic liveness for tests."""

    def __init__(self, tmp_path, npeers=3, **door_kw):
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
                            beat_interval_s=0.05).start()
            self.servers.append(ps)
            peers[f"peer{i}"] = ps.url
        kw = dict(peer_ttl_s=TTL, poll_s=0.05, health_poll_s=0.2,
                  hedge_floor_s=5.0, request_timeout_s=60.0)
        kw.update(door_kw)
        self.timeline = Timeline()
        self.door = FleetFrontDoor(peers, lease_dir=self.lease_dir,
                                   timeline=self.timeline, **kw)
        # Warm the lease watches (3 beats arm the TTL).
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            self.door.observe()
            if all(p.watch.seen for p in self.door._peers.values()):
                break
            time.sleep(0.05)

    def kill(self, name):
        """Die unannounced: socket closed, beats stop — the SIGKILL
        shape, in-process."""
        i = int(name.replace("peer", ""))
        self.servers[i].close()

    def wait_ejected(self, name, budget=10.0):
        deadline = time.monotonic() + budget
        while name in self.door.ring:
            assert time.monotonic() < deadline, "never ejected"
            self.door.observe()
            time.sleep(0.05)

    def close(self):
        self.door.close()
        for s in self.servers:
            try:
                s.close()
            except Exception:  # noqa: BLE001 — some die mid-test
                pass
            s.service.close(5)


@pytest.fixture
def fleet(tmp_path):
    f = Fleet(tmp_path)
    yield f
    f.close()


def make_req(tmp_path, i=0):
    p = str(tmp_path / f"r{i}.raw")
    synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=NTIME, seed=i)
    return ProductRequest(raw=p, nfft=NFFT, nint=1)


def owner_of(fleet, req):
    fp = fingerprint_for(req.reducer(), req.raw_source)
    return fp, fleet.door.ring.owners(fp)


class TestRouting:
    def test_same_request_routes_to_one_owner(self, fleet, tmp_path):
        req = make_req(tmp_path)
        fp, owners = owner_of(fleet, req)
        h1, d1 = fleet.door.get(req)
        h2, d2 = fleet.door.get(req)
        assert np.array_equal(d1, d2)
        by_peer = {n: p.requests for n, p in fleet.door._peers.items()}
        assert by_peer[owners[0]] == 2  # both landed on the OWNER
        assert sum(by_peer.values()) == 2
        # ... where the peer served the second from its cache.
        i = int(owners[0].replace("peer", ""))
        assert fleet.servers[i].service.counts["cache_hits"] >= 1

    def test_member_order_cannot_split_the_cache(self, fleet, tmp_path):
        # Cross-host dedupe is free because fingerprints are
        # order-insensitive (the tentpole's routing claim).
        a = str(tmp_path / "m0.raw")
        b = str(tmp_path / "m1.raw")
        synth_raw(a, nblocks=1, obsnchan=2, ntime_per_block=NTIME)
        synth_raw(b, nblocks=1, obsnchan=2, ntime_per_block=NTIME,
                  seed=5)
        r1 = ProductRequest(raw=(a, b), nfft=NFFT)
        r2 = ProductRequest(raw=(b, a), nfft=NFFT)
        fp1, _ = owner_of(fleet, r1)
        fp2, _ = owner_of(fleet, r2)
        assert fp1 == fp2


class TestFailover:
    def test_dead_owner_fails_over_byte_identical(self, fleet,
                                                  tmp_path):
        req = make_req(tmp_path, 1)
        _, owners = owner_of(fleet, req)
        _, oracle = fleet.door.get(req)  # computed on the owner
        fleet.kill(owners[0])  # socket refused; lease still un-stale
        h, d = fleet.door.get(req)  # immediate failover to the replica
        assert np.array_equal(d, oracle)
        assert fleet.door._peers[owners[0]].failures >= 1
        stats = fleet.door.stats()
        assert stats["counters"]["fleet.failover"] >= 1

    def test_all_peers_overloaded_raises_overloaded(self, fleet,
                                                    tmp_path,
                                                    monkeypatch):
        req = make_req(tmp_path, 2)
        for s in fleet.servers:
            def refuse(*a, **kw):
                raise Overloaded("full", retry_after_s=0.2)

            # submit is the peer handler's seam (ISSUE 15: it needs
            # the ticket) — and where admission refuses.
            monkeypatch.setattr(s.service, "submit", refuse)
        with pytest.raises(Overloaded):
            fleet.door.get(req)


class TestEjectionRejoin:
    def test_stale_lease_ejects_and_reroutes(self, fleet, tmp_path):
        req = make_req(tmp_path, 3)
        fp, owners = owner_of(fleet, req)
        _, oracle = fleet.door.get(req)
        victim = owners[0]
        fleet.kill(victim)
        time.sleep(TTL * 1.5)
        fleet.wait_ejected(victim)
        assert victim not in fleet.door.ring.peers()
        # The key range re-routed: the replica owns it now and serves
        # byte-identically.
        new_owners = fleet.door.ring.owners(fp)
        assert victim not in new_owners
        _, d = fleet.door.get(req)
        assert np.array_equal(d, oracle)
        stats = fleet.door.stats()
        assert stats["counters"]["fleet.eject"] == 1
        assert stats["hists"]["fleet.detect_s"]["n"] == 1

    def test_fresh_beats_rejoin_the_ring(self, fleet, tmp_path):
        from blit.recover import Lease

        victim = "peer2"
        fleet.kill(victim)
        time.sleep(TTL * 1.5)
        fleet.wait_ejected(victim)
        # The peer comes back: beats resume (a new process would beat
        # the same proc slot), the door rejoins it.
        lease = Lease(fleet.lease_dir, 2)
        deadline = time.monotonic() + 10
        while victim not in fleet.door.ring:
            assert time.monotonic() < deadline, "never rejoined"
            lease.beat()
            fleet.door.observe()
            time.sleep(0.05)
        assert fleet.door.stats()["counters"]["fleet.rejoin"] == 1


class TestHedgedReads:
    def test_slow_owner_hedges_to_replica_first_wins(self, tmp_path):
        fleet = Fleet(tmp_path, hedge_floor_s=0.1)
        try:
            req = make_req(tmp_path, 4)
            _, owners = owner_of(fleet, req)
            fleet.door.get(req)  # warm the owner's cache
            # Make the owner SLOW (not dead): the hedge, not failover,
            # must cover it.  The in-process servers share this fault
            # registry, and a ONE-SHOT delay rule is eaten by the first
            # /product handled — the owner's — so the hedge lands clean.
            faults.install(FaultRule(point="peer.request", mode="delay",
                                     delay_s=2.0, times=1))
            t0 = time.perf_counter()
            h, d = fleet.door.get(req)
            dt = time.perf_counter() - t0
            stats = fleet.door.stats()
            assert stats["counters"]["fleet.hedge"] >= 1
            assert stats["counters"].get("fleet.hedge.win", 0) >= 1
            # The hedge cut the tail: well under the injected 2 s.
            assert dt < 1.5
        finally:
            fleet.close()

    def test_hedge_is_bounded_to_one_duplicate(self, tmp_path):
        fleet = Fleet(tmp_path, hedge_floor_s=0.05)
        try:
            req = make_req(tmp_path, 5)
            faults.install(FaultRule(point="peer.request", mode="delay",
                                     delay_s=0.5, times=-1))
            fleet.door.get(req)
            stats = fleet.door.stats()
            # One request, every peer slow: exactly ONE hedge launched
            # (<= 2x compute on the hedged slice, by construction).
            assert stats["counters"]["fleet.hedge"] == 1
            assert stats["counters"]["fleet.route"] <= 2
        finally:
            fleet.close()


class TestDeadlinePropagation:
    def test_expired_at_the_door_is_never_dispatched(self, fleet,
                                                     tmp_path):
        req = make_req(tmp_path, 6)
        before = sum(p.requests for p in fleet.door._peers.values())
        before_http = [s.counts["product"] for s in fleet.servers]
        with pytest.raises(DeadlineExpired):
            fleet.door.get(req, deadline_s=0.0)
        # The acceptance pin: no peer dispatch, no peer HTTP hit.
        assert sum(p.requests
                   for p in fleet.door._peers.values()) == before
        assert [s.counts["product"] for s in fleet.servers] == before_http
        stats = fleet.door.stats()
        assert stats["counters"]["fleet.deadline_expired"] == 1

    def test_remaining_budget_rides_the_wire(self, fleet, tmp_path,
                                             monkeypatch):
        req = make_req(tmp_path, 7)
        seen = {}
        for s in fleet.servers:
            real = s.service.submit

            def spy(r, _real=real, **kw):
                seen.setdefault("deadline_s", kw.get("deadline_s"))
                return _real(r, **kw)

            monkeypatch.setattr(s.service, "submit", spy)
        fleet.door.get(req, deadline_s=30.0)
        # The peer saw the REMAINING budget, not the original.
        assert seen["deadline_s"] is not None
        assert 0 < seen["deadline_s"] <= 30.0


class TestWarmReplication:
    def test_hot_entry_warms_the_replicas(self, tmp_path):
        fleet = Fleet(tmp_path, hot_hits=2)
        try:
            req = make_req(tmp_path, 8)
            fp, owners = owner_of(fleet, req)
            fleet.door.get(req)
            fleet.door.get(req)  # crosses hot_hits -> replicas warm
            replica = owners[1]
            i = int(replica.replace("peer", ""))
            svc = fleet.servers[i].service
            deadline = time.monotonic() + 60
            while not svc.cache.contains(fp):
                assert time.monotonic() < deadline, "replica never warmed"
                time.sleep(0.05)
            # Losing the owner now degrades hit-rate, not correctness —
            # and not even hit-rate for THIS key.
            fleet.kill(owners[0])
            time.sleep(TTL * 1.5)
            fleet.wait_ejected(owners[0])
            before = svc.counts["scheduled"]
            _, d = fleet.door.get(req)
            assert svc.counts["scheduled"] == before  # served from cache
        finally:
            fleet.close()


class TestFleetHealth:
    def test_aggregated_healthz(self, fleet):
        fleet.door.observe()
        doc = fleet.door.health()
        assert doc["ok"] and doc["status"] == "ok"
        assert doc["peers"] == 3 and doc["peers_ok"] == 3
        victim = "peer1"
        fleet.kill(victim)
        time.sleep(TTL * 1.5)
        fleet.wait_ejected(victim)
        doc = fleet.door.health()
        assert not doc["ok"] and doc["status"] == "degraded"
        assert f"peer-ejected:{victim}" in doc["reasons"]
        assert victim not in doc["ring"]

    def test_peer_degradation_folds_in(self, fleet):
        fleet.door._peers["peer0"].last_health = {
            "ok": False, "status": "degraded",
            "reasons": ["quarantine:2"]}
        doc = fleet.door.health()
        assert "peer:peer0:quarantine:2" in doc["reasons"]
        assert doc["status"] == "degraded"

    def test_empty_ring_is_down(self, fleet):
        for name in list(fleet.door._peers):
            fleet.door.ring.remove(name)
            fleet.door._peers[name].in_ring = False
        assert fleet.door.health()["status"] == "down"


class TestDoorDrain:
    def test_drain_refuses_new_and_hints_hot_entries(self, tmp_path):
        fleet = Fleet(tmp_path, hot_hits=100)  # no mid-test warms
        try:
            req = make_req(tmp_path, 9)
            fp, owners = owner_of(fleet, req)
            for _ in range(3):
                fleet.door.get(req)
            res = fleet.door.drain(timeout=10)
            assert res["hints"] >= 1
            with pytest.raises(Overloaded):
                fleet.door.get(req)
            # The hints landed as /warm submissions on the owner set.
            warmed = sum(s.counts["warm"] for s in fleet.servers)
            assert warmed >= 1
        finally:
            fleet.close()


class TestFrontDoorServer:
    def test_http_door_serves_and_aggregates(self, fleet, tmp_path):
        req = make_req(tmp_path, 10)
        with FrontDoorServer(fleet.door) as fd:
            status, _, body = http_json("POST", fd.url, "/product",
                                        wire_request(req), timeout=120)
            assert status == 200
            _, d = decode_product(body)
            _, direct = fleet.door.get(req)
            assert np.array_equal(d, direct)
            status, _, health = http_json("GET", fd.url, "/healthz")
            assert status == 200 and "peers_ok" in health
            status, _, text = http_json("GET", fd.url, "/metrics")
            assert status == 200
            from blit.monitor import parse_prometheus

            assert parse_prometheus(text)
            status, _, stats = http_json("GET", fd.url, "/stats")
            assert status == 200 and stats["ring"]

    def test_deadline_expired_maps_to_504_at_the_door(self, fleet,
                                                      tmp_path):
        req = make_req(tmp_path, 11)
        with FrontDoorServer(fleet.door) as fd:
            status, _, body = http_json(
                "POST", fd.url, "/product",
                wire_request(req, deadline_s=0.0), timeout=30)
            assert status == 504
            assert body["etype"] == "DeadlineExpired"


@pytest.mark.slow
class TestFleetCLI:
    """The REAL multi-process legs (subprocess peers + SIGKILL) — the
    CI fleet-smoke job's shape, kept out of the tier-1 budget."""

    def test_chaos_fleet_kill_drill(self, tmp_path):
        out = tmp_path / "report.json"
        res = subprocess.run(
            [sys.executable, "-m", "blit", "chaos", "--fleet",
             "--fault", "kill", "--fleet-requests", "60",
             "--fleet-distinct", "3", "--nfft", "128",
             "--lease-ttl", "1.5", "--poll", "0.1",
             "--work-dir", str(tmp_path / "work"),
             "--json-out", str(out)],
            capture_output=True, text=True, timeout=600)
        assert res.returncode == 0, res.stdout + res.stderr
        rep = json.loads(out.read_text())
        assert rep["ok"] and rep["detected"] and rep["byte_identical"]
        assert rep["healthz"]["after_detect"] == "degraded"
        assert rep["hit_rate_recovered"]
