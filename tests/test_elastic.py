"""Elastic fleet controller (blit/serve/elastic.py; ISSUE 17
tentpole): standbys serve NOTHING until admitted, scale-out flips
membership only after the range-scoped warm handoff acks (fail-open on
the deadline, counted), sustained idle drains the coldest peer and
severs its pooled sockets with ZERO requests routed to it afterwards,
the flap guard holds membership through alternating fast-burn/idle at
the hysteresis boundary, and ``/healthz`` answers an honest
``"resizing"`` mid-flip on both the door and every publisher."""

import json
import subprocess
import sys
import time

import pytest

pytest.importorskip("jax")

from blit import monitor  # noqa: E402
from blit.monitor import (  # noqa: E402
    BurnRateEvaluator,
    MetricsPublisher,
    SLObjective,
)
from blit.observability import Timeline  # noqa: E402
from blit.serve import (  # noqa: E402
    FleetController,
    FleetFrontDoor,
    PeerServer,
    ProductCache,
    ProductRequest,
    ProductService,
    Scheduler,
)
from blit.serve.cache import fingerprint_for  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NFFT = 128
NTIME = (8 + 3) * NFFT
TTL = 0.6


class ElasticFleet:
    """In-process peers + standbys + a door driven by EXPLICIT
    observe() ticks — the test_fleet_door rig grown an elastic edge."""

    def __init__(self, tmp_path, npeers=2, nstandby=1, **door_kw):
        self.lease_dir = str(tmp_path / "leases")
        self.servers = {}
        peers = {}
        names = [f"peer{i}" for i in range(npeers)]
        names += [f"standby{j}" for j in range(nstandby)]
        for i, name in enumerate(names):
            tl = Timeline()
            svc = ProductService(
                cache=ProductCache(str(tmp_path / f"cache-{name}"),
                                   ram_bytes=1 << 24, timeline=tl),
                scheduler=Scheduler(max_concurrency=2, queue_depth=8,
                                    timeline=tl, retry_seed=i),
                timeline=tl)
            ps = PeerServer(svc, name=name, lease_dir=self.lease_dir,
                            proc=i, beat_interval_s=0.05).start()
            self.servers[name] = ps
            if not name.startswith("standby"):
                peers[name] = ps.url
        kw = dict(peer_ttl_s=TTL, poll_s=0.05, health_poll_s=0.2,
                  hedge_floor_s=5.0, request_timeout_s=60.0)
        kw.update(door_kw)
        self.timeline = Timeline()
        self.door = FleetFrontDoor(peers, lease_dir=self.lease_dir,
                                   timeline=self.timeline, **kw)
        for j in range(nstandby):
            nm = f"standby{j}"
            self.door.add_standby(nm, self.servers[nm].url,
                                  proc=npeers + j)
        self.ctl = None
        # Warm the lease watches (standbys included).
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            self.door.observe()
            if all(p.watch.seen for p in self.door._peers.values()):
                break
            time.sleep(0.05)

    def controller(self, evaluator=None, **kw):
        kw.setdefault("hysteresis_s", 0.0)
        kw.setdefault("warm_timeout_s", 30.0)
        kw.setdefault("min_peers", 1)
        self.ctl = FleetController(self.door, evaluator, **kw)
        return self.ctl

    def close(self):
        if self.ctl is not None:
            self.ctl.close()
        self.door.close()
        for s in self.servers.values():
            try:
                s.close()
            except Exception:  # noqa: BLE001 — some die mid-test
                pass
            s.service.close(5)


@pytest.fixture
def efleet(tmp_path):
    f = ElasticFleet(tmp_path)
    yield f
    f.close()


def make_req(tmp_path, i=0):
    p = str(tmp_path / f"r{i}.raw")
    synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=NTIME, seed=i)
    return ProductRequest(raw=p, nfft=NFFT, nint=1)


def fp_of(req):
    return fingerprint_for(req.reducer(), req.raw_source)


def grow_until_incoming(efleet, tmp_path, joiner, want=1, cap=24):
    """Add (and serve) products until >= ``want`` of them would MOVE to
    ``joiner`` on admit — tmp_path varies per run, so the key->owner
    draw does too, and the handoff tests need a non-empty range."""
    reqs, fps = [], []
    while len(reqs) < cap:
        r = make_req(tmp_path, len(reqs))
        efleet.door.get(r)
        efleet.door.get(r)  # two hits: firmly in the door's hot map
        reqs.append(r)
        fps.append(fp_of(r))
        incoming = efleet.door.ring.incoming_keys(joiner, fps)
        if want <= len(incoming) < len(fps):
            # The rig's door sees leases only on explicit ticks, and the
            # first reductions (a compile, a loaded machine) can outlast
            # the lease TTL: tick once, so the standby's freshness is
            # judged on its heartbeat and not on how long this loop took.
            efleet.door.observe()
            return reqs, fps, incoming
    raise AssertionError("keyspace never gave the joiner a share")


class TestStandby:
    def test_standby_serves_nothing_until_admitted(self, efleet,
                                                   tmp_path):
        assert "standby0" not in efleet.door.ring
        for i in range(4):
            efleet.door.get(make_req(tmp_path, i))
        sb = efleet.door._peers["standby0"]
        assert sb.standby and not sb.in_ring
        assert sb.requests == 0
        assert efleet.servers["standby0"].counts["product"] == 0

    def test_standby_listed_in_health_not_a_casualty(self, efleet):
        doc = efleet.door.health()
        assert doc["ok"] and doc["status"] == "ok"
        assert "standby0" in doc.get("standbys", [])
        assert not any("standby0" in r for r in doc["reasons"])

    def test_stalled_standby_is_not_admissible(self, efleet):
        ctl = efleet.controller()
        efleet.servers["standby0"].close()  # beats stop
        time.sleep(TTL * 1.5)
        efleet.door.observe()
        assert ctl._pick_standby() is None
        assert ctl.scale_out() is None


class TestScaleOut:
    def test_warm_handoff_lands_before_the_flip(self, efleet, tmp_path):
        ctl = efleet.controller()
        reqs, fps, incoming = grow_until_incoming(
            efleet, tmp_path, "standby0")
        sb_cache = efleet.servers["standby0"].service.cache
        assert not any(sb_cache.contains(fp) for fp in incoming)
        rec = ctl.scale_out()
        assert rec["action"] == "scale-out" and rec["peer"] == "standby0"
        assert "standby0" in efleet.door.ring
        # The ack gated the flip: every incoming hot key was ALREADY
        # on the joiner when scale_out returned.
        assert rec["acked"] and rec["hinted"] == len(incoming)
        assert rec["completed"] == len(incoming)
        for fp in incoming:
            assert sb_cache.contains(fp)
        # Only the joiner's range was streamed — nothing else.
        assert rec["hinted"] < len(fps)
        c = efleet.timeline.report()
        assert c["elastic.scale_out"]["calls"] == 1
        assert "elastic.resize_s" in efleet.timeline.hists
        # The admitted peer now serves its range byte-identically.
        moved = next(r for r in reqs if fp_of(r) in set(incoming))
        before = efleet.door._peers["standby0"].requests
        efleet.door.get(moved)
        assert efleet.door._peers["standby0"].requests == before + 1

    def test_handoff_deadline_fails_open(self, efleet, tmp_path):
        # wait_s=0 burns before the joiner computes anything: the flip
        # must STILL happen (elastic capacity now beats a warm cache)
        # and the timeout must be counted.
        ctl = efleet.controller(warm_timeout_s=0.0)
        grow_until_incoming(efleet, tmp_path, "standby0")
        rec = ctl.scale_out()
        assert rec is not None and not rec["acked"]
        assert "standby0" in efleet.door.ring
        rep = efleet.timeline.report()
        assert rep["elastic.warm_timeout"]["calls"] == 1


class TestScaleIn:
    def test_sustained_idle_drains_retires_and_severs(self, efleet,
                                                      tmp_path):
        # The drained-then-removed satellite, end to end: idle ticks
        # accumulate, the coldest peer drains, leaves the ring, its
        # pooled keep-alives are severed, ZERO later requests route to
        # it, and its still-beating lease cannot rejoin it.
        reqs = [make_req(tmp_path, i) for i in range(6)]
        for r in reqs:
            efleet.door.get(r)
        ctl = efleet.controller(idle_windows=2)
        rec = None
        for _ in range(4):
            rec = ctl.observe(interval_s=30.0)
            if rec is not None:
                break
        assert rec is not None and rec["action"] == "scale-in"
        victim = rec["peer"]
        assert rec["drained"]
        assert victim not in efleet.door.ring
        p = efleet.door._peers[victim]
        assert p.retired and not p.in_ring
        # Pooled sockets for the departed peer are GONE (the stale-
        # socket satellite): no idle entry names its port.
        port = int(p.url.rsplit(":", 1)[1])
        assert not any(str(port) in k for k in efleet.door.pool.stats())
        # Zero requests to a departed peer — and no lease rejoin, even
        # though the process is alive and beating.
        before = p.requests
        for _ in range(6):
            efleet.door.observe()
            time.sleep(0.05)
        for r in reqs:
            efleet.door.get(r)
        assert p.requests == before
        assert victim not in efleet.door.ring
        rep = efleet.timeline.report()
        assert rep.get("fleet.rejoin") is None
        assert rep["elastic.scale_in"]["calls"] == 1
        assert rep["fleet.retire"]["calls"] == 1

    def test_min_peers_floor_refuses(self, efleet):
        ctl = efleet.controller(min_peers=2, idle_windows=1)
        for _ in range(4):
            assert ctl.observe(interval_s=30.0) is None
        assert ctl.scale_in() is None
        assert len(efleet.door.ring) == 2

    def test_traffic_resets_the_idle_run(self, efleet, tmp_path):
        # idle_rps=0: ANY request in the interval counts as traffic.
        ctl = efleet.controller(idle_windows=3, idle_rps=0.0)
        req = make_req(tmp_path)
        ctl.observe(interval_s=30.0)
        ctl.observe(interval_s=30.0)
        assert ctl._idle_ticks == 2
        efleet.door.get(req)  # real traffic lands mid-run
        ctl.observe(interval_s=30.0)
        assert ctl._idle_ticks == 0  # the run restarted
        assert len(efleet.door.ring) == 2


def burn_delta(bad: bool) -> Timeline:
    tl = Timeline()
    for _ in range(10):
        tl.observe("fleet.request_s", 1.0 if bad else 0.001)
    return tl


class TestHysteresisDrill:
    def test_flap_boundary_is_one_action_per_window(self, tmp_path):
        # The pinned satellite: a REAL BurnRateEvaluator fed
        # alternating fast-burn/idle intervals right at the flap
        # boundary (fast window spans one of each, so breached() stays
        # true throughout) must produce AT MOST ONE scale action per
        # hysteresis window — page -> idle -> page cannot thrash
        # membership.
        efleet = ElasticFleet(tmp_path, npeers=2, nstandby=2)
        try:
            ev = BurnRateEvaluator(
                [SLObjective("slo", "fleet.request_s", 0.5,
                             budget=0.05)],
                fast_window=2, slow_window=4, fast_burn=4.0,
                slow_burn=2.0)
            fake = [1000.0]
            ctl = efleet.controller(
                evaluator=ev, hysteresis_s=100.0, idle_windows=1,
                clock=lambda: fake[0])
            actions = []
            for i in range(10):
                ev.observe(burn_delta(bad=(i % 2 == 0)), 1.0)
                act = ctl.observe(interval_s=1.0)
                if act is not None:
                    actions.append(act)
                fake[0] += 10.0
            # 10 ticks x 10 s = exactly one hysteresis window: the
            # first page acted, everything after was suppressed.
            assert len(actions) == 1
            assert actions[0]["action"] == "scale-out"
            rep = efleet.timeline.report()
            assert rep["elastic.flap_suppressed"]["calls"] >= 8
            # The window lapses: exactly one more action fires, then
            # the guard arms again.
            fake[0] = 1000.0 + 150.0
            ev.observe(burn_delta(bad=True), 1.0)
            act = ctl.observe(interval_s=1.0)
            assert act is not None and act["action"] == "scale-out"
            ev.observe(burn_delta(bad=False), 1.0)
            assert ctl.observe(interval_s=1.0) is None  # guarded again
        finally:
            efleet.close()


class TestResizingHealth:
    def test_door_healthz_is_resizing_mid_flip(self, efleet):
        ctl = efleet.controller()
        assert efleet.door.health()["status"] == "ok"
        ctl._set_resizing("scale-out:standby0")
        doc = efleet.door.health()
        assert doc["status"] == "resizing" and not doc["ok"]
        assert "resizing:scale-out:standby0" in doc["reasons"]
        ctl._set_resizing(None)
        assert efleet.door.health()["status"] == "ok"

    def test_publisher_health_carries_the_resize(self, efleet,
                                                 tmp_path):
        # The register_health_hook satellite: EVERY publisher health
        # document in the process answers "resizing" mid-flip.
        ctl = efleet.controller()
        pub = MetricsPublisher(interval_s=999.0, timeline=Timeline(),
                               spool_dir=str(tmp_path / "spool"))
        try:
            assert pub.health()["status"] == "ok"
            ctl._set_resizing("scale-in:peer1")
            doc = pub.health()
            assert doc["status"] == "resizing" and not doc["ok"]
            assert "elastic:scale-in:peer1" in doc["reasons"]
            ctl._set_resizing(None)
            assert pub.health()["status"] == "ok"
            # close() unregisters the hook — a dead controller cannot
            # haunt later publishers.
            ctl._set_resizing("scale-out:standby0")
            ctl.close()
            efleet.ctl = None
            assert pub.health()["status"] == "ok"
        finally:
            pub.close()


class TestWarmHints:
    def test_warm_hints_are_range_scoped(self, efleet, tmp_path):
        reqs = [make_req(tmp_path, i) for i in range(4)]
        for r in reqs:
            efleet.door.get(r)
            efleet.door.get(r)
        fps = [fp_of(r) for r in reqs]
        hints = efleet.door.warm_hints(limit=10)
        assert {fp for fp, _ in hints} == set(fps)
        assert all(rec is not None for _, rec in hints)
        sub = set(fps[:2])
        scoped = efleet.door.warm_hints(in_range=lambda fp: fp in sub,
                                        limit=10)
        assert {fp for fp, _ in scoped} == sub


@pytest.mark.slow
class TestElasticCLI:
    """The REAL multi-process legs (subprocess peers, SIGTERM/SIGKILL)
    — the CI fleet-smoke job's shape, kept out of the tier-1 budget."""

    def test_chaos_fleet_resize_drill(self, tmp_path):
        out = tmp_path / "resize.json"
        res = subprocess.run(
            [sys.executable, "-m", "blit", "chaos", "--fleet",
             "--fault", "resize", "--peers", "3",
             "--fleet-requests", "60", "--fleet-distinct", "6",
             "--nfft", "32", "--lease-ttl", "2.0",
             "--work-dir", str(tmp_path / "work"),
             "--json-out", str(out)],
            capture_output=True, text=True, timeout=600)
        assert res.returncode == 0, res.stdout + res.stderr
        rep = json.loads(out.read_text())
        assert rep["ok"] and rep["killed_mid_handoff"]
        assert rep["resizing_status"] == "resizing"
        assert rep["flip_completed"] and rep["byte_identical"]
        assert rep["detected"] and rep["hit_rate_recovered"]
