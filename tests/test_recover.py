"""The crash-recovery plane (blit/recover.py, ISSUE 12).

Unit legs: heartbeat leases, the replan ladder (reshaped mesh vs pool
fallback), the /healthz degradation hook.  End-to-end legs: real
supervised multi-process sharded scans under seeded ``kill``/``hang``
faults — detection within the lease budget, degrade-and-resume, and
final products BYTE-IDENTICAL to an uninterrupted pool-oracle run —
plus the supervised live-consumer rejoin drill (``StreamSupervisor``)
and the ``blit chaos`` CLI surface.

The subprocess drills each pay child jax imports; sizes are the chaos
CLI's smallest (2x2 grid, nfft=32) so the whole module stays well
inside the tier-1 budget.
"""

import json
import os
import time

import pytest

from blit.observability import Timeline
from blit.recover import (
    Lease,
    RECOVER_HISTS,
    ScanPlan,
    ScanSupervisor,
    StreamSupervisor,
    active_supervisors,
    lease_age_s,
    read_lease,
    replan,
)
from blit.testing import synth_raw

NFFT, WF = 32, 4


def _grid(tmp_path, nband=2, nbank=2, nchan=2):
    bank_bw = -187.5 / nbank
    grid = []
    for b in range(nband):
        row = []
        for k in range(nbank):
            p = str(tmp_path / f"blc{b}{k}.raw")
            synth_raw(p, nblocks=2, obsnchan=nchan, ntime_per_block=512,
                      seed=b * 8 + k, tone_chan=k % nchan, obsbw=bank_bw,
                      obsfreq=8000.0 + b * 500.0 + (k + 0.5) * bank_bw)
            row.append(p)
        grid.append(row)
    return grid


def _pool_oracle(grid, tmp_path):
    from blit.parallel.scan import reduce_scan_pool_to_files

    d = tmp_path / "oracle"
    d.mkdir(exist_ok=True)
    return reduce_scan_pool_to_files(
        grid, out_dir=str(d), nfft=NFFT, despike=False,
        window_frames=WF)


def _bytes(path):
    with open(path, "rb") as f:
        return f.read()


class TestLease:
    def test_beat_refreshes_and_reads_back(self, tmp_path):
        d = str(tmp_path / "leases")
        lease = Lease(d, 3)
        lease.beat(window=7)
        doc = read_lease(d, 3)
        assert doc["proc"] == 3 and doc["window"] == 7
        assert doc["pid"] == os.getpid()
        age = lease_age_s(d, 3)
        assert age is not None and age < 5.0

    def test_missing_lease_has_no_age(self, tmp_path):
        assert lease_age_s(str(tmp_path), 0) is None

    def test_staleness_grows_without_beats(self, tmp_path):
        d = str(tmp_path)
        lease = Lease(d, 0)
        lease.beat()
        # Backdate the lease file: age is judged by mtime, exactly what
        # a SIGKILLed process leaves behind.
        past = time.time() - 100
        os.utime(Lease.path_for(d, 0), (past, past))
        assert lease_age_s(d, 0) > 99


class TestReplan:
    def test_full_pod_plans_sharded(self):
        assert replan(2, 4, 4, 2) == ScanPlan("sharded", 2, 4)

    def test_survivor_with_whole_mesh_reshapes(self):
        # One host with enough chips for the whole mesh: sharded, 1 proc.
        assert replan(2, 2, 4, 1) == ScanPlan("sharded", 1, 4)

    def test_survivor_too_small_degrades_to_pool(self):
        # The surviving host cannot hold the mesh: pool fallback.
        assert replan(2, 2, 2, 1) == ScanPlan("pool")

    def test_band_row_splitting_is_refused(self):
        # 4 procs over a 2x4 mesh would give each 2 chips — half a band
        # row.  The planner must pick 2 procs (whole rows), not 4.
        assert replan(2, 4, 8, 4) == ScanPlan("sharded", 2, 4)

    def test_no_survivors_is_pool(self):
        assert replan(2, 2, 4, 0) == ScanPlan("pool")


class TestHealthHook:
    def test_mid_recovery_degrades_healthz(self, tmp_path):
        from blit import monitor
        from blit.recover import _register, _unregister

        pub = monitor.MetricsPublisher(interval_s=60, spool_dir=None,
                                       port=None)
        try:
            h = pub.health()
            assert h["status"] == "ok" and h["ok"] is True
            assert h["reasons"] == []
            state = {"kind": "reduce", "phase": "recovering",
                     "attempt": 1, "plan": "pool"}
            key = _register(state)
            try:
                assert any(s["phase"] == "recovering"
                           for s in active_supervisors())
                h = pub.health()
                assert h["status"] == "degraded" and h["ok"] is False
                assert any(r.startswith("recover:") for r in h["reasons"])
            finally:
                _unregister(key)
            h = pub.health()
            assert h["status"] == "ok"
        finally:
            pub.close()


@pytest.mark.timeout(280)
class TestScanSupervisorDrills:
    def _sup(self, grid, out_dir, *, devices_per_proc, faults,
             tl=None, **kw):
        return ScanSupervisor(
            grid, out_dir=str(out_dir), kind="reduce", nfft=NFFT,
            despike=False, window_frames=WF, nprocs=2,
            devices_per_proc=devices_per_proc, lease_ttl_s=3.0,
            poll_s=0.1, max_attempts=3, faults=faults,
            timeline=tl if tl is not None else Timeline(), **kw)

    def test_kill_reshapes_mesh_and_resumes_byte_identical(
            self, tmp_path):
        # SIGKILL proc 0 at window 2 of a 2-process pod whose hosts each
        # hold the WHOLE mesh: detection via process exit, re-plan to a
        # 1-process sharded pod, resume from the cursors — products
        # byte-identical to the uninterrupted pool oracle, and the
        # recover.* histograms populated.
        grid = _grid(tmp_path)
        oracle = _pool_oracle(grid, tmp_path)
        tl = Timeline()
        sup = self._sup(grid, tmp_path / "prod", devices_per_proc=4,
                        faults={0: "mesh.window:kill:after=2"}, tl=tl)
        rep = sup.run()
        assert rep["recovered"] is True
        assert rep["attempts"][0]["failure"]["why"] == "died"
        assert rep["attempts"][0]["failure"]["rc"] == -9
        assert rep["attempts"][1]["plan"] == "sharded"
        assert rep["attempts"][1]["nprocs"] == 1
        for b, (opath, _) in oracle.items():
            got = str(tmp_path / "prod" / os.path.basename(opath))
            assert _bytes(got) == _bytes(opath), f"band {b} differs"
        hists = tl.report().get("hists", {})
        for h in RECOVER_HISTS:
            assert hists.get(h, {}).get("n", 0) >= 1, h
        # No stale cursors after a clean finish.
        assert not [p for p in os.listdir(tmp_path / "prod")
                    if p.endswith(".cursor")]

    def test_kill_without_mesh_capacity_falls_back_to_pool(
            self, tmp_path):
        # Hosts hold only their own mesh share: losing one makes the
        # mesh unformable and the supervisor must degrade to the PR 2
        # pool path — still byte-identical.
        grid = _grid(tmp_path)
        oracle = _pool_oracle(grid, tmp_path)
        sup = self._sup(grid, tmp_path / "prod", devices_per_proc=2,
                        faults={0: "mesh.window:kill:after=2"})
        rep = sup.run()
        assert rep["recovered"] is True
        assert rep["attempts"][1]["plan"] == "pool"
        for b, (opath, _) in oracle.items():
            got = str(tmp_path / "prod" / os.path.basename(opath))
            assert _bytes(got) == _bytes(opath), f"band {b} differs"
        assert not [p for p in os.listdir(tmp_path / "prod")
                    if p.endswith(".cursor")]

    def test_hang_detected_by_lease_expiry(self, tmp_path):
        # A wedged (not dead) peer: the injected hang sleeps far past
        # the lease TTL while the process stays alive — detection must
        # come from lease staleness, and the hung child must be killed.
        grid = _grid(tmp_path)
        oracle = _pool_oracle(grid, tmp_path)
        sup = self._sup(grid, tmp_path / "prod", devices_per_proc=4,
                        faults={0: "mesh.window:hang:after=2:hang=120"})
        rep = sup.run()
        assert rep["recovered"] is True
        fail = rep["attempts"][0]["failure"]
        assert fail["why"] == "hung"
        # Detection latency is bounded by TTL + poll slack.
        assert fail["detect_s"] < 3.0 + 2.0
        for b, (opath, _) in oracle.items():
            got = str(tmp_path / "prod" / os.path.basename(opath))
            assert _bytes(got) == _bytes(opath), f"band {b} differs"


@pytest.mark.timeout(280)
class TestStreamSupervisorDrill:
    def test_killed_consumer_rejoins_byte_identical(self, tmp_path):
        from blit.pipeline import RawReducer

        raw = str(tmp_path / "live.raw")
        synth_raw(raw, nblocks=4, obsnchan=2, ntime_per_block=512,
                  seed=3)
        oracle = str(tmp_path / "oracle.fil")
        RawReducer(nfft=NFFT, chunk_frames=WF).reduce_to_file(raw, oracle)
        out = str(tmp_path / "live.fil")
        tl = Timeline()
        sup = StreamSupervisor(
            raw, out, kind="reduce",
            knobs=dict(nfft=NFFT, chunk_frames=WF),
            replay_rate=500.0, faults="stream.chunk:kill:after=2",
            lease_ttl_s=3.0, poll_s=0.05, max_attempts=3, timeline=tl)
        rep = sup.run()
        assert rep["recovered"] is True
        assert rep["attempts"][0]["failure"]["rc"] == -9
        assert _bytes(out) == _bytes(oracle)
        from blit.stream import StreamCursor

        assert StreamCursor.load(out) is None  # removed on completion
        hists = tl.report().get("hists", {})
        assert hists.get("recover.detect_s", {}).get("n", 0) >= 1


@pytest.mark.timeout(280)
class TestChaosCLI:
    def test_chaos_stream_drill_json(self, tmp_path, capsys):
        from blit.__main__ import main

        json_out = str(tmp_path / "chaos.json")
        rc = main([
            "chaos", "--workload", "stream", "--lease-ttl", "3",
            "--poll", "0.05", "--work-dir", str(tmp_path / "work"),
            "--json-out", json_out,
        ])
        assert rc == 0
        with open(json_out) as f:
            rep = json.load(f)
        assert rep["recovered"] is True
        assert rep["byte_identical"] is True
        assert rep["recover"]["recover.detect_s"].get("n", 0) >= 1
        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["byte_identical"] is True
