"""ProductService front door (blit/serve/service.py; ISSUE 3 acceptance):
the single-flight proof (>= 8 concurrent identical requests -> exactly ONE
reduction, byte-identical results for every caller), the cache hot path
never touching the GUPPI read injection point, failure isolation (no
poisoned single-flight groups), cancellation releasing queue slots, and
the counters a zipfian replay leaves in ``stats()``."""

import threading

import pytest

pytest.importorskip("jax")

from blit import faults  # noqa: E402
from blit.faults import FaultRule, InjectedFault  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.serve import (  # noqa: E402
    Cancelled,
    Overloaded,
    ProductCache,
    ProductRequest,
    ProductService,
    Scheduler,
)
from blit.testing import synth_raw  # noqa: E402

NFFT = 128
NTIME = (8 + 3) * NFFT  # 8 PFB frames at ntap=4


@pytest.fixture(autouse=True)
def clean_faults():
    from blit.faults import RetryPolicy

    faults.clear()
    faults.reset_counters()
    faults.set_io_policy(RetryPolicy(attempts=3, base_s=0.0, jitter=0.0))
    yield
    faults.clear()
    faults.reset_counters()
    faults.set_io_policy(None)


@pytest.fixture
def raw(tmp_path):
    p = str(tmp_path / "a.raw")
    synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=NTIME, tone_chan=1)
    return p


def make_service(tmp_path, *, concurrency=4, queue_depth=16, ram_bytes=1 << 24,
                 disk=True, pool=None):
    tl = Timeline()
    return ProductService(
        cache=ProductCache(str(tmp_path / "cache") if disk else None,
                           ram_bytes=ram_bytes, timeline=tl),
        scheduler=Scheduler(max_concurrency=concurrency,
                            queue_depth=queue_depth, pool=pool, timeline=tl),
        timeline=tl,
    )


class TestSingleFlight:
    def test_concurrent_identical_requests_run_one_reduction(
        self, tmp_path, raw
    ):
        # Acceptance criterion: >= 8 concurrent identical requests ->
        # exactly one reduction runs (proven via the fault-registry hit
        # counter on guppi.open — one open per reduction; the delay rule
        # holds the flight open until every caller has submitted) and all
        # callers receive byte-identical results.
        faults.install(FaultRule("guppi.open", "delay", times=-1,
                                 delay_s=1.0))
        svc = make_service(tmp_path)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        barrier = threading.Barrier(8)
        results, errors = [], []

        def caller(cid):
            try:
                barrier.wait(10)
                hdr, data = svc.get(req, timeout=60, client=f"c{cid}")
                results.append(data)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        assert errors == []
        assert len(results) == 8
        counters = faults.counters()
        assert counters["fault.guppi.open.delay"] == 1  # ONE reduction
        ref = results[0].tobytes()
        assert all(r.tobytes() == ref for r in results)
        assert svc.counts["coalesced"] == 7
        assert svc.counts["scheduled"] == 1
        svc.close()

    def test_failed_flight_does_not_poison_the_group(self, tmp_path, raw):
        # The first reduction dies on a transient injected fault (times=3
        # exhausts the io retry policy's attempts, so the failure escapes
        # the transparent retry layer); every waiter on THAT flight gets
        # the error, but the next identical request starts a fresh flight
        # and succeeds.
        faults.install(FaultRule("guppi.open", "fail", times=3))
        svc = make_service(tmp_path)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        with pytest.raises(InjectedFault):
            svc.get(req, timeout=60)
        hdr, data = svc.get(req, timeout=60)  # fresh flight, no stale error
        assert data.shape[0] > 0
        assert svc.counts["scheduled"] == 2
        svc.close()


class TestCacheHotPath:
    def test_hit_never_touches_the_guppi_read_point(self, tmp_path, raw):
        # Acceptance criterion: after warming, a repeat request is served
        # entirely from the cache — an armed guppi.read FAIL rule proves
        # the hot path cannot even reach the GUPPI layer.
        svc = make_service(tmp_path)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        hdr, warm = svc.get(req, timeout=60)
        rule = FaultRule("guppi.read", "fail", times=-1)
        faults.install(rule)
        hdr2, hot = svc.get(req, timeout=60)
        assert rule.hits == 0  # the injection point was never visited
        assert hot.tobytes() == warm.tobytes()
        assert svc.counts["cache_hits"] == 1
        svc.close()

    def test_disk_tier_survives_a_new_service(self, tmp_path, raw):
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        svc1 = make_service(tmp_path)
        hdr, warm = svc1.get(req, timeout=60)
        svc1.close()
        # New service over the same cache dir (process restart stand-in):
        # the product comes off disk; GUPPI stays cold.
        svc2 = make_service(tmp_path)
        rule = FaultRule("guppi.read", "fail", times=-1)
        faults.install(rule)
        ticket = svc2.submit(req)
        assert ticket.source == "disk"
        hdr2, data = svc2.result(ticket, timeout=10)
        assert rule.hits == 0
        assert data.tobytes() == warm.tobytes()
        svc2.close()

    def test_member_order_does_not_refetch(self, tmp_path):
        from blit.testing import synth_raw_sequence

        paths, _ = synth_raw_sequence(
            str(tmp_path / "seq"), nfiles=2, blocks_per_file=1,
            obsnchan=2, ntime_per_block=NTIME,
        )
        svc = make_service(tmp_path)
        hdr, warm = svc.get(ProductRequest(raw=paths, nfft=NFFT, nint=1),
                            timeout=60)
        # Same members, reversed glob order: same fingerprint, cache hit.
        t = svc.submit(ProductRequest(raw=list(reversed(paths)),
                                      nfft=NFFT, nint=1))
        assert t.source in ("ram", "disk")
        svc.close()


class TestOverloadAndCancel:
    def _blocked_service(self, tmp_path, blocker_raw, queue_depth=1):
        """A budget-1 service whose single slot is held by a delayed
        reduction of ``blocker_raw``."""
        faults.install(FaultRule("guppi.open", "delay", times=-1,
                                 delay_s=1.5, match=blocker_raw))
        svc = make_service(tmp_path, concurrency=1, queue_depth=queue_depth)
        blocker = svc.submit(
            ProductRequest(raw=blocker_raw, nfft=NFFT, nint=1))
        import time
        deadline = time.monotonic() + 5
        while svc.scheduler.running() == 0:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        return svc, blocker

    def test_excess_submissions_get_overloaded_not_a_hang(
        self, tmp_path, raw
    ):
        # Acceptance criterion: budget 1 + full queue -> Overloaded.
        import time

        b = str(tmp_path / "blocker.raw")
        synth_raw(b, nblocks=1, obsnchan=2, ntime_per_block=NTIME, seed=7)
        svc, blocker = self._blocked_service(tmp_path, b)
        queued = svc.submit(ProductRequest(raw=raw, nfft=NFFT, nint=1))
        other = str(tmp_path / "other.raw")
        synth_raw(other, nblocks=1, obsnchan=2, ntime_per_block=NTIME,
                  seed=8)
        t0 = time.monotonic()
        with pytest.raises(Overloaded) as ei:
            svc.submit(ProductRequest(raw=other, nfft=NFFT, nint=1))
        assert time.monotonic() - t0 < 1.0  # rejected at the door
        assert ei.value.retry_after_s > 0
        assert svc.counts["rejected"] == 1
        svc.result(blocker, timeout=60)
        svc.result(queued, timeout=60)
        svc.close()

    def test_cancel_releases_the_queue_slot(self, tmp_path, raw):
        b = str(tmp_path / "blocker.raw")
        synth_raw(b, nblocks=1, obsnchan=2, ntime_per_block=NTIME, seed=7)
        svc, blocker = self._blocked_service(tmp_path, b)
        queued = svc.submit(ProductRequest(raw=raw, nfft=NFFT, nint=1))
        assert svc.cancel(queued)
        with pytest.raises(Cancelled):
            svc.result(queued, timeout=1)
        # The released slot admits new work where it would have Overloaded.
        replacement = svc.submit(ProductRequest(raw=raw, nfft=NFFT, nint=1))
        hdr, data = svc.result(replacement, timeout=60)
        assert data.shape[0] > 0
        svc.result(blocker, timeout=60)
        svc.close()

    def test_coalesced_ticket_cancel_keeps_the_flight(self, tmp_path, raw):
        b = str(tmp_path / "blocker.raw")
        synth_raw(b, nblocks=1, obsnchan=2, ntime_per_block=NTIME, seed=7)
        svc, blocker = self._blocked_service(tmp_path, b, queue_depth=4)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        first = svc.submit(req)
        rider = svc.submit(req)
        assert rider.source == "coalesced"
        assert svc.cancel(rider)  # one rider leaves ...
        hdr, data = svc.result(first, timeout=60)  # ... flight completes
        assert data.shape[0] > 0
        with pytest.raises(Cancelled):
            svc.result(rider, timeout=1)
        svc.result(blocker, timeout=60)
        svc.close()

    def test_result_timeout_is_builtin(self, tmp_path, raw):
        faults.install(FaultRule("guppi.open", "delay", times=-1,
                                 delay_s=1.0))
        svc = make_service(tmp_path)
        t = svc.submit(ProductRequest(raw=raw, nfft=NFFT, nint=1))
        with pytest.raises(TimeoutError):
            svc.result(t, timeout=0.01)
        hdr, data = svc.result(t, timeout=60)  # still completes after
        assert data.shape[0] > 0
        svc.close()

    def test_missing_raw_rejected_at_submit(self, tmp_path):
        svc = make_service(tmp_path)
        with pytest.raises(OSError):
            svc.submit(ProductRequest(raw=str(tmp_path / "nope.raw"),
                                      nfft=NFFT, nint=1))
        svc.close()

    def test_closed_scheduler_does_not_leak_a_flight(self, tmp_path, raw):
        # Regression: a non-Overloaded admission failure (here: the
        # scheduler is closed) must drop the flight from the single-flight
        # table — a leaked jobless flight would make every later identical
        # request coalesce onto it and hang forever.
        svc = make_service(tmp_path)
        svc.scheduler.close()
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        with pytest.raises(RuntimeError, match="closed"):
            svc.submit(req)
        with pytest.raises(RuntimeError, match="closed"):
            svc.submit(req)  # NOT a coalesced hang
        assert svc.counts["coalesced"] == 0
        assert not svc._flights


class TestRequestValidation:
    def test_product_and_explicit_nfft_conflict(self):
        with pytest.raises(ValueError, match="not both"):
            ProductRequest(raw="x.raw", product="0000", nfft=2048)

    def test_list_raw_becomes_hashable_tuple(self):
        r = ProductRequest(raw=["b.raw", "a.raw"], nfft=64)
        assert isinstance(r.raw, tuple)
        hash(r)  # frozen dataclass stays hashable
        assert r.raw_source == ["b.raw", "a.raw"]


class TestZipfReplay:
    def test_stats_report_hits_coalescing_and_queue_wait(self, tmp_path):
        # What a multi-tenant deployment reads from `svc.stats()`: a
        # seeded zipfian mix re-asks for a few hot products, so most of
        # it is served from the cache or rides a flight already running.
        import random

        reqs = []
        for i in range(3):
            p = str(tmp_path / f"p{i}.raw")
            synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=NTIME,
                      seed=i)
            reqs.append(ProductRequest(raw=p, nfft=NFFT, nint=1))
        picks = random.Random(0).choices(
            range(3), weights=[1.0 / (k + 1) ** 1.1 for k in range(3)], k=12)
        assert len(set(picks)) == 3
        svc = make_service(tmp_path, concurrency=2)
        it, lock, errors = iter(picks), threading.Lock(), []

        def client(cid):
            while True:
                with lock:
                    k = next(it, None)
                if k is None:
                    return
                try:
                    svc.get(reqs[k], timeout=120, client=f"client{cid}")
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(e)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(3)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        svc.close()
        assert errors == []
        st = svc.stats()
        assert 0.0 < st["hit_rate"] <= 1.0
        # Every request was a hit, joined a flight, or started one.
        hits = sum(st["cache"][k] for k in ("hit.ram", "hit.disk"))
        assert st["scheduled"] >= 3
        assert hits + st["coalesced"] + st["scheduled"] == 12
        qw = st["queue_wait"]
        assert qw["p99"] >= qw["p50"] >= 0.0


class TestStatsAndObservability:
    def test_stats_shape(self, tmp_path, raw):
        svc = make_service(tmp_path)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        svc.get(req, timeout=60)
        svc.get(req, timeout=60)
        st = svc.stats()
        assert st["requests"] == 2
        assert st["cache_hits"] == 1
        assert st["hit_rate"] == 0.5
        assert st["budget"] >= 1
        assert {"p50", "p99", "n"} <= set(st["queue_wait"])
        # Queue gauges landed on the shared timeline.
        rep = svc.timeline.report()
        assert "gauges" in rep and "sched.wait_s" in rep["gauges"]
        svc.close()

    def test_served_arrays_are_read_only(self, tmp_path, raw):
        svc = make_service(tmp_path)
        hdr, data = svc.get(ProductRequest(raw=raw, nfft=NFFT, nint=1),
                            timeout=60)
        assert not data.flags.writeable
        with pytest.raises(ValueError):
            data[0, 0, 0] = 1.0
        svc.close()


class TestLiveAdmission:
    """kind='stream' live jobs (ISSUE 12 satellite): admitted under a
    session-length capacity hold, never cached/coalesced, product on
    disk byte-identical to the batch path, held capacity reported."""

    def test_stream_request_validation(self, raw):
        with pytest.raises(ValueError, match="out="):
            ProductRequest(raw=raw, nfft=NFFT, kind="stream")
        with pytest.raises(ValueError, match="kind='stream'"):
            ProductRequest(raw=raw, nfft=NFFT, out="/tmp/x.fil")
        r = ProductRequest(raw=raw, nfft=NFFT, kind="stream",
                           out="/tmp/x.fil", session_s=300.0,
                           replay_rate=10.0)
        assert r.session_s == 300.0

    def test_live_session_holds_capacity_and_matches_batch(
            self, tmp_path, raw):
        import os

        from blit.pipeline import RawReducer

        oracle = str(tmp_path / "oracle.fil")
        RawReducer(nfft=NFFT, nint=1).reduce_to_file(
            raw, oracle)
        out = str(tmp_path / "live.fil")
        svc = make_service(tmp_path, concurrency=2)
        req = ProductRequest(raw=raw, nfft=NFFT, kind="stream", out=out,
                             session_s=5.0, replay_rate=10000.0)
        t = svc.submit(req, client="recorder")
        # While (or after) the session runs, stats reports the hold
        # machinery; the ticket resolves with the product ON DISK.
        hdr, data = svc.result(t, timeout=60)
        assert data.shape[0] == 0  # live products live on disk
        assert "held" in svc.stats()
        # result() resolves from the job body; the scheduler's own
        # finally releases the hold a beat later — wait for it.
        import time as _t

        deadline = _t.monotonic() + 5
        while svc.scheduler.held() and _t.monotonic() < deadline:
            _t.sleep(0.01)
        assert svc.scheduler.held() == 0  # released at session end
        with open(out, "rb") as fg, open(oracle, "rb") as fo:
            assert fg.read() == fo.read()
        assert not os.path.exists(out + ".stream-cursor")
        # Never cached: an identical bounded request still reduces.
        st = svc.stats()
        assert st["cache"]["hit.ram"] + st["cache"]["hit.disk"] == 0
        svc.close()

    def test_duplicate_live_session_rejected(self, tmp_path, raw):
        # Two live consumers of ONE product path would interleave
        # appends on the same file and rejoin sidecar: the second ask
        # must be rejected while the first session is in flight.
        svc = make_service(tmp_path, concurrency=2)
        out = str(tmp_path / "dup.fil")
        # The session ends via the tail's idle timeout (the recording
        # is complete and nothing writes a done marker).
        req = ProductRequest(raw=raw, nfft=NFFT, kind="stream", out=out,
                             session_s=9.0, idle_timeout_s=2.0)
        t1 = svc.submit(req, client="a")
        with pytest.raises(Overloaded, match="already in flight"):
            svc.submit(ProductRequest(raw=raw, nfft=NFFT, kind="stream",
                                      out=out, idle_timeout_s=2.0),
                       client="b")
        assert svc.stats()["held_declared_s"] == 9.0
        hdr, _ = svc.result(t1, timeout=60)
        assert hdr.get("nsamps") is not None
        st = svc.stats()
        assert st["held_declared_s"] == 0
        assert st["rejected"] == 1
        svc.close()
