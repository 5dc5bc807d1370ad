"""Graceful drain + admission satellites (ISSUE 14): seeded jitter on
``Overloaded.retry_after_s`` (the thundering-herd fix), dispatch-time
deadline expiry (an already-dead request is NEVER computed — the
acceptance pin), ``Scheduler.drain``, and ``ProductService.drain``
releasing ``kind="stream"`` capacity holds instead of leaking them on
interpreter exit."""

import os
import signal
import threading
import time

import pytest

pytest.importorskip("jax")

from blit.observability import Timeline  # noqa: E402
from blit.serve import (  # noqa: E402
    Cancelled,
    DeadlineExpired,
    Overloaded,
    ProductCache,
    ProductRequest,
    ProductService,
    Scheduler,
)
from blit.serve.http import install_drain_handler  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NFFT = 128
NTIME = (8 + 3) * NFFT


@pytest.fixture
def raw(tmp_path):
    p = str(tmp_path / "a.raw")
    synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=NTIME,
              tone_chan=1)
    return p


def _blocked_scheduler(**kw):
    """A scheduler whose single slot is pinned by a job waiting on the
    returned event."""
    sched = Scheduler(max_concurrency=1, **kw)
    gate = threading.Event()
    running = threading.Event()

    def blocker():
        running.set()
        gate.wait(30)

    sched.submit(blocker, client="blocker")
    assert running.wait(5)
    return sched, gate


class TestRetryAfterJitter:
    def test_seeded_jitter_is_deterministic_and_spread(self):
        def rejections(seed):
            sched, gate = _blocked_scheduler(queue_depth=1,
                                             retry_seed=seed)
            sched.submit(lambda: None, client="q")  # fills the queue
            out = []
            for _ in range(4):
                with pytest.raises(Overloaded) as ei:
                    sched.submit(lambda: None, client="q")
                out.append(ei.value.retry_after_s)
            gate.set()
            sched.close(5)
            return out

        a = rejections(7)
        b = rejections(7)
        c = rejections(8)
        # Deterministic across runs with the same seed (the RetryPolicy
        # discipline), different across seeds, and SPREAD across
        # consecutive rejections — the herd does not return in lockstep.
        assert a == b
        assert a != c
        assert len(set(a)) > 1
        # Bounded: est=0 -> base 0.1s, jitter +/-50%.
        assert all(0.05 <= v <= 0.15 for v in a)

    def test_jitter_disabled_keeps_raw_estimate(self):
        sched, gate = _blocked_scheduler(queue_depth=1, retry_jitter=0.0)
        sched.submit(lambda: None, client="q")
        vals = set()
        for _ in range(3):
            with pytest.raises(Overloaded) as ei:
                sched.submit(lambda: None, client="q")
            vals.add(ei.value.retry_after_s)
        gate.set()
        sched.close(5)
        assert vals == {0.1}


class TestDispatchTimeDeadlineExpiry:
    def test_expired_in_queue_is_never_computed(self):
        clock = [0.0]
        sched = Scheduler(max_concurrency=1, clock=lambda: clock[0])
        gate = threading.Event()
        started = threading.Event()

        def blocker():
            started.set()
            gate.wait(30)

        sched.submit(blocker, client="blocker")
        assert started.wait(5)
        ran = threading.Event()
        job = sched.submit(ran.set, client="late", deadline_s=5.0)
        clock[0] = 10.0  # the deadline burns while queued
        gate.set()
        assert job.wait(5)
        with pytest.raises(DeadlineExpired):
            job.result(1)
        assert not ran.is_set()  # the pin: never dispatched, never run
        assert sched.counts["expired"] == 1
        sched.close(5)

    def test_deadline_subclass_keeps_overloaded_contract(self):
        # Existing back-off handlers catch Overloaded; DeadlineExpired
        # must ride that path.
        assert issubclass(DeadlineExpired, Overloaded)

    def test_unexpired_job_still_runs(self):
        sched = Scheduler(max_concurrency=1)
        job = sched.submit(lambda: 41 + 1, client="ok", deadline_s=30.0)
        assert job.result(5) == 42
        sched.close(5)


class TestDispatchExpiryFlightDelivery:
    def test_expired_flight_fails_waiters_and_never_leaks(self, tmp_path,
                                                          raw):
        # The review regression: a dispatch-time expiry drops the job
        # without running fn, so the single-flight group must be failed
        # through on_drop — otherwise waiters hang forever and every
        # later identical request coalesces onto the dead flight.
        clock = [0.0]
        tl = Timeline()
        sched = Scheduler(max_concurrency=1, clock=lambda: clock[0],
                          timeline=tl)
        service = ProductService(
            cache=ProductCache(None, ram_bytes=1 << 24, timeline=tl),
            scheduler=sched, timeline=tl)
        gate = threading.Event()
        started = threading.Event()

        def blocker():
            started.set()
            gate.wait(30)

        sched.submit(blocker, client="blocker")
        assert started.wait(5)
        ticket = service.submit(ProductRequest(raw=raw, nfft=NFFT),
                                deadline_s=5.0, client="late")
        clock[0] = 10.0  # burn the deadline in queue
        gate.set()
        with pytest.raises(DeadlineExpired):
            service.result(ticket, timeout=10)
        deadline = time.monotonic() + 10
        while service.stats()["inflight"]:
            assert time.monotonic() < deadline, "flight leaked"
            time.sleep(0.02)
        # A fresh identical request starts a NEW reduction and succeeds.
        _, data = service.get(ProductRequest(raw=raw, nfft=NFFT),
                              timeout=120)
        assert data.shape[0] > 0
        service.close(5)


class TestSchedulerDrain:
    def test_drain_cancels_queued_and_finishes_running(self):
        sched, gate = _blocked_scheduler(queue_depth=8)
        queued = [sched.submit(lambda: None, client=f"c{i}")
                  for i in range(3)]
        gate.set()
        cancelled = sched.drain(timeout=10)
        assert cancelled == 3
        for j in queued:
            with pytest.raises(Cancelled):
                j.result(1)
        with pytest.raises(RuntimeError):
            sched.submit(lambda: None)


def make_service(tmp_path, max_concurrency=2):
    tl = Timeline()
    return ProductService(
        cache=ProductCache(str(tmp_path / "cache"), ram_bytes=1 << 24,
                           timeline=tl),
        scheduler=Scheduler(max_concurrency=max_concurrency,
                            queue_depth=8, timeline=tl),
        timeline=tl,
    )


class TestServiceDrain:
    def test_drain_releases_stream_capacity_hold(self, tmp_path, raw):
        service = make_service(tmp_path)
        out = str(tmp_path / "live.fil")
        # A live session over a recording that never gets its .done
        # marker: without drain, the FileTailSource tails forever and
        # the capacity hold leaks on interpreter exit.
        ticket = service.submit(
            ProductRequest(raw=raw, kind="stream", out=out, nfft=NFFT),
            client="live")
        deadline = time.monotonic() + 20
        # Drain only once the session has taken its first chunk (the
        # resumable writer creates the product then): a drain that beats
        # the tailer to it leaves an empty stream, which is an error, not
        # a product — and was this test's flake on a loaded machine.
        while service.scheduler.held() < 1 or not os.path.exists(out):
            assert time.monotonic() < deadline, "session never started"
            time.sleep(0.02)
        res = service.drain(timeout=30)
        assert res["stopped"] == 1
        assert service.scheduler.held() == 0  # the hold RELEASED
        hdr, _ = service.result(ticket, timeout=10)
        assert os.path.exists(out)  # the session finished its product
        assert hdr.get("nsamps", 0) > 0
        service.close(5)

    def test_draining_service_refuses_new_submissions(self, tmp_path,
                                                      raw):
        service = make_service(tmp_path)
        service.drain(timeout=10)
        with pytest.raises(Overloaded) as ei:
            service.submit(ProductRequest(raw=raw, nfft=NFFT))
        assert ei.value.retry_after_s > 0
        service.close(5)

    def test_drain_delivers_cancelled_to_queued_flights(self, tmp_path,
                                                        raw):
        service = make_service(tmp_path, max_concurrency=1)
        gate = threading.Event()
        service.scheduler.submit(lambda: gate.wait(30), client="blocker")
        ticket = service.submit(ProductRequest(raw=raw, nfft=NFFT),
                                client="queued")
        gate.set()
        service.drain(timeout=10)
        with pytest.raises(Cancelled):
            service.result(ticket, timeout=5)
        service.close(5)


class TestSignalWiring:
    def test_sigterm_drains_then_exits(self):
        drained = []
        uninstall = install_drain_handler(lambda: drained.append(1))
        try:
            with pytest.raises(SystemExit) as ei:
                os.kill(os.getpid(), signal.SIGTERM)
                # The handler fires between bytecodes; give it one.
                time.sleep(0.5)
            assert ei.value.code == 128 + signal.SIGTERM
            assert drained == [1]
        finally:
            uninstall()

    def test_no_exit_mode_runs_drain_in_place(self):
        drained = []
        uninstall = install_drain_handler(lambda: drained.append(1),
                                          exit_after=False)
        try:
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(0.5)
            assert drained == [1]
        finally:
            uninstall()
