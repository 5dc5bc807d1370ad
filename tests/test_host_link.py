"""The host link is budgeted by transfer (ISSUE 27): ``blit.device.HostLink``
alone, on the CPU, with the link faked and transfers that land when the
test says so.  What the pump and the mesh feed do with it is in
``tests/test_staging.py``."""

import sys
import threading
import time

import pytest

jax = pytest.importorskip("jax")

from blit import device  # noqa: E402
from blit.observability import Timeline  # noqa: E402

LINK = 1000


class Transfer:
    """What ``jax.device_put`` hands back, for the budget's purposes: in
    flight until the test lands it (or until ``after`` seconds passed)."""

    def __init__(self, nbytes, after=None):
        self.nbytes = nbytes
        self._landed = threading.Event()
        self._due = None if after is None else time.perf_counter() + after

    def land(self):
        self._landed.set()

    def is_deleted(self):
        return False

    def is_ready(self):
        return self._landed.is_set() or (
            self._due is not None and time.perf_counter() >= self._due)

    def block_until_ready(self):
        if self._due is not None:
            time.sleep(max(0.0, self._due - time.perf_counter()))
            self._landed.set()
        assert self._landed.wait(timeout=10), "never landed"
        return self


class Host:
    def __init__(self, nbytes):
        self.nbytes = nbytes


@pytest.fixture
def link(monkeypatch):
    monkeypatch.setattr(device, "host_link_bytes", lambda: LINK)
    monkeypatch.setattr(jax, "device_put",
                        lambda host, dev=None: Transfer(host.nbytes))
    return device.HostLink()


def wait_row(tl):
    return tl.report()["wait.link"]


def test_admits_at_once_what_fits_under_the_link(link):
    tl = Timeline()
    held = [link.put(Host(300), timeline=tl) for _ in range(3)]
    assert link.inflight_bytes() == 900
    assert wait_row(tl) == {"calls": 0, "seconds": 0.0, "bytes": 0,
                            "gbps": 0.0, "byte_free": True}
    peak = tl.report()["hists"]["link.inflight_bytes"]
    assert peak["n"] == 3 and peak["max"] == 900
    del held


@pytest.mark.parametrize("nbytes", [100, 101, 400])
def test_blocks_what_would_reach_the_link_until_the_oldest_lands(link, nbytes):
    # 900 in flight: 100 more is exactly the region and is not counted on
    # to fit.
    tl = Timeline()
    first, second = link.put(Host(450), timeline=tl), link.put(
        Host(450), timeline=tl)
    got = []
    t = threading.Thread(
        target=lambda: got.append(link.put(Host(nbytes), timeline=tl)))
    t.start()
    t.join(timeout=0.3)
    assert t.is_alive() and not got  # blocked, in wait.link
    first.land()
    t.join(timeout=10)
    assert not t.is_alive() and got
    assert link.inflight_bytes() == 450 + nbytes
    row = wait_row(tl)
    assert row["calls"] == 1 and row["seconds"] >= 0.25
    assert tl.report()["hists"]["link.inflight_bytes"]["max"] == 900
    del second


def test_retire_lets_go_of_what_has_landed_and_of_nothing_else(link):
    # What a caller does before it donates a put it has waited in.
    a, b = link.put(Host(400)), link.put(Host(300))
    a.land()
    link.retire()
    assert [h for h, _ in link._puts] == [b] and link._bytes == 300
    b.land()
    link.retire()
    assert link._puts == [] and link._bytes == 0


def test_releases_on_readiness_of_the_put_or_of_what_consumed_it(link):
    a = link.put(Host(400))
    made = []

    def program(x):  # consumes the put array, returns a tree of results
        made.append(x)
        return Transfer(0), [Transfer(0)]

    rows, (acc,) = link.put(Host(400), then=program)
    assert link.inflight_bytes() == 800
    a.land()
    assert link.inflight_bytes() == 400
    made[0].land()      # the voltages are up, their program still runs
    rows.land()
    assert link.inflight_bytes() == 400
    acc.land()
    assert link.inflight_bytes() == 0 and link._puts == []
    tl = Timeline()
    link.put(Host(900), timeline=tl)
    assert wait_row(tl)["calls"] == 0


def test_an_oversize_transfer_is_admitted_alone(link):
    tl = Timeline()
    big = link.put(Host(2500), timeline=tl)  # nothing in flight: at once
    assert wait_row(tl)["calls"] == 0 and link.inflight_bytes() == 2500
    got = []
    t = threading.Thread(
        target=lambda: got.append(link.put(Host(2500), timeline=tl)))
    t.start()
    t.join(timeout=0.3)
    assert t.is_alive()  # not beside the first
    big.land()
    t.join(timeout=10)
    assert not t.is_alive() and link.inflight_bytes() == 2500
    assert wait_row(tl)["calls"] == 1


def test_a_put_waits_for_a_fetch_on_another_thread(link):
    tl = Timeline()
    inside, leave = threading.Event(), threading.Event()

    def fetching():
        with link.fetch(350, tl):  # counts twice: 700
            inside.set()
            assert leave.wait(timeout=10)

    f = threading.Thread(target=fetching)
    f.start()
    assert inside.wait(timeout=10)
    got = []
    p = threading.Thread(
        target=lambda: got.append(link.put(Host(350), timeline=tl)))
    p.start()
    p.join(timeout=0.3)
    assert p.is_alive()  # 700 + 350 do not fit
    leave.set()
    p.join(timeout=10)
    f.join(timeout=10)
    assert not p.is_alive() and not f.is_alive()
    assert link.inflight_bytes() == 350 and wait_row(tl)["calls"] == 1


@pytest.mark.parametrize("nbytes,rides", [(100, True), (149, True),
                                          (150, False), (600, False)])
def test_a_fetch_counts_twice_so_a_large_one_takes_turns(link, nbytes, rides):
    tl = Timeline()
    up = link.put(Host(700), timeline=tl)
    inside = threading.Event()

    def fetching():
        with link.fetch(nbytes, tl):
            inside.set()

    f = threading.Thread(target=fetching)
    f.start()
    assert inside.wait(timeout=0.3) == rides
    up.land()
    f.join(timeout=10)
    assert not f.is_alive() and inside.is_set()
    assert wait_row(tl)["calls"] == (0 if rides else 1)
    peak = tl.report()["hists"]["link.inflight_bytes"]["max"]
    assert peak == (700 + 2 * nbytes if rides else max(700, 2 * nbytes))
    assert link.inflight_bytes() == 0
    assert link.fetch_takes_all(nbytes) == (2 * nbytes >= LINK)


def test_a_failed_put_gives_its_bytes_back(link, monkeypatch):
    def refuse(host, dev=None):
        raise RuntimeError("no device")

    monkeypatch.setattr(jax, "device_put", refuse)
    with pytest.raises(RuntimeError):
        link.put(Host(600))
    monkeypatch.setattr(jax, "device_put",
                        lambda host, dev=None: Transfer(host.nbytes))
    with pytest.raises(RuntimeError):
        link.put(Host(600), then=refuse)
    with pytest.raises(ValueError), link.fetch(300):
        raise ValueError
    assert link.inflight_bytes() == 0


def test_two_threads_never_hold_more_than_the_link(link, monkeypatch):
    """A dispatcher putting and a readback thread fetching, faster than
    transfers land: what is really in flight (counted here, not by the
    budget) stays under the link at every transfer's start."""
    lock, live, over = threading.Lock(), [], []

    def flying():
        return sum(t.nbytes for t in live if not t.is_ready())

    def start(t):
        with lock:
            if flying() and flying() + t.nbytes >= LINK:
                over.append((flying(), t.nbytes))
            live.append(t)
        return t

    monkeypatch.setattr(
        jax, "device_put",
        lambda host, dev=None: start(Transfer(host.nbytes, after=0.002)))
    tl, done = Timeline(), []

    def dispatcher():
        for i in range(150):
            link.put(Host(150 + 37 * (i % 9)), timeline=tl)
        done.append("put")

    def readback():
        for i in range(150):
            t = Transfer(50 + 27 * (i % 7))
            with link.fetch(t.nbytes, tl):
                start(t)
                time.sleep(0.0005)
                t.land()
        done.append("fetch")

    threads = [threading.Thread(target=f) for f in (dispatcher, readback)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert sorted(done) == ["fetch", "put"]
    assert not over, over[:3]
    assert tl.report()["hists"]["link.inflight_bytes"]["max"] < LINK
    assert wait_row(tl)["calls"] > 0  # it did engage
    time.sleep(0.005)
    assert link.inflight_bytes() == 0


def test_is_free_where_the_backend_stages_nothing():
    assert device.host_link_bytes() is None  # this backend is the CPU
    import numpy as np

    link, tl = device.HostLink(), Timeline()
    x = link.put(np.arange(64, dtype=np.int8), timeline=tl)
    with link.fetch(1 << 40, tl):
        pass
    assert not link.fetch_takes_all(1 << 40)
    assert isinstance(x, jax.Array) and link._puts == []
    assert link.inflight_bytes() == 0
    table = tl.report()
    assert table["wait.link"]["calls"] == 0  # declared all the same
    assert "hists" not in table
    assert device.host_link() is device.host_link()
