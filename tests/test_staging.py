"""RAW bytes land in faulted memory (ISSUE 25): the staging pool keeps what
one stretch of work held at its peak — a recorder-width rotation, not a
constant sized for toy chunks — the mesh scan's window feed reads through
it, and every reduction's own ``stages`` says whether it did.

Sizes are scaled down: ``_DEFAULT_BUDGET`` (2 GiB in the field) is patched
to ``FLOOR`` bytes and the "2.95 GB" chunk buffers weigh a few KB."""

import dataclasses
import filecmp
import gc
import json
import sys
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit import hostmem  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.pipeline import RawReducer  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

FLOOR = 1000          # stands for the 2 GiB every default pool may keep
BIG = (1200,)         # one buffer larger than the whole floor
NFFT, NINT = 64, 2


@pytest.fixture
def default_pool(monkeypatch):
    """A pool built the way the process pool is when nothing is set
    (conftest clears ``BLIT_STAGING_BYTES``), its floor scaled down."""
    monkeypatch.setattr(hostmem, "_DEFAULT_BUDGET", FLOOR)
    pool = hostmem.SlabPool()
    assert pool.budget_bytes is None
    return pool


@pytest.fixture
def fresh_process_pool():
    """The process pool, empty, for tests that reduce for real."""
    hostmem._reset_pool()
    yield hostmem.slab_pool()
    hostmem._reset_pool()


def reduction(pool, shape, n, tl=None):
    """One stretch of work: ``n`` buffers of ``shape`` held together, then
    all given back -> their data pointers."""
    held = [pool.take(shape, np.int8, tl) for _ in range(n)]
    ptrs = sorted(a.ctypes.data for a in held)
    for a in held:
        pool.give(a, tl)
    return ptrs


class TestAdmission:
    def test_a_rotation_over_the_old_default_is_kept_and_reused(
            self, default_pool):
        # 3 x 1200 B against a 1000 B floor: 3 x 2.95 GB against 2 GiB.
        tl = Timeline()
        first = reduction(default_pool, BIG, 3, tl)
        st = default_pool.stats()
        assert (st["free_slabs"], st["dropped"], st["lent_bytes"]) == (3, 0, 0)
        assert st["free_bytes"] == 3 * 1200 == st["budget_bytes"]
        tl2 = Timeline()
        assert reduction(default_pool, BIG, 3, tl2) == first  # same memory
        table = tl2.report()
        assert table["staging.reuse"]["calls"] == 3
        assert table["staging.alloc"]["calls"] == 0
        assert table["staging.drop"]["calls"] == 0
        assert tl.report()["staging.alloc"]["calls"] == 3

    @pytest.mark.parametrize("how", ["argument", "env", "site_config"])
    def test_an_explicit_budget_is_a_byte_cap_as_before(self, how,
                                                        monkeypatch):
        if how == "argument":
            pool = hostmem.SlabPool(budget_bytes=FLOOR)
        elif how == "env":
            monkeypatch.setenv("BLIT_STAGING_BYTES", str(FLOOR))
            pool = hostmem.SlabPool()
        else:
            import blit.config as config

            monkeypatch.setattr(config, "DEFAULT", dataclasses.replace(
                config.DEFAULT, staging_pool_bytes=FLOOR))
            pool = hostmem.SlabPool()
        assert pool.budget_bytes == FLOOR
        reduction(pool, BIG, 3)  # each slab is over the cap: none is kept
        st = pool.stats()
        assert (st["free_bytes"], st["dropped"]) == (0, 3)
        reduction(pool, (400,), 3)  # 1200 B into 1000: the oldest leaves
        st = pool.stats()
        assert (st["free_bytes"], st["free_slabs"], st["dropped"]) \
            == (800, 2, 4)
        assert st["budget_bytes"] == FLOOR  # what was held moved nothing

    @pytest.mark.parametrize("how", ["argument", "env"])
    def test_zero_disables_as_before(self, how, monkeypatch):
        if how == "env":
            monkeypatch.setenv("BLIT_STAGING_BYTES", "0")
            pool = hostmem.SlabPool()
        else:
            pool = hostmem.SlabPool(budget_bytes=0)
        reduction(pool, (16,), 2)
        reduction(pool, (16,), 2)
        st = pool.stats()
        assert (st["free_bytes"], st["reused"], st["allocated"],
                st["dropped"]) == (0, 0, 4, 4)

    def test_held_bytes_do_not_grow_over_alternating_shapes(
            self, default_pool):
        a, b = ((1500,), 3), ((300,), 2)   # 4500 B and 600 B at the peak
        held = []
        for i in range(10):
            shape, n = (a, b)[i % 2]
            reduction(default_pool, shape, n)
            st = default_pool.stats()
            # Between reductions: no more than the one just ended held
            # (or the floor every default pool may keep).
            assert st["free_bytes"] <= max(FLOOR, shape[0] * n)
            assert st["lent_bytes"] == 0
            held.append(st["free_bytes"])
        assert held[2:] == held[:2] * 4  # a cycle, not a ramp

    def test_a_process_that_changes_shape_lets_the_old_one_go(
            self, default_pool):
        reduction(default_pool, BIG, 3)
        reduction(default_pool, (100,), 2)
        st = default_pool.stats()
        # The small stretch's peak is under the floor: the floor rules,
        # and the big slabs (each over it) went, oldest shape first.
        assert st["free_bytes"] == 200 and st["dropped"] == 3
        assert st["budget_bytes"] == FLOOR

    def test_small_shapes_coexist_under_the_floor(self, default_pool):
        reduction(default_pool, (100,), 2)
        reduction(default_pool, (200,), 2)
        reduction(default_pool, (100,), 2)
        st = default_pool.stats()
        assert (st["free_bytes"], st["reused"], st["dropped"]) == (600, 2, 0)

    def test_a_slab_stays_while_the_stretch_that_holds_more_runs(
            self, default_pool):
        # Window w's slabs come back while w+1's are out: the running
        # peak (two sets) is the cap, so the returned set is kept.
        w1 = [default_pool.take(BIG) for _ in range(2)]
        w2 = [default_pool.take(BIG) for _ in range(2)]
        for s in w1:
            default_pool.give(s)
        assert default_pool.stats()["free_slabs"] == 2
        w3 = [default_pool.take(BIG) for _ in range(2)]
        assert default_pool.stats()["reused"] == 2
        for s in w2 + w3:
            default_pool.give(s)
        st = default_pool.stats()
        assert (st["free_slabs"], st["dropped"]) == (4, 0)

    def test_a_dropped_buffer_leaves_the_ledger(self, default_pool):
        # An error path never gives its buffers back: the ledger holds
        # them weakly, so the leak neither keeps the stretch open nor
        # inflates what the next one is seen to hold.
        kept = default_pool.take(BIG)
        lost = default_pool.take(BIG)
        assert default_pool.stats()["lent_bytes"] == 2400
        del lost
        gc.collect()
        assert default_pool.stats()["lent_bytes"] == 1200
        default_pool.give(kept)
        st = default_pool.stats()
        assert (st["lent_bytes"], st["free_slabs"]) == (0, 1)
        reduction(default_pool, (100,), 1)
        assert default_pool.stats()["budget_bytes"] == FLOOR

    def test_a_buffer_never_given_by_the_pool_is_not_in_the_ledger(
            self, default_pool):
        default_pool.give(hostmem.aligned_empty((100,), np.int8))
        st = default_pool.stats()
        assert (st["free_slabs"], st["lent_bytes"]) == (1, 0)

    def test_no_buffer_is_lent_twice_under_contention(self, default_pool):
        # More workers than cores, a short switch interval: every worker
        # stamps the buffer it holds and must read its own stamp back.
        errors, stop = [], threading.Event()

        def worker(me):
            try:
                while not stop.is_set():
                    a = default_pool.take((256,), np.int8)
                    a[:] = me
                    b = default_pool.take((256,), np.int8)
                    b[:] = me
                    if not ((a == me).all() and (b == me).all()):
                        errors.append(f"worker {me}: foreign stamp")
                    default_pool.give(a)
                    default_pool.give(b)
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(repr(e))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker, args=(i + 1,),
                                        daemon=True) for i in range(32)]
            for t in threads:
                t.start()
            stop.wait(1.0)
            stop.set()
            for t in threads:
                t.join(30)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors[:3]
        st = default_pool.stats()
        assert st["lent_bytes"] == 0
        assert st["free_bytes"] <= max(FLOOR, 64 * 256)
        assert st["reused"] + st["allocated"] > 64


class TestReductionReportsItsStaging:
    def toy(self, tmp_path):
        p = str(tmp_path / "s.raw")
        synth_raw(p, nblocks=4, obsnchan=2, ntime_per_block=2048)
        return p

    def test_second_same_shape_reduction_allocates_nothing(
            self, tmp_path, fresh_process_pool):
        # In memory: the chunk rotation and the stream's head slab alone
        # stage (the readback ring, CPU backends only, takes 1-3 slabs as
        # the sink's timing has it).  A rotation buffer is a chunk's NEW
        # samples, chunk_frames * nfft of them (ISSUE 29).
        p = self.toy(tmp_path)
        tables, products = [], []
        for _ in range(3):
            red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=4)
            products.append(red.reduce(p)[1])
            tables.append(red.timeline.report())
        first, second, third = tables
        # the whole rotation, and the head
        assert first["staging.alloc"]["calls"] == 4
        assert first["staging.reuse"]["calls"] == 0
        for t in (second, third):
            assert t["staging.alloc"]["calls"] == 0
            assert t["staging.reuse"]["calls"] == 4
            assert t["staging.drop"]["calls"] == 0
        st = fresh_process_pool.stats()
        assert (st["lent_bytes"], st["dropped"], st["free_slabs"]) \
            == (0, 0, 4)
        shapes = sorted(shape for shape, _ in fresh_process_pool._free)
        assert shapes == [(2, 3 * NFFT, 2, 2), (2, 4 * NFFT, 2, 2)]
        np.testing.assert_array_equal(products[0], products[2])

    def test_to_file_the_ring_comes_back_too(self, tmp_path,
                                             fresh_process_pool):
        p = self.toy(tmp_path)
        tables = []
        for tag in ("one", "two"):
            red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=4)
            red.reduce_to_file(p, str(tmp_path / f"{tag}.fil"))
            tables.append(red.timeline.report())
        first, second = tables
        # 3 chunk buffers + the head slab + 1-3 ring slabs the first
        # time; the second finds all of the first's back, and at most
        # wants 2 ring slabs more.
        assert first["staging.alloc"]["calls"] >= 5
        assert second["staging.reuse"]["calls"] >= 5
        assert second["staging.alloc"]["calls"] <= 2
        assert second["staging.drop"]["calls"] == 0
        assert fresh_process_pool.stats()["lent_bytes"] == 0
        assert filecmp.cmp(tmp_path / "one.fil", tmp_path / "two.fil",
                           shallow=False)

    def test_with_pooling_off_every_reduction_says_it_allocated(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("BLIT_STAGING_BYTES", "0")
        hostmem._reset_pool()
        try:
            p = self.toy(tmp_path)
            for tag in ("one", "two"):
                red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=4)
                red.reduce_to_file(p, str(tmp_path / f"{tag}.fil"))
                table = red.timeline.report()
                assert table["staging.reuse"]["calls"] == 0
                assert table["staging.alloc"]["calls"] >= 2
                assert table["staging.drop"]["calls"] \
                    == table["staging.alloc"]["calls"]
        finally:
            hostmem._reset_pool()

    def test_blit_reduce_prints_the_three_counts(self, tmp_path, capsys,
                                                 fresh_process_pool):
        from blit.__main__ import main

        p = self.toy(tmp_path)
        lines = []
        for tag in ("one", "two"):
            assert main(["reduce", p, "-o", str(tmp_path / f"{tag}.fil"),
                         "--nfft", str(NFFT), "--nint", str(NINT)]) == 0
            lines.append(json.loads(
                capsys.readouterr().out.strip().splitlines()[-1]))
        # (The ring's 1-3 slabs follow the sink's timing; the rotation's
        # three buffers and the head slab do not.)
        assert lines[0]["stages"]["staging.alloc"]["calls"] >= 5
        assert lines[0]["stages"]["staging.reuse"]["calls"] == 0
        assert lines[1]["stages"]["staging.alloc"]["calls"] <= 2
        assert lines[1]["stages"]["staging.reuse"]["calls"] >= 5
        assert lines[1]["stages"]["staging.drop"] == {
            "calls": 0, "seconds": 0.0, "bytes": 0, "gbps": 0.0,
            "byte_free": True}


class TestTheLinkIsBudgetedByTransfer:
    """Once the read is fast the next chunk's input is enqueued while the
    last one's transfers are in flight.  What the runtime stages at speed
    (``blit.device.host_link_bytes``) is drawn on per transfer — a channel
    group up, a product down (``blit.device.HostLink``, ISSUE 27) — so the
    pump overlaps chunks whatever two whole chunks would weigh (until
    PR 27 it took turns where they did not fit: ISSUE 25's depth-1 rule)."""

    CHUNK = 2 * 4 * NFFT * 2 * 2  # one toy chunk buffer, bytes
    HEAD = 2 * 3 * NFFT * 2 * 2   # the stream's filter state, bytes

    @pytest.mark.parametrize("nint", [
        NINT,    # each chunk integrates inside its own program
        11,      # carried: an integration straddles chunks, a 3-frame
                 # flush chunk, most chunks fetch nothing
    ])
    @pytest.mark.parametrize("link", [
        None,                # a backend that stages nothing (the CPU)
        1 << 30,             # two chunks fit
        2 * CHUNK,           # they do not: chunks took turns here
        CHUNK + CHUNK // 4,  # two groups fit, two chunks do not
    ])
    def test_the_next_chunk_goes_up_while_this_one_computes(
            self, tmp_path, monkeypatch, link, nint):
        import blit.outplane as O
        from blit import device, observability

        seen = []

        class Spy(O.OutputRotation):
            def put(self, *a, **kw):
                seen.append(self.depth)
                return super().put(*a, **kw)

        monkeypatch.setattr(O, "OutputRotation", Spy)
        # As on the chip: a chunk goes up in two channel groups.
        monkeypatch.setattr(RawReducer, "_channel_block", lambda *a: 1)
        p = str(tmp_path / "s.raw")
        synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=2048)

        def reducer():
            return RawReducer(nfft=NFFT, nint=nint, chunk_frames=4)

        reducer().reduce_to_file(p, str(tmp_path / "ref.fil"))
        seen.clear()
        monkeypatch.setattr(device, "host_link_bytes", lambda: link)
        monkeypatch.setattr(device, "_HOST_LINK", device.HostLink())
        wait = jax.block_until_ready

        def a_program_takes_a_while(x):
            time.sleep(0.02)
            return wait(x)

        monkeypatch.setattr(jax, "block_until_ready", a_program_takes_a_while)
        observability.tracer().reset()
        red = reducer()
        red.reduce_to_file(p, str(tmp_path / "got.fil"))
        assert len(seen) > 3 and set(seen) == {2}
        assert filecmp.cmp(tmp_path / "ref.fil", tmp_path / "got.fil",
                           shallow=False)
        spans = observability.tracer().span_dicts()

        def ends(name):
            return sorted((s["t0"], s["t0"] + s["duration_s"])
                          for s in spans if s["name"] == name)

        dispatches, waits = ends("dispatch"), ends("device")
        assert len(dispatches) == len(waits) == len(seen)
        # Chunk k+1's dispatch opens before chunk k's programs are done.
        for (t0, _), (_, done) in zip(dispatches[1:], waits):
            assert t0 < done
        table = red.timeline.report()
        assert table["wait.link"]["byte_free"]  # declared, engaged or not
        if link is None:
            assert "link.inflight_bytes" not in table["hists"]
        else:
            peak = table["hists"]["link.inflight_bytes"]
            # Every group's put (the head rides with the first chunk's
            # two) and every fetched product was admitted.
            assert peak["n"] == 2 * len(seen) + table["readback"]["calls"]
            assert self.CHUNK // 2 <= peak["max"] < link
        # Two groups a chunk, whatever the link: the head went up once,
        # every later filter state stayed on the chip, no sample twice.
        assert table["state.head"]["calls"] == 2
        assert table["state.head"]["bytes"] == self.HEAD
        assert table["state.carry"]["calls"] == 2 * (len(seen) - 1)
        assert table["link.put"]["calls"] == 2 * len(seen) + 2
        # 4096 samples: 61 frames, of which the 60 in whole chunks (and
        # whole integrations) are dispatched, and the head's 3.
        assert table["link.put"]["bytes"] == (60 + 3) * self.CHUNK // 4
        assert "state" not in table
        if nint == 11:
            assert table["integrate.carry"]["calls"] > 3
            assert table["readback"]["calls"] < len(seen)

    def test_a_large_product_is_down_before_the_next_chunk_goes_up(
            self, tmp_path, monkeypatch):
        """A product whose fetch takes the whole link (it counts twice) is
        waited out before the next chunk is dispatched: H2D and D2H take
        turns there, the order PR 25 measured safe for recorder-width
        hi-res."""
        from blit import device, observability

        monkeypatch.setattr(RawReducer, "_channel_block", lambda *a: 1)
        p = str(tmp_path / "s.raw")
        synth_raw(p, nblocks=2, obsnchan=2, ntime_per_block=2048)
        product = 4 // NINT * 2 * NFFT * 4  # one chunk's rows, bytes
        link = 2 * product
        assert self.CHUNK // 2 < link  # a group fits, twice a product not
        monkeypatch.setattr(device, "host_link_bytes", lambda: link)
        monkeypatch.setattr(device, "_HOST_LINK", device.HostLink())
        red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=4)
        put = jax.device_put

        def marked_put(*a, **kw):
            red.timeline.mark("test.put")
            return put(*a, **kw)

        monkeypatch.setattr(jax, "device_put", marked_put)
        observability.tracer().reset()
        red.reduce_to_file(p, str(tmp_path / "got.fil"))
        spans = observability.tracer().span_dicts()
        monkeypatch.undo()
        RawReducer(nfft=NFFT, nint=NINT, chunk_frames=4).reduce_to_file(
                       p, str(tmp_path / "ref.fil"))
        assert filecmp.cmp(tmp_path / "ref.fil", tmp_path / "got.fil",
                           shallow=False)
        puts = sorted(s["t0"] for s in spans if s["name"] == "test.put")
        down = sorted(s["t0"] + s["duration_s"] for s in spans
                      if s["name"] == "readback")
        assert len(puts) == 2 * len(down) > 6
        for k, fetched in enumerate(down[:-1]):
            assert puts[2 * (k + 1)] >= fetched
        # ... and is waited out in OutputRotation.put, where its slab is
        # handed to the sink before the next chunk is dispatched.
        starts = sorted(s["t0"] for s in spans if s["name"] == "dispatch")
        assert len(starts) == len(down)
        for k, fetched in enumerate(down[:-1]):
            assert fetched <= starts[k + 1]
        table = red.timeline.report()
        assert table["wait.out_slot"]["calls"] >= len(down) - 1
        # (The fetch, alone on the link, is what counts for all of it.)
        assert table["hists"]["link.inflight_bytes"]["max"] == link

    @pytest.mark.parametrize("env,want", [(None, 4 << 30), ("123", 123),
                                          ("junk", 4 << 30)])
    def test_the_limit_is_the_runtimes_premapped_region(self, monkeypatch,
                                                        env, want):
        from blit import device

        if env is None:
            monkeypatch.delenv("TPU_PREMAPPED_BUFFER_SIZE", raising=False)
        else:
            monkeypatch.setenv("TPU_PREMAPPED_BUFFER_SIZE", env)
        assert device.host_link_bytes() is None  # this backend is the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert device.host_link_bytes() == want


class SpyPool(hostmem.SlabPool):
    """Records which scan window each slab served, and checks on every
    hand-over that the window it served last has synchronized: the
    ``device`` stage of window ``w`` (its ``block_until_ready``) is the
    ``w+1``-th to close on the scan's timeline."""

    def __init__(self):
        super().__init__()
        self.served = {}      # id(slab) -> window it was last taken for
        self.takes = []       # (window, shape, reused)
        self.early = []

    @staticmethod
    def _window(tl):          # the feed thread's `ingest` of window w is
        return tl.stages["ingest"].calls  # still open when it takes

    def take(self, shape, dtype=np.int8, timeline=None):
        before = self.reused
        arr = super().take(shape, dtype, timeline)
        last = self.served.get(id(arr))
        if last is not None and timeline.stages["device"].calls <= last:
            self.early.append(("take", last))
        self.served[id(arr)] = self._window(timeline)
        self.takes.append((self._window(timeline), tuple(shape),
                           self.reused > before))
        return arr

    def give(self, arr, timeline=None):
        w = self.served[id(arr)]
        if timeline.stages["device"].calls <= w:
            self.early.append(("give", w))
        super().give(arr, timeline)


class TestMeshScanWindowFeed:
    NBANK = 4

    def scan(self, tmp_path):
        bank_bw = -187.5 / self.NBANK
        row = []
        for k in range(self.NBANK):
            row.append(str(tmp_path / f"blc0{k}.raw"))
            synth_raw(row[k], nblocks=4, obsnchan=2, ntime_per_block=1024,
                      seed=k, obsbw=bank_bw,
                      obsfreq=8000.0 + (k + 0.5) * bank_bw)
        return [row]

    def test_windows_stage_through_the_pool(self, tmp_path, monkeypatch):
        from blit.parallel import scan as S

        paths = self.scan(tmp_path)
        spy = SpyPool()
        monkeypatch.setattr(hostmem, "_POOL", spy)
        aliased, roles = [], []
        put = S.M.put_local_shards

        def spying_put(blocks, mesh, shape, role="voltages", **kw):
            lent = list(spy._lent.values())
            # Sample words: one int32 per dual-pol sample, a VIEW of the
            # int8 slab the bank was read into.
            aliased.append(all(
                blk.dtype == np.int32
                and any(np.shares_memory(blk, s) for s in lent)
                for blk in blocks.values()))
            # (Window w is put before its `dispatch`, on the loop's thread.)
            roles.append((kw["timeline"].stages["dispatch"].calls, role,
                          shape[-1]))
            return put(blocks, mesh, shape, role, **kw)

        monkeypatch.setattr(S.M, "put_local_shards", spying_put)
        (tmp_path / "mesh").mkdir()
        (tmp_path / "pool").mkdir()
        tl = Timeline()
        # 61 usable frames in windows of 16: three full and a ragged last.
        got = S.reduce_scan_mesh_to_files(
            paths, out_dir=str(tmp_path / "mesh"), nfft=NFFT, nint=NINT,
            window_frames=16, timeline=tl)
        nwin = tl.stages["ingest"].calls
        shapes = [sorted({s for w, s, _ in spy.takes if w == i})
                  for i in range(nwin)]
        assert nwin >= 4
        # A window stages its NEW samples only (ISSUE 31), and ONE shape
        # class per scan holds them: the ragged last window reads into
        # the head of a full-window slab (a shape of its own pushed a set
        # of full-window slabs out of the pool every pass; ISSUE 30).
        body = (2, 16 * NFFT, 2, 2)
        assert shapes[1] == shapes[2] == shapes[-1] == [body]
        # The stream's head is a read of its own into a slab of its own:
        # the first window's, and no other's.
        assert shapes[0] == sorted([body, (2, (4 - 1) * NFFT, 2, 2)])
        # ... and a put of its own, under its own rule, before the first
        # window's samples; every other put is a window's new samples.
        assert roles[:2] == [(0, "filter_state", 3 * NFFT),
                             (0, "voltages", 16 * NFFT)]
        assert [(w, r) for w, r, _ in roles[2:]] \
            == [(w, "voltages") for w in range(1, nwin)]
        # Three sets go round (a window on the chips, one being put, one
        # being read): windows 0 and 1 allocate, window 2 does unless
        # window 0 is back already, and window 3 has a slot only once
        # window 0 gave its slabs back, already faulted.
        reused = [[r for w, _, r in spy.takes if w == i]
                  for i in range(nwin)]
        assert reused[0] == [False] * 2 * self.NBANK
        assert reused[1] == [False] * self.NBANK
        assert reused[2] in ([False] * self.NBANK, [True] * self.NBANK)
        assert reused[-1] == [True] * self.NBANK
        assert not spy.early, spy.early
        # device_put saw the slabs themselves, no copy of them.
        assert aliased == [True] * (nwin + 1)
        table = tl.report()
        assert table["staging.reuse"]["calls"] >= self.NBANK
        assert table["staging.alloc"]["calls"] \
            + table["staging.reuse"]["calls"] == (nwin + 1) * self.NBANK
        assert spy.stats()["lent_bytes"] == 0
        # Same bytes as the pool-path oracle: a slab handed on before its
        # window had read it (the CPU backend may alias a page-aligned
        # device_put) would show here.
        monkeypatch.setattr(S.M, "put_local_shards", put)
        want = S.reduce_scan_pool_to_files(
            paths, out_dir=str(tmp_path / "pool"), nfft=NFFT, nint=NINT,
            window_frames=16)
        assert sorted(got) == sorted(want)
        for b in got:
            assert filecmp.cmp(got[b][0], want[b][0], shallow=False)

    @pytest.mark.parametrize("link_blocks,want", [
        (None, "pppp"),    # a backend that stages nothing: as before
        (4.5, "pppp"),     # all four fit
        (4.0, "pppwp"),    # exactly the region: not counted on to fit
        (3.2, "pppwp"),    # four 1.34 GB banks against 4 GiB: three fit,
                           # and the fourth waits for the first alone
        (2.5, "ppwpwp"),   # the oldest in flight, not all of them
        (1.5, "pwpwpwp"),
    ])
    def test_a_put_waits_for_what_would_not_fit_beside_it(
            self, monkeypatch, link_blocks, want):
        from blit import device
        from blit.parallel import mesh as M

        mesh = M.make_mesh(1, self.NBANK)
        shape = (1, self.NBANK, 2, 256, 2, 2)
        rng = np.random.default_rng(0)
        whole = rng.integers(-8, 8, shape, np.int8)
        blocks = {(0, k): np.ascontiguousarray(whole[:, k:k + 1])
                  for k in range(self.NBANK)}
        nb = blocks[(0, 0)].nbytes
        monkeypatch.setattr(
            device, "host_link_bytes",
            lambda: None if link_blocks is None else int(link_blocks * nb))
        monkeypatch.setattr(device, "_HOST_LINK", device.HostLink())
        events, flying = [], []
        put, wait = jax.device_put, jax.block_until_ready

        def spy_put(*a, **kw):
            events.append("p")
            flying.append(put(*a, **kw))
            return flying[-1]

        def spy_wait(x):
            events.append("w")
            assert x is flying.pop(0)  # the oldest in flight, alone
            return wait(x)

        # On the CPU a put has landed when it returns: here one is in
        # flight until it is waited for.
        monkeypatch.setattr(device, "_landed",
                            lambda arr: not any(arr is f for f in flying))
        monkeypatch.setattr(jax, "device_put", spy_put)
        monkeypatch.setattr(jax, "block_until_ready", spy_wait)
        tl = Timeline()
        volt = M.put_local_shards(blocks, mesh, shape, timeline=tl)
        monkeypatch.undo()
        assert "".join(events) == want
        table = tl.report()
        assert table["feed.put"]["calls"] == self.NBANK
        assert table["wait.link"]["calls"] == want.count("w")
        if link_blocks is not None:
            assert table["hists"]["link.inflight_bytes"]["max"] \
                < link_blocks * nb
        np.testing.assert_array_equal(np.asarray(volt), whole)

    def test_a_window_comes_down_through_the_budget_too(self, tmp_path,
                                                        monkeypatch):
        """The next window's banks are already going up when a window's
        stitched band is fetched: that fetch is admitted by the same
        budget, not enqueued blind behind them."""
        from blit import device
        from blit.parallel.scan import reduce_scan_mesh_to_files

        paths = self.scan(tmp_path)
        tables = {}
        for tag, link in (("free", None), ("budgeted", 1 << 20)):
            (tmp_path / tag).mkdir()
            monkeypatch.setattr(device, "host_link_bytes", lambda: link)
            monkeypatch.setattr(device, "_HOST_LINK", device.HostLink())
            tl = Timeline()
            reduce_scan_mesh_to_files(
                paths, out_dir=str(tmp_path / tag), nfft=NFFT, nint=NINT,
                window_frames=16, timeline=tl)
            tables[tag] = tl.report()
        assert filecmp.cmp(tmp_path / "free" / "band0.fil",
                           tmp_path / "budgeted" / "band0.fil",
                           shallow=False)
        table = tables["budgeted"]
        assert table["hists"]["link.inflight_bytes"]["n"] \
            == table["feed.put"]["calls"] + table["readback"]["calls"]
        assert table["wait.link"]["byte_free"]
        assert "link.inflight_bytes" not in tables["free"].get("hists", {})

    def test_a_second_scan_allocates_nothing(self, tmp_path,
                                             fresh_process_pool):
        from blit.parallel.scan import reduce_scan_mesh_to_files

        paths = self.scan(tmp_path)
        tables = []
        for tag in ("one", "two"):
            (tmp_path / tag).mkdir()
            tl = Timeline()
            reduce_scan_mesh_to_files(
                paths, out_dir=str(tmp_path / tag), nfft=NFFT, nint=NINT,
                window_frames=16, timeline=tl)
            tables.append(tl.report())
        assert tables[0]["staging.alloc"]["calls"] > 0
        assert tables[1]["staging.alloc"]["calls"] == 0
        assert tables[1]["staging.drop"]["calls"] == 0
        assert filecmp.cmp(tmp_path / "one" / "band0.fil",
                           tmp_path / "two" / "band0.fil", shallow=False)

    def test_three_windows_of_slabs_are_all_a_scan_holds(
            self, tmp_path, fresh_process_pool):
        """The read runs ONE window ahead: a window on the chips, one
        being put and one being read are alive at once, however many
        windows the scan has, and a second pass finds them all faulted."""
        from blit.parallel.scan import reduce_scan_mesh_to_files

        paths = self.scan(tmp_path)
        tables = []
        for tag in ("one", "two"):
            (tmp_path / tag).mkdir()
            tl = Timeline()
            # 61 usable frames in windows of 13: four full and a ragged one.
            reduce_scan_mesh_to_files(
                paths, out_dir=str(tmp_path / tag), nfft=NFFT, nint=NINT,
                window_frames=13, timeline=tl)
            tables.append(tl.report())
        first, second = tables
        assert first["ingest"]["calls"] == 5
        # Bodies of at most three windows, and the stream's heads.
        assert 2 * self.NBANK < first["staging.alloc"]["calls"] \
            <= (3 + 1) * self.NBANK
        assert first["staging.alloc"]["calls"] \
            + first["staging.reuse"]["calls"] == (5 + 1) * self.NBANK
        assert second["staging.alloc"]["calls"] == 0
        assert second["staging.drop"]["calls"] == 0
        assert second["staging.reuse"]["calls"] == (5 + 1) * self.NBANK
        assert hostmem.slab_pool().stats()["lent_bytes"] == 0
        assert filecmp.cmp(tmp_path / "one" / "band0.fil",
                           tmp_path / "two" / "band0.fil", shallow=False)
