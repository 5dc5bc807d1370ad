"""Data-integrity plane (ISSUE 13 tentpole): ingest digest masking,
product manifests, serve-cache content verification, fsck + quarantine
+ repair, the background scrubber, and the degraded /healthz surface."""

import filecmp
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit import faults, integrity  # noqa: E402
from blit.io.guppi import GuppiRaw, write_raw  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.pipeline import RawReducer  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NFFT = 32


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counters()
    yield
    faults.clear()
    faults.reset_counters()


@pytest.fixture(autouse=True)
def _isolate_quarantine_watch():
    """The quarantine watch registry is process-wide by design (a serve
    process watches the caches it opened); restore it after each test so
    a drill's leftover quarantine cannot degrade /healthz for unrelated
    test files (test_monitor's clean-process assertions)."""
    with integrity._WATCH_LOCK:
        saved = set(integrity._WATCHED_QUARANTINES)
    yield
    with integrity._WATCH_LOCK:
        integrity._WATCHED_QUARANTINES.clear()
        integrity._WATCHED_QUARANTINES.update(saved)


def _kw(cf=4):
    return dict(nfft=NFFT, chunk_frames=cf)


def _flip_byte(path, back=9):
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) - back)
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ 0x20]))


class TestIngestDigests:
    """RAW digest sidecars: verified blocks deliver; corrupt ones mask."""

    def _setup(self, tmp_path, nblocks=4, per_block=512):
        d = tmp_path / "in"
        d.mkdir()
        raw = str(d / "t.raw")
        synth_raw(raw, nblocks=nblocks, obsnchan=2,
                  ntime_per_block=per_block, seed=1)
        return raw

    def test_sidecar_roundtrip_clean(self, tmp_path):
        raw = self._setup(tmp_path)
        ref = str(tmp_path / "ref.fil")
        RawReducer(**_kw()).reduce_to_file(raw, ref)
        integrity.write_raw_digests(raw)
        out = str(tmp_path / "out.fil")
        rdr = GuppiRaw(raw)
        RawReducer(**_kw()).reduce_to_file(rdr, out)
        # Clean bytes under an armed sidecar: zero masks, identical
        # product — verification must never change a healthy reduction.
        assert rdr.bad_blocks == set()
        assert filecmp.cmp(out, ref, shallow=False)
        assert "integrity.bad_block" not in faults.counters()

    def _zero_oracle(self, tmp_path, raw, victim):
        """The same recording (same basename) with ``victim`` zeroed."""
        rdr = GuppiRaw(raw, native=False)
        blocks = [np.array(rdr.read_block(i))
                  for i in range(rdr.nblocks)]
        blocks[victim][:] = 0
        od = tmp_path / "oracle_in"
        od.mkdir()
        opath = str(od / os.path.basename(raw))
        write_raw(opath, dict(rdr.header(0)), blocks)
        oracle = str(tmp_path / "oracle.fil")
        RawReducer(**_kw()).reduce_to_file(opath, oracle)
        return oracle

    def test_disk_rot_masked_to_zero_oracle(self, tmp_path):
        # A flipped byte ON DISK inside block 1's payload: the block
        # fails its sidecar digest and the product is byte-identical to
        # the zero-filled oracle (the acceptance golden).
        raw = self._setup(tmp_path)
        integrity.write_raw_digests(raw)
        oracle = self._zero_oracle(tmp_path, raw, victim=1)
        rdr0 = GuppiRaw(raw, native=False)
        off = rdr0._data_offsets[1] + 100
        with open(raw, "r+b") as f:
            f.seek(off)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0x01]))
        out = str(tmp_path / "out.fil")
        rdr = GuppiRaw(raw)
        hdr = RawReducer(**_kw()).reduce_to_file(rdr, out)
        assert rdr.bad_blocks == {1}
        assert hdr["_masked_blocks"] == [1]
        assert faults.counters()["integrity.bad_block"] == 1
        assert filecmp.cmp(out, oracle, shallow=False)

    def test_seeded_corrupt_fault_masked_to_zero_oracle(self, tmp_path):
        # The seeded ``corrupt`` fault mode (in-flight flip of the
        # DELIVERED frame, disk clean): detected per delivery, masked,
        # byte-identical to the zero-filled oracle.  Single-chunk
        # geometry (chunk spans the recording) makes every block one
        # delivery, after the stream's head (block 0's first samples, a
        # delivery of its own): after=3 targets exactly block 2.
        raw = self._setup(tmp_path)
        integrity.write_raw_digests(raw)
        kw = dict(nfft=NFFT, chunk_frames=4 * 512 // NFFT - 3)
        rdr0 = GuppiRaw(raw, native=False)
        blocks = [np.array(rdr0.read_block(i)) for i in range(4)]
        blocks[2][:] = 0
        od = tmp_path / "oin"
        od.mkdir()
        opath = str(od / "t.raw")
        write_raw(opath, dict(rdr0.header(0)), blocks)
        oracle = str(tmp_path / "oracle.fil")
        RawReducer(**kw).reduce_to_file(opath, oracle)
        faults.install(faults.FaultRule(point="guppi.read",
                                        mode="corrupt", after=3, times=1))
        out = str(tmp_path / "out.fil")
        rdr = GuppiRaw(raw)
        hdr = RawReducer(**kw).reduce_to_file(rdr, out)
        assert rdr.bad_blocks == {2}
        assert hdr["_masked_blocks"] == [2]
        assert filecmp.cmp(out, oracle, shallow=False)

    def test_malformed_sidecar_refused_loudly(self, tmp_path):
        raw = self._setup(tmp_path)
        with open(integrity.raw_digests_path(raw), "w") as f:
            f.write('{"kind": "blit.digests", "blocks": [truncated')
        with pytest.raises(integrity.IntegrityError):
            GuppiRaw(raw)

    def test_verify_disabled_by_env(self, tmp_path, monkeypatch):
        raw = self._setup(tmp_path)
        integrity.write_raw_digests(raw)
        monkeypatch.setenv("BLIT_VERIFY_INGEST", "0")
        rdr = GuppiRaw(raw)
        assert rdr._block_digests is None


class TestManifests:
    def test_fil_manifest_published_and_verifies(self, tmp_path):
        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512, seed=2)
        out = str(tmp_path / "p.fil")
        RawReducer(**_kw()).reduce_to_file(raw, out)
        doc, problems = integrity.verify_product(out)
        assert doc is not None and doc["complete"] and not problems
        assert doc["format"] == "fil" and doc["rows"] > 0
        assert doc["windows"], "per-window claim ledger missing"

    def test_single_flipped_byte_detected(self, tmp_path):
        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512, seed=2)
        out = str(tmp_path / "p.fil")
        RawReducer(**_kw()).reduce_to_file(raw, out)
        _flip_byte(out)
        _doc, problems = integrity.verify_product(out)
        assert problems and "digest mismatch" in problems[0]

    def test_h5_manifest_whole_file_digest(self, tmp_path):
        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512, seed=2)
        out = str(tmp_path / "p.h5")
        RawReducer(**_kw()).reduce_to_file(raw, out)
        doc, problems = integrity.verify_product(out)
        assert doc is not None and doc["complete"] and not problems
        _flip_byte(out, back=5)
        _doc, problems = integrity.verify_product(out)
        assert problems

    def test_hits_manifest(self, tmp_path):
        from blit.search import DedopplerReducer

        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512,
                  seed=2, tone_chan=0)
        out = str(tmp_path / "p.hits")
        DedopplerReducer(nfft=NFFT, chunk_frames=8, window_spectra=4,
                         snr_threshold=2.0).search_to_file(raw, out)
        doc, problems = integrity.verify_product(out)
        assert doc is not None and doc["complete"] and not problems
        _flip_byte(out, back=3)
        _doc, problems = integrity.verify_product(out)
        assert problems


class TestSigprocPayloadGuard:
    """The ISSUE 13 satellite closing the blit/io/sigproc.py gap: a .fil
    whose payload is not a whole number of header-described spectra is
    REFUSED at read-back, never silently mis-shaped."""

    def test_truncated_payload_refused(self, tmp_path):
        from blit.io.sigproc import read_fil_data, write_fil

        p = str(tmp_path / "x.fil")
        hdr = {"nchans": 4, "nifs": 1, "nbits": 32, "tsamp": 1.0,
               "fch1": 1000.0, "foff": -0.1}
        write_fil(p, hdr, np.arange(12, dtype=np.float32).reshape(3, 1, 4))
        read_fil_data(p)  # whole spectra: fine
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) - 6)  # torn mid-row
        with pytest.raises(ValueError, match="whole number"):
            read_fil_data(p)

    def test_resume_probe_fails_closed_on_torn_row(self, tmp_path):
        from blit.io.sigproc import write_fil
        from blit.pipeline import resume_fil_ok

        p = str(tmp_path / "x.fil")
        hdr = {"nchans": 4, "nifs": 1, "nbits": 32, "tsamp": 1.0,
               "fch1": 1000.0, "foff": -0.1}
        write_fil(p, hdr, np.zeros((3, 1, 4), np.float32))
        assert resume_fil_ok(p, 1, 4, 3)


class TestCacheIntegrity:
    def _publish(self, tmp_path):
        from blit.serve.cache import ProductCache, fingerprint_for
        from blit.serve.service import ProductRequest

        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512, seed=3)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        reducer = req.reducer()
        fp = fingerprint_for(reducer, raw)
        header, data = reducer.reduce(raw)
        cdir = str(tmp_path / "cache")
        cache = ProductCache(cdir, ram_bytes=0)
        cache.put(fp, header, data, recipe=req.recipe())
        return cache, cdir, fp, raw

    def test_meta_carries_digest_and_recipe(self, tmp_path):
        cache, cdir, fp, _raw = self._publish(tmp_path)
        meta = json.load(open(os.path.join(cdir, f"{fp}.json")))
        assert integrity.parse_crc(meta["crc32"]) is not None
        assert meta["recipe"]["nfft"] == NFFT
        assert cache.get(fp) is not None  # verified load serves

    def test_flipped_entry_evicted_as_corrupt_on_load(self, tmp_path):
        cache, cdir, fp, _raw = self._publish(tmp_path)
        _flip_byte(os.path.join(cdir, f"{fp}.h5"))
        assert cache.get(fp) is None
        assert cache.stats()["evict.corrupt"] >= 1
        assert faults.counters().get("integrity.cache.corrupt", 0) >= 1

    def test_scrubber_quarantines_and_health_degrades(self, tmp_path):
        from blit import monitor

        cache, cdir, fp, _raw = self._publish(tmp_path)
        tl = Timeline()
        sc = integrity.Scrubber(cache, timeline=tl, interval_s=999)
        assert sc.scrub_once()["ok"]
        _flip_byte(os.path.join(cdir, f"{fp}.h5"), back=30)
        r = sc.scrub_once()
        assert r is not None and not r["ok"]
        rep = tl.report()
        assert "integrity.scrub.corrupt" in rep
        assert "integrity.verify_s" in rep.get("hists", {})
        # The corrupt entry moved to .quarantine and stopped serving.
        qdir = os.path.join(cdir, integrity.QUARANTINE_DIR)
        assert os.listdir(qdir)
        assert cache.get(fp) is None
        # /healthz says degraded while the quarantine is non-empty.
        pub = monitor.MetricsPublisher(interval_s=999)
        try:
            h = pub.health()
            assert h["status"] == "degraded"
            assert any(r.startswith("integrity:") for r in h["reasons"])
        finally:
            pub.close()
            # Triage: clear the quarantine so later tests see a clean
            # health surface (the watch registry is process-wide).
            for n in os.listdir(qdir):
                os.unlink(os.path.join(qdir, n))
        assert not integrity.quarantine_health()


class TestScrubKnobs:
    def test_interval_zero_disables(self, monkeypatch):
        from blit.config import scrub_defaults

        for v in ("0", "", "none", "-1"):
            monkeypatch.setenv("BLIT_SCRUB_INTERVAL", v)
            assert scrub_defaults()["enabled"] is False, v
        monkeypatch.setenv("BLIT_SCRUB_INTERVAL", "0.5")
        d = scrub_defaults()
        assert d["enabled"] and d["interval_s"] == 0.5

    def test_vanished_entry_is_not_corrupt(self, tmp_path):
        # An entry evicted between index() and verify (a routine LRU
        # race) must not page operators via integrity.scrub.corrupt.
        from blit.serve.cache import ProductCache

        class _Racy(ProductCache):
            def index(self):
                return ["gone" * 16]

        cache = _Racy(str(tmp_path / "c"), ram_bytes=0)
        tl = Timeline()
        sc = integrity.Scrubber(cache, timeline=tl, interval_s=999)
        assert sc.scrub_once() is None
        assert sc.corrupt == 0
        assert "integrity.scrub.corrupt" not in tl.report()


class TestMonitorSurface:
    def test_integrity_counters_ride_metrics_and_top(self):
        """ISSUE 13 satellite: integrity.* counters and the
        integrity.verify_s histogram ride the PR 10 monitor plane —
        blit_fault_total / blit_latency_* on /metrics, fault rows on
        `blit top`, and (via local_fleet_report) the
        telemetry-report.json CI artifact."""
        from blit.monitor import parse_prometheus, render_top
        from blit.observability import (
            local_fleet_report,
            render_prometheus,
        )

        integrity.incr("integrity.bad_block")
        integrity.observe_verify(0.003)
        rep = local_fleet_report()
        assert rep["faults"].get("integrity.bad_block", 0) >= 1
        text = render_prometheus(rep)
        samples = parse_prometheus(text)
        assert any(n == "blit_fault_total"
                   and labels.get("counter") == "integrity.bad_block"
                   for n, labels, _v in samples)
        assert any(labels.get("name") == "integrity.verify_s"
                   for _n, labels, _v in samples)
        assert "integrity.bad_block" in render_top(rep)


class TestFsck:
    def _tree(self, tmp_path):
        from blit.serve.cache import ProductCache, fingerprint_for
        from blit.serve.service import ProductRequest

        tree = tmp_path / "tree"
        (tree / "products").mkdir(parents=True)
        raw = str(tmp_path / "drill.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512, seed=4)
        product = str(tree / "products" / "drill.fil")
        RawReducer(**_kw()).reduce_to_file(raw, product)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        reducer = req.reducer()
        fp = fingerprint_for(reducer, raw)
        header, data = reducer.reduce(raw)
        cdir = str(tree / "cache")
        ProductCache(cdir, ram_bytes=0).put(fp, header, data,
                                            recipe=req.recipe())
        return str(tree), product, cdir, fp, raw

    def test_clean_tree(self, tmp_path):
        tree, *_ = self._tree(tmp_path)
        rep = integrity.fsck(tree)
        assert rep["clean"] and rep["checked"] == 2 and rep["ok"] == 2

    def test_flips_detected_quarantined_and_repaired(self, tmp_path):
        tree, product, cdir, fp, raw = self._tree(tmp_path)
        _flip_byte(product)
        _flip_byte(os.path.join(cdir, f"{fp}.h5"))
        rep = integrity.fsck(tree)
        assert not rep["clean"]
        bad_paths = " ".join(b["path"] for b in rep["bad"])
        assert "drill.fil" in bad_paths and f"{fp}.h5" in bad_paths
        assert all(b["quarantined"] for b in rep["bad"])
        # The corrupt artifacts are OUT of the tree (contained).
        assert not os.path.exists(product)
        # Operator re-reduces the product; --repair re-derives the
        # cache entry from its recorded recipe and retires the corpses.
        RawReducer(**_kw()).reduce_to_file(raw, product)
        rep = integrity.fsck(tree, repair=True)
        assert rep["clean"] and len(rep["repaired"]) >= 2, rep
        rep2 = integrity.fsck(tree)
        assert rep2["clean"] and rep2["checked"] == 2

    def test_raw_member_sidecar_verified_report_only(self, tmp_path):
        # A digest-armed RAW member inside the tree: fsck re-derives
        # its block digests; rot is REPORTED (exit != 0) but the member
        # is never quarantined — it is the read-only source of truth.
        tree = tmp_path / "tree"
        tree.mkdir()
        raw = str(tree / "m.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512,
                  seed=6)
        integrity.write_raw_digests(raw)
        rep = integrity.fsck(str(tree))
        assert rep["clean"] and rep["checked"] == 1
        rdr = GuppiRaw(raw, native=False)
        with open(raw, "r+b") as f:
            f.seek(rdr._data_offsets[1] + 50)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0x02]))
        rep = integrity.fsck(str(tree))
        assert not rep["clean"]
        assert rep["bad"][0]["kind"] == "raw"
        assert "block 1" in rep["bad"][0]["problems"][0]
        assert os.path.exists(raw)  # never moved

    def test_torn_cache_meta_fails_closed(self, tmp_path):
        tree, _product, cdir, fp, _raw = self._tree(tmp_path)
        with open(os.path.join(cdir, f"{fp}.json"), "w") as f:
            f.write('{"fingerprint": "trunca')
        rep = integrity.fsck(tree)
        assert not rep["clean"]

    def test_cli_roundtrip(self, tmp_path):
        from blit.__main__ import main

        tree, product, _cdir, _fp, _raw = self._tree(tmp_path)
        out = str(tmp_path / "fsck.json")
        assert main(["fsck", tree, "--json-out", out]) == 0
        _flip_byte(product)
        assert main(["fsck", tree, "--json-out", out]) == 1
        rep = json.load(open(out))
        assert rep["bad"] and not rep["clean"]


class TestChaosCorruptCLI:
    def test_corrupt_leg(self, tmp_path):
        from blit.__main__ import main

        out = str(tmp_path / "report.json")
        rc = main(["chaos", "--fault", "corrupt",
                   "--work-dir", str(tmp_path / "work"),
                   "--json-out", out])
        assert rc == 0
        rep = json.load(open(out))
        assert rep["recovered"] is True
        assert rep["byte_identical"] is True
        assert rep["integrity"]["integrity.bad_block"] >= 1
        assert rep["masked_blocks"] == [rep["victim_block"]]
