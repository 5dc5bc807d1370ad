"""Torn- and tampered-manifest drills (ISSUE 13 satellite), parallel to
tests/test_cursor_drills.py: every way a manifest can disagree with its
product — truncated JSON, a digest claiming the wrong window, a
manifest older/newer than the product, corruption inside the claimed
region — must fail CLOSED (fresh start or quarantine), never trust, and
every drill still finishes byte-identical to an uninterrupted run."""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit import faults, integrity  # noqa: E402
from blit.pipeline import RawReducer  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NFFT, CF = 32, 4


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


def _kw():
    return dict(nfft=NFFT, chunk_frames=CF)


def _bytes(path):
    with open(path, "rb") as f:
        return f.read()


class TestManifestDrills:
    def _interrupted(self, tmp_path):
        """A reference product plus an 'interrupted' resumable twin
        (the test_cursor_drills rig): crash after two durable appends,
        leaving product + cursor + partial manifest behind."""
        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=4, obsnchan=2, ntime_per_block=512,
                  seed=2)
        ref = str(tmp_path / "ref.fil")
        RawReducer(**_kw()).reduce_to_file(raw, ref)
        out = str(tmp_path / "res.fil")
        faults.install_spec("sink.write:fail:after=2")
        with pytest.raises(OSError):
            RawReducer(**_kw()).reduce_resumable(raw, out)
        faults.clear()
        assert os.path.exists(integrity.manifest_path(out))
        return raw, ref, out

    def _full_frames(self, raw):
        return RawReducer(**_kw()).reduce(raw)[1].shape[0]

    def _finish(self, raw, out):
        red = RawReducer(**_kw())
        red.reduce_resumable(raw, out)
        return red

    def test_truncated_manifest_fails_closed(self, tmp_path):
        # Torn JSON (a crash mid-manifest-write on a non-atomic fs):
        # the claim is unverifiable — fresh start, never trust.
        raw, ref, out = self._interrupted(tmp_path)
        mp = integrity.manifest_path(out)
        blob = open(mp).read()
        with open(mp, "w") as f:
            f.write(blob[: len(blob) // 2])
        red = self._finish(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert red.stats.output_frames == self._full_frames(raw)

    def test_wrong_window_digest_fails_closed(self, tmp_path):
        # A ledger entry whose digest is not the claimed window's (the
        # tampered-sidecar shape): fresh start.
        raw, ref, out = self._interrupted(tmp_path)
        mp = integrity.manifest_path(out)
        doc = json.load(open(mp))
        assert doc["windows"]
        doc["windows"][-1][2] = integrity.hex_crc(
            integrity.parse_crc(doc["windows"][-1][2]) ^ 0xFFFF)
        json.dump(doc, open(mp, "w"))
        red = self._finish(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert red.stats.output_frames == self._full_frames(raw)

    def test_malformed_ledger_fields_fail_closed_not_raise(self,
                                                           tmp_path):
        # Tampered NON-numeric fields (short entries, string row_bytes)
        # must fail closed like any other tamper — never raise out of
        # the resume probe or the fsck walk.
        raw, ref, out = self._interrupted(tmp_path)
        mp = integrity.manifest_path(out)
        doc = json.load(open(mp))
        doc["windows"] = [[doc["windows"][-1][0]]]  # short entry
        doc["row_bytes"] = "abc"
        json.dump(doc, open(mp, "w"))
        assert integrity.verify_claim(
            out, doc["windows"][0][0], fmt="fil") is False
        _doc2, problems = integrity.verify_product(out)
        assert problems  # fsck flags it instead of crashing the walk
        red = self._finish(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert red.stats.output_frames == self._full_frames(raw)

    def test_flip_inside_claimed_region_fails_closed(self, tmp_path):
        # The case the old length-only probe could NEVER catch: the
        # file still holds the claimed bytes, but one of them rotted.
        raw, ref, out = self._interrupted(tmp_path)
        with open(out, "r+b") as f:
            f.seek(200)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0x01]))
        red = self._finish(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert red.stats.output_frames == self._full_frames(raw)

    def test_manifest_for_a_different_product_fails_closed(self,
                                                           tmp_path):
        # Product replaced under a stale cursor+manifest (the
        # manifest-older-than-product shape): a DIFFERENT recording's
        # product lands at out while the sidecars still claim the old
        # one — the claimed-region digest disagrees, fresh start.
        raw, ref, out = self._interrupted(tmp_path)
        other_raw = str(tmp_path / "other.raw")
        synth_raw(other_raw, nblocks=4, obsnchan=2,
                  ntime_per_block=512, seed=9)
        other = str(tmp_path / "other.fil")
        RawReducer(**_kw()).reduce_to_file(other_raw, other)
        data = _bytes(other)
        with open(out, "wb") as f:
            f.write(data)
        red = self._finish(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert red.stats.output_frames == self._full_frames(raw)

    def test_missing_manifest_keeps_length_only_resume(self, tmp_path):
        # Back-compat: a legacy product (no manifest) still resumes on
        # the length-only probe — the upgrade must not strand cursors
        # written before the integrity plane existed.
        raw, ref, out = self._interrupted(tmp_path)
        os.unlink(integrity.manifest_path(out))
        red = self._finish(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert red.stats.output_frames < self._full_frames(raw)

    def test_clean_crash_state_still_resumes(self, tmp_path):
        # Control: the legal crash state (manifest consistent with the
        # cursor) must RESUME — fail-closed must not mean fail-always.
        raw, ref, out = self._interrupted(tmp_path)
        red = self._finish(raw, out)
        assert _bytes(out) == _bytes(ref)
        assert red.stats.output_frames < self._full_frames(raw)
        # Completed: cursor gone, manifest flipped to complete + clean.
        assert not os.path.exists(out + ".cursor")
        doc, problems = integrity.verify_product(out)
        assert doc["complete"] and not problems


class TestH5ManifestDrills:
    def _interrupted(self, tmp_path):
        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=4, obsnchan=2, ntime_per_block=512,
                  seed=3)
        ref = str(tmp_path / "ref.h5")
        RawReducer(**_kw()).reduce_to_file(raw, ref)
        out = str(tmp_path / "res.h5")
        faults.install_spec("sink.write:fail:after=2")
        with pytest.raises(OSError):
            RawReducer(**_kw()).reduce_resumable(raw, out)
        faults.clear()
        return raw, ref, out

    def test_flip_inside_claimed_rows_fails_closed(self, tmp_path):
        # Bit rot inside the claimed FBH5 rows: the structural probe
        # (open + decode last row) passes, the logical-row digest does
        # not — fresh start, and the decoded payload still matches.
        from blit.io import read_fbh5_data

        raw, ref, out = self._interrupted(tmp_path)
        import h5py

        with h5py.File(out, "r+") as h5:
            ds = h5["data"]
            row = np.array(ds[0])
            row.flat[0] += 1.0
            ds[0] = row
        red = RawReducer(**_kw())
        red.reduce_resumable(raw, out)
        assert red.stats.output_frames == \
            RawReducer(**_kw()).reduce(raw)[1].shape[0]
        np.testing.assert_array_equal(read_fbh5_data(out),
                                      read_fbh5_data(ref))

    def test_clean_h5_resume_still_resumes(self, tmp_path):
        from blit.io import read_fbh5_data

        raw, ref, out = self._interrupted(tmp_path)
        red = RawReducer(**_kw())
        red.reduce_resumable(raw, out)
        assert red.stats.output_frames < \
            RawReducer(**_kw()).reduce(raw)[1].shape[0]
        np.testing.assert_array_equal(read_fbh5_data(out),
                                      read_fbh5_data(ref))
        doc, problems = integrity.verify_product(out)
        assert doc["complete"] and not problems


class TestHitsManifestDrills:
    def _interrupted(self, tmp_path):
        from blit.search import DedopplerReducer

        raw = str(tmp_path / "r.raw")
        synth_raw(raw, nblocks=4, obsnchan=2, ntime_per_block=512,
                  seed=5, tone_chan=0)
        skw = dict(nfft=NFFT, chunk_frames=8, window_spectra=4,
                   snr_threshold=2.0, top_k=4)
        ref = str(tmp_path / "ref.hits")
        DedopplerReducer(**skw).search_to_file(raw, ref)
        out = str(tmp_path / "res.hits")
        faults.install_spec("sink.write:fail:after=2")
        with pytest.raises(OSError):
            DedopplerReducer(**skw).search_resumable(raw, out)
        faults.clear()
        return raw, ref, out, skw

    def test_tampered_hits_ledger_fails_closed(self, tmp_path):
        from blit.search import DedopplerReducer
        from blit.search.dedoppler import SearchCursor

        raw, ref, out, skw = self._interrupted(tmp_path)
        cur = SearchCursor.load(out)
        assert cur is not None and cur.windows_done > 0
        mp = integrity.manifest_path(out)
        doc = json.load(open(mp))
        assert doc["windows"]
        doc["windows"][-1][2] = "deadbeef"
        json.dump(doc, open(mp, "w"))
        DedopplerReducer(**skw).search_resumable(raw, out)
        assert _bytes(out) == _bytes(ref)

    def test_clean_hits_resume_still_resumes(self, tmp_path):
        from blit.search import DedopplerReducer

        raw, ref, out, skw = self._interrupted(tmp_path)
        DedopplerReducer(**skw).search_resumable(raw, out)
        assert _bytes(out) == _bytes(ref)
        doc, problems = integrity.verify_product(out)
        assert doc["complete"] and not problems


def _flip_byte(path, back=9):
    with open(path, "r+b") as f:
        f.seek(os.path.getsize(path) - back)
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ 0x20]))


class TestColdTierDrills:
    """Corrupt-cold-entry drills (ISSUE 19 satellite): the cold tier
    shares the hot tier's sidecar convention, so ``blit fsck`` walks it
    with the SAME detection/quarantine rules — and ``--repair``
    re-derives a quarantined cold entry through its recorded recipe."""

    def _cold_tree(self, tmp_path):
        from blit.serve.cache import ProductCache, fingerprint_for
        from blit.serve.service import ProductRequest

        raw = str(tmp_path / "cold-drill.raw")
        synth_raw(raw, nblocks=2, obsnchan=2, ntime_per_block=512,
                  seed=11)
        req = ProductRequest(raw=raw, nfft=NFFT, nint=1)
        reducer = req.reducer()
        fp = fingerprint_for(reducer, raw)
        header, data = reducer.reduce(raw)
        hot = str(tmp_path / "hot")
        cold = str(tmp_path / "cold")
        c = ProductCache(hot, ram_bytes=0, cold_dir=cold)
        c.put(fp, header, data, recipe=req.recipe())
        assert c._demote(fp)
        return hot, cold, c, fp, data

    def test_clean_cold_tier_passes(self, tmp_path):
        _hot, cold, _c, _fp, _data = self._cold_tree(tmp_path)
        rep = integrity.fsck(cold)
        assert rep["clean"] and rep["checked"] == 1 and rep["ok"] == 1

    def test_corrupt_cold_entry_quarantined_and_repaired(self, tmp_path):
        hot, cold, c, fp, data = self._cold_tree(tmp_path)
        _flip_byte(c.cold_data_path(fp))
        rep = integrity.fsck(cold)
        assert not rep["clean"]
        assert f"{fp}.h5" in rep["bad"][0]["path"]
        assert rep["bad"][0]["quarantined"]
        assert not os.path.exists(c.cold_data_path(fp))
        # --repair re-derives the entry from its recorded recipe INTO
        # the cold shard it was quarantined from...
        rep = integrity.fsck(cold, repair=True)
        assert rep["clean"] and rep["repaired"], rep
        rep2 = integrity.fsck(cold)
        assert rep2["clean"] and rep2["checked"] == 1
        # ...and the repaired entry serves byte-identical again.
        c2 = __import__("blit.serve.cache",
                        fromlist=["ProductCache"]).ProductCache(
            hot, ram_bytes=1 << 20, cold_dir=cold)
        got = c2.get(fp)
        assert got is not None and got[2] == "cold"
        np.testing.assert_array_equal(got[1], data)

    def test_cli_walks_both_tiers(self, tmp_path):
        import json as _json

        from blit.__main__ import main

        hot, cold, c, fp, _data = self._cold_tree(tmp_path)
        out = str(tmp_path / "fsck.json")
        assert main(["fsck", hot, "--cold-dir", cold,
                     "--json-out", out]) == 0
        rep = _json.load(open(out))
        assert rep["clean"] and rep["cold_root"] == os.path.abspath(cold)
        _flip_byte(c.cold_data_path(fp))
        assert main(["fsck", hot, "--cold-dir", cold,
                     "--json-out", out]) == 1
        rep = _json.load(open(out))
        assert not rep["clean"]
        assert any(f"{fp}.h5" in b["path"] for b in rep["bad"])
