"""The sharded reduction plane (blit/parallel/sharded.py, ISSUE 9).

The acceptance contract: sharded-path products are BYTE-IDENTICAL to
the pool-path oracle (`reduce_scan_pool_to_files` — the reference's "64
workers doing 64 small jobs" shape) for `.fil`, `.h5` and `.hits`,
including masked-antenna and resume-replay runs, on the >= 8-device
forced-host CPU mesh the suite provisions (tests/conftest.py /
the CI mesh-smoke job's XLA_FLAGS).  Plus the plane's building blocks:
the partition-rule registry, `ShardedAccumulator`'s spec-drift check,
ICI byte accounting and the `BLIT_MESH_*` knob resolution.
"""

import filecmp
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit.observability import Timeline  # noqa: E402
from blit.parallel import mesh as M  # noqa: E402
from blit.parallel.mesh import make_mesh  # noqa: E402
from blit.parallel.scan import (  # noqa: E402
    reduce_scan_mesh_to_files,
    reduce_scan_pool_to_files,
)
from blit.parallel.sharded import (  # noqa: E402
    reduce_scan_sharded_to_files,
    search_scan_sharded_to_files,
)
from blit.testing import synth_raw  # noqa: E402

NFFT, NINT, NCHAN = 64, 2, 2
WF = 4  # window_frames: several windows per scan at these shapes


def make_scan(tmp_path, nband=1, nbank=8, ntime=1024, nblocks=2):
    """One synthetic scan (the tests/test_scan_mesh.py grid): per-player
    RAW files with contiguous bank frequencies."""
    paths = []
    bank_bw = -187.5 / nbank
    for b in range(nband):
        row = []
        for k in range(nbank):
            p = str(tmp_path / f"blc{b}{k}.raw")
            synth_raw(p, nblocks=nblocks, obsnchan=NCHAN,
                      ntime_per_block=ntime, seed=b * 8 + k,
                      tone_chan=(k % NCHAN), obsbw=bank_bw,
                      obsfreq=8000.0 + b * 500.0 + (k + 0.5) * bank_bw)
            row.append(p)
        paths.append(row)
    return paths


def run_three_ways(paths, tmp_path, **kw):
    """The same scan through the sharded plane, the pool oracle and the
    serial mesh loop, each into its own directory."""
    outs = {}
    for tag, fn in (("sharded", reduce_scan_sharded_to_files),
                    ("pool", reduce_scan_pool_to_files),
                    ("mesh", reduce_scan_mesh_to_files)):
        d = tmp_path / tag
        d.mkdir(exist_ok=True)
        outs[tag] = fn(paths, out_dir=str(d), nfft=NFFT, nint=NINT,
                       window_frames=WF, **kw)
    return outs


class TestByteIdentityGoldens:
    """THE acceptance criterion: sharded products == pool-path goldens,
    byte for byte."""

    @pytest.mark.parametrize("nband,nbank", [(1, 8), (2, 4)])
    def test_fil_products_byte_identical(self, tmp_path, nband, nbank):
        paths = make_scan(tmp_path, nband, nbank)
        outs = run_three_ways(paths, tmp_path)
        assert sorted(outs["sharded"]) == sorted(outs["pool"])
        for b in outs["sharded"]:
            sp, shdr = outs["sharded"][b]
            assert filecmp.cmp(sp, outs["pool"][b][0], shallow=False), (
                f"band {b}: sharded .fil != pool oracle"
            )
            assert filecmp.cmp(sp, outs["mesh"][b][0], shallow=False), (
                f"band {b}: sharded .fil != serial mesh loop"
            )
            assert shdr["nsamps"] == outs["pool"][b][1]["nsamps"]

    def test_h5_products_byte_identical(self, tmp_path):
        pytest.importorskip("h5py")
        from blit.io import bshuf

        if not bshuf.available():
            pytest.skip("native bitshuffle codec unbuilt")
        paths = make_scan(tmp_path, 1, 8)
        outs = run_three_ways(paths, tmp_path, compression="bitshuffle")
        for b in outs["sharded"]:
            sp = outs["sharded"][b][0]
            assert sp.endswith(".h5")
            assert filecmp.cmp(sp, outs["pool"][b][0], shallow=False), (
                f"band {b}: sharded .h5 != pool oracle"
            )

    def test_despiked_products_byte_identical(self, tmp_path):
        # The stitch epilogue differs mechanically (host despike on the
        # pool path, post-all_gather despike over ICI on the sharded
        # path) — the bytes must not.
        paths = make_scan(tmp_path, 1, 8)
        d1, d2 = tmp_path / "s", tmp_path / "p"
        d1.mkdir(), d2.mkdir()
        w1 = reduce_scan_sharded_to_files(
            paths, out_dir=str(d1), nfft=NFFT, nint=NINT,
            window_frames=WF, despike=True,
        )
        w2 = reduce_scan_pool_to_files(
            paths, out_dir=str(d2), nfft=NFFT, nint=NINT,
            window_frames=WF, despike=True,
        )
        for b in w1:
            assert filecmp.cmp(w1[b][0], w2[b][0], shallow=False)

    def test_sharded_probe_reports_collectives(self, tmp_path):
        # Telemetry contract: probe windows sample mesh.gather_s and
        # every window accounts per-chip ICI bytes on mesh.ici.
        paths = make_scan(tmp_path, 1, 8)
        (tmp_path / "out").mkdir()
        tl = Timeline()
        reduce_scan_sharded_to_files(
            paths, out_dir=str(tmp_path / "out"), nfft=NFFT, nint=NINT,
            window_frames=WF, probe_windows=2, timeline=tl,
        )
        assert tl.stages["mesh.ici"].calls > 0
        assert tl.stages["mesh.ici"].bytes > 0
        assert tl.hists["mesh.gather_s"].n == 2  # the probe windows
        assert tl.hists["mesh.gather_ici_bytes"].n == \
            tl.stages["mesh.ici"].calls


class TestResumeReplay:
    def test_crash_resume_byte_identical_to_uninterrupted(
            self, tmp_path, monkeypatch):
        # The mesh-writer resume discipline on the SHARDED plane: crash
        # after the 3rd window's dispatch, leave cursors, resume, and
        # byte-match both the uninterrupted sharded run AND the pool
        # oracle.
        paths = make_scan(tmp_path, 1, 8, nblocks=4)
        gold = tmp_path / "gold"
        gold.mkdir()
        gw = reduce_scan_sharded_to_files(
            paths, out_dir=str(gold), nfft=NFFT, nint=NINT,
            window_frames=WF, resume=False,
        )
        pool = tmp_path / "pool"
        pool.mkdir()
        pw = reduce_scan_pool_to_files(
            paths, out_dir=str(pool), nfft=NFFT, nint=NINT,
            window_frames=WF,
        )

        res = tmp_path / "res"
        res.mkdir()
        real = M.band_reduce
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("synthetic crash")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_reduce", flaky)
        with pytest.raises(RuntimeError, match="synthetic crash"):
            reduce_scan_sharded_to_files(
                paths, out_dir=str(res), nfft=NFFT, nint=NINT,
                window_frames=WF, resume=True,
            )
        monkeypatch.setattr(M, "band_reduce", real)
        assert len(calls) == 3, "the injected crash did not fire"
        assert [p for p in os.listdir(res) if p.endswith(".cursor")], (
            "no cursor sidecar after the crash"
        )

        rw = reduce_scan_sharded_to_files(
            paths, out_dir=str(res), nfft=NFFT, nint=NINT,
            window_frames=WF, resume=True,
        )
        assert not [p for p in os.listdir(res) if p.endswith(".cursor")]
        for b in rw:
            assert filecmp.cmp(rw[b][0], gw[b][0], shallow=False), (
                f"band {b}: resumed sharded product != uninterrupted"
            )
            assert filecmp.cmp(rw[b][0], pw[b][0], shallow=False), (
                f"band {b}: resumed sharded product != pool oracle"
            )


class TestSearchHitsParity:
    def test_hits_byte_identical_to_pool_reducers(self, tmp_path):
        # The sharded search plane: every chip searches its own
        # frequency slice; each per-player .hits must be byte-identical
        # to the pool path's own DedopplerReducer.search_to_file at the
        # matching dispatch shape (chunk_frames == window_frames).
        from blit.search import DedopplerReducer

        nband, nbank = 1, 8
        paths = make_scan(tmp_path, nband, nbank)
        wspec, wf = 4, 16
        sd = tmp_path / "sharded"
        sd.mkdir()
        written = search_scan_sharded_to_files(
            paths, out_dir=str(sd), nfft=NFFT, nint=NINT,
            window_spectra=wspec, window_frames=wf, snr_threshold=4.0,
        )
        assert sorted(written) == [(0, k) for k in range(nbank)]
        pd = tmp_path / "pool"
        pd.mkdir()
        for (b, k), (spath, shdr) in written.items():
            red = DedopplerReducer(
                nfft=NFFT, nint=NINT, window_spectra=wspec,
                snr_threshold=4.0, chunk_frames=wf,
            )
            out = str(pd / f"band{b}bank{k}.hits")
            red.search_to_file(paths[b][k], out)
            assert filecmp.cmp(spath, out, shallow=False), (
                f"player ({b},{k}): sharded .hits != pool oracle"
            )
            assert shdr["search_windows"] > 0


class _StubWindow:
    """A hand-fed window for beamform_accumulate goldens: the consumer
    contract (arrays/ntime/index/release) with no producer thread."""

    def __init__(self, index, arrays, ntime):
        self.index, self.arrays, self.ntime = index, arrays, ntime
        self.masked = ()

    def release(self):
        pass


class TestMaskedAntennaParity:
    """ISSUE 9 satellite: a zero-weight seat under the sharded
    accumulator path produces the same bytes as the pool path's masked
    product (the zero-filled golden)."""

    NANT, W, TOTAL, START = 4, 128, 896, 48

    @pytest.fixture()
    def ant_files(self, tmp_path):
        paths = []
        for a in range(self.NANT):
            p = str(tmp_path / f"ant{a}.raw")
            synth_raw(p, nblocks=2, obsnchan=4, ntime_per_block=480,
                      seed=200 + a, tone_chan=a % 4)
            paths.append(p)
        return paths

    def test_masked_accumulate_matches_zero_filled_golden(
            self, ant_files):
        from blit import faults
        from blit.faults import FaultRule
        from blit.parallel.antenna import AntennaStream, load_antennas_mesh
        from blit.parallel.beamform import (
            antenna_sharding,
            beamform_accumulate,
            weight_sharding,
        )

        mesh = make_mesh(1, 4)
        rng = np.random.default_rng(5)
        w = (rng.standard_normal((3, self.NANT, 4))
             + 1j * rng.standard_normal((3, self.NANT, 4))
             ).astype(np.complex64)
        ws = weight_sharding(mesh)
        wput = (jax.device_put(w.real.astype(np.float32), ws),
                jax.device_put(w.imag.astype(np.float32), ws))

        faults.clear()
        faults.reset_counters()
        try:
            faults.install(FaultRule("guppi.read", "truncate", times=1,
                                     after=2, match="ant2"))
            feed = AntennaStream(
                ant_files, mesh=mesh, window_samples=self.W,
                start_sample=self.START, max_samples=self.TOTAL,
                on_antenna_error="mask",
            )
            per_window = []

            def spy(f):
                for win in f:
                    per_window.append(win.masked)
                    yield win

            got = np.asarray(beamform_accumulate(spy(feed), wput,
                                                 mesh=mesh))
            assert feed.masked_antennas == {2}
            wmask = next(i for i, m in enumerate(per_window) if m)
            assert 0 < wmask < feed.nwindows  # genuinely mid-stream
        finally:
            faults.clear()
            faults.reset_counters()

        # The pool path's masked product: the SAME accumulate program
        # over stub windows sliced from planes with antenna 2 zeroed
        # from the mask boundary on — identical window shapes, identical
        # fold order, so the bytes must match exactly.
        _, (vr, vi) = load_antennas_mesh(
            ant_files, mesh=mesh, start_sample=self.START,
            max_samples=self.TOTAL,
        )
        zr, zi = np.asarray(vr).copy(), np.asarray(vi).copy()
        zr[2, :, wmask * self.W:] = 0
        zi[2, :, wmask * self.W:] = 0
        sh = antenna_sharding(mesh)
        stubs = [
            _StubWindow(i, (
                jax.device_put(zr[:, :, s:s + self.W], sh),
                jax.device_put(zi[:, :, s:s + self.W], sh),
            ), self.W)
            for i, s in enumerate(range(0, self.TOTAL, self.W))
        ]
        golden = np.asarray(beamform_accumulate(iter(stubs), wput,
                                                mesh=mesh))
        np.testing.assert_array_equal(got, golden)


class TestPartitionRules:
    def test_registry_roles_resolve(self):
        from jax.sharding import PartitionSpec as P

        assert M.partition_rule("voltages") == P("band", "bank")
        assert M.partition_rule("replicated") == P()
        # A spec passes through untouched.
        spec = P("band", None)
        assert M.partition_rule(spec) is spec

    def test_unknown_role_lists_known(self):
        with pytest.raises(KeyError, match="voltages"):
            M.partition_rule("no_such_role")

    def test_sharding_for_builds_namedsharding(self):
        mesh = make_mesh(1, 8)
        s = M.sharding_for(mesh, "filterbank_sharded")
        assert s.mesh.shape == {"band": 1, "bank": 8}
        assert s.spec == M.PARTITION_RULES["filterbank_sharded"]

    def test_ici_byte_models(self):
        # all_gather: each chip receives the other n-1 shards.
        assert M.gather_ici_bytes(100, 8) == 700
        assert M.gather_ici_bytes(100, 1) == 0
        # ring all-reduce: 2 * (n-1)/n * nbytes.
        assert M.psum_ici_bytes(800, 2) == 800
        assert M.psum_ici_bytes(800, 1) == 0

    def test_record_ici_accounting(self):
        tl = Timeline()
        M.record_ici(tl, "gather", 1024, 0.5)
        M.record_ici(tl, "gather", 1024)  # untimed: bytes only
        assert tl.stages["mesh.ici"].calls == 2
        assert tl.stages["mesh.ici"].bytes == 2048
        assert tl.hists["mesh.gather_s"].n == 1
        assert tl.hists["mesh.gather_ici_bytes"].n == 2


class TestShardedAccumulator:
    def test_fold_before_init_raises(self):
        acc = M.ShardedAccumulator(make_mesh(1, 8), "beamform_acc")
        with pytest.raises(RuntimeError, match="before init"):
            acc.fold(lambda v: v)

    def test_fold_preserving_rule_passes(self):
        mesh = make_mesh(1, 8)
        acc = M.ShardedAccumulator(mesh, "replicated")
        sh = M.sharding_for(mesh, "replicated")
        acc.init(jax.device_put(np.zeros((8, 4), np.float32), sh))
        add = jax.jit(lambda a, p: a + p, donate_argnums=0)
        out = acc.fold(add,
                       jax.device_put(np.ones((8, 4), np.float32), sh))
        assert np.asarray(out).sum() == 32.0

    def test_fold_aux_hands_back_the_product_and_keeps_the_state(self):
        mesh = make_mesh(1, 8)
        acc = M.ShardedAccumulator(mesh, "replicated")
        with pytest.raises(RuntimeError, match="before init"):
            acc.fold_aux(lambda v: (v, v))
        sh = M.sharding_for(mesh, "replicated")
        first = acc.init(jax.device_put(np.zeros((8, 4), np.float32), sh))
        step = jax.jit(lambda a, p: ((a + p).sum(), a + p),
                       donate_argnums=0)
        aux = acc.fold_aux(step,
                           jax.device_put(np.ones((8, 4), np.float32), sh))
        assert float(aux) == 32.0 and first.is_deleted()
        assert np.asarray(acc.value).sum() == 32.0

    def test_spec_drift_fails_loudly(self):
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(1, 8)
        acc = M.ShardedAccumulator(mesh, "replicated")
        acc.init(jax.device_put(np.zeros((8, 4), np.float32),
                                M.sharding_for(mesh, "replicated")))

        def reshard(a):
            return jax.device_put(
                np.asarray(a), jax.sharding.NamedSharding(mesh, P("bank"))
            )

        with pytest.raises(ValueError, match="drifted"):
            acc.fold(reshard)


class TestMeshDefaults:
    def test_env_overrides(self, monkeypatch):
        from blit.config import mesh_defaults

        monkeypatch.setenv("BLIT_MESH_SHARDED", "1")
        monkeypatch.setenv("BLIT_MESH_PROBE", "5")
        monkeypatch.setenv("BLIT_MESH_PREFETCH", "3")
        monkeypatch.setenv("BLIT_MESH_OUT_DEPTH", "4")
        d = mesh_defaults()
        assert d == {"sharded": True, "probe_windows": 5,
                     "prefetch_depth": 3, "out_depth": 4}
        monkeypatch.setenv("BLIT_MESH_SHARDED", "0")
        assert mesh_defaults()["sharded"] is False

    def test_defaults_without_env(self, monkeypatch):
        from blit.config import SiteConfig, mesh_defaults

        for k in ("BLIT_MESH_SHARDED", "BLIT_MESH_PROBE",
                  "BLIT_MESH_PREFETCH", "BLIT_MESH_OUT_DEPTH"):
            monkeypatch.delenv(k, raising=False)
        d = mesh_defaults(SiteConfig())
        assert d == {"sharded": False, "probe_windows": 2,
                     "prefetch_depth": None, "out_depth": None}


class TestGbtWrappers:
    def test_lazy_wrappers_resolve(self):
        # The deployment surface (blit.gbt) exposes the sharded plane
        # and its pool oracle without importing jax at module import.
        from blit import gbt

        for name in ("reduce_scan_sharded_to_files",
                     "reduce_scan_pool_to_files",
                     "search_scan_sharded_to_files"):
            assert callable(getattr(gbt, name)), name


class TestScanCLI:
    def _tree(self, tmp_path):
        from blit.testing import build_observation_tree

        root = str(tmp_path / "datax")
        build_observation_tree(
            root, kind="raw", players=((0, 0), (0, 1)), nchans=2,
            nfiles=2, raw_ntime=512,
        )
        return root

    def _run(self, capsys, *args):
        from blit.__main__ import main

        rc = main(list(args))
        return rc, capsys.readouterr().out

    def test_scan_sharded_matches_pool_flag(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        d1, d2 = tmp_path / "s", tmp_path / "p"
        d1.mkdir(), d2.mkdir()
        rc1, txt1 = self._run(
            capsys, "scan", root, "AGBT22B_999_01", "0011", "-o", str(d1),
            "--nfft", "64", "--nint", "2", "--window-frames", "4",
            "--sharded",
        )
        rc2, txt2 = self._run(
            capsys, "scan", root, "AGBT22B_999_01", "0011", "-o", str(d2),
            "--nfft", "64", "--nint", "2", "--window-frames", "4",
            "--pool",
        )
        assert rc1 == rc2 == 0
        assert filecmp.cmp(str(d1 / "band0.fil"), str(d2 / "band0.fil"),
                           shallow=False)
        s1 = json.loads(txt1.strip().splitlines()[-1])
        s2 = json.loads(txt2.strip().splitlines()[-1])
        assert s1["parallel"] == "sharded"
        assert s2["parallel"] == "pool"

    def test_scan_sharded_env_default(self, tmp_path, capsys, monkeypatch):
        # BLIT_MESH_SHARDED=1 flips the default path without a flag.
        root = self._tree(tmp_path)
        monkeypatch.setenv("BLIT_MESH_SHARDED", "1")
        (tmp_path / "o").mkdir()
        rc, txt = self._run(
            capsys, "scan", root, "AGBT22B_999_01", "0011",
            "-o", str(tmp_path / "o"), "--nfft", "64", "--nint", "2",
            "--window-frames", "4",
        )
        assert rc == 0
        assert json.loads(txt.strip().splitlines()[-1])["parallel"] == \
            "sharded"

    def test_scan_search_sharded_vs_pool(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        d1, d2 = tmp_path / "s", tmp_path / "p"
        d1.mkdir(), d2.mkdir()
        common = ("scan", root, "AGBT22B_999_01", "0011",
                  "--nfft", "64", "--nint", "2", "--window-frames", "16",
                  "--search", "--window-spectra", "4", "--snr", "4")
        rc1, txt1 = self._run(capsys, *common, "-o", str(d1), "--sharded")
        rc2, txt2 = self._run(capsys, *common, "-o", str(d2), "--pool")
        assert rc1 == rc2 == 0
        hits1 = sorted(p.name for p in d1.glob("*.hits"))
        hits2 = sorted(p.name for p in d2.glob("*.hits"))
        assert hits1 == hits2 and hits1
        for name in hits1:
            assert filecmp.cmp(str(d1 / name), str(d2 / name),
                               shallow=False), name


class TestSearchResumeReplay:
    def test_search_crash_resume_byte_identical(self, tmp_path,
                                                monkeypatch):
        # The SearchCursor twin of TestResumeReplay (ISSUE 12): crash
        # the sharded SEARCH after the 3rd window's channelize, leave
        # per-player cursors (window_claims ledger included), resume at
        # the pod-agreed window, and byte-match both the uninterrupted
        # sharded run AND the pool oracle.
        from blit.search import DedopplerReducer
        from blit.search.dedoppler import SearchCursor

        nband, nbank = 1, 8
        paths = make_scan(tmp_path, nband, nbank, nblocks=4)
        wspec, wf = 4, 8
        kw = dict(nfft=NFFT, nint=NINT, window_spectra=wspec,
                  window_frames=wf, snr_threshold=4.0)
        gold = tmp_path / "gold"
        gold.mkdir()
        gw = search_scan_sharded_to_files(paths, out_dir=str(gold), **kw)

        res = tmp_path / "res"
        res.mkdir()
        real = M.band_reduce
        calls = []

        def flaky(*a, **k):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("synthetic crash")
            return real(*a, **k)

        monkeypatch.setattr(M, "band_reduce", flaky)
        with pytest.raises(RuntimeError, match="synthetic crash"):
            search_scan_sharded_to_files(paths, out_dir=str(res),
                                         resume=True, **kw)
        monkeypatch.setattr(M, "band_reduce", real)
        cursors = [p for p in os.listdir(res) if p.endswith(".cursor")]
        assert len(cursors) == nbank, "every player keeps a cursor"
        cur = SearchCursor.load(str(res / "band0bank0.hits"))
        assert cur is not None and cur.window_claims is not None

        rw = search_scan_sharded_to_files(paths, out_dir=str(res),
                                          resume=True, **kw)
        assert not [p for p in os.listdir(res) if p.endswith(".cursor")]
        pd = tmp_path / "poolhits"
        pd.mkdir()
        for (b, k), (spath, shdr) in rw.items():
            assert filecmp.cmp(spath, gw[(b, k)][0], shallow=False), (
                f"player ({b},{k}): resumed != uninterrupted")
            red = DedopplerReducer(nfft=NFFT, nint=NINT,
                                   window_spectra=wspec,
                                   snr_threshold=4.0, chunk_frames=wf)
            opath = str(pd / f"band{b}bank{k}.hits")
            red.search_to_file(paths[b][k], opath)
            assert filecmp.cmp(spath, opath, shallow=False), (
                f"player ({b},{k}): resumed != pool oracle")
            assert shdr["search_windows"] > 0

    def test_search_resume_restart_at_earlier_agreed_window(
            self, tmp_path):
        # The pod-minimum restart on the RAGGED product: hand-roll one
        # player's cursor BACK two windows (as if a peer had claimed
        # less) and check the resumed product still finishes exact —
        # the window_claims ledger truncation.
        from blit.search.dedoppler import SearchCursor

        nband, nbank = 1, 8
        paths = make_scan(tmp_path, nband, nbank, nblocks=4)
        wspec, wf = 4, 8
        kw = dict(nfft=NFFT, nint=NINT, window_spectra=wspec,
                  window_frames=wf, snr_threshold=4.0)
        gold = tmp_path / "gold"
        gold.mkdir()
        gw = search_scan_sharded_to_files(paths, out_dir=str(gold), **kw)

        res = tmp_path / "res"
        res.mkdir()
        with pytest.raises(RuntimeError):
            _crash_search_after(paths, res, kw, nwindows=3)
        # Roll ONE player back: the pod-wide agreement must restart
        # every player at the minimum.
        target = str(res / "band0bank3.hits")
        cur = SearchCursor.load(target)
        assert cur.windows_done >= 2
        back = cur.windows_done - 1
        off, hits = cur.claim_at(back)
        cur.windows_done, cur.byte_offset, cur.hits_done = back, off, hits
        cur.window_claims = [e for e in cur.window_claims
                             if e[0] <= back]
        cur.save(target)
        with open(target, "r+b") as f:
            f.truncate(off)

        rw = search_scan_sharded_to_files(paths, out_dir=str(res),
                                          resume=True, **kw)
        for (b, k), (spath, _) in rw.items():
            assert filecmp.cmp(spath, gw[(b, k)][0], shallow=False), (
                f"player ({b},{k}): agreed-restart resume != golden")


def _crash_search_after(paths, outdir, kw, nwindows):
    """Run the sharded search with a band_reduce that crashes after
    ``nwindows`` scan windows (monkeypatch-free helper for reuse)."""
    real = M.band_reduce
    calls = []

    def flaky(*a, **k):
        calls.append(1)
        if len(calls) == nwindows:
            raise RuntimeError("synthetic crash")
        return real(*a, **k)

    M.band_reduce = flaky
    try:
        search_scan_sharded_to_files(paths, out_dir=str(outdir),
                                     resume=True, **kw)
    finally:
        M.band_reduce = real
