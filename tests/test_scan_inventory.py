"""Inventory-driven mesh workflow (VERDICT r3 item 1): synthetic
observation tree → get_inventory → scan_grid → load_scan_mesh(session,
scan) / reduce_scan_mesh_to_files, golden-tested against the host
pipeline — the reference's whole-scan call shape (``loadscan(session,
scan, suffix)``, src/gbt.jl:99) driving the TPU data plane."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from blit.inventory import get_inventory, scan_grid  # noqa: E402
from blit.io.sigproc import read_fil_data  # noqa: E402
from blit.ops.fqav import fqav_range  # noqa: E402
from blit.parallel.scan import (  # noqa: E402
    load_scan_mesh,
    reduce_scan_mesh_to_files,
)
from blit.pipeline import RawReducer  # noqa: E402
from blit.testing import build_observation_tree  # noqa: E402

SESSION = "AGBT22B_999_01"
SCAN = "0011"
NFFT, NINT = 64, 2
PLAYERS = ((0, 0), (0, 1), (0, 2), (0, 3))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("datax"))
    build_observation_tree(
        root, session=SESSION, scans=(SCAN, "0012"), players=PLAYERS,
        kind="raw", nchans=2, nfiles=2, raw_ntime=512,
    )
    invs = [get_inventory(file_re=r"\.raw$", root=root)]
    return root, invs


def host_golden(invs, fqav_by=1, stokes="I"):
    """Per-bank RawReducer over the same sequences, channel-concatenated."""
    _, _, grid = scan_grid(invs, SESSION, SCAN)
    banks = []
    for paths in grid[0]:
        red = RawReducer(nfft=NFFT, nint=NINT, fqav_by=fqav_by,
                         stokes=stokes)
        _, d = red.reduce(paths)
        banks.append(d)
    return np.concatenate(banks, axis=-1)


class TestScanGrid:
    def test_grid_shape_and_band_ids(self, tree):
        _, invs = tree
        band_ids, bank_ids, grid = scan_grid(invs, SESSION, SCAN)
        assert band_ids == [0] and bank_ids == [0, 1, 2, 3]
        assert len(grid) == 1 and len(grid[0]) == 4
        # Each cell is the full 2-file .NNNN.raw sequence, sorted.
        for k, paths in enumerate(grid[0]):
            assert len(paths) == 2
            assert paths == sorted(paths)
            assert f"BLP0{k}/" in paths[0]

    def test_scan_filter(self, tree):
        _, invs = tree
        b12, _, g12 = scan_grid(invs, SESSION, "0012")
        assert b12 == [0]
        assert g12[0][0] != scan_grid(invs, SESSION, SCAN)[2][0][0]

    def test_unknown_scan_rejected(self, tree):
        _, invs = tree
        with pytest.raises(ValueError, match="no RAW sequences"):
            scan_grid(invs, SESSION, "9999")

    def test_ragged_grid_rejected(self, tree):
        _, invs = tree
        # A second band missing one bank the first has: the (band, bank)
        # rectangle has a hole.  (Dropping a bank from EVERY band just
        # shrinks the grid — only cross-band raggedness is an error.)
        fake_band1 = [
            r._replace(band=1, file=r.file.replace("BLP0", "BLP1"))
            for r in invs[0]
            if r.bank != 3
        ]
        with pytest.raises(ValueError, match="rectangular"):
            scan_grid([invs[0] + fake_band1], SESSION, SCAN)

    def test_worker_error_entries_skipped(self, tree):
        # The REAL captured-failure type (a dataclass, not an Exception):
        # get_inventories(on_error="capture") returns these inline.
        from blit.parallel.pool import WorkerError

        _, invs = tree
        dead = WorkerError(worker=9, host="blc99",
                           error=RuntimeError("worker died"))
        band_ids, _, _ = scan_grid(invs + [dead], SESSION, SCAN)
        assert band_ids == [0]


class TestLoadScanMeshFromInventory:
    def test_matches_host_pipeline(self, tree):
        _, invs = tree
        hdr, out = load_scan_mesh(
            SESSION, SCAN, inventories=invs, nfft=NFFT, nint=NINT,
            despike=False,
        )
        got = np.asarray(out)
        want = host_golden(invs)[: got.shape[1]]
        assert hdr["nchans"] == want.shape[-1] == got.shape[-1]
        np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=0.5)

    def test_session_form_needs_inventories(self):
        with pytest.raises(ValueError, match="session-form"):
            load_scan_mesh(SESSION, SCAN, nfft=NFFT)

    def test_explicit_grid_rejects_inventories(self, tree):
        _, invs = tree
        with pytest.raises(ValueError, match="explicit raw_paths"):
            load_scan_mesh([["x.raw"]], inventories=invs, nfft=NFFT)


class TestMeshFqav:
    def test_fqav_matches_host(self, tree):
        _, invs = tree
        hdr, out = load_scan_mesh(
            SESSION, SCAN, inventories=invs, nfft=NFFT, nint=NINT,
            fqav_by=4, despike=False,
        )
        got = np.asarray(out)
        want = host_golden(invs, fqav_by=4)[: got.shape[1]]
        assert got.shape[-1] == want.shape[-1] == 4 * 2 * NFFT // 4
        np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=2.0)

    def test_fqav_header_math(self, tree):
        _, invs = tree
        h1, _ = load_scan_mesh(SESSION, SCAN, inventories=invs, nfft=NFFT,
                               nint=NINT, despike=False)
        h4, _ = load_scan_mesh(SESSION, SCAN, inventories=invs, nfft=NFFT,
                               nint=NINT, fqav_by=4, despike=False)
        fch1, foff, nchans = fqav_range(
            h1["fch1"], h1["foff"], h1["nchans"], 4
        )
        assert h4["foff"] == pytest.approx(foff)
        assert h4["fch1"] == pytest.approx(fch1)
        assert h4["nchans"] == nchans and h4["nfpc"] == NFFT // 4
        # Same total band span either way.
        assert abs(h4["foff"]) * h4["nchans"] == pytest.approx(
            abs(h1["foff"]) * h1["nchans"]
        )


class TestReduceScanMeshToFiles:
    def test_windowed_products_match_unwindowed(self, tree, tmp_path):
        _, invs = tree
        hdr, out = load_scan_mesh(
            SESSION, SCAN, inventories=invs, nfft=NFFT, nint=NINT,
        )
        whole = np.asarray(out)
        written = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, window_frames=4,
        )
        assert list(written) == [0]
        path, whdr = written[0]
        assert path.endswith("band0.fil") and whdr["nsamps"] == whole.shape[1]
        rhdr, data = read_fil_data(path)
        assert rhdr["nchans"] == hdr["nchans"]
        assert rhdr["fch1"] == pytest.approx(hdr["fch1"])
        np.testing.assert_allclose(
            np.asarray(data), whole[0], rtol=1e-4, atol=0.5
        )

    def test_fqav_product_matches_host(self, tree, tmp_path):
        _, invs = tree
        written = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, fqav_by=4, despike=False, window_frames=6,
        )
        _, data = read_fil_data(written[0][0])
        want = host_golden(invs, fqav_by=4)[: data.shape[0]]
        np.testing.assert_allclose(np.asarray(data), want, rtol=1e-4,
                                   atol=2.0)

    def test_no_partial_left_behind(self, tree, tmp_path):
        _, invs = tree
        reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT,
        )
        assert not list(tmp_path.glob("*.partial"))

    def test_max_frames_caps_product(self, tree, tmp_path):
        _, invs = tree
        written = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, max_frames=4,
        )
        _, data = read_fil_data(written[0][0])
        assert data.shape[0] == 4 // NINT

    def test_h5_product_matches_fil(self, tree, tmp_path):
        # The mesh writer's .h5 leg (FBH5Writer, bitshuffle) carries the
        # same payload as the .fil leg.
        from blit.io.fbh5 import read_fbh5_data, read_fbh5_header

        _, invs = tree
        fil = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, window_frames=4,
        )
        h5 = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, window_frames=4, compression="bitshuffle",
        )
        assert h5[0][0].endswith("band0.h5")
        _, fdata = read_fil_data(fil[0][0])
        np.testing.assert_array_equal(
            read_fbh5_data(h5[0][0]), np.asarray(fdata)
        )
        hh = read_fbh5_header(h5[0][0])
        assert hh["nchans"] == fil[0][1]["nchans"]
        assert hh["fch1"] == pytest.approx(fil[0][1]["fch1"])
        assert not list(tmp_path.glob("*.partial"))

    def test_creation_failure_leaves_no_partials(self, tree, tmp_path):
        _, invs = tree
        bad = str(tmp_path / "no_such_dir" / "band0.fil")
        with pytest.raises(FileNotFoundError):
            reduce_scan_mesh_to_files(
                SESSION, SCAN, inventories=invs, out_paths=[bad],
                nfft=NFFT, nint=NINT,
            )
        assert not list(tmp_path.rglob("*.partial"))

    def test_midstream_failure_drops_partials(self, tree, tmp_path,
                                              monkeypatch):
        # The reduction dying between windows must abort every writer:
        # no .partial siblings, no valid-looking truncated products.
        from blit.parallel import mesh as M

        _, invs = tree
        real = M.band_stream
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("synthetic device failure")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_stream", flaky)
        with pytest.raises(RuntimeError, match="synthetic device failure"):
            reduce_scan_mesh_to_files(
                SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
                nfft=NFFT, nint=NINT, window_frames=4,
            )
        assert not list(tmp_path.glob("*.partial"))
        assert not list(tmp_path.glob("*.fil"))


class TestWindowEquivalenceFuzz:
    @pytest.mark.parametrize("seed", range(4))
    def test_random_window_configs_match_unwindowed(self, tree, tmp_path,
                                                    seed):
        # Property: for ANY window size, nint, and fqav the windowed
        # streaming product equals the one-shot mesh reduction (PFB
        # overlap re-reads, nint-aligned windows, ragged last window).
        rng = np.random.default_rng(seed)
        _, invs = tree
        nint = int(rng.choice([1, 2, 4]))
        fqav = int(rng.choice([1, 2, 8]))
        wf = int(rng.integers(1, 9))
        _, out = load_scan_mesh(
            SESSION, SCAN, inventories=invs, nfft=NFFT, nint=nint,
            fqav_by=fqav,
        )
        written = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=nint, fqav_by=fqav, window_frames=wf,
        )
        _, data = read_fil_data(written[0][0])
        np.testing.assert_allclose(
            np.asarray(data), np.asarray(out)[0], rtol=1e-4, atol=0.5,
            err_msg=f"nint={nint} fqav={fqav} window_frames={wf}",
        )


class TestBf16StagesMeshProduct:
    def test_bf16_stages_match_f32_within_rounding(self, tree, tmp_path):
        # The single-chip pipeline's biggest measured lever (DESIGN §3)
        # reaches the mesh path: dtype="bfloat16" runs the per-chip
        # channelizer stages half-width; the product stays float32 and
        # matches the f32 reduction within bf16 rounding.
        _, invs = tree
        f32_dir, bf_dir = tmp_path / "f32", tmp_path / "bf16"
        f32_dir.mkdir(), bf_dir.mkdir()
        reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(f32_dir),
            nfft=NFFT, nint=NINT, window_frames=4,
        )
        written = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(bf_dir),
            nfft=NFFT, nint=NINT, window_frames=4, dtype="bfloat16",
        )
        _, a = read_fil_data(str(f32_dir / "band0.fil"))
        hdr, b = read_fil_data(written[0][0])
        assert np.asarray(b).dtype == np.float32
        scale = float(np.abs(np.asarray(a)).max())
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-2, atol=2e-2 * scale)

    def test_dtype_flip_restarts_resume_fresh(self, tree, tmp_path,
                                              monkeypatch):
        # dtype is output-affecting: a resume under the other dtype must
        # restart fresh (cursor identity), not splice mixed-rounding
        # spectra.
        from blit.parallel import mesh as M

        _, invs = tree
        real = M.band_stream
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            # Call 3: one window is already FLUSHED (the loop keeps one
            # window in flight, so the first append happens after the
            # 2nd dispatch) — the cursor genuinely claims progress and
            # the dtype-flipped resume must DISCARD it, not splice.
            if len(calls) == 3:
                raise RuntimeError("boom")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_stream", flaky)
        with pytest.raises(RuntimeError):
            reduce_scan_mesh_to_files(
                SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
                nfft=NFFT, nint=NINT, window_frames=4, resume=True,
                despike=False,
            )
        _, partial = read_fil_data(str(tmp_path / "band0.fil"), mmap=False)
        assert partial.shape[0] > 0  # the identity guard has work to undo
        monkeypatch.setattr(M, "band_stream", real)
        reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, window_frames=4, resume=True,
            dtype="bfloat16", despike=False,
        )
        _, data = read_fil_data(str(tmp_path / "band0.fil"))
        want = host_golden(invs)[: data.shape[0]]
        scale = float(np.abs(want).max())
        np.testing.assert_allclose(np.asarray(data), want, rtol=2e-2,
                                   atol=2e-2 * scale)


class TestFullStokesMeshProduct:
    def test_iquv_product_matches_host(self, tree, tmp_path):
        # Full polarimetry through the WHOLE mesh workflow: the nif=4
        # product streams per band with nifs=4 headers, matching the
        # host pipeline's IQUV reduction (the fused tail2_detect product
        # generalization, bench leg stokes_iquv_gbps).
        _, invs = tree
        written = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, stokes="IQUV", despike=False,
            window_frames=4,
        )
        hdr, data = read_fil_data(written[0][0])
        assert hdr["nifs"] == 4 and data.shape[1] == 4
        want = host_golden(invs, stokes="IQUV")[: data.shape[0]]
        np.testing.assert_allclose(np.asarray(data), want, rtol=1e-4,
                                   atol=0.5)


class TestBoundedDefaultWindow:
    def test_library_default_windows_the_scan(self, tree, tmp_path,
                                              monkeypatch):
        # window_frames=None must bound the device window at EVERY entry
        # point, not just the CLI: the library derives the HBM-safe
        # default from nfft.  (Shrunk here so the synthetic scan spans
        # several windows; the product must still match one-shot.)
        import blit.config as C
        from blit.observability import Timeline

        _, invs = tree
        monkeypatch.setattr(C, "default_window_frames", lambda nfft: 4)
        tl = Timeline()
        written = reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, timeline=tl,
        )
        assert tl.stages["ingest"].calls > 1  # it actually windowed
        _, out = load_scan_mesh(SESSION, SCAN, inventories=invs,
                                nfft=NFFT, nint=NINT)
        _, data = read_fil_data(written[0][0])
        np.testing.assert_allclose(np.asarray(data), np.asarray(out)[0],
                                   rtol=1e-4, atol=0.5)


class TestMeshResume:
    def run_resumable(self, invs, outdir, **kw):
        return reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(outdir),
            nfft=NFFT, nint=NINT, window_frames=4, resume=True, **kw,
        )

    def test_interrupted_run_resumes_to_identical_product(
        self, tree, tmp_path, monkeypatch
    ):
        from blit.parallel import mesh as M

        _, invs = tree
        golden_dir = tmp_path / "golden"
        golden_dir.mkdir()
        self.run_resumable(invs, golden_dir)
        _, golden = read_fil_data(str(golden_dir / "band0.fil"))

        # Crash mid-stream on the third device window.
        crash_dir = tmp_path / "crash"
        crash_dir.mkdir()
        real = M.band_stream
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("synthetic crash")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_stream", flaky)
        with pytest.raises(RuntimeError, match="synthetic crash"):
            self.run_resumable(invs, crash_dir)
        # The partial product + cursor sidecar survive the crash.
        out = crash_dir / "band0.fil"
        assert out.exists() and (crash_dir / "band0.fil.cursor").exists()
        _, partial = read_fil_data(str(out), mmap=False)
        assert 0 < partial.shape[0] < golden.shape[0]

        # Resume: continues from the checkpoint, finishes, removes the
        # cursor, and the product is IDENTICAL to the uninterrupted run.
        monkeypatch.setattr(M, "band_stream", real)
        written = self.run_resumable(invs, crash_dir)
        assert not (crash_dir / "band0.fil.cursor").exists()
        _, data = read_fil_data(str(out))
        np.testing.assert_array_equal(np.asarray(data), np.asarray(golden))
        assert written[0][1]["nsamps"] == golden.shape[0]

    def test_config_change_restarts_from_scratch(self, tree, tmp_path,
                                                 monkeypatch):
        from blit.parallel import mesh as M

        _, invs = tree
        real = M.band_stream
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("boom")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_stream", flaky)
        with pytest.raises(RuntimeError):
            self.run_resumable(invs, tmp_path)
        monkeypatch.setattr(M, "band_stream", real)
        # Different fqav_by: the cursor must NOT match — the run restarts
        # cleanly instead of splicing incompatible spectra.
        written = self.run_resumable(invs, tmp_path, fqav_by=2,
                                     despike=False)
        _, data = read_fil_data(written[0][0])
        want = host_golden(invs, fqav_by=2)[: data.shape[0]]
        np.testing.assert_allclose(np.asarray(data), want, rtol=1e-4,
                                   atol=1.0)

    def test_h5_bitshuffle_interrupted_resumes_identically(
        self, tree, tmp_path, monkeypatch
    ):
        # The native-format twin of the .fil resume above (VERDICT r4
        # missing item 2): bitshuffle FBH5 band products crash-resume via
        # resize-truncate, decoded payload identical to an uninterrupted
        # run, with chunk rows tied to the window granularity so the
        # pod-agreed restart offset stays chunk-aligned.
        pytest.importorskip("blit.io.bshuf").available() or pytest.skip(
            "native codec unbuilt")
        from blit.io.fbh5 import read_fbh5_data
        from blit.parallel import mesh as M

        _, invs = tree
        golden_dir = tmp_path / "golden"
        golden_dir.mkdir()
        self.run_resumable(invs, golden_dir, compression="bitshuffle")
        golden = read_fbh5_data(str(golden_dir / "band0.h5"))

        crash_dir = tmp_path / "crash"
        crash_dir.mkdir()
        real = M.band_stream
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("synthetic crash")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_stream", flaky)
        with pytest.raises(RuntimeError, match="synthetic crash"):
            self.run_resumable(invs, crash_dir, compression="bitshuffle")
        out = crash_dir / "band0.h5"
        assert out.exists() and (crash_dir / "band0.h5.cursor").exists()
        partial = read_fbh5_data(str(out))
        assert 0 < partial.shape[0] < golden.shape[0]

        monkeypatch.setattr(M, "band_stream", real)
        written = self.run_resumable(invs, crash_dir,
                                     compression="bitshuffle")
        assert not (crash_dir / "band0.h5.cursor").exists()
        np.testing.assert_array_equal(read_fbh5_data(str(out)), golden)
        assert written[0][1]["nsamps"] == golden.shape[0]

    def test_compression_with_fil_paths_rejected_before_collectives(
        self, tree, tmp_path
    ):
        # The mismatch must raise on EVERY process before any collective
        # (out_paths is globally known): a per-band raise would fire only
        # on band-owning processes and deadlock the rest in the window
        # loop.  Exercised here through explicit .fil out_paths.
        _, invs = tree
        with pytest.raises(ValueError, match="uncompressed"):
            reduce_scan_mesh_to_files(
                SESSION, SCAN, inventories=invs,
                out_paths=[str(tmp_path / "band0.fil")],
                nfft=NFFT, nint=NINT, window_frames=4,
                compression="bitshuffle", resume=True,
            )

    def test_h5_window_change_restarts_fresh(self, tree, tmp_path,
                                             monkeypatch):
        # Bitshuffle .h5 chunk rows derive from the window granularity, so
        # a resume under a different --window-frames must restart fresh
        # (window_rows is part of the cursor identity), not die on the
        # writer's chunk-mismatch refusal.
        pytest.importorskip("blit.io.bshuf").available() or pytest.skip(
            "native codec unbuilt")
        from blit.io.fbh5 import read_fbh5_data
        from blit.parallel import mesh as M

        _, invs = tree
        real = M.band_stream
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("boom")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_stream", flaky)
        with pytest.raises(RuntimeError):
            self.run_resumable(invs, tmp_path, compression="bitshuffle")
        monkeypatch.setattr(M, "band_stream", real)
        golden_dir = tmp_path / "golden"
        golden_dir.mkdir()
        reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(golden_dir),
            nfft=NFFT, nint=NINT, window_frames=6,
            compression="bitshuffle",
        )
        reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(tmp_path),
            nfft=NFFT, nint=NINT, window_frames=6, resume=True,
            compression="bitshuffle",
        )
        np.testing.assert_array_equal(
            read_fbh5_data(str(tmp_path / "band0.h5")),
            read_fbh5_data(str(golden_dir / "band0.h5")),
        )

    def test_completed_resumable_equals_plain(self, tree, tmp_path):
        _, invs = tree
        plain = tmp_path / "plain"
        res = tmp_path / "res"
        plain.mkdir(), res.mkdir()
        reduce_scan_mesh_to_files(
            SESSION, SCAN, inventories=invs, out_dir=str(plain),
            nfft=NFFT, nint=NINT, window_frames=4,
        )
        self.run_resumable(invs, res)
        _, a = read_fil_data(str(plain / "band0.fil"))
        _, b = read_fil_data(str(res / "band0.fil"))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_despike_flip_restarts_from_scratch(self, tree, tmp_path,
                                                monkeypatch):
        # despike is output-affecting: a resume with the flag flipped must
        # NOT splice despiked and raw spectra (cursor identity includes
        # despike_nfpc).
        from blit.parallel import mesh as M

        _, invs = tree
        real = M.band_stream
        calls = []

        def flaky(*a, **kw):
            calls.append(1)
            if len(calls) == 2:
                raise RuntimeError("boom")
            return real(*a, **kw)

        monkeypatch.setattr(M, "band_stream", flaky)
        with pytest.raises(RuntimeError):
            self.run_resumable(invs, tmp_path)  # despike=True default
        monkeypatch.setattr(M, "band_stream", real)
        self.run_resumable(invs, tmp_path, despike=False)
        _, data = read_fil_data(str(tmp_path / "band0.fil"))
        want = host_golden(invs)[: data.shape[0]]  # un-despiked golden
        np.testing.assert_allclose(np.asarray(data), want, rtol=1e-4,
                                   atol=0.5)
