"""The three ingest knobs (``chunk_frames`` / ``prefetch_depth`` /
``out_depth``) have ONE source (ISSUE 47): the caller's value, else what
the code derives.  Pinned here: the knobs every benchmark cell's command
resolves, the rotation arithmetic behind the depths, the provenance every
report carries, and that nothing outside the process (the tuning
profiles and ``BLIT_TUNE*`` variables PR 47 removed) is read or written.
"""

import hashlib
import json
import os
import socket

import pytest

jax = pytest.importorskip("jax")

import blit.__main__ as M  # noqa: E402
from blit.config import default_window_frames  # noqa: E402
from blit.outplane import readback_extra_slots  # noqa: E402
from blit.parallel.scan import scan_window_frames  # noqa: E402
from blit.pipeline import RawReducer  # noqa: E402
from blit.search import DedopplerReducer  # noqa: E402
from blit.testing import build_observation_tree, synth_raw  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NFFT, NINT = 64, 2
SESSION, SCAN = "AGBT22B_999_01", "0011"


def _cell_argv(cell, **fill):
    """The argv ``benchmark/run.py`` hands the CLI for ``cell``: the
    traffic file's own, placeholders filled."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        traffic = {w["name"]: w["traffic"]
                   for w in json.load(f)["workloads"]}[cell]
    with open(os.path.join(REPO, "benchmark", "traffic",
                           traffic + ".json")) as f:
        words = json.load(f)["argv"]
    return [w.format(**fill) for w in words]


def _raw(path, chunks=12, chunk_frames=4, nfft=NFFT, **kw):
    """A recording of ``chunks`` whole chunks (and the filter's head)."""
    ntime = (chunks * chunk_frames + 3) * nfft
    synth_raw(str(path), nblocks=2, obsnchan=2,
              ntime_per_block=-(-ntime // 2), tone_chan=1, **kw)
    return str(path)


def _tree(tmp_path):
    root = str(tmp_path / "datax")
    build_observation_tree(root, kind="raw", players=((0, 0), (0, 1)),
                           nchans=2, nfiles=2, raw_ntime=512)
    return root


def _scan(capsys, root, out, *more):
    assert M.main(["scan", root, SESSION, SCAN, "-o", str(out),
                   "--nfft", str(NFFT), "--nint", str(NINT), *more]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- (a) the knobs the ledger's numbers were taken at -------------------------

# cell -> chunk_frames and, per product, (carried, _lanes) for `blit
# reduce`; the window for `blit scan`.
CELLS = {
    "bank.hires": (8, [(False, 0)]),
    "bank.lowres": (3072, [(False, 0)]),
    "rawspec.hires51": (8, [(True, 0)]),
    "rawspec3.hires51": (8, [(True, 0), (True, 1024), (True, 0)]),
    "band4.hires": 2,
    "band4.hires51": 2,
    "band4.rawspec3": 2,
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_knobs_of_a_cells_command(cell, tmp_path, capsys, monkeypatch):
    """Each cell's argv through the CLI's own entry point, stopped where
    the reduction would start: the reducer ``_cmd_reduce`` built, or
    what ``_cmd_scan`` handed the mesh loop."""
    seen = {}
    if isinstance(CELLS[cell], tuple):
        def stop(self, *a, **kw):
            seen["red"] = self
            return [{} for _ in self.products] if self.also else {}

        monkeypatch.setattr(RawReducer, "reduce_to_file", stop)
        monkeypatch.setattr(RawReducer, "reduce_to_files", stop)
        assert M.main(_cell_argv(cell, raws="x.raw",
                                 out=str(tmp_path / "o"))) == 0
        red = seen["red"]
        chunk_frames, legs = CELLS[cell]
        assert (red.chunk_frames, red.prefetch_depth, red.out_depth) == \
            (chunk_frames, 2, 2)
        assert [(red._leg_carries(k), red._lanes(k, 2))
                for k in range(len(red.products))] == legs
        assert set(red.tuning_provenance()["sources"].values()) == \
            {"default"}
        return
    import blit.parallel.scan as S
    import blit.parallel.sharded as SH

    def mesh_loop(session, scan, **kw):
        seen.update(kw)
        return {}

    def other_plane(*a, **kw):
        raise AssertionError("not the mesh loop")

    monkeypatch.setattr(S, "reduce_scan_mesh_to_files", mesh_loop)
    monkeypatch.setattr(S, "reduce_scan_pool_to_files", other_plane)
    monkeypatch.setattr(SH, "reduce_scan_sharded_to_files", other_plane)
    assert M.main(_cell_argv(cell, root=str(tmp_path), session=SESSION,
                             scan=SCAN, out=str(tmp_path))) == 0
    stats = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert seen["window_frames"] == stats["window_frames"] == CELLS[cell]
    assert stats["tuning"] == {"source": "explicit"}
    assert stats["parallel"] == "mesh"
    assert len(seen["also"]) == (2 if cell == "band4.rawspec3" else 0)


# -- (b) the depths: rotation arithmetic, and no product byte -----------------

@pytest.mark.parametrize("out_depth,prefetch_depth,extra", [
    (2, 2, 1), (4, 2, 3), (1, 1, 1), (3, 4, 1), (8, 2, 7)])
def test_readback_extra_slots(out_depth, prefetch_depth, extra):
    assert readback_extra_slots(out_depth, prefetch_depth) == extra


@pytest.mark.parametrize("out_depth,prefetch_depth",
                         [(2, 2), (2, 4), (3, 2), (4, 4)])
def test_depths_move_no_product_byte(out_depth, prefetch_depth, tmp_path):
    raw = _raw(tmp_path / "d.raw")
    kw = dict(nfft=NFFT, nint=NINT, chunk_frames=4)
    RawReducer(async_output=False, **kw).reduce_to_file(
        raw, str(tmp_path / "sync.fil"))
    RawReducer(out_depth=out_depth, prefetch_depth=prefetch_depth,
               **kw).reduce_to_file(raw, str(tmp_path / "deep.fil"))
    assert (tmp_path / "deep.fil").read_bytes() == \
        (tmp_path / "sync.fil").read_bytes()


# -- (c) provenance -----------------------------------------------------------

GIVEN = {"chunk_frames": 4, "prefetch_depth": 3, "out_depth": 3}


@pytest.mark.parametrize("given", [True, False])
@pytest.mark.parametrize("knob", sorted(GIVEN))
def test_a_knobs_source_is_the_caller_or_the_default(knob, given):
    red = RawReducer(nfft=NFFT, nint=NINT,
                     **({knob: GIVEN[knob]} if given else {}))
    prov = red.tuning_provenance()
    assert sorted(prov) == ["chunk_frames", "out_depth", "prefetch_depth",
                            "sources"]
    assert prov["sources"] == {
        k: "explicit" if given and k == knob else "default" for k in GIVEN}
    assert all(prov[k] == getattr(red, k) for k in GIVEN)
    if given:
        assert prov[knob] == GIVEN[knob]
    else:
        # the budget's chunk (whole integrations), depth 2
        assert (prov["chunk_frames"], prov["prefetch_depth"],
                prov["out_depth"]) == (64, 2, 2)


def test_a_search_reports_its_inner_reducers_knobs():
    red = DedopplerReducer(nfft=128, window_spectra=8, chunk_frames=16)
    prov = red.tuning_provenance()
    assert prov == red._red.tuning_provenance()
    assert prov["sources"] == {"chunk_frames": "explicit",
                               "prefetch_depth": "default",
                               "out_depth": "default"}
    assert (red.prefetch_depth, red.out_depth) == (2, 2)


def test_a_live_products_header_carries_stream_tuning(tmp_path):
    from blit.stream import ReplaySource, stream_reduce

    raw = _raw(tmp_path / "live.raw", chunks=4)
    red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=4)
    hdr = stream_reduce(ReplaySource(raw, rate=1e6),
                        str(tmp_path / "live.fil"), reducer=red)
    assert hdr["stream_tuning"] == red.tuning_provenance()
    assert hdr["stream_tuning"]["sources"]["chunk_frames"] == "explicit"


@pytest.mark.parametrize("window", [None, 4])
def test_blit_scan_names_its_windows_source(window, tmp_path, capsys):
    stats = _scan(capsys, _tree(tmp_path), tmp_path,
                  *(["--window-frames", str(window)] if window else []))
    assert stats["tuning"] == {
        "source": "explicit" if window else "default"}
    assert stats["window_frames"] == scan_window_frames(
        NFFT, NINT, window or default_window_frames(NFFT))
    assert stats["parallel"] == "mesh"


# -- (d) one source -----------------------------------------------------------

def _plant_profile(directory, nfft, nint):
    """A tuning profile as the tree before PR 47 wrote it, under the key
    that tree looks up for this rig and shape: there it sets
    ``chunk_frames`` 4 and depths 3 / 4 wherever the caller left them
    unset.  Returns ``(path, bytes)``."""
    devs = jax.devices()
    rig = {"host": socket.gethostname(), "backend": jax.default_backend(),
           "device_kind": devs[0].device_kind, "device_count": len(devs),
           "workload": "reduce", "nfft": nfft, "ntap": 4, "nint": nint,
           "stokes": "I", "window": "hamming", "fqav_by": 1,
           "dtype": "float32", "fft_method": "auto", "nbits": 32}
    key = hashlib.sha256(
        json.dumps(rig, sort_keys=True).encode()).hexdigest()
    doc = {"version": 1, "key": key, "rig": rig, "chunk_frames": 4,
           "prefetch_depth": 3, "out_depth": 4, "score_gbps": 1.0,
           "trials": 1, "stages": {}, "source": "offline",
           "created_s": 1.0, "tuned_nchan": 2}
    path = os.path.join(directory, f"tune-{key[:24]}.json")
    with open(path, "w") as f:
        json.dump(doc, f, sort_keys=True)
    with open(path, "rb") as f:
        return path, f.read()


def _reduction_knobs(tmp_path, capsys):
    red = RawReducer(nfft=NFFT, nint=NINT)
    # 12 chunks: past the old online tuner's 8-chunk warm-up
    red.reduce_to_file(_raw(tmp_path / "r.raw", chunk_frames=64),
                       str(tmp_path / "r.fil"))
    return red.tuning_provenance()


def _mesh_scan_knobs(tmp_path, capsys):
    stats = _scan(capsys, _tree(tmp_path), tmp_path)
    return stats["window_frames"], stats["tuning"]


def _search_knobs(tmp_path, capsys):
    red = DedopplerReducer(nfft=128, window_spectra=8, top_k=4,
                           snr_threshold=2.0, kernel="reference")
    red.search(_raw(tmp_path / "s.raw", chunks=3, chunk_frames=8,
                    nfft=128))
    return red.tuning_provenance()


@pytest.mark.parametrize("run,shape", [
    (_reduction_knobs, (NFFT, NINT)), (_mesh_scan_knobs, (NFFT, NINT)),
    (_search_knobs, (128, 1))], ids=["reduction", "mesh-scan", "search"])
def test_nothing_outside_the_process_sets_a_knob(run, shape, tmp_path,
                                                 capsys, monkeypatch):
    (tmp_path / "without").mkdir()
    (tmp_path / "with").mkdir()
    want = run(tmp_path / "without", capsys)
    store = tmp_path / "profiles"
    store.mkdir()
    path, was = _plant_profile(str(store), *shape)
    monkeypatch.setenv("BLIT_TUNE_DIR", str(store))
    monkeypatch.setenv("BLIT_TUNE_ONLINE", "1")
    assert run(tmp_path / "with", capsys) == want
    assert os.listdir(store) == [os.path.basename(path)]
    with open(path, "rb") as f:
        assert f.read() == was


def test_blit_tune_is_no_command(capsys):
    with pytest.raises(SystemExit) as e:
        M.main(["tune"])
    assert e.value.code == 2
    assert "invalid choice" in capsys.readouterr().err


def test_a_stale_caller_fails_loudly():
    with pytest.raises(TypeError, match="tune_online"):
        RawReducer(nfft=NFFT, tune_online=False)


# -- (e) no tuner's gauges ----------------------------------------------------

def test_a_reduction_publishes_no_tune_gauge(tmp_path):
    red = RawReducer(nfft=NFFT, nint=NINT, chunk_frames=4)
    red.reduce_to_file(_raw(tmp_path / "g.raw", chunks=10),
                       str(tmp_path / "g.fil"))
    assert red.timeline.stages["dispatch"].calls == 10
    names = set(red.timeline.gauges) | set(
        red.timeline.report().get("gauges", {}))
    assert not [n for n in names if n.startswith("tune.")]
