"""Several band products from ONE read of a scan (ISSUE 40): ``blit scan
--nfft 1048576,8,1024 --nint 51,128,3072 --window-frames 2`` at a toy
geometry — ``--nfft 1024,8,64 --nint 3,128,51`` on four virtual CPU
devices — where every mesh window is read once, put once as words and
consumed by a leg per product per chip: the one-chip reducer's own steps
under ``shard_map`` (``parallel/mesh.band_programs``), each product's
integration folded into a bank-sharded accumulator, a stitch only for a
product that closed a row, a band writer per product.

The plain reference is ``channelize_np`` over each bank's whole file,
stitched and despiked, per product; the second reference is the product's
own single command.
"""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit.__main__ import main as blit_main  # noqa: E402
from blit.io.guppi import GuppiRaw  # noqa: E402
from blit.io.sigproc import FilWriter, read_fil_data  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.ops import channelize as ch  # noqa: E402
from blit.ops.channelize import (  # noqa: E402
    channelize_np,
    integrate_carry,
    lanes_block,
    pfb_coeffs,
    usable_frames,
)
from blit.parallel import mesh as M  # noqa: E402
from blit.parallel.scan import (  # noqa: E402
    rawspec_band_path,
    reduce_scan_mesh_to_files,
)
from blit.testing import synth_raw  # noqa: E402

NTAP, NBANK, NCHAN = 4, 4, 2
SESSION, SCAN = "AGBT22B_999_01", "0011"
PRODUCTS = [(1024, 3), (8, 128), (64, 51)]
# 18 frames of 1024 a bank: the 3-frame head and 15 more, eight 2-frame
# windows of which the last holds one.  Of the small products the head
# holds 381 frames of 8 (two rows and 125 frames: 128 does not divide it)
# and 45 of 64 (under one row of 51), a window 256 and 32: both carry an
# open integration over every window boundary, and the nfft 8 leg closes
# rows in its head step and in every window after.
NSAMPS = 18 * 1024
WF = 2
# As tests/test_scan_carry.py holds a carried scan: scale-relative max
# error in float64; the cases here read 2e-7 to 3e-7 on the CPU, a frame
# in the wrong row moves a tone's peak by 1/nint >= 3e-4 of itself, and an
# accumulator kept in bfloat16 reads 1e-3 or more in every product (the
# control below).
TOL = 1e-5


@pytest.fixture(scope="module")
def band(tmp_path_factory):
    """``(root, grid)``: one band of four banks as a GUPPI tree (the CLI
    resolves it) and as an explicit grid (the library call)."""
    root = tmp_path_factory.mktemp("fanout-band")
    bank_bw = -187.5 / NBANK
    row = []
    for k in range(NBANK):
        d = root / SESSION / "GUPPI" / f"BLP0{k}"
        d.mkdir(parents=True)
        p = str(d / f"blc0{k}_guppi_59897_21221_HD_84406_{SCAN}.0000.raw")
        synth_raw(p, nblocks=4, obsnchan=NCHAN, ntime_per_block=NSAMPS // 4,
                  seed=40 + k, tone_chan=k % NCHAN, obsbw=bank_bw,
                  obsfreq=8000.0 + (k + 0.5) * bank_bw)
        row.append(p)
    return str(root), [row]


def rows_of(nfft, nint, nsamps=NSAMPS):
    return usable_frames(nsamps, nfft, NTAP, nint) // nint


def reference(grid, nfft, nint, nsamps=NSAMPS):
    """channelize_np over each bank's whole file, stitched, despiked."""
    rows, banks = rows_of(nfft, nint, nsamps), []
    for p in grid[0]:
        stream = np.concatenate(
            [blk for _, blk in GuppiRaw(p).iter_blocks(drop_overlap=True)],
            axis=1)
        banks.append(np.asarray(channelize_np(
            stream[:, :(rows * nint + NTAP - 1) * nfft],
            pfb_coeffs(NTAP, nfft), nfft=nfft, ntap=NTAP, nint=nint),
            np.float64))
    want = np.concatenate(banks, axis=-1)
    want[..., nfft // 2::nfft] = want[..., nfft // 2 - 1::nfft]
    return want


def rel_err(got, want):
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


def scan(grid, out, products=PRODUCTS, **kw):
    """The library call; ``fft_method="matmul"`` is what the chip runs and
    the only one whose bytes repeat on the CPU at nfft 1024."""
    (nfft, nint), *also = products
    os.makedirs(out, exist_ok=True)
    kw.setdefault("window_frames", WF)
    kw.setdefault("fft_method", "matmul")
    tl = kw.setdefault("timeline", Timeline())
    written = reduce_scan_mesh_to_files(
        grid, out_dir=out, nfft=nfft, nint=nint, also=tuple(also), **kw)
    return written, tl.report()


@pytest.fixture(scope="module")
def three(band, tmp_path_factory):
    """One three-product scan of the band, shared by the read-only tests:
    ``(written, stages, out_dir)``."""
    out = str(tmp_path_factory.mktemp("three"))
    written, st = scan(band[1], out)
    return written, st, out


# -- (a) against the plain reference -------------------------------------------

@pytest.mark.parametrize("k", range(3), ids=["0000", "0001", "0002"])
def test_each_product_matches_the_reference(band, three, k):
    written, _, out = three
    nfft, nint = PRODUCTS[k]
    path, hdr = written[0][k]
    assert path == rawspec_band_path(out, 0, k) \
        == os.path.join(out, f"band0.rawspec.000{k}.fil")
    want = reference(band[1], nfft, nint)
    fhdr, got = read_fil_data(path)
    # its own head, its own rows, its own dropped tail, its own tsamp
    assert got.shape == want.shape == (rows_of(nfft, nint), 1,
                                       NBANK * NCHAN * nfft)
    assert hdr["nsamps"] == rows_of(nfft, nint)
    tbin = GuppiRaw(band[1][0][0]).header(0)["TBIN"]
    assert fhdr["tsamp"] == pytest.approx(tbin * nfft * nint, rel=1e-12)
    assert rel_err(got, want) < TOL
    assert not os.path.exists(path + ".partial")
    with open(path + ".manifest.json") as f:
        doc = json.load(f)
    assert doc["complete"] and doc["bytes"] == os.path.getsize(path)


def test_the_rows_are_rawspecs():
    assert [rows_of(*p) for p in PRODUCTS] == [5, 17, 5]
    # the nfft 8 leg runs frames-on-lanes on the mesh as on one chip
    assert [bool(lanes_block(*p)) for p in PRODUCTS] == [False, True, False]


def test_both_small_integrations_straddle_windows(three):
    """Eight windows; every leg ends some of them with its integration
    open: 0000 (3 frames a row, 2 a window) after windows 1, 2, 4, 5 and 7
    of its eight... counted from the stream: the frames after each window
    are 2, 4, .. 14, 15; 0001 holds 125 frames after its head and after
    every 256-frame window but its last, which ends on its 17th row; 0002
    holds 45 + 32 w."""
    _, st, _ = three
    ends = [2 * w for w in range(1, 8)] + [15]
    open0 = sum(e % 3 != 0 for e in ends)
    # 0001: 2176 frames = 381 in the head, 256 in windows 1-7, 3 in the
    # 8th; open after the head's fold too (125 frames).
    at, open1 = 381, 1
    for took in [256] * 7 + [3]:
        at += took
        open1 += at % 128 != 0
    assert at == rows_of(8, 128) * 128
    # 0002: 255 frames = 45 + 32 x 6 + 18 in the 7th window, none after.
    at, open2 = 45, 1
    for took in [32] * 6 + [18]:
        at += took
        open2 += at % 51 != 0
    assert at == rows_of(64, 51) * 51
    assert st["integrate.carry"]["calls"] == open0 + open1 + open2
    assert open1 >= 8 and open2 >= 7  # every boundary but the last


# -- (b) against the single-product command ------------------------------------

@pytest.mark.parametrize("k", range(3), ids=["0000", "0001", "0002"])
def test_each_product_equals_its_own_command(band, three, tmp_path, k):
    """To the byte: the same frames added in the same order, wherever the
    window grid falls — the single command of a small product windows on a
    grid of its own (frames of its own nfft, from its own 3-frame head).
    Its window is below one integration, so it is kept as given and the
    command folds as the fan-out does; a window of whole integrations sums
    inside its program, in the compiler's order, and reads 3.5e-7 away on
    the CPU (the same frames in the same rows)."""
    written, _, _ = three
    nfft, nint = PRODUCTS[k]
    alone, _ = scan(band[1], str(tmp_path / "alone"), [(nfft, nint)],
                    window_frames={1024: WF, 8: 100, 64: 40}[nfft])
    path, hdr = alone[0]
    assert path.endswith("band0.fil")
    with open(written[0][k][0], "rb") as f, open(path, "rb") as g:
        assert f.read() == g.read()
    assert hdr["nsamps"] == written[0][k][1]["nsamps"]


def test_products_in_another_order(band, tmp_path):
    """The leg that owns the head need not be the first."""
    prods = [(8, 128), (1024, 3), (64, 51)]
    written, st = scan(band[1], str(tmp_path / "o"), prods)
    for (nfft, nint), (path, _) in zip(prods, written[0]):
        assert rel_err(read_fil_data(path)[1],
                       reference(band[1], nfft, nint)) < TOL, (nfft, nint)
    assert st["integrate.emit.0000"]["calls"] == rows_of(8, 128)


def test_max_frames_cuts_the_recording_for_every_product(band, tmp_path):
    """``max_frames`` counts frames of the largest nfft; each product is
    what its own command makes of the samples they span."""
    written, _ = scan(band[1], str(tmp_path / "m"), max_frames=7)
    cut = (7 + NTAP - 1) * 1024
    for (nfft, nint), (path, hdr) in zip(PRODUCTS, written[0]):
        assert hdr["nsamps"] == rows_of(nfft, nint, cut)
        assert rel_err(read_fil_data(path)[1],
                       reference(band[1], nfft, nint, cut)) < TOL
    assert [h["nsamps"] for _, h in written[0]] == [2, 9, 3]


# -- (c) the precision control ---------------------------------------------------

def _round_each_add(monkeypatch, only_nint):
    """``_seq_sum`` with the running sum rounded to bfloat16 after EVERY
    add (tests/test_reduce_fanout.py's control, written apart from the
    fold's own chain), in the fold of ONE leg: the one of ``only_nint``."""
    sound, fold = ch._seq_sum, integrate_carry.__wrapped__

    def seq_sum(start, xg, lo, hi, valid=None):
        def add(p, s):
            take = (p >= lo) & (p < hi)
            if valid is not None:
                take = take & valid(p)
            return jnp.where(take, jax.lax.reduce_precision(
                s + jax.lax.dynamic_index_in_dim(xg, p, 1, keepdims=False),
                8, 7), s)

        return jax.lax.fori_loop(0, xg.shape[1], add, start)

    def touched(power, acc, filled, *, nint, **kw):
        if nint != only_nint:
            return fold(power, acc, filled, nint=nint, **kw)
        ch._seq_sum = seq_sum
        try:
            return fold(power, acc, filled, nint=nint, **kw)
        finally:
            ch._seq_sum = sound

    monkeypatch.setattr(M, "integrate_carry", touched)


@pytest.mark.parametrize("k", range(3), ids=["0000", "0001", "0002"])
def test_a_bfloat16_accumulator_in_any_leg_fails(band, tmp_path,
                                                 monkeypatch, k):
    """The control for ``TOL``, on the mesh: ONE leg's sharded accumulator
    rounded to bfloat16 after every add puts that product far outside it
    and leaves the other two sound."""
    M.band_carry.clear_cache()  # traced anew under the fault, and after
    try:
        _round_each_add(monkeypatch, PRODUCTS[k][1])
        written, _ = scan(band[1], str(tmp_path / "c"))
    finally:
        monkeypatch.undo()
        M.band_carry.clear_cache()
    for j, ((nfft, nint), (path, _)) in enumerate(zip(PRODUCTS,
                                                      written[0])):
        err = rel_err(read_fil_data(path)[1],
                      reference(band[1], nfft, nint))
        print(f"control in leg {k}: product {j} reads {err:.3g}")
        assert (err > 50 * TOL) == (j == k), (k, j, err)


# -- (d) one read, one upload ----------------------------------------------------

def test_the_band_is_read_and_put_once(band, three):
    _, st, _ = three
    raw = NBANK * NCHAN * NSAMPS * 4
    assert st["feed.read"]["bytes"] == st["link.put"]["bytes"] \
        == st["ingest"]["bytes"] == raw
    # A head and eight bodies a bank.
    assert st["link.put"]["calls"] == st["feed.read"]["calls"] == NBANK * 9
    assert st["ingest"]["calls"] == st["dispatch"]["calls"] == 8
    # Per bank: window 1 runs three steps and two head steps on one upload
    # (4 programs that did not put it), windows 2-7 three steps, window 8
    # two (0002 ended in the 7th).  What they did not send again: twice
    # each upload where three legs stepped, once the last window's.
    assert st["fanout.share"]["calls"] == NBANK * (4 + 6 * 2 + 1)
    last = NBANK * NCHAN * 1024 * 4
    assert st["fanout.share"]["bytes"] == 2 * raw - last
    # Per leg the filter state came up once per bank and stayed after.
    assert st["state.head"]["calls"] == 3 * NBANK
    assert st["state.carry"]["calls"] == (7 + 7 + 6) * NBANK
    assert st["coeffs"]["calls"] == 3


def test_a_product_is_handed_only_the_rows_that_closed(three):
    written, st, _ = three
    total = 0
    for k, ((nfft, nint), (path, hdr)) in enumerate(zip(PRODUCTS,
                                                        written[0])):
        row = st[f"integrate.emit.{k:04d}"]
        data = read_fil_data(path)[1]
        assert row["calls"] == hdr["nsamps"] == data.shape[0]
        assert row["bytes"] == data.nbytes
        total += data.nbytes
    assert st["integrate.emit"]["calls"] == 5 + 17 + 5
    assert st["integrate.emit"]["bytes"] == total
    assert st["readback"]["bytes"] == st["write"]["bytes"] == total
    # `stitch.<k>`: a call per window (or head step) in which the product
    # closed rows — 0000 and 0002 a row at a time, 0001 two in its head
    # step and in every window; its bytes are what the gathers moved (each
    # of four chips receives the three other banks' shards).
    assert [st[f"stitch.000{k}"]["calls"] for k in range(3)] == [5, 9, 5]
    for k in range(3):
        assert st[f"stitch.000{k}"]["bytes"] == \
            3 * st[f"integrate.emit.000{k}"]["bytes"]
    assert st["readback"]["calls"] == st["write"]["calls"] == 5 + 9 + 5


def test_readback_and_write_spans_name_their_product(band, tmp_path):
    from blit import observability

    tr = observability.tracer()
    cursor, _ = tr.spans_since(0)
    scan(band[1], str(tmp_path / "s"))
    _, spans = tr.spans_since(cursor)
    for name in ("readback", "write"):
        seen = {s["attrs"].get("product") for s in spans
                if s["name"] == name and s.get("attrs")}
        assert {"0000", "0001", "0002"} <= seen, (name, seen)


def test_each_leg_has_a_program_name_of_its_own():
    names = [M.band_programs(n)[0].__name__
             for n in ("band_stream", "band_stream_0001",
                       "band_stream_0002")]
    assert names == ["band_stream", "band_stream_0001", "band_stream_0002"]
    assert M.band_programs("band_stream")[0] is M.band_stream


# -- (e) all or none ---------------------------------------------------------------

def _nothing_left(out):
    return sorted(os.listdir(out)) == []


@pytest.mark.parametrize("fails_at", ["append", "close"])
def test_a_failing_writer_leaves_no_product(band, tmp_path, monkeypatch,
                                            fails_at):
    """An error in ONE product's writer — while rows are appended, or when
    the last of the three is renamed into place, the other two already
    published — leaves no final path, manifest or ``.partial`` of any."""
    out = str(tmp_path / "fail")
    real = getattr(FilWriter, fails_at)

    def failing(self, *a, **kw):
        if self.final_path.endswith(".rawspec.0002.fil"):
            raise OSError("injected: the 0002 product's disk is full")
        return real(self, *a, **kw)

    monkeypatch.setattr(FilWriter, fails_at, failing)
    with pytest.raises(OSError, match="injected"):
        scan(band[1], out)
    assert _nothing_left(out)


def test_a_window_that_fails_leaves_no_product(band, tmp_path, monkeypatch):
    out, calls = str(tmp_path / "boom"), []
    real = M.band_stream

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 4:
            raise RuntimeError("injected: the fourth window's dispatch")
        return real(*a, **kw)

    monkeypatch.setattr(M, "band_stream", flaky)
    with pytest.raises(RuntimeError, match="injected"):
        scan(band[1], out)
    assert _nothing_left(out)


# -- (f) the command ------------------------------------------------------------------

def run_cli(capsys, *argv):
    rc = blit_main(list(argv))
    return rc, [json.loads(ln) for ln in
                capsys.readouterr().out.strip().splitlines()
                if ln.startswith("{")]


def test_the_command_writes_three_band_products(band, tmp_path, capsys):
    out = str(tmp_path / "cli")
    os.makedirs(out)
    rc, lines = run_cli(
        capsys, "scan", band[0], SESSION, SCAN, "-o", out, "--nfft",
        "1024,8,64", "--nint", "3,128,51", "--window-frames", str(WF))
    assert rc == 0
    made, stats = lines[:-1], lines[-1]
    assert [os.path.basename(m["output"]) for m in made] == [
        "band0.rawspec.0000.fil", "band0.rawspec.0001.fil",
        "band0.rawspec.0002.fil"]
    assert [m["nsamps"] for m in made] == [5, 17, 5]
    assert [m["nchans"] for m in made] == [NBANK * NCHAN * f
                                           for f, _ in PRODUCTS]
    assert stats["window_frames"] == WF and stats["parallel"] == "mesh"
    assert stats["stages"]["fanout.share"]["calls"] > 0
    assert sorted(os.listdir(out)) == sorted(
        f"band0.rawspec.000{k}.fil{s}" for k in range(3)
        for s in ("", ".manifest.json"))
    for (nfft, nint), m in zip(PRODUCTS, made):
        assert rel_err(read_fil_data(m["output"])[1],
                       reference(band[1], nfft, nint)) < TOL


def test_a_list_of_one_is_the_single_command(band, tmp_path, capsys):
    out = str(tmp_path / "one")
    os.makedirs(out)
    rc, lines = run_cli(
        capsys, "scan", band[0], SESSION, SCAN, "-o", out, "--nfft", "64",
        "--nint", "51", "--window-frames", "40")
    assert rc == 0
    assert lines[0]["output"] == os.path.join(out, "band0.fil")
    assert lines[0]["nsamps"] == rows_of(64, 51)
    st = lines[-1]["stages"]
    for row in ("fanout.share", "integrate.emit.0000", "stitch.0000"):
        assert row not in st  # none of the rows several products add
    assert sorted(os.listdir(out)) == ["band0.fil",
                                       "band0.fil.manifest.json"]


@pytest.mark.parametrize("flag, extra", [
    ("--resume", ["--resume"]),
    ("--compression", ["--compression", "gzip"]),
    ("--sharded", ["--sharded"]),
    ("--pool", ["--pool"]),
    ("--search", ["--search"]),
])
def test_what_goes_with_one_product_only_is_refused_by_flag(
        tmp_path, flag, extra):
    # (no tree under the root: refused before the inventory is even listed)
    with pytest.raises(SystemExit) as e:
        blit_main(["scan", str(tmp_path / "nowhere"), SESSION, SCAN, "-o",
                   str(tmp_path), "--nfft", "1024,8", "--nint", "3,128",
                   *extra])
    assert f"blit scan: {flag} with several products" in str(e.value)


@pytest.mark.parametrize("nfft, nint, extra, says", [
    ("1024,8,64", "3,128", [], "--nfft lists 3 products and --nint 2"),
    ("1024,8", "3,128", ["--fqav", "16"],
     "--fqav 16 does not divide --nfft 8"),
    ("1024,x", "3,128", [], None),  # argparse's own refusal
])
def test_a_list_that_cannot_be_paired_is_refused(tmp_path, capsys, nfft,
                                                 nint, extra, says):
    with pytest.raises(SystemExit) as e:
        blit_main(["scan", str(tmp_path / "nowhere"), SESSION, SCAN, "-o",
                   str(tmp_path), "--nfft", nfft, "--nint", nint, *extra])
    if says is None:
        assert e.value.code == 2
        assert "not a comma list" in capsys.readouterr().err
    else:
        assert says in str(e.value)


def test_the_library_refuses_what_the_command_refuses(band, tmp_path):
    for kw, says in ((dict(resume=True), "resume="),
                     (dict(compression="gzip"), "compression="),
                     (dict(out_paths=["x.fil"]), "out_paths=")):
        with pytest.raises(ValueError, match=says):
            reduce_scan_mesh_to_files(
                band[1], out_dir=str(tmp_path), nfft=1024, nint=3,
                also=((8, 128),), **kw)
    with pytest.raises(ValueError, match="must divide the largest"):
        reduce_scan_mesh_to_files(band[1], out_dir=str(tmp_path),
                                  nfft=1024, nint=3, also=((24, 4),))
