"""Several products from ONE read (ISSUE 34): ``blit reduce --nfft
1048576,8,1024 --nint 51,128,3072`` at a toy geometry — ``--nfft 1024,8,64
--nint 3,128,51`` — whose small-product integrations straddle dispatches
and whose filter states differ: each channel group goes up once and feeds
every product's channeliser, fold and writer.

The plain reference is ``channelize_np`` over the whole file, per product.
"""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit import faults  # noqa: E402
from blit.__main__ import main as blit_main  # noqa: E402
from blit.__main__ import rawspec_product_path  # noqa: E402
from blit.io.guppi import GuppiRaw  # noqa: E402
from blit.io.sigproc import FilWriter, read_fil_data  # noqa: E402
from blit.ops import channelize as ch  # noqa: E402
from blit.ops.channelize import (  # noqa: E402
    channelize_lanes,
    channelize_np,
    integrate_carry,
    lanes_block,
    pfb_coeffs,
    sample_words,
    usable_frames,
)
from blit.pipeline import RawReducer  # noqa: E402
from blit.testing import synth_raw  # noqa: E402

NTAP = 4
PRODUCTS = [(1024, 3), (8, 128), (64, 51)]
# 18 frames of 1024: the 3-frame head, three 4-frame chunks and a 3-frame
# flush that closes the fifth row of the first product, so every sample of
# the recording is dispatched.  Of the others the head holds 381 frames of
# 8 (128 does not divide it) and 45 of 64 (nor 51), a chunk 512 and 64:
# both carry an open integration over every dispatch boundary.
NSAMPS = 18 * 1024
# Scale-relative max error (max|got - want| / max|want|) against
# channelize_np over the whole file, compared in float64, as
# tests/test_integrate_carry.py holds a carried reduction: both sides are
# float32 arithmetic and differ in FFT rounding and in the order of the
# sum; the cases here read 1e-7 to 4e-7 on the CPU.  A frame in the wrong
# row, twice or missing moves the tone's peak by 1/nint >= 3e-4 of itself,
# and an accumulator kept in bfloat16 reads 1e-3 or more in every product
# (the control below): 1e-5 is 25 times the worst reading and 30 times
# under the smallest fault.
TOL = 1e-5


@pytest.fixture(scope="module")
def recording(tmp_path_factory):
    p = str(tmp_path_factory.mktemp("fanout") / "r.raw")
    synth_raw(p, nblocks=4, obsnchan=4, ntime_per_block=NSAMPS // 4, seed=7,
              tone_chan=1)
    stream = np.concatenate(
        [blk for _, blk in GuppiRaw(p).iter_blocks(drop_overlap=True)],
        axis=1)
    assert stream.shape[1] == NSAMPS
    return p, stream


def rows_of(nfft, nint, nsamps=NSAMPS):
    return usable_frames(nsamps, nfft, NTAP, nint) // nint


def reference(stream, nfft, nint):
    rows = rows_of(nfft, nint, stream.shape[1])
    return channelize_np(stream[:, :(rows * nint + NTAP - 1) * nfft],
                         pfb_coeffs(NTAP, nfft), nfft=nfft, ntap=NTAP,
                         nint=nint)


def rel_err(got, want):
    want = np.asarray(want, np.float64)
    return float(np.abs(np.asarray(got, np.float64) - want).max()
                 / np.abs(want).max())


def fanout(products=PRODUCTS, **kw):
    (nfft, nint), *also = products
    kw.setdefault("chunk_frames", 4)
    return RawReducer(nfft=nfft, nint=nint, also=tuple(also), **kw)


def paths(tmp_path, tag="out"):
    stem = str(tmp_path / tag)
    return stem, [rawspec_product_path(stem, k) for k in range(3)]


# -- (a) against the plain reference -------------------------------------------

@pytest.mark.parametrize("async_output", [True, False],
                         ids=["async", "sync"])
@pytest.mark.parametrize("small", [(8, 128), (8, 24)],
                         ids=["lanes", "general"])
def test_three_products_match_the_reference(tmp_path, recording, small,
                                            async_output):
    """The ``nfft`` 8 leg with its frames on the lanes (an integration of
    whole vectors: the shape decides, on every backend) and, at an
    integration of 192 words, through the general program."""
    raw, stream = recording
    products = [PRODUCTS[0], small, PRODUCTS[2]]
    red = fanout(products, async_output=async_output)
    assert [red._leg_carries(k) for k in range(3)] == [True, True, True]
    assert [bool(red._lanes(k, 2)) for k in range(3)] == [
        False, small == (8, 128), False]
    _, outs = paths(tmp_path)
    hdrs = red.reduce_to_files(raw, outs)
    tbin = GuppiRaw(raw).header(0)["TBIN"]
    for (nfft, nint), out, hdr in zip(products, outs, hdrs):
        want = reference(stream, nfft, nint)
        fhdr, got = read_fil_data(out)
        # its own head, its own rows, its own dropped tail, its own tsamp
        assert got.shape == want.shape == (rows_of(nfft, nint), 1, 4 * nfft)
        assert hdr["nsamps"] == rows_of(nfft, nint)
        assert fhdr["tsamp"] == pytest.approx(tbin * nfft * nint, rel=1e-12)
        assert rel_err(got, want) < TOL, (nfft, nint)
        assert not os.path.exists(out + ".partial")
        with open(out + ".manifest.json") as f:
            doc = json.load(f)
        assert doc["complete"] and doc["bytes"] == os.path.getsize(out)
    assert [rows_of(*p) for p in products] == [
        5, {128: 17, 24: 95}[small[1]], 5]


def _round_each_dispatch(monkeypatch):
    real = ch.integrate_carry

    def rounded(power, acc, filled, **kw):
        rows, acc = real(power, acc, filled, **kw)
        return (jax.lax.reduce_precision(rows, 8, 7),
                jax.lax.reduce_precision(acc, 8, 7))

    monkeypatch.setattr(ch, "integrate_carry", rounded)


def _round_each_add(monkeypatch):
    """``_seq_sum`` with the running sum rounded to bfloat16 after EVERY
    add: the fault the benchmark's 0001 limit was set against
    (benchmark/traffic/rawspec3-t51.json: 3.93e-2 on the chip against a
    limit of 1e-4).  Written apart from the fold's own chain, a position
    at a time, so it stays a control when that chain is rewritten."""
    def seq_sum(start, xg, lo, hi, valid=None):
        def add(p, s):
            take = (p >= lo) & (p < hi)
            if valid is not None:
                take = take & valid(p)
            return jnp.where(take, jax.lax.reduce_precision(
                s + jax.lax.dynamic_index_in_dim(xg, p, 1, keepdims=False),
                8, 7), s)

        return jax.lax.fori_loop(0, xg.shape[1], add, start)

    monkeypatch.setattr(ch, "_seq_sum", seq_sum)


@pytest.mark.parametrize("fault", [_round_each_dispatch, _round_each_add],
                         ids=["each_dispatch", "each_add"])
def test_a_bfloat16_accumulator_in_any_leg_fails(tmp_path, recording,
                                                 monkeypatch, fault):
    """The control for ``TOL``: the same reduction with every leg's
    accumulator rounded to bfloat16 — after each dispatch's fold (with the
    rows it closes), or after every add inside it — is far outside it, in
    each product (read on the CPU: 2.7e-3 to 4.0e-3 a dispatch, 3.3e-3 to
    4.0e-2 an add, the last in the nfft 8 leg as on the chip)."""
    raw, stream = recording
    # the fold is traced anew under the fault, and again without it after
    integrate_carry.clear_cache()
    try:
        fault(monkeypatch)
        _, outs = paths(tmp_path)
        fanout().reduce_to_files(raw, outs)
    finally:
        monkeypatch.undo()
        integrate_carry.clear_cache()
    for (nfft, nint), out in zip(PRODUCTS, outs):
        err = rel_err(read_fil_data(out)[1], reference(stream, nfft, nint))
        print(f"control {fault.__name__} nfft={nfft} nint={nint}: {err:.3g}")
        assert err > 50 * TOL, (nfft, nint, err)


def test_the_default_grid_is_the_sample_budget(tmp_path, recording):
    """No product's ``nint`` sizes the chunk: 2^23 samples of the first
    product's frames (here the whole toy recording is one flush)."""
    raw, stream = recording
    red = fanout(chunk_frames=None)
    assert red._chunk_samples == 1 << 23 and red.chunk_frames == 1 << 13
    _, outs = paths(tmp_path)
    red.reduce_to_files(raw, outs)
    for (nfft, nint), out in zip(PRODUCTS, outs):
        assert rel_err(read_fil_data(out)[1],
                       reference(stream, nfft, nint)) < TOL
    assert red.timeline.report()["dispatch"]["calls"] == 1


def test_products_in_another_order_and_a_shared_nfft(tmp_path, recording):
    """The leg that owns the head need not be the first, and two products
    of one ``nfft`` each keep a filter state of their own."""
    raw, stream = recording
    prods = [(8, 128), (1024, 3), (1024, 5), (64, 51)]
    red = RawReducer(nfft=8, nint=128, also=tuple(prods[1:]),
                     chunk_frames=512)
    outs = [str(tmp_path / f"p{k}.fil") for k in range(4)]
    red.reduce_to_files(raw, outs)
    for (nfft, nint), out in zip(prods, outs):
        assert rel_err(read_fil_data(out)[1],
                       reference(stream, nfft, nint)) < TOL, (nfft, nint)
    assert red.stats.output_frames == rows_of(8, 128) * 128


def test_a_first_product_that_integrates_inside_and_owns_no_head(
        tmp_path, recording):
    """381 frames of 8 in the head and 384 in a chunk, whole integrations
    of 3: the first dispatch closes two batches of that product's rows,
    the head's and the chunk's, and every frame is counted once."""
    raw, stream = recording
    prods = [(8, 3), (1024, 3)]
    red = RawReducer(nfft=8, nint=3, also=((1024, 3),), chunk_frames=384)
    assert [red._leg_carries(k) for k in range(2)] == [False, False]
    outs = [str(tmp_path / f"p{k}.fil") for k in range(2)]
    red.reduce_to_files(raw, outs)
    for (nfft, nint), out in zip(prods, outs):
        assert rel_err(read_fil_data(out)[1],
                       reference(stream, nfft, nint)) < TOL, (nfft, nint)
    assert red.stats.output_frames == rows_of(8, 3) * 3


# -- (b) against the single-product command ------------------------------------

def test_each_product_equals_its_own_command(tmp_path, recording):
    raw, _ = recording
    _, outs = paths(tmp_path)
    fanout().reduce_to_files(raw, outs)
    for k, ((nfft, nint), out) in enumerate(zip(PRODUCTS, outs)):
        alone = str(tmp_path / f"alone{k}.fil")
        # The first product owns the head: alone at the same chunk it
        # folds on the same grid.  The others' own commands dispatch on a
        # grid of their own.
        kw = dict(chunk_frames=4) if k == 0 else {}
        RawReducer(nfft=nfft, nint=nint, **kw).reduce_to_file(raw, alone)
        got, want = read_fil_data(out)[1], read_fil_data(alone)[1]
        assert got.shape == want.shape
        # Same frames, same order of addition.  Not held to the byte on
        # the CPU: its threaded FFT rounds in an order that depends on
        # timing (two float32 variants a bit apart, benchmark/run.py), and
        # a program batched over another number of frames may round
        # differently in the last bit — float32's 6e-8 a value, summed
        # over up to 128 frames.  (On the chip rawspec.hires51's CRC is
        # held against the parent's: PERF.md section 6.)
        assert rel_err(got, want) < 1e-6


# -- (c) one read, one upload ---------------------------------------------------

@pytest.mark.parametrize("async_output", [True, False],
                         ids=["async", "sync"])
def test_the_recording_is_read_and_put_once(tmp_path, recording,
                                            async_output):
    raw, stream = recording
    red = fanout(async_output=async_output)
    _, outs = paths(tmp_path)
    hdrs = red.reduce_to_files(raw, outs)
    st = red.timeline.report()
    assert st["ingest"]["bytes"] == st["link.put"]["bytes"] == stream.nbytes
    # 4 dispatches (three chunks and the flush) of one channel group (the
    # CPU reports no memory limit): head + body in the first, a body in
    # each of the rest.
    assert st["link.put"]["calls"] == 5
    if async_output:
        assert st["dispatch"]["calls"] == 4
    # Two more programs consumed each group than uploaded it (and the two
    # small legs' head steps the head), and what they did not send again
    # is twice the recording.
    assert st["fanout.share"]["calls"] == 4 * 2 + 2
    assert st["fanout.share"]["bytes"] == 2 * stream.nbytes
    # Per leg the filter state came up once and stayed three times.
    assert st["state.head"]["calls"] == 3
    assert st["state.carry"]["calls"] == 3 * 3
    total = 0
    for k, (hdr, out) in enumerate(zip(hdrs, outs)):
        row = st[f"integrate.emit.{k:04d}"]
        data = read_fil_data(out)[1]
        assert row["calls"] == hdr["nsamps"] == data.shape[0]
        assert row["bytes"] == data.nbytes
        total += data.nbytes
    assert st["integrate.emit"]["calls"] == sum(h["nsamps"] for h in hdrs)
    assert st["integrate.emit"]["bytes"] == total
    if async_output:
        assert st["readback"]["bytes"] == st["write"]["bytes"] == total


def test_readback_and_write_spans_name_their_product(tmp_path, recording):
    from blit import observability

    raw, _ = recording
    tr = observability.tracer()
    cursor, _ = tr.spans_since(0)
    _, outs = paths(tmp_path)
    fanout().reduce_to_files(raw, outs)
    _, spans = tr.spans_since(cursor)
    for name, want in (("readback", {"0000", "0001", "0002"}),
                       ("write", {"0000", "0001", "0002"})):
        seen = {s["attrs"].get("product") for s in spans
                if s["name"] == name and s.get("attrs")}
        assert want <= seen, (name, seen)


# -- (d) a list of one is today's command --------------------------------------

@pytest.mark.parametrize("nfft, nint", [(32, 51), (64, 4)],
                         ids=["carried", "inside"])
def test_a_list_of_one_is_the_single_command(tmp_path, recording, capsys,
                                             nfft, nint):
    raw, stream = recording
    out = str(tmp_path / "one.fil")
    assert blit_main(["reduce", raw, "-o", out, "--nfft", str(nfft),
                      "--nint", str(nint)]) == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert doc["output"] == out and "products" not in doc
    assert doc["nsamps"] == rows_of(nfft, nint)
    # no row a reduction of one product never had
    assert not [k for k in doc["stages"]
                if k.startswith(("fanout", "integrate.emit."))]
    direct = str(tmp_path / "direct.fil")
    red = RawReducer(nfft=nfft, nint=nint)
    red.reduce_to_file(raw, direct)
    assert open(out, "rb").read() == open(direct, "rb").read()
    assert set(red.timeline.report()) == set(doc["stages"])
    assert rel_err(read_fil_data(out)[1], reference(stream, nfft, nint)) < TOL


def test_the_command_takes_rawspecs_spelling(tmp_path, recording, capsys):
    raw, stream = recording
    stem, outs = paths(tmp_path, "cli")
    assert blit_main(["reduce", raw, "-o", stem, "--nfft", "1024,8,64",
                      "--nint", "3,128,51"]) == 0
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [p["path"] for p in doc["products"]] == outs
    assert [(p["nfft"], p["nint"]) for p in doc["products"]] == PRODUCTS
    assert [p["nsamps"] for p in doc["products"]] == [5, 17, 5]
    assert [p["nchans"] for p in doc["products"]] == [4096, 32, 256]
    assert doc["stages"]["link.put"]["bytes"] == stream.nbytes
    assert doc["input_bytes"] == stream.nbytes
    for (nfft, nint), out in zip(PRODUCTS, outs):
        assert out.endswith(f".rawspec.{PRODUCTS.index((nfft, nint)):04d}"
                            ".fil")
        assert rel_err(read_fil_data(out)[1],
                       reference(stream, nfft, nint)) < TOL


# -- (e) all three or none ------------------------------------------------------

def leftovers(tmp_path):
    return sorted(f for f in os.listdir(tmp_path)
                  if f.endswith((".fil", ".partial", ".manifest.json")))


class TestAWriterErrorLeavesNothing:
    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        faults.clear()
        faults.reset_counters()
        yield
        faults.clear()
        faults.reset_counters()

    @pytest.mark.parametrize("after", [0, 3, 9])
    def test_on_the_async_plane(self, tmp_path, recording, after):
        raw, _ = recording
        _, outs = paths(tmp_path)
        faults.install_spec(f"sink.write:fail:after={after}")
        with pytest.raises(OSError):
            fanout().reduce_to_files(raw, outs)
        assert leftovers(tmp_path) == []

    @pytest.mark.parametrize("broken", [0, 1, 2])
    def test_on_the_synchronous_path(self, tmp_path, recording, monkeypatch,
                                     broken):
        raw, _ = recording
        _, outs = paths(tmp_path)
        real = FilWriter.append

        def append(self, slab):
            if self.final_path == outs[broken] and self.nsamps >= 1:
                raise OSError("disk full")
            return real(self, slab)

        monkeypatch.setattr(FilWriter, "append", append)
        with pytest.raises(OSError, match="disk full"):
            fanout(async_output=False).reduce_to_files(raw, outs)
        assert leftovers(tmp_path) == []

    def test_a_failed_rename_withdraws_the_published(self, tmp_path,
                                                     recording, monkeypatch):
        raw, _ = recording
        _, outs = paths(tmp_path)
        real = FilWriter.close

        def close(self):
            if self.final_path == outs[2]:
                self.abort()
                raise OSError("rename failed")
            return real(self)

        monkeypatch.setattr(FilWriter, "close", close)
        with pytest.raises(OSError, match="rename failed"):
            fanout().reduce_to_files(raw, outs)
        assert leftovers(tmp_path) == []


# -- (f) what is refused, by name, before a byte is read ------------------------

@pytest.mark.parametrize("argv, said", [
    (["--nfft", "1024,8,64", "--nint", "3,128,51", "--resume"], "--resume"),
    (["--nfft", "1024,8,64", "--nint", "3,128,51", "--fqav", "16"],
     "--fqav 16 does not divide --nfft 8"),
    (["--nfft", "1024,8,64", "--nint", "3,128"], "one --nint per --nfft"),
    (["--nfft", "1024,8", "--nint", "3,128", "--compression", "gzip"],
     "--compression"),
    (["--nfft", "1024,8", "--nint", "3,128", "-o", "x.fil"], "STEM"),
], ids=["resume", "fqav", "lengths", "compression", "stem"])
def test_the_command_refuses_by_flag(tmp_path, argv, said):
    missing = str(tmp_path / "never-opened.raw")
    words = ["reduce", missing, "-o", str(tmp_path / "stem")] + argv
    with pytest.raises(SystemExit) as e:
        blit_main(words)
    assert said in str(e.value)
    assert leftovers(tmp_path) == []


def test_the_reducer_refuses_what_one_product_only_can(recording):
    raw, _ = recording
    red = fanout()
    for call in (lambda: next(red.stream(GuppiRaw(raw))),
                 lambda: red.reduce(raw),
                 lambda: red.reduce_resumable(raw, "x.fil"),
                 lambda: red.reduce_to_file(raw, "x.h5")):
        with pytest.raises(ValueError, match="ONE product"):
            call()
    with pytest.raises(ValueError, match="3 products, 1 paths"):
        red.reduce_to_files(raw, ["x.fil"])
    with pytest.raises(ValueError, match="no whole number of nfft=1024"):
        RawReducer(nfft=8, nint=128, also=((1024, 3),), chunk_frames=100)
    with pytest.raises(ValueError, match="fqav_by=16 does not divide nfft=8"):
        fanout(fqav_by=16)


def test_a_recording_inside_the_largest_filter_state_is_refused(tmp_path):
    p = str(tmp_path / "short.raw")
    synth_raw(p, nblocks=1, obsnchan=2, ntime_per_block=3 * 1024, seed=1)
    with pytest.raises(ValueError, match="filter state of nfft=1024"):
        fanout().reduce_to_files(p, paths(tmp_path)[1])
    assert leftovers(tmp_path) == []


# -- (g) the fold, against the loop it replaces ---------------------------------

def frame_at_a_time(power, acc, filled, nint):
    """``integrate_carry`` as it was until ISSUE 34: one frame per step,
    in NumPy float32 (every add is the same IEEE add)."""
    rows = np.zeros(((nint - 1 + power.shape[0]) // nint,) + acc.shape,
                    np.float32)
    acc = acc.copy()
    for j in range(power.shape[0]):
        acc = acc + power[j]
        n = filled + j + 1
        if n % nint == 0:
            rows[n // nint - 1] = acc
            acc = np.zeros_like(acc)
    return rows, acc


# (frames, nint, rest): tests/test_integrate_carry.py's chunk grids
# (8 | 17 | 64 of 51, 8 of 6, 3 of 7, 8192 of 3072, 4 of 11, 17 of 17),
# tests/test_scan_carry.py's 2-frame windows of 51, and the three legs'.
FOLDS = [(8, 51, (1, 64)), (3, 51, (4, 64)), (17, 51, (1, 64)),
         (64, 51, (1, 64)), (8, 6, (4, 64)), (3, 7, (1, 64)),
         (8192, 3072, (1, 16)), (4, 11, (1, 128)), (17, 17, (2, 96)),
         (2, 51, (1, 256)), (1, 51, (1, 256)), (381, 128, (1, 32)),
         (512, 128, (1, 32)), (45, 51, (1, 256)), (100, 40, (2, 8))]


@pytest.mark.parametrize("frames, nint, rest", FOLDS,
                         ids=lambda v: str(v).replace(" ", ""))
def test_the_fold_gives_the_loops_bits(frames, nint, rest):
    rng = np.random.default_rng([frames, nint])
    # signed, as Stokes Q, U, V are
    power = rng.standard_normal((frames,) + rest).astype(np.float32)
    for filled in sorted({0, 1, nint // 2, nint - 1, max(0, nint - frames),
                          max(0, nint - frames - 1),
                          min(nint - 1, max(0, nint - frames + 1))}):
        acc = (rng.standard_normal(rest).astype(np.float32) if filled
               else np.zeros(rest, np.float32))
        want_rows, want_acc = frame_at_a_time(power, acc, filled, nint)
        rows, got = integrate_carry(power, acc, np.int32(filled), nint=nint)
        assert np.asarray(rows).tobytes() == want_rows.tobytes(), filled
        assert np.asarray(got).tobytes() == want_acc.tobytes(), filled


def frame_major(power):
    """``channelize_lanes``'s power ``(m, C, nif, nfft, c, groups)`` as
    ``channelize``'s at ``nint=1``: frame ``g * m + p`` of channel ``i * c
    + j`` at ``[g * m + p, :, (i * c + j) * nfft:][:nfft]``."""
    m, slabs, nif, nfft, c, groups = power.shape
    return np.transpose(np.asarray(power), (5, 0, 2, 1, 4, 3)).reshape(
        groups * m, nif, slabs * c * nfft)


def lanes_acc(acc):
    """The lanes fold's accumulator ``(C, nif, nfft, c)`` as the
    frame-major fold's ``(nif, nchan * nfft)``."""
    slabs, nif, nfft, c = acc.shape
    return np.transpose(np.asarray(acc), (1, 0, 3, 2)).reshape(
        nif, slabs * c * nfft)


@pytest.mark.parametrize("valid", [5 * 16, 5 * 16 - 1, 4 * 16 + 1, 16, 7])
def test_the_lanes_fold_is_the_same_fold(valid):
    """Positions major, channels on the sublanes, frame groups on the
    lanes, the last group short: the rows and the accumulator of the
    frame-major fold over the same frames."""
    nint, slabs, c, nfft = 16, 2, 3, 8
    groups = -(-valid // nint)
    rng = np.random.default_rng(valid)
    lanes = rng.standard_normal((nint, slabs, 1, nfft, c, groups)) \
        .astype(np.float32)
    major = frame_major(lanes)[:valid]
    for filled in (0, 1, 9, 15):
        acc = rng.standard_normal((slabs, 1, nfft, c)).astype(np.float32) \
            * (filled > 0)
        want_rows, want_acc = frame_at_a_time(major, lanes_acc(acc), filled,
                                              nint)
        rows, got = integrate_carry(lanes, acc, np.int32(filled), nint=nint,
                                    nframes=valid, lanes=True)
        closed = (filled + valid) // nint
        assert np.asarray(rows)[:closed].tobytes() \
            == want_rows[:closed].tobytes()
        assert lanes_acc(got).tobytes() == want_acc.tobytes()


def test_a_row_does_not_depend_on_the_dispatch_grid_in_bulk():
    """One integration of 40 frames fed as 40, as 7 + 33 and a frame at a
    time, beside three whole ones: the same bits (the fold is laid out
    across rows, and still adds within a row in stream order)."""
    rng = np.random.default_rng(11)
    power = rng.standard_normal((160, 2, 24)).astype(np.float32) ** 2

    def run(cuts):
        acc, at, out = np.zeros((2, 24), np.float32), 0, []
        for a, b in zip((0,) + cuts, cuts + (160,)):
            rows, acc = integrate_carry(power[a:b], acc, np.int32(at % 40),
                                        nint=40)
            out.append(np.asarray(rows)[:(at % 40 + b - a) // 40])
            at += b - a
        return np.concatenate(out).tobytes()

    whole = run(())
    assert whole == run((7,)) == run((40, 47, 121)) \
        == run(tuple(range(1, 160)))
    want, _ = frame_at_a_time(power, np.zeros((2, 24), np.float32), 0, 40)
    assert whole == want.tobytes()


# -- the small-nfft channeliser ------------------------------------------------

@pytest.mark.parametrize("stokes", ["I", "IQUV"])
@pytest.mark.parametrize("nfft, nint, frames, pad", [
    (8, 128, 128 * 5, 0), (8, 128, 128 * 5 + 7, 0), (8, 128, 381, 0),
    (8, 128, 300, 5), (16, 8, 77, 0), (64, 4, 9, 0)])
def test_channelize_lanes_matches_the_reference(nfft, nint, frames, pad,
                                                stokes):
    """Frames on the lanes: the same spectra as ``channelize_np`` frame by
    frame, where the samples end before the last block does (zero-padded)
    and where they go on past ``frames`` (``pad`` more frames computed,
    not the stream's)."""
    cb, block = 9, lanes_block(nfft, nint)
    assert block == nfft * nint
    rng = np.random.default_rng([nfft, frames])
    v = rng.integers(-40, 40, size=(cb, (frames + pad + NTAP - 1) * nfft,
                                    2, 2)).astype(np.int8)
    h = pfb_coeffs(NTAP, nfft)
    want = channelize_np(v[:, :(frames + NTAP - 1) * nfft], h, nfft=nfft,
                         ntap=NTAP, nint=1, stokes=stokes)
    got = np.asarray(channelize_lanes(
        (jnp.asarray(sample_words(v)),), jnp.asarray(h), nfft=nfft,
        ntap=NTAP, block=block, frames=frames, stokes=stokes))
    groups = -(-frames // nint)
    assert got.shape == (nint, 1, want.shape[1], nfft, cb, groups)
    # float32 on both sides, no matrix unit in this path: 2e-7 read
    assert rel_err(frame_major(got)[:frames], want) < 2e-6


@pytest.mark.parametrize("stokes", ["I", "IQUV"])
@pytest.mark.parametrize("cb", [9, 16], ids=["plain", "slabs"])
def test_the_lanes_leg_end_to_end_is_the_frame_major_leg(cb, stokes):
    """The ``nfft`` 8 leg's own programs over two dispatches, a row
    straddling them (300 frames, then 350: 44 of 128 open between, 10
    after), at 9 coarse channels (one slab) and at 16 (a ``lax.map`` over
    slabs of 8).
    Its rows are, byte for byte, the frame-major fold's over the same
    power, and the frame-major LEG's (``channelize`` at ``nint=1`` ->
    ``integrate_carry``) to float32 rounding: the two channelisers
    transform by different butterflies (``_fft_planes`` across planes,
    ``jnp.fft`` along the minor axis), so their power differs in the last
    bit in half the values and no fold can make bytes of that."""
    nfft, nint, fed = 8, 128, (300, 350)
    state, lanes = (NTAP - 1) * nfft, lanes_block(nfft, nint)
    rng = np.random.default_rng([cb, len(stokes)])
    v = rng.integers(-40, 40, size=(cb, state + sum(fed) * nfft, 2, 2)) \
        .astype(np.int8)
    words, h = jnp.asarray(sample_words(v)), jnp.asarray(pfb_coeffs(NTAP,
                                                                     nfft))
    step = ch.leg_programs("channelize_0001")[0]
    whole = np.asarray(ch.channelize(jnp.asarray(v), h, nfft=nfft,
                                     ntap=NTAP, nint=1, stokes=stokes))
    nif = whole.shape[1]
    tail, at, filled, acc = words[:, :state], state, 0, None
    own_acc = ref_acc = jnp.zeros((nif, cb * nfft), jnp.float32)
    rows, own_rows, ref_rows = [], [], []
    for frames in fed:
        body = words[:, at:at + frames * nfft]
        power, tail = step(tail, body, h, nfft=nfft, ntap=NTAP,
                           stokes=stokes, lanes=lanes)
        if acc is None:
            acc = jnp.zeros(power.shape[1:5], jnp.float32)
        closed = (filled + frames) // nint
        r, acc = integrate_carry(power, acc, np.int32(filled), nint=nint,
                                 nframes=frames, lanes=True)
        rows.append(np.asarray(r)[:closed])
        r, own_acc = integrate_carry(frame_major(power)[:frames], own_acc,
                                     np.int32(filled), nint=nint)
        own_rows.append(np.asarray(r)[:closed])
        first = (at - state) // nfft
        r, ref_acc = integrate_carry(whole[first:first + frames], ref_acc,
                                     np.int32(filled), nint=nint)
        ref_rows.append(np.asarray(r)[:closed])
        at, filled = at + frames * nfft, (filled + frames) % nint
    rows, own_rows, ref_rows = map(np.concatenate,
                                   (rows, own_rows, ref_rows))
    assert rows.shape == (sum(fed) // nint, nif, cb * nfft) and filled
    assert rows.tobytes() == own_rows.tobytes()
    assert lanes_acc(acc).tobytes() == np.asarray(own_acc).tobytes()
    assert np.asarray(tail).tobytes() \
        == np.asarray(words[:, at - state:at]).tobytes()
    assert rel_err(rows, ref_rows) < 2e-6
    assert rel_err(lanes_acc(acc), ref_acc) < 2e-6


def test_lanes_block_serves_small_power_of_two_nfft_only():
    assert lanes_block(8, 128) == 1024 and lanes_block(64, 4) == 256
    assert lanes_block(64, 2) == 0       # a block shorter than the state
    assert lanes_block(8, 3) == 0        # 24 words: no whole vector
    assert lanes_block(128, 8) == 0      # fills the lanes by itself
    assert lanes_block(24, 16) == 0      # radix 2 only
    assert lanes_block(8, 128, npol=1) == 0


def test_each_leg_has_a_program_name_of_its_own():
    """The trace names programs: the first product keeps the names a
    reduction of one product has (``jit_channelize_stream``, and
    ``jit_integrate_carry`` for every leg's fold), the others' device work
    is named after them."""
    red = fanout()
    legs = red._legs(2)
    assert [leg.label for leg in legs] == ["0000", "0001", "0002"]
    words = jax.ShapeDtypeStruct((2, 24), jnp.int32)
    head = jax.ShapeDtypeStruct((2, 56), jnp.int32)
    names = []
    for leg in legs:
        for program in (leg.step, leg.head):
            args = (head,) if program is leg.head else (words, words)
            names.append(program.lower(
                *args, jax.ShapeDtypeStruct((NTAP, 8), jnp.float32),
                nfft=8).as_text().split("@", 1)[1].split(" ", 1)[0])
    assert names == ["jit_channelize_stream"] * 2 \
        + ["jit_channelize_0001"] * 2 + ["jit_channelize_0002"] * 2
    assert ch.channelize_stream is ch.leg_programs("channelize_stream")[0]
    assert RawReducer(nfft=32, nint=51)._legs(2)[0].label is None
