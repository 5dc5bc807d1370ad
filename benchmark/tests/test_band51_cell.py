"""The cell ``band4.hires51`` (PR 30): added as files only, bar one reader.
Its toy run end to end — which DOES carry: an explicit 2-frame window
below one integration is kept as given — the reference at ``nint`` 51 with
the despike on, and ``readers/band_carry.py`` on what the builder's traced
run on the four chips recorded (``data/band4.hires51.pr30.*``: the
``.xplane.pb`` as written, and the run's stage table and result line, under
the metric names of its day: ``test_layer_metrics.FOLDED`` maps them)."""

import json
import os

import numpy as np
import pytest
from conftest import BENCH, EVERY_PASS, lines_of, run_harness, run_line

import reference
from readers import band_carry, stage_bytes, xplane
from test_layer_metrics import FOLDED

CELL = "band4.hires51"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROW = 4 * 64 * (1 << 20) * 4     # one band row: 1 GiB
RAW = 4 * 108 * 134217728        # 58.0 GB


def spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_end_to_end_run_at_toy_size_carries():
    p, out = run_harness("--workload", CELL, "--seed", "3000000005",
                         "--seconds", "0.05", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["rehearsal"] is True and doc["platform"] == "cpu"
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1
    # first_product_s lists its cells by name: this one is not among them
    assert doc["metric_names"] == ["reduce_rate", "setup_s"]
    assert "metrics" not in doc
    (plan,) = lines_of(out, "plan")
    (band,) = plan["products"]
    assert plan["blocks"] == 108 and band["rows"] == 1 == band["warm_rows"]
    (ref,) = lines_of(out, "reference")
    assert ref["launched"] == ref["tasks"] == 4 == run_line(p)[
        "reference"]["tasks"] and not ref["failed"]
    # the warm-up is a whole pass, checked against the reference in all
    # four banks' slots
    warm = json.loads(next(ln for ln in out
                           if ln.startswith("[warmup]"))[9:])
    assert warm["whole_pass"] is True
    ref = json.loads(next(ln for ln in out
                          if ln.startswith("[check.reference]"))[18:])
    assert len(ref["rel_err_by_slot"]) == 4 == len(ref["tone_channel_by_slot"])
    assert ref["tolerance"] <= 1.0e-2
    # what `blit scan` said it ran (the echo is cut at 1500 characters, so
    # the stage table is read from the traced run's own line, below)
    echoed = [ln for ln in out if ln.startswith("  blit> {")][-1]
    assert '"window_frames": 2' in echoed and '"parallel": "mesh"' in echoed


def test_traced_run_reports_only_what_a_cpu_can():
    """The counters and host clocks have something to read on the CPU; the
    device readers (and those that need the chip's trace) return nothing."""
    p, out = run_harness("--workload", CELL, "--seed", "3000000006",
                         "--seconds", "0.05", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is True and doc["breakdown"] is False
    # `wait.link` is a declared wait: 0 calls on the CPU, and so 0.0 s/GB
    assert doc["metric_names"] == EVERY_PASS
    # The traced pass's stage table, whole, as `blit scan` printed it and
    # `run_cli` returned it.  54 frames in 2-frame windows: 25 end with the
    # integration open, the 26th (one frame) closes the row; one fetch and
    # one write a pass
    (plan,) = lines_of(out, "plan")
    (traced,) = lines_of(out, "traced")
    st = traced["stages"]
    assert st["integrate.carry"]["calls"] == 25
    assert st["integrate.emit"]["calls"] == 1
    assert st["read"]["calls"] == st["device"]["calls"] == 26
    assert st["readback"]["calls"] == st["write"]["calls"] == 1
    assert st["readback"]["bytes"] == plan["product_bytes"] \
        == st["integrate.emit"]["bytes"]
    # from the second reduction of the shape on, nothing is allocated
    assert st["staging.alloc"]["calls"] == 0
    # every sample crosses the link once (PR 31): a window puts its new
    # frames only and each bank's filter state stays on its chip
    assert st["link.put"]["bytes"] == plan["raw_bytes"] == st["read"]["bytes"]
    assert st["state.head"]["calls"] == 4                 # banks
    assert st["state.carry"]["calls"] == 4 * (26 - 1)     # banks x windows-1


def test_reference_integrates_51_spectra_and_despikes():
    """``reference.stokes_i(nint=51, despike=True)`` is this
    configuration's reference as it stands (it has no notion of a window):
    pinned against the program's own golden model, two rows, a tail it
    must drop, and the despike applied to the integrated row."""
    from blit.ops.channelize import channelize_np, pfb_coeffs

    rng = np.random.default_rng(30)
    nfft, nint, rows, tail = 64, 51, 2, 7
    frames = rows * nint + tail
    v = rng.integers(-40, 40, (2, (frames + 3) * nfft, 2, 2), dtype=np.int8)
    want = channelize_np(v[:, :(rows * nint + 3) * nfft], pfb_coeffs(4, nfft),
                         nfft=nfft, nint=nint)
    for c in range(2):
        got = reference.stokes_i(v[c], nfft=nfft, nint=nint, despike=True)
        ref = want[:, 0, c * nfft:(c + 1) * nfft].copy()
        ref[:, nfft // 2] = ref[:, nfft // 2 - 1]
        assert got.shape == ref.shape == (rows, nfft)
        # channelize_np filters and sums in float32
        assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()
        assert (got[:, nfft // 2] == got[:, nfft // 2 - 1]).all()


def test_the_configuration_restates_no_guarantee_weaker():
    def load(name):
        with open(os.path.join(BENCH, "configs", name + ".json")) as f:
            return json.load(f)

    cfg, band, bank = (load(n) for n in ("gbt-band4-rawspec", "gbt-band4",
                                         "gbt-bank-rawspec"))
    assert cfg["guarantees"] == band["guarantees"] + bank["guarantees"][4:]
    assert len(cfg["guarantees"]) == 6
    assert cfg["geometry"] == band["geometry"] == bank["geometry"]
    assert (cfg["banks"], cfg["chips"], cfg["mesh"]) == (4, 4, [1, 4])
    with open(os.path.join(BENCH, "traffic", "band-hires-t51.json")) as f:
        t = json.load(f)
    assert (t["nfft"], t["nint"], t["ntap"], t["blocks"]) \
        == (1 << 20, 51, 4, 108)
    assert t["despike"] is True and t["tolerance"] <= 1.0e-2
    assert t["argv"][-6:] == ["--nfft", "1048576", "--nint", "51",
                              "--window-frames", "2"]


def test_carry_least_bytes_is_one_chips_share():
    chip_row = ROW // 4
    # 51 frames of that chip's power read once, 26 windows x (read +
    # write) of its accumulator
    assert band_carry.least_bytes(1, chip_row, 51, 26) == (51 + 52) * chip_row
    assert band_carry.least_bytes(2, chip_row, 51, 52) \
        == (102 + 104) * chip_row


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A program from before the carry (the parent, which cannot run the
    cell at all), a CPU trace, a stage table without the row: no value,
    and no exception."""
    ev = {"traced_raw_bytes": RAW, "device_kind": "TPU v5 lite",
          "peaks": {"TPU v5 lite": {"hbm_GBps": 819.0}}, "stages": {}}
    for name in ("b51_carry_busy_s_per_GB", "b51_carry_roof_share"):
        args = spec(name)["args"]
        assert band_carry.read(args, dict(ev, trace=None)) is None
        assert band_carry.read(args, dict(ev, trace={"per_op_s": {
            "jit_band_reduce/fusion.1": 0.5}, "chips": ["a"] * 4})) is None
    # the ops are there but the stage table has no integrate.emit row
    assert band_carry.read(
        spec("b51_carry_roof_share")["args"],
        dict(ev, trace={"per_op_s": {"jit_band_carry/while": 0.1},
                        "chips": ["a"] * 4})) is None
    assert stage_bytes.read(spec("d2h_MB_per_GB")["args"], ev) is None
    assert stage_bytes.read(
        spec("d2h_MB_per_GB")["args"],
        dict(ev, stages={"readback": {"bytes": ROW}})) \
        == pytest.approx(18.5185, rel=1e-4)
    assert stage_bytes.read(
        spec("h2d_MB_per_GB")["args"],
        dict(ev, stages={"link.put": {"bytes": RAW * 129 // 54}})) \
        == pytest.approx(2388.9, rel=1e-4)   # the feed PR 31 replaced
    assert stage_bytes.read(spec("h2d_MB_per_GB")["args"],
                            dict(ev, stages={"link.put": {"bytes": RAW}})) \
        == pytest.approx(1000.0)


def test_roof_share_on_made_up_numbers():
    """0.1 s of ``jit_band_carry`` per chip for one row over 26 windows:
    103 quarter-rows at 819 GB/s are 33.76 ms, 33.8%."""
    ev = {"traced_raw_bytes": RAW, "device_kind": "TPU v5 lite",
          "peaks": {"TPU v5 lite": {"hbm_GBps": 819.0}},
          "trace": {"per_op_s": {"jit_band_carry/while.1": 0.06,
                                 "jit_band_carry/fusion.2": 0.04,
                                 "jit_band_reduce/fusion.9": 1.0},
                    "chips": ["/device:TPU:%d" % i for i in range(4)]},
          "stages": {"integrate.emit": {"calls": 1, "bytes": ROW},
                     "dispatch": {"calls": 26}}}
    assert band_carry.read(spec("b51_carry_busy_s_per_GB")["args"], ev) \
        == pytest.approx(0.1 / (RAW / 1e9))
    assert band_carry.read(spec("b51_carry_roof_share")["args"], ev) \
        == pytest.approx(100 * (103 * ROW / 4 / 819e9) / 0.1)


@pytest.fixture(scope="module")
def recorded():
    trace = os.path.join(DATA, CELL + ".pr30.xplane.pb")
    facts = os.path.join(DATA, CELL + ".pr30.facts.json")
    if not (os.path.exists(trace) and os.path.exists(facts)):
        pytest.skip("the traced run of PR 30 was not recorded")
    with open(facts) as f:
        return trace, json.load(f)


def test_readers_on_the_recorded_traced_pass(recorded):
    path, facts = recorded
    tr = xplane.reduce_trace(path, facts["window_s"])
    assert tr["chips"] == ["/device:TPU:%d" % i for i in range(4)]
    programs = {op.split("/", 1)[0] for op in tr["per_op_s"]}
    assert {"jit_band_reduce", "jit_band_carry",
            "jit_stitch_despike"} <= programs
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    ev = {"trace": tr, "stages": facts["stages"],
          "traced_raw_bytes": facts["raw_bytes"],
          "device_kind": "TPU v5 lite", "peaks": peaks}
    st = facts["stages"]
    assert st["integrate.carry"]["calls"] == 25
    assert st["integrate.emit"] == dict(st["integrate.emit"], calls=1,
                                        bytes=ROW)
    assert st["dispatch"]["calls"] == 26 and st["readback"]["calls"] == 1
    for name in ("b51_carry_busy_s_per_GB", "b51_carry_roof_share",
                 "b51_collective_s_per_GB", "b51_d2h_MB_per_GB",
                 "b51_h2d_MB_per_GB"):   # as the run of PR 30 named them
        s = spec(FOLDED.get(name, name))
        reader = {"band_carry": band_carry, "xplane": xplane,
                  "stage_bytes": stage_bytes}[s["reader"]]
        got = reader.read(s["args"], ev)
        assert got == pytest.approx(facts["metrics"][name], rel=1e-9), name
    share = band_carry.read(spec("b51_carry_roof_share")["args"], ev)
    assert 0 < share < 100   # over 100 the bytes are counted too high
    # the gather runs once a pass, for the one row
    assert tr["collective_s"] > 0
    assert facts["metrics"]["b51_d2h_MB_per_GB"] \
        == pytest.approx(18.5185, rel=1e-4)
