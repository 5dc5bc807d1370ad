"""The cell ``rawspec.hires51`` (PR 26): added as files only.  Its toy run
end to end, the reference at ``nint`` 51, and its two readers on what the
builder's traced run on the chip recorded (``data/rawspec.hires51.pr26.*``:
the ``.xplane.pb`` as written, and the run's stage table and result
line)."""

import json
import os

import numpy as np
import pytest
from conftest import (BENCH, EVERY_PASS, PUMP_CALL, PUMP_WAITS, lines_of,
                      run_harness, run_line)

import reference
from readers import carry, stage_bytes, timeline, xplane

CELL = "rawspec.hires51"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
STAGE_METRICS = ["dispatch_s_per_GB", "read_rate", "readback_s_per_GB",
                 "write_s_per_GB"]


def spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_end_to_end_run_at_toy_size():
    p, out = run_harness("--workload", CELL, "--seed", "2600000005",
                         "--seconds", "0.05", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["rehearsal"] is True and doc["platform"] == "cpu"
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 1
    # first_product_s lists its cells by name: this one is not among them
    assert doc["metric_names"] == ["reduce_rate", "setup_s"]
    assert "metrics" not in doc
    assert any(ln.startswith("[check.reference]") for ln in out)
    (plan,) = lines_of(out, "plan")
    assert plan["blocks"] == 108 and plan["products"][0]["rows"] == 1
    (ref,) = lines_of(out, "reference")
    assert ref["launched"] == ref["tasks"] == 2 == run_line(p)[
        "reference"]["tasks"] and not ref["failed"]


def test_traced_run_reports_only_what_a_cpu_can():
    """The counters and host clocks have something to read on the CPU; the
    device readers (and the two that need the chip's trace) return
    nothing.  ``link_wait_s_per_GB`` reads ``wait.link``, a declared wait
    (0 calls on the CPU, whose link is not budgeted): 0.0 s/GB."""
    p, out = run_harness("--workload", CELL, "--seed", "2600000006",
                         "--seconds", "0.05", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    doc = json.loads(out[-1])
    assert doc["correct"] is True and doc["breakdown"] is False
    assert set(STAGE_METRICS) <= set(EVERY_PASS)
    assert doc["metric_names"] == sorted(EVERY_PASS + PUMP_WAITS
                                         + [PUMP_CALL])


def test_reference_integrates_51_spectra():
    """``reference.stokes_i`` is this configuration's reference as it
    stands (it has no notion of a chunk): pinned at nint 51 against the
    program's own golden model, two rows and a tail it must drop."""
    from blit.ops.channelize import channelize_np, pfb_coeffs

    rng = np.random.default_rng(26)
    nfft, nint, rows, tail = 64, 51, 2, 7
    frames = rows * nint + tail
    v = rng.integers(-40, 40, (2, (frames + 3) * nfft, 2, 2), dtype=np.int8)
    want = channelize_np(v[:, :(rows * nint + 3) * nfft], pfb_coeffs(4, nfft),
                         nfft=nfft, nint=nint)
    for c in range(2):
        got = reference.stokes_i(v[c], nfft=nfft, nint=nint)
        ref = want[:, 0, c * nfft:(c + 1) * nfft]
        assert got.shape == ref.shape == (rows, nfft)
        # channelize_np filters and sums in float32
        assert np.abs(got - ref).max() <= 1e-5 * np.abs(ref).max()


def test_carry_least_bytes_is_power_once_and_accumulators_twice():
    row = 64 * (1 << 20) * 4
    # 51 frames of power read once, 7 dispatches x (read + write) of 1 row
    assert carry.least_bytes(1, row, 51, 7) == (51 + 14) * row
    assert carry.least_bytes(2, row, 51, 13) == (102 + 26) * row


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """A program from before the carry (the parent), a CPU trace, a stage
    table without the row: no value, and no exception."""
    ev = {"traced_raw_bytes": 10 ** 9, "device_kind": "TPU v5 lite",
          "peaks": {"TPU v5 lite": {"hbm_GBps": 819.0}}, "stages": {}}
    for name in ("carry_busy_s_per_GB", "carry_roof_share"):
        args = spec(name)["args"]
        assert carry.read(args, dict(ev, trace=None)) is None
        assert carry.read(args, dict(ev, trace={"per_op_s": {
            "jit_channelize/fusion.1": 0.5}})) is None
    assert stage_bytes.read(spec("d2h_MB_per_GB")["args"], ev) is None
    assert stage_bytes.read(
        spec("d2h_MB_per_GB")["args"],
        dict(ev, stages={"readback": {"bytes": 268435456}})) \
        == pytest.approx(268.435456)


@pytest.fixture(scope="module")
def recorded():
    trace = os.path.join(DATA, CELL + ".pr26.xplane.pb")
    facts = os.path.join(DATA, CELL + ".pr26.facts.json")
    if not (os.path.exists(trace) and os.path.exists(facts)):
        pytest.skip("the traced run of PR 26 was not recorded")
    with open(facts) as f:
        return trace, json.load(f)


def test_readers_on_the_recorded_traced_pass(recorded):
    path, facts = recorded
    tr = xplane.reduce_trace(path, facts["window_s"])
    assert tr["chips"] == ["/device:TPU:0"]
    programs = {op.split("/", 1)[0] for op in tr["per_op_s"]}
    assert {"jit_channelize", "jit_integrate_carry"} <= programs
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    ev = {"trace": tr, "stages": facts["stages"],
          "traced_raw_bytes": facts["raw_bytes"],
          "device_kind": "TPU v5 lite", "peaks": peaks}
    said = facts["metrics"]  # the run's own result line
    busy = carry.read(spec("carry_busy_s_per_GB")["args"], ev)
    share = carry.read(spec("carry_roof_share")["args"], ev)
    d2h = stage_bytes.read(spec("d2h_MB_per_GB")["args"], ev)
    assert busy == pytest.approx(said["carry_busy_s_per_GB"], rel=1e-9)
    assert share == pytest.approx(said["carry_roof_share"], rel=1e-9)
    assert d2h == pytest.approx(said["d2h_MB_per_GB"], rel=1e-9)
    # ... and re-derived the slow way from the same trace and table
    own = sum(s for op, s in tr["per_op_s"].items()
              if op.startswith("jit_integrate_carry/"))
    assert 0 < own < tr["busy_s"]
    assert busy == pytest.approx(own / (facts["raw_bytes"] / 1e9))
    st = facts["stages"]
    assert st["integrate.emit"]["calls"] == 1
    assert st["integrate.carry"]["calls"] == 6
    row = st["integrate.emit"]["bytes"]
    least = (51 + 2 * st["dispatch"]["calls"]) * row
    assert share == pytest.approx(100 * least / 819e9 / own)
    assert 0 < share < 100
    # the stage-table metrics, from the same recorded table
    gb = facts["raw_bytes"] / 1e9
    for name, stage in [("dispatch_s_per_GB", "dispatch"),
                        ("readback_s_per_GB", "readback"),
                        ("write_s_per_GB", "write")]:
        assert timeline.read(spec(name)["args"], ev) \
            == pytest.approx(st[stage]["seconds"] / gb)
    assert timeline.read(spec("read_rate")["args"], ev) \
        == pytest.approx(st["ingest"]["bytes"] / st["ingest"]["seconds"]
                         / 1e9)
    # the dispatching thread's own copy sets the pace in this cell
    assert st["dispatch"]["seconds"] > st["wait.out_slot"]["seconds"]
    # only the closed row crossed to the host: 256 MiB per 14.5 GB
    assert st["readback"]["bytes"] == row == 64 * (1 << 20) * 4
    assert d2h == pytest.approx(row / 1e6 / (facts["raw_bytes"] / 1e9))
