"""The trace reduction on traces recorded on the chip during PR 22 (one
traced pass each, TPU v5 lite, ``TRACE_ONLY_XLA``, host tracer off; a
hundred KB as recorded, nothing cut).  The numbers are re-derived here
the slow way from the same events."""

import os

import pytest

from readers import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = {
    "bank.hires": dict(chips=1, programs={"jit_channelize", "jit_concatenate"},
                       pallas=2, collective=False),
    "bank.lowres": dict(chips=1, programs={"jit_channelize", "jit_concatenate"},
                        pallas=0, collective=False),
    "band4.hires": dict(chips=4, programs=None, pallas=2, collective=True),
}


def device_ops(path):
    """plane name -> [(start, end, text)] of its XLA Ops line."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
    return out


def swept_union(ivals):
    """Busy ns by the textbook sweep: +1 at a start, -1 at an end."""
    edges = sorted([(s, 1) for s, _, _ in ivals] + [(e, -1) for _, e, _ in ivals],
                   key=lambda x: (x[0], -x[1]))
    depth, since, total = 0, None, 0.0
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            total += t - since
    return total


@pytest.fixture(params=sorted(TRACES))
def trace(request):
    path = os.path.join(DATA, request.param + ".xplane.pb")
    if not os.path.exists(path):
        pytest.skip(f"{path} was not recorded")
    return request.param, path, TRACES[request.param]


def test_busy_is_the_union_of_op_intervals(trace):
    name, path, want = trace
    red = xplane.reduce_trace(path)
    ops = device_ops(path)
    assert red["chips"] == sorted(ops) and len(ops) == want["chips"]
    by_chip = [swept_union(ops[c]) / 1e9 for c in red["chips"]]
    assert red["busy_s_by_chip"] == pytest.approx(by_chip, rel=1e-9)
    assert red["busy_s"] == pytest.approx(sum(by_chip) / len(by_chip))
    assert 0 < red["busy_s"] < red["window_s"]


def test_self_times_partition_busy_and_name_programs(trace):
    name, path, want = trace
    red = xplane.reduce_trace(path)
    assert sum(red["per_op_s"].values()) == pytest.approx(red["busy_s"],
                                                          rel=1e-6)
    programs = {k.split("/")[0] for k in red["per_op_s"]}
    assert "?" not in programs
    if want["programs"]:
        assert programs == want["programs"]
    assert sum(k.endswith("[pallas]") for k in red["per_op_s"]) \
        == want["pallas"]
    assert (red["collective_s"] > 0) == want["collective"]


def test_gaps_and_busy_fill_the_window(trace):
    name, path, _ = trace
    span = xplane.reduce_trace(path)["window_s"]
    red = xplane.reduce_trace(path, window_s=span + 2.5)
    assert red["window_s"] == span + 2.5
    first = red["busy_s_by_chip"][0]
    assert sum(red["idle_gaps_s"].values()) + first == pytest.approx(span + 2.5)
    assert red["idle_gaps_s"][
        "host: before the first op and after the last"] == pytest.approx(2.5)


def test_readers_serve_the_metrics(trace):
    name, path, _ = trace
    red = xplane.reduce_trace(path, window_s=10.0)
    ev = {"trace": red, "traced_raw_bytes": 5e9, "traced_least_bytes": 9e9,
          "device_kind": "TPU v5 lite",
          "peaks": {"TPU v5 lite": {"hbm_GBps": 819.0}}}
    assert xplane.read({"value": "busy_s_per_GB"}, ev) \
        == pytest.approx(red["busy_s"] / 5)
    assert xplane.read({"value": "idle_share"}, ev) \
        == pytest.approx(100 * (1 - red["busy_s"] / 10))
    assert xplane.read({"value": "hbm_roof_share"}, ev) \
        == pytest.approx(100 * (9e9 / 819e9) / red["busy_s"])
    assert xplane.read({"value": "collective_s_per_GB"}, ev) \
        == pytest.approx(red["collective_s"] / 5)
    assert xplane.read({"value": "idle_share"}, {"trace": None}) is None
    with pytest.raises(ValueError):
        xplane.read({"value": "nonsense"}, ev)


def test_recorded_values():
    """What the hand reading of the bank.hires trace gave (PERF.md)."""
    red = xplane.reduce_trace(os.path.join(DATA, "bank.hires.xplane.pb"))
    assert red["busy_s"] == pytest.approx(0.317247794, rel=1e-6)
    top = max(red["per_op_s"], key=red["per_op_s"].get)
    assert top == "jit_channelize/channelize.3[pallas]"
