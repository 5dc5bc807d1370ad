"""Recorder packet front end (ISSUE 18): GUPPI packet framing
round-trips, the assembler's gap discipline (seeded drop/reorder/dup
replays byte-identical to the zero-filled batch oracle), the UDP
loopback capture path, the ``packet.recv`` fault point (reorder/drop
drills), whole-session orchestration (SessionSupervisor + rejoin under
a packet source), the tail-idle liveness satellite, and the ``blit
session`` CLI leg."""

import contextlib
import glob
import io
import json
import os
import threading

import pytest

from blit import faults
from blit.config import DEFAULT, packet_defaults, slo_defaults
from blit.faults import FaultRule
from blit.io.guppi import open_raw, write_raw
from blit.observability import Timeline
from blit.pipeline import RawReducer
from blit.stream import (
    FileTailSource,
    PacketAssembler,
    PacketReplaySource,
    PacketSource,
    packets_of,
    source_from_spec,
    stream_reduce,
)
from blit.stream.packet import (
    MAGIC,
    PKT_DATA,
    PKT_FIN,
    PKT_HEADER,
    PacketFramer,
    decode_packet,
    encode_packet,
)
from blit.testing import synth_raw

NFFT = 256
NINT = 2
CHUNK_FRAMES = 4


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_counters()
    yield
    faults.clear()
    faults.reset_counters()


@pytest.fixture(autouse=True)
def _flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("BLIT_FLIGHT_DIR", str(tmp_path / "flight"))
    os.makedirs(str(tmp_path / "flight"), exist_ok=True)


def _synth(path, nblocks=4, overlap=NFFT, seed=1, **kw):
    return synth_raw(str(path), nblocks=nblocks, obsnchan=2,
                     ntime_per_block=(8 + 3) * NFFT, overlap=overlap,
                     seed=seed, tone_chan=1, **kw)


def _reducer(**kw):
    kw.setdefault("timeline", Timeline())
    return RawReducer(nfft=NFFT, nint=NINT, chunk_frames=CHUNK_FRAMES,
                      **kw)


def _batch(raw, out):
    _reducer().reduce_to_file(str(raw), str(out))
    with open(out, "rb") as f:
        return f.read()


def _read(path):
    with open(path, "rb") as f:
        return f.read()


def _zero_masked_ref(tmp_path, hdr0, blocks, masked):
    """Batch comparator: the recording with the masked blocks' samples
    zeroed — exactly what zero-weight masking must yield."""
    zb = [b.copy() for b in blocks]
    for i in masked:
        zb[i][:] = 0
    zraw = tmp_path / "zeroed.raw"
    write_raw(str(zraw), hdr0, zb)
    return _batch(zraw, tmp_path / "zref.fil")


class TestFraming:
    def test_encode_decode_roundtrip(self):
        pkt = encode_packet(PKT_DATA, 42, block=3, chan0=1, time0=512,
                            nchan=1, ntime=64, payload=b"\x01\x02")
        f, payload = decode_packet(pkt)
        assert f["ptype"] == PKT_DATA
        assert f["pktidx"] == 42
        assert f["block"] == 3
        assert f["chan0"] == 1
        assert f["time0"] == 512
        assert f["nchan"] == 1
        assert f["ntime"] == 64
        assert payload == b"\x01\x02"

    def test_decode_rejects_garbage(self):
        with pytest.raises(ValueError):
            decode_packet(b"short")
        bad_magic = b"XXXX" + encode_packet(PKT_FIN, 0)[4:]
        with pytest.raises(ValueError):
            decode_packet(bad_magic)
        good = bytearray(encode_packet(PKT_FIN, 0))
        good[4] = 99  # unknown version
        with pytest.raises(ValueError):
            decode_packet(bytes(good))

    def test_packets_of_covers_every_block(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, nblocks=3)
        pkts = list(packets_of(str(raw), packet_ntime=64))
        fr = PacketFramer(open_raw(str(raw)).header(0), 64)
        assert len(pkts) == 2 + 3 * fr.packets_per_block()
        first, _ = decode_packet(pkts[0])
        last, _ = decode_packet(pkts[-1])
        assert pkts[0][:4] == MAGIC
        assert first["ptype"] == PKT_HEADER
        assert last["ptype"] == PKT_FIN
        assert last["block"] == 3  # FIN carries the session total

    def test_assembler_rebuilds_blocks_byte_identical(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, nblocks=3)
        src = open_raw(str(raw))
        asm = PacketAssembler(timeline=Timeline())
        for pkt in packets_of(src, packet_ntime=64):
            asm.feed(pkt)
        got = []
        while True:
            c = asm.pop()
            if c is None:
                break
            got.append(c)
        assert [c.seq for c in got] == [0, 1, 2]
        for c in got:
            assert c.data.tobytes() == src.read_block(c.seq).tobytes()
        rep = asm.report()
        assert rep["gaps"] == 0 and rep["reorders"] == 0
        assert rep["assembly_p99_s"] is not None


class TestReplayIdentity:
    """The cap drill: seeded packet chaos ≡ batch with gapped blocks
    zero-filled, byte for byte."""

    def test_clean_replay_identical_to_batch(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        out = tmp_path / "s.fil"
        src = PacketReplaySource(str(raw), rate=1e6, packet_ntime=64)
        hdr = stream_reduce(src, str(out), reducer=_reducer())
        assert _read(out) == ref
        assert hdr["stream_masked_chunks"] == 0
        rep = src.packet_report()
        assert rep["gaps"] == 0 and rep["dups"] == 0

    def test_dropped_block_matches_zero_filled_oracle(self, tmp_path):
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        ref = _zero_masked_ref(tmp_path, hdr0, blocks, [2])
        out = tmp_path / "s.fil"
        tl = Timeline()  # the plane counts on the reducer's timeline
        src = PacketReplaySource(str(raw), rate=1e6, packet_ntime=64,
                                 drop_blocks=[2], timeline=tl)
        hdr = stream_reduce(src, str(out),
                            reducer=_reducer(timeline=tl),
                            lateness_s=5.0)
        assert _read(out) == ref
        assert hdr["stream_masked_chunks"] == 1
        assert hdr["_masked_chunks"] == [2]
        rep = src.packet_report()
        assert rep["gaps"] == 1 and rep["gapped_blocks"] == [2]
        # The plane masked off the assembler's gap PROOF, not the
        # watermark timeout.
        assert tl.stages["stream.chunk.gap_fastpath"].calls >= 1
        assert faults.counters().get("mask.chunk", 0) == 1

    def test_seeded_reorder_and_dup_do_not_mask(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        out = tmp_path / "s.fil"
        src = PacketReplaySource(str(raw), rate=1e6, packet_ntime=64,
                                 reorder=0.2, dup=0.1, seed=7)
        hdr = stream_reduce(src, str(out), reducer=_reducer(),
                            lateness_s=5.0)
        assert _read(out) == ref
        assert hdr["stream_masked_chunks"] == 0
        rep = src.packet_report()
        assert rep["reorders"] > 0 and rep["dups"] > 0
        assert rep["gaps"] == 0

    def test_fractional_drop_gaps_match_oracle(self, tmp_path):
        # A seeded per-packet loss rate: whichever blocks lost a tile
        # must mask, and the product must equal the oracle built from
        # the assembler's OWN gap ledger.
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        out = tmp_path / "s.fil"
        src = PacketReplaySource(str(raw), rate=1e6, packet_ntime=64,
                                 drop=0.01, seed=1)
        hdr = stream_reduce(src, str(out), reducer=_reducer(),
                            lateness_s=5.0)
        rep = src.packet_report()
        assert rep["gaps"] >= 1  # seeded: some block loses a tile
        assert hdr["_masked_chunks"] == rep["gapped_blocks"]
        ref = _zero_masked_ref(tmp_path, hdr0, blocks,
                               rep["gapped_blocks"])
        assert _read(out) == ref


class TestUdpCapture:
    def test_loopback_session_identical_to_batch(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, nblocks=3)
        ref = _batch(raw, tmp_path / "ref.fil")
        src = PacketSource("127.0.0.1", 0)
        import socket

        def send():
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            for pkt in packets_of(str(raw), packet_ntime=64):
                s.sendto(pkt, ("127.0.0.1", src.port))
            s.close()

        t = threading.Thread(target=send)
        t.start()
        out = tmp_path / "s.fil"
        hdr = stream_reduce(src, str(out), reducer=_reducer())
        t.join()
        src.close()
        assert _read(out) == ref
        assert hdr["stream_masked_chunks"] == 0
        assert src.packet_report()["packets"] > 0

    def test_packet_defaults_env_overrides(self, monkeypatch):
        monkeypatch.setenv("BLIT_PACKET_PORT", "61234")
        monkeypatch.setenv("BLIT_PACKET_NTIME", "32")
        monkeypatch.setenv("BLIT_PACKET_HORIZON", "5")
        d = packet_defaults(DEFAULT)
        assert d["port"] == 61234
        assert d["ntime"] == 32
        assert d["horizon_blocks"] == 5

    def test_packet_assembly_slo_template(self, monkeypatch):
        names = [o["name"] for o in slo_defaults(DEFAULT)]
        assert "packet-assembly" not in names  # off until configured
        monkeypatch.setenv("BLIT_SLO_PACKET_P99", "0.25")
        objs = {o["name"]: o for o in slo_defaults(DEFAULT)}
        slo = objs["packet-assembly"]
        assert slo["metric"] == "packet.assembly_s"
        assert slo["threshold"] == 0.25


class TestPacketFaultDrills:
    """The ``packet.recv`` injection point: datagram-level chaos on a
    live capture, without touching the sender."""

    def test_reorder_fault_holds_then_releases(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw)
        ref = _batch(raw, tmp_path / "ref.fil")
        faults.install(FaultRule("packet.recv", "reorder", times=1,
                                 after=3, amount=3))
        out = tmp_path / "s.fil"
        src = PacketReplaySource(str(raw), rate=1e6, packet_ntime=64)
        stream_reduce(src, str(out), reducer=_reducer(),
                      lateness_s=5.0)
        rep = src.packet_report()
        assert rep["reorders"] >= 1
        assert rep["gaps"] == 0  # held packets land before FIN resolves
        assert _read(out) == ref

    def test_drop_fault_becomes_gap_not_garbage(self, tmp_path):
        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw)
        faults.install(FaultRule("packet.recv", "drop", times=1, after=6))
        out = tmp_path / "s.fil"
        src = PacketReplaySource(str(raw), rate=1e6, packet_ntime=64)
        hdr = stream_reduce(src, str(out), reducer=_reducer(),
                            lateness_s=5.0)
        rep = src.packet_report()
        assert rep["gaps"] == 1
        assert hdr["_masked_chunks"] == rep["gapped_blocks"]
        ref = _zero_masked_ref(tmp_path, hdr0, blocks,
                               rep["gapped_blocks"])
        assert _read(out) == ref
        assert faults.counters().get("packet.gap", 0) == 1

    def test_reorder_spec_parses(self):
        rules = faults.parse_spec("packet.recv:reorder:after=3")
        assert rules[0].point == "packet.recv"
        assert rules[0].mode == "reorder"


class TestTailIdleLiveness:
    """Satellite: the tailer publishes its idle age and dumps the
    flight recorder when the idle timeout ends a session."""

    def test_idle_gauge_and_flight_dump(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, nblocks=2)
        tl = Timeline()
        src = FileTailSource(str(raw), poll_s=0.01, idle_timeout_s=0.05,
                             timeline=tl)
        got = 0
        while True:
            c = src.get(timeout=2.0)
            if c is not None:
                got += 1
                continue
            if src.finished:
                break
        assert got == 2
        g = tl.gauges["stream.tail.idle_s"]
        assert g.n >= 1 and g.hi >= 0.05
        dumps = glob.glob(os.path.join(
            os.environ["BLIT_FLIGHT_DIR"], "*.json"))
        assert any("tail idle" in _read(p).decode("utf-8", "replace")
                   for p in dumps)


class TestSessionOrchestration:
    def _seat_spec(self, raw, out, **src_kw):
        return {
            "name": os.path.basename(str(out)).split(".")[0],
            "out": str(out),
            "source": dict({"kind": "packet-replay", "raw": str(raw),
                            "rate": 1e6, "packet_ntime": 64}, **src_kw),
            "knobs": dict(nfft=NFFT, nint=NINT,
                          chunk_frames=CHUNK_FRAMES),
        }

    def test_source_from_spec_dispatch(self, tmp_path):
        raw = tmp_path / "r.raw"
        _synth(raw, nblocks=2)
        src = source_from_spec({"kind": "packet-replay",
                                "raw": str(raw), "rate": 1e6})
        assert isinstance(src, PacketReplaySource)
        src = source_from_spec({"kind": "tail", "raw": str(raw)})
        assert isinstance(src, FileTailSource)
        with pytest.raises(ValueError):
            source_from_spec({"kind": "carrier-pigeon"})

    def test_two_seat_session_folds_reports(self, tmp_path):
        from blit.stream import SessionSupervisor

        raw_a, raw_b = tmp_path / "a.raw", tmp_path / "b.raw"
        _synth(raw_a, seed=1)
        _synth(raw_b, seed=2)
        ref_a = _batch(raw_a, tmp_path / "ref_a.fil")
        ref_b = _batch(raw_b, tmp_path / "ref_b.fil")
        seats = [
            self._seat_spec(raw_a, tmp_path / "blc00.fil"),
            self._seat_spec(raw_b, tmp_path / "blc01.fil",
                            drop_blocks=[1]),
        ]
        sup = SessionSupervisor(seats,
                                work_dir=str(tmp_path / "work"),
                                lease_ttl_s=3.0, poll_s=0.05)
        rep = sup.run()
        assert rep["ok"]
        assert set(rep["seats"]) == {"blc00", "blc01"}
        assert all(s["ok"] for s in rep["seats"].values())
        assert rep["masked_total"] == 1
        assert _read(tmp_path / "blc00.fil") == ref_a
        # Seat blc01 lost block 1 on the wire: product == zeroed oracle.
        hdr0, blocks = open_raw(str(raw_b)).header(0), [
            open_raw(str(raw_b)).read_block(i) for i in range(4)]
        assert _read(tmp_path / "blc01.fil") == _zero_masked_ref(
            tmp_path, hdr0, blocks, [1])

    def test_duplicate_seat_names_rejected(self, tmp_path):
        from blit.stream import SessionSupervisor

        seats = [{"name": "x", "out": "a.fil"},
                 {"name": "x", "out": "b.fil"}]
        with pytest.raises(ValueError):
            SessionSupervisor(seats, work_dir=str(tmp_path))

    def test_cursor_rejoin_under_packet_source(self, tmp_path):
        """Satellite drill: kill the consumer mid-session while the
        packet stream is ALSO dropping a block — the restarted seat
        rejoins from its cursor and the product still equals the
        zero-filled oracle."""
        from blit.recover import StreamSupervisor

        raw = tmp_path / "r.raw"
        hdr0, blocks = _synth(raw, nblocks=6)
        ref = _zero_masked_ref(tmp_path, hdr0, blocks, [3])
        out = tmp_path / "s.fil"
        sup = StreamSupervisor(
            str(raw), str(out), kind="reduce",
            knobs=dict(nfft=NFFT, nint=NINT, chunk_frames=CHUNK_FRAMES),
            source={"kind": "packet-replay", "raw": str(raw),
                    "rate": 1e6, "packet_ntime": 64,
                    "drop_blocks": [3]},
            faults="stream.chunk:kill:after=2",
            lease_ttl_s=3.0, poll_s=0.05,
        )
        rep = sup.run()
        assert rep["recovered"]
        assert len(rep["attempts"]) >= 2
        assert rep["result"]["masked"] == 1
        assert rep["result"]["packet"]["gaps"] == 1
        assert _read(out) == ref

    def test_session_cli_smoke(self, tmp_path):
        from blit.__main__ import main

        raw = tmp_path / "r.raw"
        _synth(raw, nblocks=2)
        ref = _batch(raw, tmp_path / "ref.fil")
        spec = {"seats": [self._seat_spec(raw, tmp_path / "s.fil")]}
        spec_path = tmp_path / "session.json"
        spec_path.write_text(json.dumps(spec))
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = main(["session", str(spec_path),
                       "--work-dir", str(tmp_path / "work"),
                       "--lease-ttl", "3.0", "--poll", "0.05"])
        assert rc == 0
        rep = json.loads(buf.getvalue())
        assert rep["kind"] == "session" and rep["ok"]
        assert _read(tmp_path / "s.fil") == ref
