"""What can be known of the TPU path without a TPU.

Every branch below is one only the TPU backend takes, so the CPU suite
never sees it unless it asks:

- every Pallas kernel is CROSS-LOWERED for the TPU at its production
  shape (``jax.export`` with ``platforms=["tpu"]`` runs the Pallas→Mosaic
  lowering on any host) — a kernel that cannot lower is caught here, not
  on chip time;
- ``band_reduce`` runs on the CPU mesh with a Pallas kernel (interpreted)
  inside the per-chip ``channelize`` — what ``auto`` resolves to on a chip;
- kernel requests on a backend that is neither TPU nor CPU raise;
- the reducer splits a chunk into channel groups when the device reports
  a memory limit, and holds the chip path to the native reader;
- the compile cache is placed by ``JAX_COMPILATION_CACHE_DIR`` when set.
"""

import functools
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from blit import device  # noqa: E402
from blit.ops import channelize as ch  # noqa: E402
from blit.ops import dft as D  # noqa: E402

NFFT = 1 << 20
FACTORS = D.default_factors(NFFT)  # (128, 128, 64)
NCHAN, FRAMES, NTAP = 2, 8, 4


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def lower_for_tpu(fn, *specs):
    """Pallas→Mosaic lowering of ``fn`` for the TPU, on this host."""
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*specs)
    assert "tpu_custom_call" in exported.mlir_module()


class TestEveryKernelLowersForTheTpu:
    """Production shapes: nfft 2^20 = 128 x 128 x 64, 8 frames per chunk,
    f32 and bf16 stages; the collective kernels at chip_smoke.py's shapes."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_pfb_dft1(self, dtype):
        from blit.ops.pallas_pfb import pfb_dft1

        n1 = FACTORS[0]
        # A stream's (tail, body): words, an operand each.
        lower_for_tpu(
            lambda t, b, *a: pfb_dft1((t, b), *a, dtype=dtype),
            spec((NCHAN, (NTAP - 1) * NFFT), "int32"),
            spec((NCHAN, FRAMES * NFFT), "int32"),
            spec((NTAP, NFFT), "float32"),
            spec((n1, n1), "float32"), spec((n1, n1), "float32"),
            spec((n1, NFFT // n1), "float32"),
            spec((n1, NFFT // n1), "float32"),
        )

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_pfb_dequant(self, dtype):
        from blit.ops.pallas_pfb import pfb_dequant

        lower_for_tpu(
            functools.partial(pfb_dequant, dtype=dtype),
            spec((NCHAN, (FRAMES + NTAP - 1) * NFFT), "int32"),
            spec((NTAP, NFFT), "float32"),
        )

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("stokes", ["I", "IQUV"])
    def test_tail2_detect(self, stokes, dtype):
        from blit.ops.pallas_detect import tail2_detect

        u = spec((NCHAN, 2, FRAMES, FACTORS[0], NFFT // FACTORS[0]), dtype)
        lower_for_tpu(
            lambda a, b: tail2_detect(a, b, FACTORS[1], FACTORS[2],
                                      stokes=stokes), u, u)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_dft_tail2(self, dtype):
        from blit.ops.pallas_dft import dft_tail2

        u = spec((NCHAN, 2, FRAMES, FACTORS[0], NFFT // FACTORS[0]), dtype)
        lower_for_tpu(
            lambda a, b: dft_tail2(a, b, FACTORS[1], FACTORS[2],
                                   dtype=dtype), u, u)

    def test_detect_untwist_i(self):
        from blit.ops.pallas_detect import detect_untwist_i

        s = spec((NCHAN, 2, FRAMES, NFFT), "bfloat16")
        lower_for_tpu(lambda a, b: detect_untwist_i(a, b, FACTORS), s, s)

    def test_fused_beamform_detect(self):
        from blit.ops.pallas_beamform import fused_beamform_detect

        nant, nbeam, nchan, ntime, nint = 64, 64, 64, 8192, 8
        v = spec((nchan, nant, 2, ntime), "bfloat16")
        w = spec((nchan, nbeam, nant), "bfloat16")
        lower_for_tpu(
            functools.partial(fused_beamform_detect, nint=nint), v, v, w, w)

    def test_xengine_packed(self):
        from blit.ops.pallas_xengine import pick_ft, xengine_packed

        nant, nchan, nfft, nframes = 64, 16, 512, 61
        ft = pick_ft(nant * 2, nfft, nframes, itemsize=4)
        assert ft is not None
        s = spec((nant, nchan, 2, nframes, nfft), "float32")
        lower_for_tpu(functools.partial(xengine_packed, ft=ft), s, s)

    def test_taylor_tree(self):
        from blit.ops.pallas_dedoppler import taylor_tree

        lower_for_tpu(functools.partial(taylor_tree, kernel="pallas"),
                      spec((64, NFFT), "float32"))


class TestMeshWithPallasInside:
    """On a chip ``auto`` puts a Pallas kernel inside ``band_reduce``'s
    shard_map body; ``check_vma=True`` rejected exactly that, on a branch
    the CPU's XLA path never took."""

    @pytest.mark.parametrize("stitch", [False, True])
    def test_band_reduce_runs_with_a_pallas_channelize(self, monkeypatch,
                                                       stitch):
        from blit.parallel import mesh as M

        nfft, nbank, nchan = 128, 4, 2
        monkeypatch.setattr(M, "channelize", functools.partial(
            ch.channelize, pfb_kernel="pallas"))  # interpreted on the CPU
        mesh = M.make_mesh(1, nbank)
        rng = np.random.default_rng(5)
        # A shape no other test traces, so the patched body is the one
        # band_reduce's jit cache holds.
        v = rng.integers(-40, 40, (1, nbank, nchan, 9 * nfft, 2, 2), np.int8)
        h = jnp.asarray(ch.pfb_coeffs(4, nfft))
        out = np.asarray(M.band_reduce(
            M.shard_voltages(v, mesh), h, mesh=mesh, nfft=nfft, nint=2,
            stitch=stitch))
        assert ch.last_kernel_plan()["pfb_kernel"] == "pallas"
        want = np.concatenate(
            [ch.channelize_np(v[0, k], ch.pfb_coeffs(4, nfft), nfft=nfft,
                              nint=2) for k in range(nbank)], axis=-1)
        np.testing.assert_allclose(out[0], want, rtol=1e-4, atol=1e-2)


class TestCarriedMeshStepLowersForTheTpu:
    """The carried scan's per-window programs (ISSUE 30) at the cell's own
    shape — 64 ch x (2 + 3) frames x 2^20 per chip on the (1, 4) mesh —
    cross-lowered for the TPU: the channeliser at ``nint`` 1, unstitched
    (``band_stream`` since ISSUE 31: the 3 frames of filter state on the
    chip, the 2 new ones as words; ``fused1`` + ``tail2_detect`` inside
    ``shard_map``), and the per-chip fold.  Neither holds a collective: nothing is gathered until a row
    closes (``stitch_despike``, which does)."""

    NCH, WF, NBANK = 64, 2, 4

    def _spec(self, mesh, shape, dtype, rule):
        from blit.parallel import mesh as M

        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=M.sharding_for(mesh, rule))

    def _export(self, fn, *specs):
        exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*specs)
        assert exported.nr_devices == self.NBANK
        return exported.mlir_module()

    @pytest.mark.parametrize("frames", [2, 1], ids=["window", "last-window"])
    def test_channelise_and_fold_per_chip(self, monkeypatch, frames):
        from blit.parallel import mesh as M

        # What ``auto`` resolves to on a chip; nothing runs.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = M.make_mesh(1, self.NBANK)
        band = self.NBANK * self.NCH * NFFT
        text = self._export(
            functools.partial(M.band_stream, mesh=mesh, nfft=NFFT, ntap=NTAP,
                              nint=1, stokes="I", stitch=False,
                              despike_nfpc=0),
            self._spec(mesh, (1, self.NBANK, self.NCH, (NTAP - 1) * NFFT),
                       "int32", "filter_state"),
            self._spec(mesh, (1, self.NBANK, self.NCH, frames * NFFT),
                       "int32", "voltages"),
            self._spec(mesh, (NTAP, NFFT), "float32", "replicated"))
        assert text.count("tpu_custom_call") == 2
        assert ch.last_kernel_plan()["pfb_kernel"] == "fused1"
        assert ch.last_kernel_plan()["tail_kernel"] == "tail2_detect"
        assert "all_gather" not in text and "all-gather" not in text
        text = self._export(
            functools.partial(M.band_carry, mesh=mesh, nint=51),
            self._spec(mesh, (1, 1, band), "float32", "integration_acc"),
            self._spec(mesh, (1, frames, 1, band), "float32",
                       "filterbank_sharded"),
            jax.ShapeDtypeStruct((), jnp.int32))
        assert "all_gather" not in text and "all-gather" not in text

    def test_the_stitch_of_a_closed_row_gathers(self):
        from blit.parallel import mesh as M

        mesh = M.make_mesh(1, self.NBANK)
        band = self.NBANK * self.NCH * NFFT
        text = self._export(
            functools.partial(M.stitch_despike, mesh=mesh,
                              despike_nfpc=NFFT),
            self._spec(mesh, (1, 1, 1, band), "float32",
                       "filterbank_sharded"))
        assert "all_gather" in text or "all-gather" in text


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four chips of one v5e host, described and not attached: the
    installed TPU compiler compiles for them without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


class TestMeshStreamStepCompilesForTheHost:
    """The scan's per-window program since ISSUE 31 (``band_stream``: the
    filter state stays on the chips, a window is its new samples as
    words) COMPILED for ``v5e:2x2`` at ``band4.hires51``'s own shape —
    per chip 64 ch x (3 + 2) frames x 2^20 — by the chip's own compiler:
    what it refuses on the chip it refuses here."""

    NCH, NBANK, HBM = 64, 4, 15.75 * 2 ** 30

    def _compile(self, topo, monkeypatch, frames, **kw):
        from jax.sharding import Mesh

        from blit.parallel import mesh as M

        # What ``auto`` resolves to on a chip; nothing runs.
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = Mesh(np.asarray(topo.devices).reshape(1, self.NBANK),
                    (M.BAND_AXIS, M.BANK_AXIS))

        def spec(shape, dtype, rule):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                        sharding=M.sharding_for(mesh, rule))

        return M.band_stream.lower(
            spec((1, self.NBANK, self.NCH, (NTAP - 1) * NFFT), "int32",
                 "filter_state"),
            spec((1, self.NBANK, self.NCH, frames * NFFT), "int32",
                 "voltages"),
            spec((NTAP, NFFT), "float32", "replicated"),
            mesh=mesh, nfft=NFFT, ntap=NTAP, stokes="I", **kw).compile()

    def _held(self, compiled):
        """Bytes one chip holds while the program runs (the donated tail
        counted once)."""
        m = compiled.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)

    @pytest.mark.parametrize("frames", [2, 1], ids=["window", "last-window"])
    def test_the_carried_window_has_no_collective_and_fits(
            self, v5e_2x2, monkeypatch, frames):
        compiled = self._compile(v5e_2x2, monkeypatch, frames, nint=1,
                                 stitch=False, despike_nfpc=0)
        text = compiled.as_text()
        assert ch.last_kernel_plan()["pfb_kernel"] == "fused1"
        assert ch.last_kernel_plan()["tail_kernel"] == "tail2_detect"
        assert text.count("tpu_custom_call") >= 2
        assert "all-gather" not in text and "all_gather" not in text
        m = compiled.memory_analysis()
        # The tail is donated: the next tail takes its place (0.75 GiB).
        tail = self.NCH * (NTAP - 1) * NFFT * 4
        assert m.alias_size_in_bytes == tail
        # Beside it a chip keeps the next window's samples, the window
        # before's product and its accumulator: 1.5 GiB of a 16 GB chip.
        assert self._held(compiled) + 1.5 * 2 ** 30 < self.HBM

    def test_the_stitched_window_gathers_and_fits(self, v5e_2x2,
                                                  monkeypatch):
        # band4.hires: nint 1 divides the window, every window stitches.
        compiled = self._compile(v5e_2x2, monkeypatch, 2, nint=1,
                                 stitch=True, despike_nfpc=NFFT)
        text = compiled.as_text()
        assert "all-gather" in text or "all_gather" in text
        # Two windows in flight: the one before's 2 GiB gathered band,
        # and the next one's samples.
        assert self._held(compiled) + 2.5 * 2 ** 30 < self.HBM


class TestThreeLegWindowCompilesForTheHost:
    """``band4.rawspec3``'s window (ISSUE 40: ``blit scan --nfft
    1048576,8,1024 --nint 51,128,3072 --window-frames 2``) COMPILED for
    ``v5e:2x2`` at its own shape — per chip 64 ch x 2 frames x 2^20 words
    put once, three legs' programs on them (``mesh.band_programs``), each
    leg's fold (``band_carry``) and the stitch of the rows a window closes
    — by the chip's own compiler.  The programs run one after the other,
    so each has to fit beside what STAYS on a chip from window to window:
    the three filter states, the three accumulators, the window's words
    and the power a leg has written for its fold."""

    NCH, NBANK, HBM = 64, 4, 15.75 * 2 ** 30
    LEGS = (("band_stream", NFFT, 51), ("band_stream_0001", 8, 128),
            ("band_stream_0002", 1024, 3072))
    FRAMES = 2  # of 2^20: the window

    def _mesh(self, topo, monkeypatch):
        from jax.sharding import Mesh

        from blit.parallel import mesh as M

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mesh = Mesh(np.asarray(topo.devices).reshape(1, self.NBANK),
                    (M.BAND_AXIS, M.BANK_AXIS))

        def spec(shape, dtype, rule):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                        sharding=M.sharding_for(mesh, rule))

        return M, mesh, spec

    def _leg(self, topo, monkeypatch, name, nfft, nint, head=False):
        """``(program, fold)`` compiled: the leg's window step (or its
        head step over the 3 x 2^20-word head) and the fold of what it
        wrote."""
        M, mesh, spec = self._mesh(topo, monkeypatch)
        lanes = ch.lanes_block(nfft, nint)
        kw = dict(mesh=mesh, nfft=nfft, ntap=NTAP, stokes="I", nint=1,
                  stitch=False, despike_nfpc=0, **(
                      {"lanes": lanes} if lanes else {}))
        block = (1, self.NBANK, self.NCH)
        coeffs = spec((NTAP, nfft), "float32", "replicated")
        step, first = M.band_programs(name)
        if head:
            frames = (NTAP - 1) * (NFFT - nfft) // nfft
            program = first.lower(
                spec(block + ((NTAP - 1) * NFFT,), "int32", "filter_state"),
                coeffs, **kw).compile()
        else:
            frames = self.FRAMES * NFFT // nfft
            program = step.lower(
                spec(block + ((NTAP - 1) * nfft,), "int32", "filter_state"),
                spec(block + (self.FRAMES * NFFT,), "int32", "voltages"),
                coeffs, **kw).compile()
        nslots, at = self.NBANK * self.NCH, jax.ShapeDtypeStruct(
            (), jnp.int32)
        if lanes:
            m = lanes // nfft
            fold = M.band_carry.lower(
                spec((1, nslots // 8, 1, nfft, 8), "float32", "lanes_acc"),
                spec((1, m, nslots // 8, 1, nfft, 8, -(-frames // m)),
                     "float32", "lanes_power"),
                at, mesh=mesh, nint=nint, lanes=True,
                nframes=frames).compile()
        else:
            fold = M.band_carry.lower(
                spec((1, 1, nslots * nfft), "float32", "integration_acc"),
                spec((1, frames, 1, nslots * nfft), "float32",
                     "filterbank_sharded"),
                at, mesh=mesh, nint=nint).compile()
        return program, fold

    @staticmethod
    def _held(compiled):
        m = compiled.memory_analysis()
        return (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)

    def _resident(self):
        """Bytes a chip keeps whatever program runs: three filter states,
        three accumulators (one bank's row each), the window in flight
        and the next one's words."""
        word = 4 * self.NCH
        return (sum((NTAP - 1) * f * word + f * word for _, f, _ in
                    self.LEGS) + 2 * self.FRAMES * NFFT * word)

    @pytest.mark.parametrize("leg", range(3), ids=["0000", "0001", "0002"])
    def test_a_legs_window_step_and_fold_fit(self, v5e_2x2, monkeypatch,
                                             leg):
        program, fold = self._leg(v5e_2x2, monkeypatch, *self.LEGS[leg])
        for compiled in (program, fold):
            text = compiled.as_text()
            assert "all-gather" not in text and "all_gather" not in text
            # Beside what stays and the power the leg before left for
            # its fold (0.5 GiB a leg: 2^21 words a channel, float32).
            assert (self._held(compiled) + self._resident()
                    + 2 ** 29) < self.HBM
        if leg == 0:
            assert program.as_text().count("tpu_custom_call") >= 2

    @pytest.mark.parametrize("leg", [1, 2], ids=["0001", "0002"])
    def test_a_small_legs_head_step_fits(self, v5e_2x2, monkeypatch, leg):
        """The stream's head (3 x 2^20 words a channel: the 0000 leg's
        filter state) is DATA to the small legs: 393 213 frames of 8 and
        3069 of 1024, reduced before the head's owner takes it."""
        program, fold = self._leg(v5e_2x2, monkeypatch, *self.LEGS[leg],
                                  head=True)
        for compiled in (program, fold):
            assert self._held(compiled) + self._resident() < self.HBM

    @pytest.mark.parametrize("rows, nfft", [(1, NFFT), (2048, 8), (3071, 8),
                                            (1, 1024)])
    def test_the_stitch_of_each_products_rows_gathers_and_fits(
            self, v5e_2x2, monkeypatch, rows, nfft):
        M, mesh, spec = self._mesh(v5e_2x2, monkeypatch)
        compiled = M.stitch_despike.lower(
            spec((1, rows, 1, self.NBANK * self.NCH * nfft), "float32",
                 "filterbank_sharded"), mesh=mesh,
            despike_nfpc=nfft).compile()
        text = compiled.as_text()
        assert "all-gather" in text or "all_gather" in text
        assert self._held(compiled) + self._resident() + 2 ** 29 < self.HBM


class TestSmallNfftLegCompilesForTheChip:
    """``rawspec3.hires51``'s ``nfft`` 8 leg (``channelize_lanes`` through
    ``leg_programs``, then the lanes fold) COMPILED for one chip of
    ``v5e:2x2`` at the cell's shape, a 32-channel group of 2^23 words in
    blocks of 1024: the pin that keeps the plane assembly of PR 34 from
    coming back (PERF.md section 6, PR 35)."""

    CB, WORDS, NFFT8, NINT = 32, 1 << 23, 8, 128

    def _spec(self, topo):
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topo.devices[0])
        return lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, jnp.dtype(dtype), sharding=chip)

    def test_the_step_assembles_no_planes_and_holds_no_more(self, v5e_2x2):
        spec = self._spec(v5e_2x2)
        lanes = ch.lanes_block(self.NFFT8, self.NINT)
        assert lanes == 1024
        compiled = ch.leg_programs("channelize_0001")[0].lower(
            spec((self.CB, (NTAP - 1) * self.NFFT8), "int32"),
            spec((self.CB, self.WORDS), "int32"),
            spec((NTAP, self.NFFT8), "float32"),
            nfft=self.NFFT8, ntap=NTAP, stokes="I", lanes=lanes).compile()
        m = compiled.memory_analysis()
        gib = 2 ** 30
        assert m.argument_size_in_bytes // gib == 1
        assert m.output_size_in_bytes // gib == 1
        # The parent's account (PR 34): 3.169 GiB of temporaries.
        assert m.temp_size_in_bytes <= 3.17 * gib
        # A stack of planes that are one SUBLANE of each tile is compiled
        # as T(1,128) planes copied a sublane at a time into the (8,128)
        # tiles: half the leg's seconds on the chip, until PR 35.
        sublane_copies = [
            line.strip()[:120] for line in compiled.as_text().splitlines()
            if "dynamic-update-slice" in line and "T(1,128)" in line]
        assert not sublane_copies

    def test_the_fold_reads_the_power_where_the_leg_wrote_it(self, v5e_2x2):
        spec = self._spec(v5e_2x2)
        slabs, c = self.CB // 8, 8
        groups = self.WORDS // (self.NFFT8 * self.NINT)
        m = ch.integrate_carry.lower(
            spec((self.NINT, slabs, 1, self.NFFT8, c, groups), "float32"),
            spec((slabs, 1, self.NFFT8, c), "float32"), spec((), "int32"),
            nint=self.NINT, nframes=self.WORDS // self.NFFT8,
            lanes=True).compile().memory_analysis()
        # No position-major copy of the 1 GiB of power in front of the
        # fold (the parent's account: 1.0 GiB of temporaries).
        assert m.argument_size_in_bytes // 2 ** 30 == 1
        assert m.temp_size_in_bytes < 2 ** 26


class TestNfft1024PathCompilesForTheChip:
    """``bank.lowres``'s program and ``rawspec3.hires51``'s ``nfft`` 1024
    leg (``channelize``'s XLA path through ``leg_programs``) COMPILED for
    one chip of ``v5e:2x2`` at a reduced shape, 16 channels x 256 frames:
    the pin that keeps the re-tiling of the float32 samples from coming
    back (frames on the sublanes: two bare ``reshape``s and a fusion of
    misaligned slices, 69 % of the chip's seconds; PERF.md section 6,
    PR 37)."""

    CB, FRAMES, N = 16, 256, 1024

    def _entry_moves(self, compiled):
        """``(bytes, line)`` of every bare ``reshape`` and ``copy`` of a
        float32 array in the compiled program's entry computation."""
        import re

        text = compiled.as_text()
        moves = []
        for line in text[text.index("ENTRY"):].splitlines():
            m = re.match(
                r"\s*(?:ROOT )?%?[\w.\-]+ = f32\[([\d,]*)\]\S* "
                r"(reshape|copy)\(", line)
            if m:
                size = 4 * int(np.prod([int(d) for d in
                                        m.group(1).split(",") if d]))
                moves.append((size, line.strip()[:100]))
        return moves

    def _compile(self, topo, name, **kw):
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(topo.devices[0])

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                        sharding=chip)

        compiled = ch.leg_programs(name)[0].lower(
            spec((self.CB, (NTAP - 1) * self.N), "int32"),
            spec((self.CB, self.FRAMES * self.N), "int32"),
            spec((NTAP, self.N), "float32"),
            nfft=self.N, ntap=NTAP, stokes="I", fft_method="matmul",
            pfb_kernel="xla",  # what the cells' thousands of frames get
            **kw).compile()
        return compiled

    def test_the_integrating_program_moves_no_float32_samples(
            self, v5e_2x2):
        compiled = self._compile(v5e_2x2, "channelize_stream",
                                 nint=self.FRAMES)
        words = self.CB * self.FRAMES * self.N * 4
        big = [m for m in self._entry_moves(compiled) if m[0] >= words]
        assert not big, big
        # Temporaries: 2.07 times the words until PR 36, 1.04 now.
        assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * words

    def test_the_per_frame_leg_moves_only_its_product(self, v5e_2x2):
        # ``nint`` 1: the power leaves as the product, whose ``nif`` 1
        # axis re-tiles it once on the way out (ROADMAP A2.2a); nothing
        # larger moves, and not the float32 samples (four planes of it).
        compiled = self._compile(v5e_2x2, "channelize_0002")
        power = self.CB * self.FRAMES * self.N * 4
        big = [m for m in self._entry_moves(compiled) if m[0] > power]
        assert not big, big


class TestTheHiresProgramMovesNothingBetweenItsKernels:
    """The hi-res program (``fused1`` + ``tail2_detect``; every hi-res
    cell) COMPILED for ``v5e:2x2`` at the cells' own shapes — one chip a
    32-channel group of 3 + 8 frames through ``channelize_stream``, four
    chips 64 channels of 3 + 2 frames each through ``band_stream`` — by
    the chip's own compiler: what crosses a kernel's boundary crosses it
    in the layout the other side holds.  Until ISSUE 46 XLA wrote the
    gross words four times in front of ``fused1`` (``concat`` + int8
    bitcast, the int8 packed back, a ``bitcast-convert``, a re-tiling
    ``copy``) and each float32 plane of stage-1 spectra twice behind it:
    63 % of the chip's seconds in ``rawspec.hires51`` and 10.0 GiB of
    temporaries (PERF.md section 6, PR 46).  The ENTRY computation's op
    names are the ones a device trace's ``breakdown`` carries."""

    NCH, FR, NBANK = 32, 8, 4
    BAND_NCH, BAND_FR = 64, 2
    TEMP_GIB, BAND_TEMP_GIB = 5.0, 2.25  # what the compiler reads now
    MOVES = ("copy", "fusion", "transpose", "reshape", "bitcast-convert",
             "concatenate", "convert", "pad")

    @staticmethod
    def _entry(compiled):
        """``(name, dtype, bytes, opcode)`` of every op of the compiled
        program's ENTRY computation that yields ONE array."""
        import re

        text = compiled.as_text()
        ops, outputs = [], ()
        for line in text[text.index("ENTRY"):].splitlines():
            if line.lstrip().startswith("ROOT"):  # what the program yields
                outputs = re.findall(r"%([\w.\-]+)",
                                     line[line.rindex("("):])
            m = re.match(
                r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                r"([\w\-]+)\(", line)
            if m:
                name, dtype, dims, opcode = m.groups()
                size = int(np.prod([int(d) for d in dims.split(",") if d]))
                ops.append((name, dtype,
                            size * jnp.dtype(
                                {"s32": "int32", "u32": "uint32",
                                 "s8": "int8", "u8": "uint8",
                                 "f32": "float32", "bf16": "bfloat16",
                                 "pred": "bool"}.get(dtype, "int32")
                            ).itemsize, opcode))
        return [op for op in ops if op[0] not in outputs]

    @pytest.fixture(scope="class")
    def one_chip(self, v5e_2x2):
        from jax.sharding import SingleDeviceSharding

        chip = SingleDeviceSharding(v5e_2x2.devices[0])

        def on_chip(shape, dtype):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                        sharding=chip)

        was = jax.default_backend
        jax.default_backend = lambda: "tpu"  # what ``auto`` resolves to
        try:
            compiled = ch.channelize_stream.lower(
                on_chip((self.NCH, (NTAP - 1) * NFFT), "int32"),
                on_chip((self.NCH, self.FR * NFFT), "int32"),
                on_chip((NTAP, NFFT), "float32"),
                nfft=NFFT, ntap=NTAP, stokes="I").compile()
            plan = ch.last_kernel_plan()
        finally:
            jax.default_backend = was
        assert (plan["pfb_kernel"], plan["tail_kernel"]) == (
            "fused1", "tail2_detect")
        # (program, bytes of the smaller run of words — here the filter
        # state —, bytes of one plane of spectra)
        return (compiled, self.NCH * (NTAP - 1) * NFFT * 4,
                self.NCH * 2 * self.FR * NFFT * 4)

    @pytest.fixture(scope="class")
    def band(self, v5e_2x2):
        from jax.sharding import Mesh

        from blit.parallel import mesh as M

        mesh = Mesh(np.asarray(v5e_2x2.devices).reshape(1, self.NBANK),
                    (M.BAND_AXIS, M.BANK_AXIS))

        def spec(shape, dtype, rule):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                        sharding=M.sharding_for(mesh, rule))

        was = jax.default_backend
        jax.default_backend = lambda: "tpu"
        try:
            compiled = M.band_stream.lower(
                spec((1, self.NBANK, self.BAND_NCH, (NTAP - 1) * NFFT),
                     "int32", "filter_state"),
                spec((1, self.NBANK, self.BAND_NCH, self.BAND_FR * NFFT),
                     "int32", "voltages"),
                spec((NTAP, NFFT), "float32", "replicated"),
                mesh=mesh, nfft=NFFT, ntap=NTAP, stokes="I", nint=1,
                stitch=False, despike_nfpc=0).compile()
            plan = ch.last_kernel_plan()
        finally:
            jax.default_backend = was
        assert (plan["pfb_kernel"], plan["tail_kernel"]) == (
            "fused1", "tail2_detect")
        return (compiled, self.BAND_NCH * self.BAND_FR * NFFT * 4,
                self.BAND_NCH * 2 * self.BAND_FR * NFFT * 4)

    def _check_words(self, program):
        compiled, words, _ = program
        ops = self._entry(compiled)
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2
        # Every op that yields an integer array as large as the smaller
        # run of words and is not the program's own output (the next
        # filter state: a slice of the body, or at a window shorter than
        # the state the old one's end and the body, written once).
        big = [op for op in ops if op[1] in ("s32", "u32", "s8", "u8")
               and op[2] >= words and op[3] in self.MOVES]
        assert not [op for op in big
                    if op[3] in ("bitcast-convert", "fusion", "convert")], big
        assert len(big) <= 1, big

    def _check_planes(self, program):
        compiled, _, plane = program
        ops = self._entry(compiled)
        # Nothing writes a float32 array as large as a plane of stage-1
        # spectra but the kernel that makes it (two ``copy`` a plane
        # until ISSUE 46; the issue allows one, step 2 leaves none).
        big = [op for op in ops if op[1] in ("f32", "bf16")
               and op[2] >= plane and op[3] in self.MOVES]
        assert not big, big

    def test_nothing_writes_the_words_in_front_of_fused1(self, one_chip):
        self._check_words(one_chip)

    def test_nothing_writes_a_plane_between_the_kernels(self, one_chip):
        self._check_planes(one_chip)

    def test_the_temporaries_are_the_two_planes_and_the_power(self,
                                                              one_chip):
        m = one_chip[0].memory_analysis()
        # 10.0 GiB until ISSUE 46.  Now: two planes of 2 GiB and the
        # power's 1 GiB before its last swap; 10 % over.
        assert m.temp_size_in_bytes < 1.1 * self.TEMP_GIB * 2 ** 30
        assert m.alias_size_in_bytes == self.NCH * (NTAP - 1) * NFFT * 4

    def test_bfloat16_stages_compile_for_the_chip_too(self, v5e_2x2,
                                                      monkeypatch):
        # Cross-lowering (``jax.export``) does not run Mosaic's compiler:
        # a strided load of 16-bit rows lowers and then fails on the chip
        # (``Strided load with non 32-bit data``, my chip run, PR 46), so
        # bfloat16 spectra keep the re-tiled (f1, f2, f3) view.
        from jax.sharding import SingleDeviceSharding

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        chip = SingleDeviceSharding(v5e_2x2.devices[0])

        def on_chip(shape, dtype):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                        sharding=chip)

        compiled = ch.channelize_stream.lower(
            on_chip((8, (NTAP - 1) * NFFT), "int32"),
            on_chip((8, 2 * NFFT), "int32"),
            on_chip((NTAP, NFFT), "float32"),
            nfft=NFFT, ntap=NTAP, stokes="I", dtype="bfloat16").compile()
        assert ch.last_kernel_plan()["tail_kernel"] == "tail2_detect"
        assert compiled.as_text().count("tpu_custom_call") >= 2

    def test_the_bands_per_chip_program_moves_nothing_either(self, band):
        self._check_words(band)
        self._check_planes(band)

    def test_the_bands_temporaries(self, band):
        m = band[0].memory_analysis()
        # Per chip: 64 channels x 2 frames, planes of 1 GiB (5.0 GiB
        # until ISSUE 46).
        assert m.temp_size_in_bytes < 1.1 * self.BAND_TEMP_GIB * 2 ** 30


class TestKernelRequestsOffTpuAndCpu:
    def test_pallas_interpret_names_its_backends(self):
        assert device.pallas_interpret("tpu") is False
        assert device.pallas_interpret("cpu") is True
        with pytest.raises(ValueError, match="not supported on backend"):
            device.pallas_interpret("gpu")

    def test_requested_kernel_raises_on_another_backend(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        v = jnp.zeros((1, 5 * 256, 2, 2), jnp.int8)
        h = jnp.asarray(ch.pfb_coeffs(4, 256))
        with pytest.raises(ValueError, match="not supported on backend"):
            ch.channelize(v, h, nfft=256, pfb_kernel="pallas",
                          stokes="XX")  # a signature no other test traces
        x = jnp.zeros((2, 256), jnp.float32), jnp.zeros((2, 256), jnp.float32)
        with pytest.raises(ValueError, match="not supported on backend"):
            D.dft(*x, use_pallas=True)


class TestReducerOnADeviceWithAMemoryLimit:
    def _recording(self, tmp_path, nchan=16, nfft=1024):
        from blit.testing import synth_raw

        path = str(tmp_path / "a.raw")
        synth_raw(path, nblocks=4, obsnchan=nchan,
                  ntime_per_block=20 * nfft, seed=1, tone_chan=3)
        return path

    def test_chunks_split_into_channel_groups(self, tmp_path, monkeypatch):
        import blit.pipeline as P

        path = self._recording(tmp_path)
        kw = dict(nfft=1024, chunk_frames=16)
        _, whole = P.RawReducer(**kw).reduce(path)
        # A device that holds ~12 MB: the compiler's account of one
        # 16-channel dispatch does not fit beside the resident products.
        monkeypatch.setattr(P, "hbm_bytes_limit", lambda: 12_000_000)
        red = P.RawReducer(**kw)
        _, grouped = red.reduce(path)
        assert red._channel_block((16, 16 * 1024, 2, 2)) < 16
        # Grouping changes no arithmetic; a backend may round a
        # differently-batched program differently in the last bit.
        np.testing.assert_allclose(grouped, whole, rtol=1e-6, atol=1e-6)

    def test_carried_integration_is_sized_and_grouped_once(self, tmp_path,
                                                           monkeypatch):
        # nint 24 in 16-frame chunks: the integration is carried, the
        # 8-frame flush chunk would fit more channels per dispatch than a
        # full one, and the accumulators keep the first chunk's groups
        # (my chip run, PR 26: 32 channels against 64 at nfft 2^20).
        import blit.pipeline as P

        path = self._recording(tmp_path)
        kw = dict(nfft=1024, nint=24, chunk_frames=16)
        _, whole = P.RawReducer(**kw).reduce(path)
        assert whole.shape[0] == 3  # 77 frames: three rows, 5 dropped
        # ~8 MB (12 until PR 37 halved the XLA path's temporaries).
        monkeypatch.setattr(P, "hbm_bytes_limit", lambda: 8_000_000)
        red = P.RawReducer(**kw)
        _, grouped = red.reduce(path)
        full = red._channel_block((16, 16 * 1024, 2, 2))
        assert full < 16 and full < red._channel_block((16, 8 * 1024, 2, 2))
        np.testing.assert_allclose(grouped, whole, rtol=1e-6, atol=1e-6)

    def test_a_chunk_that_cannot_fit_raises(self, tmp_path, monkeypatch):
        import blit.pipeline as P

        path = self._recording(tmp_path)
        monkeypatch.setattr(P, "hbm_bytes_limit", lambda: 3_000_000)
        with pytest.raises(MemoryError, match="device memory"):
            P.RawReducer(nfft=1024, chunk_frames=16).reduce(path)

    def test_no_limit_reported_means_one_dispatch(self):
        import blit.pipeline as P

        red = P.RawReducer(nfft=1024, chunk_frames=16)
        assert red._channel_block((16, 16 * 1024, 2, 2)) == 16

    def test_chip_path_requires_the_native_reader(self, tmp_path,
                                                  monkeypatch):
        from blit.io.guppi import GuppiRaw, require_native_reader

        raw = GuppiRaw(self._recording(tmp_path), native=False)
        require_native_reader(raw)  # the CPU: the memmap reader is fine
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="native GUPPI reader"):
            require_native_reader(raw)


class TestCompileCachePlacement:
    def test_variable_set_means_nothing_is_set_in_code(self, monkeypatch,
                                                       tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        assert device.use_compile_cache() == str(tmp_path)
        assert calls == []

    def test_unset_means_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a, **k: calls.append(a))
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(
            device.__file__)))
        want = os.path.join(checkout, ".jax_cache")
        assert device.use_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]
