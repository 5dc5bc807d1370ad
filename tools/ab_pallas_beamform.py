"""Pallas fused beamform+detect prototype vs the production einsum path,
interleaved on-chip (round 5: the beamform leg runs ~84 GB/s f32-eq bf16
against a ~0.6 GB fully-fused minimum — the einsum path materializes the
(nbeam, nchan, ntime, npol) beam planes in HBM twice, then reads them
back for detection).

Kernel: grid (nchan, ntime tiles).  Per step it holds the chan's weights
(nbeam, nant) and one time tile of voltages (nant, npol, T) in VMEM,
forms the four real products as dot_generals, squares, and integrates by
``nint`` via a static 0/1 block-diagonal matmul on the MXU (reshaping the
lane axis is a mosaic no-go; a matmul against S (T, T/nint) is not).
Beam planes never exist in HBM — voltages are read once, the integrated
power written once.

Layouts: voltages (nchan, nant, npol, ntime) [pol before time, lane=T],
weights (nchan, nbeam, nant), output (nchan, nbeam, npol, ntime/nint) —
packed, chan-major; the public API's (nbeam, nchan, t, npol) is one
cheap transpose of the SMALL output if a consumer needs it.

Run on the chip:
  python tools/ab_pallas_beamform.py [nant nbeam nchan ntime nint rounds reps tile dtype]
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_fused(nint, tile):
    """The SHIPPED kernel (blit/ops/pallas_beamform.py), not a prototype
    copy: re-running this tool keeps measuring what
    ``beamform(layout="chan")`` dispatches."""
    from blit.ops.pallas_beamform import fused_beamform_detect

    def fused(vr, vi, wr, wi):
        return fused_beamform_detect(vr, vi, wr, wi, nint=nint, tile=tile)

    return fused


def main() -> int:
    nant = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    nbeam = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    nchan = int(sys.argv[3]) if len(sys.argv) > 3 else 64
    ntime = int(sys.argv[4]) if len(sys.argv) > 4 else 8192
    nint = int(sys.argv[5]) if len(sys.argv) > 5 else 8
    rounds = int(sys.argv[6]) if len(sys.argv) > 6 else 3
    reps = int(sys.argv[7]) if len(sys.argv) > 7 else 48
    # Default follows the kernel's output-lane rule (tile = nint*128);
    # DESIGN.md's numbers were measured at nint=8 -> 1024.
    tile = int(sys.argv[8]) if len(sys.argv) > 8 else nint * 128
    dtype = sys.argv[9] if len(sys.argv) > 9 else "bfloat16"
    npol = 2

    from blit.device import use_compile_cache

    use_compile_cache()

    from blit.parallel import beamform as B
    from blit.parallel import mesh as M

    mesh = M.make_mesh(1, 1)
    rng = np.random.default_rng(0)
    v8 = rng.integers(-127, 128, (2, nant, nchan, ntime, npol)).astype(
        np.float32
    )
    wr, wi = B.delay_weights_planar(
        jnp.asarray(rng.uniform(0, 1e-9, (nbeam, nant))),
        jnp.asarray(np.linspace(1e9, 1.1e9, nchan)),
    )
    f32eq_bytes = 2 * v8[0].nbytes

    # Production path operands (API layout).
    vp = jax.device_put(
        (v8[0].astype(dtype), v8[1].astype(dtype)), B.antenna_sharding(mesh)
    )
    wp = jax.device_put((np.asarray(wr), np.asarray(wi)),
                        B.weight_sharding(mesh))

    # Kernel operands: (c, a, p, t) voltages, (c, b, a) weights.
    def pack_v(x):
        # host-side transpose: the kernel operands are materialized in
        # their packed layout (np.ascontiguousarray), not a lazy view.
        return jnp.asarray(np.ascontiguousarray(
            np.transpose(x, (1, 0, 3, 2))).astype(dtype))

    kvr, kvi = pack_v(v8[0]), pack_v(v8[1])
    kwr = jnp.asarray(np.ascontiguousarray(
        np.transpose(np.asarray(wr), (2, 0, 1))).astype(dtype))
    kwi = jnp.asarray(np.ascontiguousarray(
        np.transpose(np.asarray(wi), (2, 0, 1))).astype(dtype))
    jax.block_until_ready((vp, wp, kvr, kvi, kwr, kwi))

    fused = make_fused(nint, tile)

    def fa():
        return jnp.sum(B.beamform(vp, wp, mesh=mesh, nint=nint))

    def fb():
        return jnp.sum(fused(kvr, kvi, kwr, kwi))

    t0 = time.time()
    pa = np.asarray(B.beamform(vp, wp, mesh=mesh, nint=nint))
    pb = np.asarray(fused(kvr, kvi, kwr, kwi))
    # fused output (c, b, p, t/nint) -> API (b, c, t/nint, p)
    pb_api = np.transpose(pb, (1, 0, 3, 2))
    err = np.abs(pb_api - pa).max() / max(np.abs(pa).max(), 1e-9)
    print(f"warmup (incl. compile) {time.time() - t0:.1f}s  "
          f"max rel err vs production {err:.2e}", flush=True)
    assert err < 3e-2, err

    def block(f):
        t0 = time.time()
        out = None
        for _ in range(reps):
            out = f()
        float(out)
        return reps * f32eq_bytes / (time.time() - t0) / 1e9

    ga, gb = [], []
    for r in range(rounds):
        ga.append(block(fa))
        gb.append(block(fb))
        print(f"round {r}: A(einsum {dtype}) {ga[-1]:.2f}  "
              f"B(pallas tile={tile}) {gb[-1]:.2f} GB/s(f32-eq)", flush=True)
    print(f"A einsum: {min(ga):.2f}-{max(ga):.2f} (median {np.median(ga):.2f})")
    print(f"B pallas: {min(gb):.2f}-{max(gb):.2f} (median {np.median(gb):.2f})")
    print(f"median ratio B/A: {np.median(gb) / np.median(ga):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
