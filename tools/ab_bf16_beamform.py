"""Interleaved on-chip A/B: beamforming from bf16-RESIDENT voltage planes
vs the production f32 planes (VERDICT r4 item 6: "bf16 collectives:
measure or bury").

Why residency is the variable: the TPU's default matmul precision already
multiplies f32 einsum operands at bf16 (measured — a plain f32
dot_general shows bf16-scale error vs NumPy), so casting inside the jit
changes nothing (tools/ab_fx64.py variant C: parity).  The lever is
HBM-resident bf16 operands — half the voltage read traffic and half the
ICI psum bytes.  Antenna voltages come from 8-bit RAW samples, whose
integer values bf16's 8-bit mantissa represents EXACTLY, so bf16
residency of the data plane is lossless for this workload; only the
weight phasors round.

  A  f32 planes + production beamform
  B  bf16 planes + bf16 step (psum in bf16, detection in f32)

Reports time/call and f32-equivalent input GB/s (same voltage content on
both sides), plus max relative error of the detected power.

Run on the chip:  python tools/ab_bf16_beamform.py [nant nbeam nchan ntime nint rounds reps]
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    nant = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    nbeam = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    nchan = int(sys.argv[3]) if len(sys.argv) > 3 else 64
    ntime = int(sys.argv[4]) if len(sys.argv) > 4 else 8192
    nint = int(sys.argv[5]) if len(sys.argv) > 5 else 8
    rounds = int(sys.argv[6]) if len(sys.argv) > 6 else 3
    reps = int(sys.argv[7]) if len(sys.argv) > 7 else 48
    npol = 2

    from blit.device import use_compile_cache

    use_compile_cache()

    from blit.ops.channelize import integrate
    from blit.parallel import beamform as B
    from blit.parallel import mesh as M

    mesh = M.make_mesh(1, 1)
    rng = np.random.default_rng(0)
    # 8-bit-quantized voltages, as RAW recordings deliver them: the int
    # values are exactly representable in bf16 (8 mantissa bits).
    v8 = rng.integers(-127, 128, (2, nant, nchan, ntime, npol)).astype(
        np.float32
    )
    wr, wi = B.delay_weights_planar(
        jnp.asarray(rng.uniform(0, 1e-9, (nbeam, nant))),
        jnp.asarray(np.linspace(1e9, 1.1e9, nchan)),
    )
    f32eq_bytes = 2 * v8[0].nbytes  # same content both sides

    vp32 = jax.device_put(
        (v8[0], v8[1]), B.antenna_sharding(mesh)
    )
    vp16 = jax.device_put(
        (v8[0].astype(jnp.bfloat16), v8[1].astype(jnp.bfloat16)),
        B.antenna_sharding(mesh),
    )
    wp32 = jax.device_put((np.asarray(wr), np.asarray(wi)),
                          B.weight_sharding(mesh))
    wp16 = jax.device_put(
        (np.asarray(wr).astype(jnp.bfloat16),
         np.asarray(wi).astype(jnp.bfloat16)),
        B.weight_sharding(mesh),
    )
    jax.block_until_ready((vp32, vp16, wp32, wp16))

    def fa(vp, wp):
        return B.beamform(vp, wp, mesh=mesh, nint=nint)

    @jax.jit
    def fb(vp, wp):
        vr, vi = vp
        wr, wi = wp

        def step(vr, vi, wr, wi):
            rr = jnp.einsum("bac,actp->bctp", wr, vr)
            ii = jnp.einsum("bac,actp->bctp", wi, vi)
            ri = jnp.einsum("bac,actp->bctp", wr, vi)
            ir = jnp.einsum("bac,actp->bctp", wi, vr)
            br, bi = rr - ii, ri + ir  # bf16 partial beams
            br, bi = jax.lax.psum((br, bi), "bank")  # bf16 on the wire
            br = br.astype(jnp.float32)
            bi = bi.astype(jnp.float32)
            return integrate(br**2 + bi**2, nint)

        return jax.shard_map(
            step, mesh=mesh,
            in_specs=(P("bank"), P("bank"), P(None, "bank"),
                      P(None, "bank")),
            out_specs=P(), check_vma=False,
        )(vr, vi, wr, wi)

    t0 = time.time()
    pa = np.asarray(fa(vp32, wp32))
    pb = np.asarray(fb(vp16, wp16))
    err = np.abs(pb - pa) / np.maximum(np.abs(pa), 1e-6)
    print(f"warmup (incl. compile) {time.time() - t0:.1f}s  "
          f"detected-power max rel err {err.max():.2e} "
          f"mean {err.mean():.2e}", flush=True)

    def block(f, vp, wp):
        t0 = time.time()
        out = None
        for _ in range(reps):
            out = jnp.sum(f(vp, wp))
        float(out)
        return reps * f32eq_bytes / (time.time() - t0) / 1e9

    ga, gb = [], []
    for r in range(rounds):
        ga.append(block(fa, vp32, wp32))
        gb.append(block(fb, vp16, wp16))
        print(f"round {r}: A(f32) {ga[-1]:.2f}  B(bf16) {gb[-1]:.2f} "
              "GB/s(f32-eq)", flush=True)
    print(f"A f32 : {min(ga):.2f}-{max(ga):.2f} (median {np.median(ga):.2f})")
    print(f"B bf16: {min(gb):.2f}-{max(gb):.2f} (median {np.median(gb):.2f})")
    print(f"median ratio B/A: {np.median(gb) / np.median(ga):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
