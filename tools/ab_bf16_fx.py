"""Interleaved on-chip A/B: bf16 stages in the packed-pallas FX correlator
at nant=64 (VERDICT r4 item 6, correlator half).

tools/ab_fx64.py measured in-jit bf16 casts at parity for the EINSUM
X-engine (no materialization boundary, so a cast changes no traffic).
The pallas path is different: the pack transpose materializes the
spectra between cast and kernel, so bf16 spectra halve that write, the
kernel's read, and its VMEM blocks.

  A  f32 spectra  -> pack -> pallas kernel (shipped round-5 path)
  B  bf16 spectra -> pack -> pallas kernel (dots accumulate f32)
  C  B + bf16-resident input voltages and bf16 FIR (maximal bf16 staging,
     mirroring the primary pipeline's bf16 stages — DESIGN.md §3/§8;
     8-bit RAW voltages are exact in bf16)

Accuracy is reported as max/mean relative error of visibilities vs A.

Run on the chip:  python tools/ab_bf16_fx.py [nant nchan nfft nblk rounds reps]
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    nant = int(sys.argv[1]) if len(sys.argv) > 1 else 64
    nchan = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    nfft = int(sys.argv[3]) if len(sys.argv) > 3 else 512
    nblk = int(sys.argv[4]) if len(sys.argv) > 4 else 64
    rounds = int(sys.argv[5]) if len(sys.argv) > 5 else 3
    reps = int(sys.argv[6]) if len(sys.argv) > 6 else 24
    ntap, npol = 4, 2
    ntime = nblk * nfft

    from blit.device import use_compile_cache

    use_compile_cache()

    from blit.ops.channelize import pfb_coeffs
    from blit.ops.pallas_xengine import xengine_packed
    from blit.parallel.correlator import f_engine_planar

    rng = np.random.default_rng(0)
    shape = (nant, nchan, npol, ntime)
    v8 = rng.integers(-40, 41, (2,) + shape).astype(np.float32)
    vr32 = jnp.asarray(v8[0])
    vi32 = jnp.asarray(v8[1])
    vr16 = jnp.asarray(v8[0].astype(jnp.bfloat16))
    vi16 = jnp.asarray(v8[1].astype(jnp.bfloat16))
    hj = jnp.asarray(pfb_coeffs(ntap, nfft).astype(np.float32))
    f32eq_bytes = 2 * vr32.nbytes

    @jax.jit
    def fa(a, b):
        sr, si = f_engine_planar(a, b, hj)
        return xengine_packed(sr, si)

    @jax.jit
    def fb(a, b):
        sr, si = f_engine_planar(a, b, hj)
        return xengine_packed(sr.astype(jnp.bfloat16),
                              si.astype(jnp.bfloat16))

    @jax.jit
    def fc(a, b):
        sr, si = f_engine_planar(a, b, hj.astype(jnp.bfloat16))
        return xengine_packed(sr.astype(jnp.bfloat16),
                              si.astype(jnp.bfloat16))

    t0 = time.time()
    va = [np.asarray(x) for x in fa(vr32, vi32)]
    vb = [np.asarray(x) for x in fb(vr32, vi32)]
    vc = [np.asarray(x) for x in fc(vr16, vi16)]
    scale = max(np.abs(va[0]).max(), np.abs(va[1]).max())

    def err(v):
        return max(np.abs(v[0] - va[0]).max(), np.abs(v[1] - va[1]).max()) / scale

    print(f"warmup (incl. compile) {time.time() - t0:.1f}s  "
          f"rel err B {err(vb):.2e}  C {err(vc):.2e}", flush=True)

    def block(f, a, b):
        t0 = time.time()
        out = None
        for _ in range(reps):
            vr, vi = f(a, b)
            out = jnp.sum(vr) + jnp.sum(vi)
        float(out)
        return reps * f32eq_bytes / (time.time() - t0) / 1e9

    gs = {"A": [], "B": [], "C": []}
    for r in range(rounds):
        gs["A"].append(block(fa, vr32, vi32))
        gs["B"].append(block(fb, vr32, vi32))
        gs["C"].append(block(fc, vr16, vi16))
        print(f"round {r}: A {gs['A'][-1]:.2f}  B {gs['B'][-1]:.2f}  "
              f"C {gs['C'][-1]:.2f} GB/s(f32-eq)", flush=True)
    for k, label in (("A", "f32 spectra"), ("B", "bf16 spectra"),
                     ("C", "bf16 input+FIR+spectra")):
        print(f"{k} {label:22s} {min(gs[k]):.2f}-{max(gs[k]):.2f} "
              f"(median {np.median(gs[k]):.2f})")
    print(f"median ratio B/A: {np.median(gs['B']) / np.median(gs['A']):.3f}  "
          f"C/A: {np.median(gs['C']) / np.median(gs['A']):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
