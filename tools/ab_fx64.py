"""Interleaved on-chip A/B of FX-correlator variants AT ARRAY SCALE
(nant=64 — VERDICT r4 item 1: the X-engine decision was made on nant=8
evidence; at 64 antennas the per-(chan, fine) matmul is 128², exactly
MXU-sized, and must be re-measured).

Same interleaving + single-fetch methodology as tools/ab_fx.py (never
compare across processes).

Variants (whole jitted F+X call, input GB/s; sum() sink is
layout-invariant so checksums cross-check the math):

  A  split4/standard   production: 4 einsums -> (a,b,c,f,p,q)
  B  split4/packed     4 einsums  -> (c,f,a,p,b,q) — skips the
                       visibility post-transpose XLA performs for the
                       standard layout (the roofline's 5x gap to the
                       4.47 ms analytic bound is layout traffic, not
                       MXU work)
  C  packed + bf16     B with spectra cast to bf16 before the X-engine
                       (MXU-native dots, f32 accumulation): halves the
                       X-engine's spectra read traffic

Run on the chip:  python tools/ab_fx64.py [nant nchan nfft nblk rounds reps]
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
    from blit.parallel.correlator import _xengine_planar, f_engine_planar

    rng = np.random.default_rng(0)
    shape = (nant, nchan, npol, ntime)
    vr = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    vi = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    hj = jnp.asarray(pfb_coeffs(ntap, nfft).astype(np.float32))
    nbytes = vr.nbytes + vi.nbytes

    def xengine_packed(sr, si):
        rr = jnp.einsum("acptf,bcqtf->cfapbq", sr, sr)
        ii = jnp.einsum("acptf,bcqtf->cfapbq", si, si)
        ir = jnp.einsum("acptf,bcqtf->cfapbq", si, sr)
        ri = jnp.einsum("acptf,bcqtf->cfapbq", sr, si)
        return rr + ii, ir - ri

    def xengine_packed_bf16(sr, si):
        sr = sr.astype(jnp.bfloat16)
        si = si.astype(jnp.bfloat16)
        kw = dict(preferred_element_type=jnp.float32)
        rr = jnp.einsum("acptf,bcqtf->cfapbq", sr, sr, **kw)
        ii = jnp.einsum("acptf,bcqtf->cfapbq", si, si, **kw)
        ir = jnp.einsum("acptf,bcqtf->cfapbq", si, sr, **kw)
        ri = jnp.einsum("acptf,bcqtf->cfapbq", sr, si, **kw)
        return rr + ii, ir - ri

    def make(xe):
        @jax.jit
        def f(a, b):
            sr, si = f_engine_planar(a, b, hj)
            visr, visi = xe(sr, si)
            return jnp.sum(visr) + jnp.sum(visi)

        return f

    fa = make(_xengine_planar)  # production
    fb = make(xengine_packed)
    fc = make(xengine_packed_bf16)
    t0 = time.time()
    ca, cb, cc = float(fa(vr, vi)), float(fb(vr, vi)), float(fc(vr, vi))
    print(f"warmup (incl. compile) {time.time() - t0:.1f}s", flush=True)
    print(f"checksum B/A delta {abs(cb - ca) / max(abs(ca), 1e-9):.2e}  "
          f"C/A delta {abs(cc - ca) / max(abs(ca), 1e-9):.2e}", flush=True)

    def block(f):
        t0 = time.time()
        out = None
        for _ in range(reps):
            out = f(vr, vi)
        float(out)
        return reps * nbytes / (time.time() - t0) / 1e9

    gs = {"A": [], "B": [], "C": []}
    for r in range(rounds):
        gs["A"].append(block(fa))
        gs["B"].append(block(fb))
        gs["C"].append(block(fc))
        print(f"round {r}: A {gs['A'][-1]:.2f}  B {gs['B'][-1]:.2f}  "
              f"C {gs['C'][-1]:.2f} GB/s", flush=True)
    for k, label in (("A", "split4/standard"), ("B", "split4/packed"),
                     ("C", "packed+bf16")):
        print(f"{k} {label:18s} {min(gs[k]):.2f}-{max(gs[k]):.2f} GB/s "
              f"(median {np.median(gs[k]):.2f})")
    print(f"median ratio B/A: {np.median(gs['B']) / np.median(gs['A']):.3f}  "
          f"C/A: {np.median(gs['C']) / np.median(gs['A']):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
