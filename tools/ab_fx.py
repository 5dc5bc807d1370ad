"""Interleaved on-chip A/B of FX-correlator X-engine variants.

Same interleaving + single-fetch methodology as tools/ab_channelize.py
(cross-process comparisons drown in run-to-run spread).  Compares the whole jitted correlate
call — input GB/s — with the X-engine computed as:

  A  split4   four (nant·npol)² einsums over (re, im) pairs
  B  stacked  one (2·nant·npol)² einsum over the re/im-stacked operand

Run on the chip:  python tools/ab_fx.py [nant nchan nfft nblk rounds reps]
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
    nant = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    nchan = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    nfft = int(sys.argv[3]) if len(sys.argv) > 3 else 512
    nblk = int(sys.argv[4]) if len(sys.argv) > 4 else 64
    rounds = int(sys.argv[5]) if len(sys.argv) > 5 else 3
    reps = int(sys.argv[6]) if len(sys.argv) > 6 else 48
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

    # Variant A IS the production kernel — imported, not copied, so this
    # A/B keeps describing what ships.
    xengine_split4 = _xengine_planar

    def xengine_stacked(sr, si):
        s2 = jnp.concatenate([sr, si], axis=2)
        big = jnp.einsum("acptf,bcqtf->abcfpq", s2, s2)
        rr = big[..., :npol, :npol]
        ii = big[..., npol:, npol:]
        ri = big[..., :npol, npol:]
        ir = big[..., npol:, :npol]
        return rr + ii, ir - ri

    def make(xe):
        @jax.jit
        def f(a, b):
            sr, si = f_engine_planar(a, b, hj)
            visr, visi = xe(sr, si)
            return jnp.sum(visr) + jnp.sum(visi)

        return f

    fa, fb = make(xengine_split4), make(xengine_stacked)
    t0 = time.time()
    ca, cb = float(fa(vr, vi)), float(fb(vr, vi))
    print(f"warmup (incl. compile) {time.time() - t0:.1f}s "
          f"checksum delta {abs(ca - cb) / max(abs(ca), 1e-9):.2e}",
          flush=True)

    def block(f):
        t0 = time.time()
        out = None
        for _ in range(reps):
            out = f(vr, vi)
        float(out)
        return reps * nbytes / (time.time() - t0) / 1e9

    ga, gb = [], []
    for r in range(rounds):
        ga.append(block(fa))
        gb.append(block(fb))
        print(f"round {r}: A {ga[-1]:.2f}  B {gb[-1]:.2f} GB/s", flush=True)
    print(f"A split4:  {min(ga):.2f}-{max(ga):.2f} GB/s")
    print(f"B stacked: {min(gb):.2f}-{max(gb):.2f} GB/s")
    print(f"median ratio B/A: {np.median(gb) / np.median(ga):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
