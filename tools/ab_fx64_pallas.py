"""Pallas VMEM-resident X-engine prototype vs the production einsum
X-engine, interleaved on-chip at nant=64 (VERDICT r4 item 1: "build the
VMEM-resident X-engine if the measured shape justifies it, or record the
dead end at that shape").

The kernel consumes spectra pre-transposed (ONE XLA pass) to
``(nchan, nfft, nant*npol, nframes)`` and emits packed visibilities
``(nchan, nfft, ap, bq)``: per (chan, fine-tile) grid step it loads both
planes' (FT, 128, nframes) blocks into VMEM and runs 4 batched
dot_generals — every spectra byte is read exactly once, every visibility
byte written once.  tools/ab_fx64.py already measured packed-layout
OUTPUT parity for the einsum path, so the packed emission is not the
variable under test; the single-pass VMEM residency is.

Run on the chip:  python tools/ab_fx64_pallas.py [nant nchan nfft nblk rounds reps ft]
"""

from __future__ import annotations

import functools
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
    ft = int(sys.argv[7]) if len(sys.argv) > 7 else 8
    ntap, npol = 4, 2
    ntime = nblk * nfft

    from blit.device import use_compile_cache

    use_compile_cache()

    from blit.ops.channelize import pfb_coeffs
    # The SHIPPED kernel, not a prototype copy: re-running this tool keeps
    # measuring the code path correlate(vis_layout="packed") dispatches.
    from blit.ops.pallas_xengine import xengine_packed
    from blit.parallel.correlator import _xengine_planar, f_engine_planar

    rng = np.random.default_rng(0)
    shape = (nant, nchan, npol, ntime)
    vr = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    vi = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    hj = jnp.asarray(pfb_coeffs(ntap, nfft).astype(np.float32))
    nbytes = vr.nbytes + vi.nbytes

    xe_pl = functools.partial(xengine_packed, ft=ft)

    def make(xe):
        @jax.jit
        def f(a, b):
            sr, si = f_engine_planar(a, b, hj)
            visr, visi = xe(sr, si)
            return jnp.sum(visr) + jnp.sum(visi)

        return f

    fa = make(_xengine_planar)
    fb = make(xe_pl)
    t0 = time.time()
    ca, cb = float(fa(vr, vi)), float(fb(vr, vi))
    rel = abs(cb - ca) / max(abs(ca), 1e-9)
    print(f"warmup (incl. compile) {time.time() - t0:.1f}s "
          f"checksum delta {rel:.2e}", flush=True)
    # Both paths multiply at the TPU's default (bf16) matmul precision but
    # reduce in different orders; interpret-mode element-wise equality is
    # pinned separately, the chip checksum only guards gross breakage.
    assert rel < 1e-3, "pallas X-engine disagrees with the einsum path"

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
        print(f"round {r}: A {ga[-1]:.2f}  B(pallas ft={ft}) {gb[-1]:.2f} "
              "GB/s", flush=True)
    print(f"A einsum:  {min(ga):.2f}-{max(ga):.2f} GB/s "
          f"(median {np.median(ga):.2f})")
    print(f"B pallas:  {min(gb):.2f}-{max(gb):.2f} GB/s "
          f"(median {np.median(gb):.2f})")
    print(f"median ratio B/A: {np.median(gb) / np.median(ga):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
