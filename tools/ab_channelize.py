"""Interleaved on-chip A/B of two `channelize` kwarg variants.

Run-to-run spread across processes can exceed the differences under test,
so the variants interleave in ONE process: A-block, B-block, A-block, ...
with each block timed by the §9 methodology — per-call device-side scalar
sink, K calls enqueued back-to-back, exactly one scalar fetch closing the
window (the in-order queue guarantees all enqueued calls executed).

Usage (note: "auto" resolves to the fused tail+detect whenever eligible,
so pin the baseline's kernels explicitly — e.g. the tail-only kernel is
detect_kernel="xla"):
    python tools/ab_channelize.py \
        '{"tail_kernel": "pallas", "detect_kernel": "xla"}' \
        '{"tail_kernel": "pallas", "detect_kernel": "pallas"}' \
        [nchan frames dtype rounds K]

A variant may also override the dispatch shape itself with the pseudo
kwargs "nchan"/"frames" (popped before the channelize call), e.g.
'{"nchan": 64}' A/Bs 64 coarse channels against the base shape at equal
net-bytes accounting.  Prints per-round GB/s and the pooled summary.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv) -> int:
    if len(argv) < 3:
        print(__doc__, file=sys.stderr)
        print("error: need two JSON kwarg variants", file=sys.stderr)
        return 2
    try:
        kw_a = json.loads(argv[1])
        kw_b = json.loads(argv[2])
    except json.JSONDecodeError as e:
        print(f"error: variant is not valid JSON: {e}", file=sys.stderr)
        return 2
    nchan = int(argv[3]) if len(argv) > 3 else 48
    frames = int(argv[4]) if len(argv) > 4 else 8
    dtype = argv[5] if len(argv) > 5 else "bfloat16"
    rounds = int(argv[6]) if len(argv) > 6 else 3
    reps = int(argv[7]) if len(argv) > 7 else 4

    from blit.device import use_compile_cache

    use_compile_cache()

    from blit.ops.channelize import channelize, pfb_coeffs

    nfft, ntap = 1 << 20, 4
    coeffs = jnp.asarray(pfb_coeffs(ntap, nfft))
    base = dict(nfft=nfft, ntap=ntap, nint=1, stokes="I",
                fft_method="auto", dtype=dtype)

    inputs = {}  # (nchan, frames) -> shared device array: equal shapes
    # time the SAME tensor, and distinct shapes don't double input HBM.

    def make(kw):
        kw = dict(kw)
        nc = int(kw.pop("nchan", nchan))
        fr = int(kw.pop("frames", frames))
        if (nc, fr) not in inputs:
            ntime = (ntap - 1 + fr) * nfft
            inputs[(nc, fr)] = jnp.asarray(np.random.default_rng(0).integers(
                -40, 40, size=(nc, ntime, 2, 2), dtype=np.int8))
        merged = {**base, **kw}

        @jax.jit
        def f(x):
            return jnp.sum(channelize(x, coeffs, **merged))

        return f, inputs[(nc, fr)], fr * nfft * nc * 4  # int8 2pol×re/im

    fa, va, na = make(kw_a)
    fb, vb, nb = make(kw_b)
    # Warm both (compile + first-run allocs), then one fetch each.
    t0 = time.time()
    float(fa(va))
    float(fb(vb))
    print(f"warmup (incl. compile) {time.time() - t0:.1f}s", flush=True)

    def block(f, v, net_bytes):
        t0 = time.time()
        out = None
        for _ in range(reps):
            out = f(v)
        float(out)  # one fetch; in-order queue ⇒ all reps executed
        dt = time.time() - t0
        return reps * net_bytes / dt / 1e9

    ga, gb = [], []
    for r in range(rounds):
        ga.append(block(fa, va, na))
        gb.append(block(fb, vb, nb))
        print(f"round {r}: A {ga[-1]:.2f}  B {gb[-1]:.2f} GB/s", flush=True)
    print(f"A {kw_a}: {min(ga):.2f}-{max(ga):.2f} GB/s")
    print(f"B {kw_b}: {min(gb):.2f}-{max(gb):.2f} GB/s")
    print(f"median ratio B/A: {np.median(gb) / np.median(ga):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
