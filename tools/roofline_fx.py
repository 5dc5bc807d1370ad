"""Per-stage roofline of one FX-correlator `correlate` call on the real
chip (VERDICT r3 item 2: "the correlator leg is the one unjustified perf
number left — roofline it, then fuse or prove its ceiling").

Stages at the bench config (nant=8, nchan=64, nfft=512, ntap=4,
ntime=64*nfft, npol=2; blit/parallel/correlator.py):

  pfb x2        FIR frontend on the re and im planes
  dft           planar matmul DFT over the frame axis (fft_planar)
  xengine       baseline cross-products + frame sum (4 einsums)
  whole         jitted correlate() (XLA fuses across stage seams)

Byte accounting: the "min" column is the analytic minimum (read inputs
once, write outputs once, f32); achieved GB/s divides the sink-inclusive
bytes (`scalarized_bytes`: timed()'s on-device scalar sink re-reads each
stage's outputs once), the same convention as tools/roofline.py.

Run on the chip:  python tools/roofline_fx.py [nant nchan nfft nblk reps]
"""

from __future__ import annotations

import os
import sys

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.roofline import (  # noqa: E402
    hbm_peak_gbps,
    scalarized_bytes,
    time_whole,
    timed,
)


def main() -> None:
    nant = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    nchan = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    nfft = int(sys.argv[3]) if len(sys.argv) > 3 else 512
    nblk = int(sys.argv[4]) if len(sys.argv) > 4 else 64
    # These stages are short: enough reps that the one closing fetch is a
    # negligible share of the timed window.
    reps = int(sys.argv[5]) if len(sys.argv) > 5 else 32
    ntap, npol = 4, 2
    ntime = nblk * nfft
    nframes = nblk - ntap + 1

    from blit.device import use_compile_cache

    use_compile_cache()
    peak = hbm_peak_gbps()

    from blit.ops.channelize import fft_planar, pfb_coeffs, pfb_frontend
    from blit.parallel import correlator as C
    from blit.parallel import mesh as M

    rng = np.random.default_rng(0)
    shape = (nant, nchan, npol, ntime)  # pol before time, as correlate does
    vr = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    vi = jnp.asarray(rng.standard_normal(shape).astype(np.float32))
    coeffs = pfb_coeffs(ntap, nfft).astype(np.float32)
    sign = np.where(np.arange(nfft) % 2 == 0, 1.0, -1.0).astype(np.float32)
    hj = jnp.asarray(coeffs * sign[None, :])

    plane = nant * nchan * npol * ntime * 4          # one f32 input plane
    spec = nant * nchan * npol * nframes * nfft * 4  # one spectra plane
    vis = nant * nant * nchan * nfft * npol * npol * 4

    rows = []

    def report(name, seconds, rd, wr):
        # timed()'s on-device scalar sink re-reads the outputs once per
        # rep: achieved bandwidth divides the SINK-inclusive bytes
        # (scalarized_bytes = rd + 2*wr), the shared roofline convention.
        moved = scalarized_bytes(rd, wr)
        rows.append((name, seconds, moved / seconds / 1e9))
        print(f"{name:24s} {seconds * 1e3:8.2f} ms   min {(rd + wr) / 1e6:9.1f} MB"
              f"   (+sink {moved / 1e6:9.1f})"
              f"   {moved / seconds / 1e9:7.1f} GB/s of {peak:.0f}",
              flush=True)

    # Stage 1: FIR on both planes.
    t, (fr, fi) = timed(
        lambda a, b: (pfb_frontend(a, hj), pfb_frontend(b, hj)), vr, vi,
        reps=reps,
    )
    report("pfb x2 (fir)", t, 2 * plane, 2 * spec)

    # Stage 2: planar matmul DFT on the framed planes.
    t, (sr, si) = timed(lambda a, b: fft_planar(a, b), fr, fi,
                        reps=reps)
    report("dft (planar matmul)", t, 2 * spec, 2 * spec)

    # Stage 3: X-engine cross products.
    t, _ = timed(lambda a, b: C._xengine_planar(a, b), sr, si,
                 reps=reps)
    report("xengine (4 einsums)", t, 2 * spec, 2 * vis)
    del fr, fi, sr, si

    # Whole jitted correlate on a 1x1 mesh (the bench path).
    mesh = M.make_mesh(1, 1)
    vr4 = jnp.moveaxis(vr, 2, 3)  # (a, c, t, p): correlate's input layout
    vi4 = jnp.moveaxis(vi, 2, 3)
    vp = jax.device_put(
        (jax.block_until_ready(vr4), jax.block_until_ready(vi4)),
        C.correlator_sharding(mesh),
    )
    hplain = jnp.asarray(coeffs)

    def whole(pair):
        a, b = C.correlate(pair, hplain, mesh=mesh, nfft=nfft, ntap=ntap)
        return jnp.sum(a) + jnp.sum(b)

    sec, compile_s = time_whole(whole, vp, reps=reps)
    input_bytes = 2 * plane
    print(f"{'whole correlate':24s} {sec * 1e3:8.2f} ms   "
          f"input {input_bytes / 1e6:9.1f} MB   "
          f"{input_bytes / sec / 1e9:7.1f} GB/s input rate "
          f"(compile {compile_s:.1f}s)", flush=True)
    ssum = sum(r[1] for r in rows)
    print(f"{'sum of stages':24s} {ssum * 1e3:8.2f} ms")
    min_total = (2 * plane + 2 * spec) + (4 * spec) + (2 * spec + 2 * vis)
    print(f"analytic min traffic {min_total / 1e6:.1f} MB "
          f"→ bound {min_total / peak / 1e9 * 1e3:.2f} ms/call; "
          f"whole-call implies {input_bytes / sec / 1e9:.2f} GB/s input")


if __name__ == "__main__":
    main()
