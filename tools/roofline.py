"""Per-stage roofline of one `channelize` call on the real chip.

Times each pipeline stage separately under jit at the bench shapes and
compares the achieved HBM bandwidth against the analytic minimum traffic
(read every input once + write every output once).  The table this prints
backs DESIGN.md §9 — the evidence for where the next optimization dollar
goes (VERDICT round-2 "write the roofline, then attack it").

Run on the chip:  python tools/roofline.py [nchan frames [dtype]]

Stages (f32 planar, factors (128, 128, 64) for nfft=2^20):
  dequant+pfb   int8 → planar f32 frames (windowed sums)
  dft1          128-pt DFT matmul + twiddle  (per recursion level 0)
  dft2          128-pt DFT matmul + twiddle  (level 1)
  dft3          64-pt DFT matmul             (level 2, innermost)
  untwist2/1    swapaxes+reshape epilogues of levels 1 and 0
  detect+int    |X|²+|Y|² detect (+ time integration) + product transpose
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from blit.device import pallas_interpret, use_compile_cache
from blit.ops import dft as D
from blit.ops.channelize import dequantize, pfb_coeffs, pfb_frontend, detect_stokes_planar, integrate

# Published HBM bandwidth per device kind, GB/s — the "roof".  Source:
# Google Cloud documentation, "TPU v5e" (819 GB/s per chip).  A device
# that is not in the table is an error, not a default.
HBM_PEAK_GBPS_BY_KIND = {"TPU v5 lite": 819.0}


def hbm_peak_gbps() -> float:
    kind = jax.devices()[0].device_kind
    try:
        return HBM_PEAK_GBPS_BY_KIND[kind]
    except KeyError:
        raise KeyError(
            f"no HBM peak recorded for device kind {kind!r}; add it to "
            "HBM_PEAK_GBPS_BY_KIND with its source"
        ) from None


def timed(fn, *args, reps=6):
    """Mean per-call device time of ``fn``.  A queue of GB-sized stage
    outputs would exhaust HBM, so each rep reduces the stage outputs to
    one scalar ON DEVICE (a full extra read pass of the outputs —
    accounted by the caller via :func:`scalarized_bytes`), the reps
    enqueue back-to-back, and one fetch at the end closes the window.

    Also returns the stage's real outputs from one extra (untimed) call so
    the caller can chain stages."""
    g = jax.jit(lambda *a: sum(jnp.sum(o.astype(jnp.float32)) for o in
                               jax.tree.leaves(fn(*a))))
    float(g(*args))  # compile + settle
    t0 = time.perf_counter()
    acc = [g(*args) for _ in range(reps)]
    # ONE fetch: the in-order queue means the last scalar materializing
    # implies every rep executed.
    float(acc[-1])
    per = (time.perf_counter() - t0) / reps
    out = jax.jit(fn)(*args)
    return per, out


def scalarized_bytes(rd: int, wr: int) -> int:
    """Bytes actually moved when a stage is timed through :func:`timed`'s
    on-device scalar sink: the harness re-reads the outputs once (+wr).
    Both report modes must use this same accounting."""
    return rd + 2 * wr


def time_whole(fn, vj, reps: int = 4):
    """Warm (compile) then time ``reps`` enqueued calls of the whole
    channelize with one closing fetch (the same rule as :func:`timed`).
    Returns (seconds_per_call, compile_seconds)."""
    g = jax.jit(fn)
    t0 = time.perf_counter()
    float(g(vj))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    acc = [g(vj) for _ in range(reps)]
    float(acc[-1])
    return (time.perf_counter() - t0) / reps, compile_s


def fused_main(nchan: int, frames: int, dtype: str) -> None:
    """Per-pass decomposition of the FUSED production pipeline (the
    DESIGN.md §9 post-fusion table): pfb_dft1 → tail2_detect (+ its XLA
    lane swap, also isolated on a synthetic array) → whole channelize.

    Run:  python tools/roofline.py --fused [nchan frames [dtype]]
    """
    from blit.ops.channelize import channelize
    from blit.ops.pallas_detect import tail2_detect
    from blit.ops.pallas_pfb import pfb_dft1

    nfft, ntap, npol = 1 << 20, 4, 2
    ntime = (ntap - 1 + frames) * nfft
    esize = 2 if dtype == "bfloat16" else 4
    rng = np.random.default_rng(0)
    v = rng.integers(-40, 40, (nchan, ntime, npol, 2), np.int8)
    vj = jax.block_until_ready(jnp.asarray(v))
    interp = pallas_interpret(jax.default_backend())
    factors = D.default_factors(nfft)
    n1 = factors[0]
    sign = np.where(np.arange(nfft) % 2 == 0, 1.0, -1.0).astype(np.float32)
    shifted = jnp.asarray(pfb_coeffs(ntap, nfft) * sign)
    w1r, w1i = (jnp.asarray(a) for a in D.dft_matrices(n1, "float32"))
    t1r, t1i = (jnp.asarray(a) for a in D.twiddles(n1, nfft // n1, "float32"))

    E = nchan * npol * frames * nfft
    plane = E * esize           # one (re or im) stage-1 plane
    power = E // npol * 4       # the f32 Stokes-I product

    print(f"fused roofline @ nchan={nchan} frames={frames} dtype={dtype}")

    def report(name, seconds, rd, wr):
        bts = scalarized_bytes(rd, wr)
        print(f"  {name:<28}{seconds * 1e3:>8.1f} ms  "
              f"{(rd + wr) / 1e9:>6.2f} GB  {bts / seconds / 1e9:>6.0f} GB/s",
              flush=True)

    t, (ur, ui) = timed(
        lambda x: pfb_dft1(x, shifted, w1r, w1i, t1r, t1i, dtype=dtype,
                           interpret=interp), vj)
    report("pfb_dft1 (int8->stage-1)", t, v.nbytes, 2 * plane)

    t, td_out = timed(
        lambda a, b: tail2_detect(a, b, factors[1], factors[2],
                                  interpret=interp), ur, ui)
    report("tail2_detect (+lane swap)", t, 2 * plane, power)
    del td_out

    # The lane swap isolated — models the Stokes-I case: tail2_detect's raw
    # output carries a nif axis (frames, nif, nchan, f3, f1, f2) which is
    # size 1 for "I" and folds away here; multi-pol products (nif=4) move
    # proportionally more bytes than this probe measures (ADVICE r3).
    x = jnp.zeros((frames, nchan, factors[2], factors[0], factors[1]),
                  jnp.float32)
    t, sw_out = timed(lambda y: jnp.swapaxes(y, -1, -2).reshape(
        frames, nchan, nfft), x)
    report("lane swap alone (xla)", t, power, power)
    # Free every stage array before the whole-call rerun — pinned planes
    # at these shapes are exactly the OOM-sensitive HBM margin (§9).
    del ur, ui, x, sw_out

    def whole(y):
        return jnp.sum(channelize(
            y, jnp.asarray(pfb_coeffs(ntap, nfft)), nfft=nfft, ntap=ntap,
            nint=1, stokes="I", fft_method="auto",
            **({} if dtype == "float32" else {"dtype": dtype})))

    whole_t, _compile_s = time_whole(whole, vj)
    net = frames * nfft * nchan * npol * 2
    print(f"  whole channelize: {whole_t * 1e3:.1f} ms, net {net / 1e9:.2f} GB"
          f" -> {net / whole_t / 1e9:.2f} GB/s/chip")


def main() -> None:
    use_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "--fused":
        args = sys.argv[2:]
        fused_main(
            int(args[0]) if len(args) > 0 else 48,
            int(args[1]) if len(args) > 1 else 8,
            args[2] if len(args) > 2 else "bfloat16",
        )
        return
    nchan = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    frames = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    dtype = sys.argv[3] if len(sys.argv) > 3 else "float32"
    nfft, ntap, npol = 1 << 20, 4, 2
    ntime = (ntap - 1 + frames) * nfft
    esize = 2 if dtype == "bfloat16" else 4

    rng = np.random.default_rng(0)
    v = rng.integers(-40, 40, (nchan, ntime, npol, 2), np.int8)
    coeffs = jnp.asarray(pfb_coeffs(ntap, nfft))
    vj = jax.block_until_ready(jnp.asarray(v))
    peak = hbm_peak_gbps()

    # Planar complex element count of one full intermediate.
    E = nchan * npol * frames * nfft
    plane = E * esize  # bytes of ONE (re or im) plane
    f32_plane = E * 4

    rows = []

    def row(name, seconds, rd, wr):
        bts = scalarized_bytes(rd, wr)
        rows.append((name, seconds, rd, wr, bts / seconds / 1e9))
        print(f"  {name}: {seconds * 1e3:.1f} ms, {bts / seconds / 1e9:.0f} GB/s",
              flush=True)

    # -- dequant + PFB (mirrors channelize: bf16 mode runs the whole stage
    # half-width, from the dequant planes on) ------------------------------
    work_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    wcoeffs = coeffs.astype(work_dtype)

    def s_pfb(x):
        re, im = dequantize(x, dtype=work_dtype)
        re = jnp.moveaxis(re, -1, 1)
        im = jnp.moveaxis(im, -1, 1)
        fr = pfb_frontend(re, wcoeffs)
        fi = pfb_frontend(im, wcoeffs)
        return fr, fi

    t, (fr, fi) = timed(s_pfb, vj)
    row("dequant+pfb (xla)", t, v.nbytes, 2 * plane)
    frames_shape = fr.shape

    # The fused pallas variant (production default on the chip, §4/§9).
    if npol == 2:
        from blit.ops.pallas_pfb import pfb_dequant

        interp = pallas_interpret(jax.default_backend())
        t, _ = timed(
            lambda x: pfb_dequant(x, coeffs, dtype=dtype, interpret=interp),
            vj,
        )
        row("dequant+pfb (pallas)", t, v.nbytes, 2 * plane)

    # -- DFT stages, timed one recursion level at a time -------------------
    # Intermediates are del'd as soon as the next stage's inputs exist: the
    # whole-pipeline HBM budget fits because XLA frees each stage's inputs;
    # a tool that pins every stage's output OOMs at the very shapes it is
    # supposed to measure.
    factors = D.default_factors(nfft)
    xr = jnp.reshape(fr, frames_shape[:-1] + (factors[0], nfft // factors[0]))
    xi = jnp.reshape(fi, frames_shape[:-1] + (factors[0], nfft // factors[0]))
    del fr, fi

    def stage_fn(n1, n2):
        w1r, w1i = (jnp.asarray(a) for a in D.dft_matrices(n1, dtype))
        tr, ti = (jnp.asarray(a) for a in D.twiddles(n1, n2, dtype))

        def f(ar_, ai_):
            a = jnp.einsum("kj,...jm->...km", w1r, ar_)
            b = jnp.einsum("kj,...jm->...km", w1i, ar_)
            c = jnp.einsum("kj,...jm->...km", w1r, ai_)
            d = jnp.einsum("kj,...jm->...km", w1i, ai_)
            sr, si = a - d, b + c
            return sr * tr - si * ti, sr * ti + si * tr

        return f

    rest = nfft
    level = 0
    while len(D.default_factors(rest)) > 1:
        n1 = D.default_factors(rest)[0]
        n2 = rest // n1
        t, (xr2, xi2) = timed(stage_fn(n1, n2), xr, xi)
        row(f"dft{level + 1} (n1={n1})", t, 2 * plane, 2 * plane)
        del xr, xi
        # reshape for the next level: rows stay batch, last axis splits again
        nf = D.default_factors(n2)[0]
        if len(D.default_factors(n2)) > 1:
            xr = xr2.reshape(xr2.shape[:-1] + (nf, n2 // nf))
            xi = xi2.reshape(xi2.shape[:-1] + (nf, n2 // nf))
        else:
            xr, xi = xr2, xi2
        del xr2, xi2
        rest = n2
        level += 1

    wlast = rest

    def last_fn(n):
        wr, wi = (jnp.asarray(a) for a in D.dft_matrices(n, dtype))

        def f(ar_, ai_):
            a = jnp.matmul(ar_, wr)
            b = jnp.matmul(ar_, wi)
            c = jnp.matmul(ai_, wr)
            d = jnp.matmul(ai_, wi)
            return a - d, b + c

        return f

    t, (yr, yi) = timed(last_fn(wlast), xr, xi)
    row(f"dft{level + 1} (n={wlast})", t, 2 * plane, 2 * plane)
    del xr, xi

    # -- the untwist transposes (swapaxes + reshape per level) -------------
    def untwist(ar_, ai_):
        # reshape after swapaxes forces materialization in the new layout
        # (jit outputs are default-layout, so this is the real transpose
        # cost the pipeline pays).
        a = jnp.swapaxes(ar_, -1, -2)
        b = jnp.swapaxes(ai_, -1, -2)
        flat = ar_.shape[:-2] + (ar_.shape[-1] * ar_.shape[-2],)
        return a.reshape(flat), b.reshape(flat)

    t, _ = timed(untwist, yr, yi)
    row("untwist (x1 of 2)", t, 2 * plane, 2 * plane)

    # -- detect + integrate + product transpose -----------------------------
    sr = yr.reshape(frames_shape)
    si = yi.reshape(frames_shape)
    del yr, yi

    def s_detect(ar_, ai_):
        if ar_.dtype != jnp.float32:
            ar_, ai_ = ar_.astype(jnp.float32), ai_.astype(jnp.float32)
        p = detect_stokes_planar(ar_, ai_, "I")
        p = integrate(p, 1)
        out = jnp.transpose(p, (2, 1, 0, 3))
        return out.reshape(out.shape[0], out.shape[1], -1)

    t, _ = timed(s_detect, sr, si)
    row("detect+transpose", t, 2 * plane, f32_plane // npol)
    del sr, si  # free the pinned stage arrays before the whole-call rerun

    # -- whole fused call for comparison ------------------------------------
    from blit.ops.channelize import channelize

    def whole(x):
        return jnp.sum(channelize(x, coeffs, nfft=nfft, ntap=ntap, nint=1,
                                  stokes="I", fft_method="auto",
                                  **({} if dtype == "float32" else {"dtype": dtype})))

    whole_t, compile_s = time_whole(whole, vj)

    net = frames * nfft * nchan * npol * 2  # int8 bytes credited by bench.py

    print(f"\nroofline @ nchan={nchan} frames={frames} nfft=2^20 dtype={dtype}"
          f"  (plane={plane / 1e9:.2f} GB, HBM peak {peak:.0f} GB/s)")
    print(f"{'stage':<22}{'ms':>9}{'rd GB':>8}{'wr GB':>8}{'GB/s':>9}{'%roof':>7}")
    tot_ms = tot_bytes = 0.0
    for name, s, rd, wr, gbps in rows:
        n_un = 2 if name.startswith("untwist") else 1
        if "(pallas)" not in name:  # alternative stage, not an addend
            tot_ms += s * 1e3 * n_un
            tot_bytes += (rd + wr) * n_un
        print(f"{name:<22}{s * 1e3:>9.1f}{rd / 1e9:>8.2f}{wr / 1e9:>8.2f}"
              f"{gbps:>9.0f}{100 * gbps / peak:>6.0f}%")
    print(f"{'sum of stages':<22}{tot_ms:>9.1f}  (analytic min traffic "
          f"{tot_bytes / 1e9:.1f} GB → {tot_bytes / peak / 1e6:.1f} ms at roof)")
    print(f"{'whole channelize':<22}{whole_t * 1e3:>9.1f}  net {net / 1e9:.3f} GB"
          f" → {net / whole_t / 1e9:.2f} GB/s/chip  (compile {compile_s:.0f}s)")


if __name__ == "__main__":
    main()
