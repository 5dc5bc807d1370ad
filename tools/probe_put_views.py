"""What the runtime makes of one channel group's samples, by the view of
them it is handed (ISSUE 29, PERF.md section 6).

The TPU runtime re-tiles a host array into the device's layout on the
host, before the wire.  One faulted, page-aligned 32-channel hi-res group
goes up as the int8 ``(cb, T, 2, 2)`` array it is, and as views of the same
bytes; the best of three says what each costs in seconds and cpu-seconds.
Then the program that reads each form runs on resident inputs (16
channels: a 32-channel program reserves 10 GB) and its bits are compared.

    chiprun -- python tools/probe_put_views.py

Read on a v5e (my chip runs, PR 29): 8 frames (1.074 GB) int8 ``(32, 2^23,
2, 2)`` 0.533 s and 4.2 cpu-s; int8 ``(32, 2^23 + 64, 2, 2)`` 0.200 s;
int8 ``(32, 2^25)`` 0.175 s; int16 ``(32, 2^23, 2)`` 0.183 s; int32 ``(32,
2^23)`` 0.102 s and 0.17 cpu-s; 11 frames (1.476 GB) int8 0.289 s, int32
0.154 s.  Programs at 16 channels: gross int8 37 ms, (tail, body) int8 38,
words 40, bits equal.  ``toy`` as the only argument runs tiny shapes (a
CPU rehearsal of the script; it proves nothing).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from blit import hostmem  # noqa: E402
from blit.ops import channelize as ch  # noqa: E402

TOY = sys.argv[1:] == ["toy"]
N = 1 << (10 if TOY else 20)
CB = 4 if TOY else 32
RNG = np.random.default_rng(1)


def timed(label, fn, reps=3):
    best = out = None
    for _ in range(reps):
        t0, c0 = time.perf_counter(), time.process_time()
        out = fn()
        jax.block_until_ready(out)
        took = (time.perf_counter() - t0, time.process_time() - c0)
        best = took if best is None or took < best else best
    print(f"{label}: {best[0]:.3f} s, cpu {best[1]:.2f} s", flush=True)
    return out


def slab(nchan, samples):
    a = hostmem.aligned_empty((nchan, samples, 2, 2), np.int8)
    a[:] = RNG.integers(-8, 8, a.shape[1:], np.int8)  # faulted, not fresh
    return a


def transfers() -> None:
    for frames in (8, 11):
        t = frames * N
        a = slab(CB, t)
        print(f"-- {frames} frames, {a.nbytes / 1e9:.3f} GB", flush=True)
        flat = a.reshape(CB, t, 4)
        for label, view in (
                (f"int8 ({CB}, T, 2, 2)", a),
                (f"int8 ({CB}, 4T)", a.reshape(CB, 4 * t)),
                (f"int16 ({CB}, T, 2)", flat.view(np.int16)),
                (f"int32 ({CB}, T)  [sample_words]", ch.sample_words(a))):
            timed("   " + label, lambda view=view: jax.device_put(view))
        if frames == 8:
            for pad in (64, 4096):
                p = slab(CB, t + pad)
                timed(f"   int8 ({CB}, T+{pad}, 2, 2)",
                      lambda p=p: jax.device_put(p))


def programs() -> None:
    cb, t = CB // 2, 8 * N
    kw = dict(nfft=N, ntap=4, nint=1, stokes="I", fft_method="auto")
    h = jnp.asarray(ch.pfb_coeffs(4, N))
    head, body = slab(cb, 3 * N), slab(cb, t)

    @jax.jit
    def gross(v):
        return ch.channelize(v, h, **kw)

    @jax.jit
    def int8_pair(tail, new):
        return ch.channelize(jnp.concatenate([tail, new], axis=1), h, **kw)

    def words(tail, new):  # the tail is donated: a copy of it every call
        return ch.channelize_stream(jnp.array(tail), new, h, **kw)[0]

    print(f"-- programs at {cb} channels, 8 frames", flush=True)
    g = jax.device_put(np.concatenate([head, body], axis=1))
    want = np.asarray(timed("   gross int8 (until PR 29)", lambda: gross(g),
                            reps=5))
    del g
    t8, b8 = jax.device_put(head), jax.device_put(body)
    got = np.asarray(timed("   (tail, body) int8", lambda: int8_pair(t8, b8),
                           reps=5))
    print("   bits equal:", np.array_equal(got, want), flush=True)
    del t8, b8
    tw = jax.device_put(ch.sample_words(head))
    bw = jax.device_put(ch.sample_words(body))
    got = np.asarray(timed("   (tail, body) words", lambda: words(tw, bw),
                           reps=5))
    print("   bits equal:", np.array_equal(got, want), flush=True)
    print("   plan", ch.last_kernel_plan(), flush=True)


if __name__ == "__main__":
    print(jax.devices(), flush=True)
    transfers()
    programs()
