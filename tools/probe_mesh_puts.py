"""What the runtime makes of one mesh window's four banks, by the form and
the order they are put in (ISSUE 31 step 0, PERF.md section 6).

``blit scan`` feeds a (1, 4) mesh one bank per chip.  Until PR 31 every
2-frame window re-sent its 3-frame filter prologue: four int8 blocks
``(1, 1, 64, 5*2^20, 2, 2)``, 1.34 GB each, whose ``device_put`` returns
at once while the runtime's threads re-tile them behind it.  This probe
asks what the window's NEW samples alone cost (64 ch x 2 x 2^20 samples a
bank, 0.54 GB), each bank to its own chip from faulted, page-aligned
memory:

- ``base``  int8 ``(1, 1, 64, 5*2^20, 2, 2)``: the feed until PR 31;
- ``(a)``   int8 ``(1, 1, 64, 2^21, 2, 2)``: the body as the array it is;
- ``(b)``   words ``(1, 1, 64, 2^21)`` int32 (``sample_words``), the four
  puts one after the other from one thread;
- ``(c)``   the same words, the four puts issued side by side (a thread a
  bank).

For each: seconds until the last ``device_put`` RETURNS, seconds until
all four have landed, cpu-seconds, best of three by the landing time (and
every repeat's numbers).  Then the same four forms with two windows back
to back (eight puts, 4 GiB of words: what the scan keeps in flight).

    chiprun --chips 4 -- python tools/probe_mesh_puts.py

Read on four v5e chips (my chip run, PR 31; returned / landed / cpu-s,
best of three): ``base`` 0.009 / 6.800 s / 15.65 (5.37 GB enqueued blind,
over the premapped 4 GiB: 0.8 GB/s; in the scan the link budget makes the
fourth bank wait instead); ``(a)`` 0.003 / 0.177 / 2.04 (12.1 GB/s);
``(b)`` 0.013 / **0.085** / 1.18 (25.1 GB/s); ``(c)`` 0.034 / 0.087 /
1.17; int8 bodies a thread a bank 0.004 / 0.181 / 2.04.  Two windows back
to back: ``(a)`` 0.043 / 0.265 / 4.02, ``(b)`` 0.055 / 0.162 / 2.64
(26.5 GB/s), ``(c)`` 0.070 / 0.164 / 2.53.  So a word put returns at
once, the four chips' copies overlap behind one calling thread, and a
thread a bank buys nothing: ``mesh.put_local_shards`` stays one loop.
``toy`` as the only argument runs tiny shapes on four virtual CPU devices
(a rehearsal of the script; it proves nothing).
"""

from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

TOY = sys.argv[1:] == ["toy"]
if TOY:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from blit import hostmem  # noqa: E402
from blit.ops.channelize import sample_words  # noqa: E402

N = 1 << (10 if TOY else 20)
NCHAN = 4 if TOY else 64
NBANK = 4
RNG = np.random.default_rng(1)
POOL = ThreadPoolExecutor(2 * NBANK)


def slab(samples):
    a = hostmem.aligned_empty((NCHAN, samples, 2, 2), np.int8)
    a[:] = RNG.integers(-8, 8, a.shape[1:], np.int8)  # faulted, not fresh
    return a


def head_of(a, samples):
    """The leading ``samples`` of every channel, contiguous in the slab's
    head (how a shorter window reads into a full-window slab)."""
    return a.reshape(-1)[:NCHAN * samples * 4].reshape(NCHAN, samples, 2, 2)


def put_all(blocks, devices, threaded):
    """-> (arrays, seconds until the last put returned)."""
    t0 = time.perf_counter()
    if threaded:
        out = list(POOL.map(jax.device_put, blocks, devices))
    else:
        out = [jax.device_put(b, d) for b, d in zip(blocks, devices)]
    return out, time.perf_counter() - t0


def timed(label, blocks, devices, threaded=False, reps=3):
    nbytes = sum(b.nbytes for b in blocks)
    runs = []
    for _ in range(reps):
        t0, c0 = time.perf_counter(), time.process_time()
        out, returned = put_all(blocks, devices, threaded)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t0, returned,
                     time.process_time() - c0))
        del out
    landed, returned, cpu = min(runs)
    print(f"   {label}: returned {returned:.3f} s, landed {landed:.3f} s "
          f"({nbytes / landed / 1e9:.1f} GB/s), cpu {cpu:.2f} s   all: "
          + " ".join(f"{r:.3f}/{l:.3f}/{c:.2f}" for l, r, c in runs),
          flush=True)


def main() -> None:
    devices = jax.devices()[:NBANK]
    print(devices, flush=True)
    slabs = [slab(5 * N) for _ in range(NBANK)]
    more = [slab(2 * N) for _ in range(NBANK)]  # a second window's bodies
    gross = [s[None, None] for s in slabs]
    body8 = [head_of(s, 2 * N)[None, None] for s in slabs]
    words = [sample_words(head_of(s, 2 * N))[None, None] for s in slabs]
    words2 = words + [sample_words(m)[None, None] for m in more]
    body8_2 = body8 + [m[None, None] for m in more]
    print(f"-- one window: four banks, {words[0].nbytes / 1e9:.3f} GB a "
          f"body, {gross[0].nbytes / 1e9:.3f} GB with the prologue",
          flush=True)
    timed("base  int8 (1,1,C,5N,2,2)", gross, devices)
    timed("(a)   int8 (1,1,C,2N,2,2)", body8, devices)
    timed("(b)   words (1,1,C,2N), one thread", words, devices)
    timed("(c)   words (1,1,C,2N), a thread a bank", words, devices, True)
    timed("(a')  int8 body, a thread a bank", body8, devices, True)
    print("-- two windows back to back (eight puts)", flush=True)
    two = devices + devices
    timed("(a)   int8 bodies", body8_2, two)
    timed("(b)   words, one thread", words2, two)
    timed("(c)   words, a thread a put", words2, two, True)


if __name__ == "__main__":
    main()
