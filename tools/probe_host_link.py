"""What the host<->device link does with two hi-res chunks, without files.

PR 25 (PERF.md section 6) found that the TPU runtime stages transfers
through a premapped host region (``TPU_PREMAPPED_BUFFER_SIZE``, 4 GiB
unless set) and that a transfer ENQUEUED while the region is taken
crawls.  This is the probe that showed it: two faulted recorder-width
chunk buffers go through ``RawReducer._dispatch`` (the pump's own call)
in three orders, and each line says when a chunk's product was ready and
when it had been fetched.

    chiprun -- python tools/probe_host_link.py
    chiprun -- env TPU_PREMAPPED_BUFFER_SIZE=12884901888 \\
        python tools/probe_host_link.py

Read on a v5e (my chip runs, PR 25): dispatched together the second chunk
is ready at 8.0-8.6 s (2.2 s with 12 GiB premapped); one after the other
both are in and fetched within 2.3-2.7 s.  ``toy`` as the only argument
runs tiny shapes (a CPU rehearsal of the script; it proves nothing).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from blit import hostmem  # noqa: E402
from blit.pipeline import RawReducer, reducer_for_product  # noqa: E402

TOY = sys.argv[1:] == ["toy"]
SHAPE = (4, 11 << 10, 2, 2) if TOY else (64, 11 << 20, 2, 2)


def main() -> None:
    red = (RawReducer(nfft=1024, nint=1) if TOY
           else reducer_for_product("0000"))
    rng = np.random.default_rng(1)
    a = hostmem.aligned_empty(SHAPE, np.int8)
    b = hostmem.aligned_empty(SHAPE, np.int8)
    a[:] = rng.integers(-8, 8, SHAPE[1:], np.int8)  # faulted, not fresh
    b[:] = a
    t0 = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - t0

    def fetch(out, label: str) -> None:
        out.block_until_ready()
        ready = now()
        np.asarray(out)
        print(f"   {label}: ready at {ready:6.3f} s, fetched at {now():6.3f} s",
              flush=True)

    def together() -> None:
        x, y = red._dispatch(a), red._dispatch(b)
        print(f"   both dispatched by {now():.3f} s")
        fetch(x, "first")
        del x
        fetch(y, "second")

    def one_after_the_other() -> None:
        fetch(red._dispatch(a), "first")
        print(f"   second dispatched at {now():.3f} s")
        fetch(red._dispatch(b), "second")

    def second_once_first_is_ready() -> None:
        x = red._dispatch(a)
        x.block_until_ready()
        y = red._dispatch(b)
        print(f"   second dispatched at {now():.3f} s")
        y.block_until_ready()
        fetch(x, "first")
        del x
        fetch(y, "second")

    print("TPU_PREMAPPED_BUFFER_SIZE",
          os.environ.get("TPU_PREMAPPED_BUFFER_SIZE"),
          "channels per dispatch", red._channel_block(SHAPE), flush=True)
    for name, run in (("warm-up (compile or cache load)", one_after_the_other),
                      ("dispatched together", together),
                      ("one after the other", one_after_the_other),
                      ("second in once the first is ready", second_once_first_is_ready),
                      ("dispatched together, again", together)):
        print(f"-- {name}", flush=True)
        t0 = time.perf_counter()
        run()
        print(f"   total {now():.3f} s", flush=True)


if __name__ == "__main__":
    main()
