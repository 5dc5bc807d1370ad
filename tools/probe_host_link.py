"""What the host<->device link does with two hi-res chunks, without files.

PR 25 (PERF.md section 6) found that the TPU runtime stages transfers
through a premapped host region (``TPU_PREMAPPED_BUFFER_SIZE``, 4 GiB
unless set) and that a transfer ENQUEUED while the region is taken
crawls.  This is the probe that showed it: two faulted recorder-width
chunk buffers go through the channelizer in four orders, and each line
says when a chunk's product was ready and when it had been fetched.  The
first three enqueue blind, as the pump did until PR 27 (the groups go up
as the jit's own arguments); "by group" is the pump's order since: each
channel group an explicit ``device_put`` admitted by a link budget
(``blit.device.HostLink``), chunk B right behind chunk A — once with a
group's bytes released when its put array is ready and once when the
program that consumes it has its output ready (the measured choice of
ISSUE 27, step 0).  Every order runs for both recorder-width hi-res
reductions: the 0000 preset (``nint`` 1: each chunk fetches a 2.1 GB
product) and rawspec's ``-t 51`` (carried on the chip, nothing fetched,
groups twice as wide).

    chiprun -- python tools/probe_host_link.py
    chiprun -- env TPU_PREMAPPED_BUFFER_SIZE=12884901888 \\
        python tools/probe_host_link.py

Read on a v5e (my chip runs, PR 25): dispatched together the second chunk
is ready at 8.0-8.6 s (2.2 s with 12 GiB premapped); one after the other
both are in and fetched within 2.3-2.7 s; by group, PERF.md section 6,
PR 27.  ``toy`` as the only argument runs tiny shapes with the link faked
at two and a half groups (a CPU rehearsal of the script; it proves
nothing).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from blit import device, hostmem  # noqa: E402
from blit.observability import Timeline  # noqa: E402
from blit.ops.channelize import channelize, integrate_carry  # noqa: E402
from blit.pipeline import RawReducer  # noqa: E402

TOY = sys.argv[1:] == ["toy"]
NFFT = 1 << 10 if TOY else 1 << 20
# An 8-frame chunk as the pump sent it until PR 29: with the 3 frames of
# filter state in front (they now stay on the chip; the probe keeps the
# transfer sizes its recorded readings were taken at).
SHAPE = (4 if TOY else 64, 11 * NFFT, 2, 2)


def main() -> None:
    rng = np.random.default_rng(1)
    a = hostmem.aligned_empty(SHAPE, np.int8)
    b = hostmem.aligned_empty(SHAPE, np.int8)
    a[:] = rng.integers(-8, 8, SHAPE[1:], np.int8)  # faulted, not fresh
    b[:] = a
    t0 = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - t0

    def fetch(parts, label: str) -> None:
        """``parts``: a chunk's per-group results.  A product is assembled
        and fetched as the output plane would; accumulators stay put."""
        out = jnp.concatenate(parts, axis=-1) if parts[0].ndim == 3 else parts
        del parts
        jax.block_until_ready(out)
        ready = now()
        if not isinstance(out, list):
            np.asarray(out)
        print(f"   {label}: ready at {ready:6.3f} s, fetched at {now():6.3f} s",
              flush=True)

    def orders(red: RawReducer, cb: int, run) -> None:
        def groups(chunk):
            return [chunk[c:c + cb] for c in range(0, SHAPE[0], cb)]

        def blind(chunk):
            return [run(g) for g in groups(chunk)]

        def together() -> None:
            x, y = blind(a), blind(b)
            print(f"   both dispatched by {now():.3f} s")
            fetch(x, "first")
            del x
            fetch(y, "second")

        def one_after_the_other() -> None:
            fetch(blind(a), "first")
            print(f"   second dispatched at {now():.3f} s")
            fetch(blind(b), "second")

        def second_once_first_is_ready() -> None:
            x = blind(a)
            jax.block_until_ready(x)
            y = blind(b)
            print(f"   second dispatched at {now():.3f} s")
            jax.block_until_ready(y)
            fetch(x, "first")
            del x
            fetch(y, "second")

        def by_group(release: str) -> None:
            link, tl = device.HostLink(), Timeline()

            def put_and_run(group):
                if release == "program":
                    return link.put(group, timeline=tl, then=run)
                return run(link.put(group, timeline=tl))

            x = [put_and_run(g) for g in groups(a)]
            print(f"   first dispatched by {now():.3f} s")
            y = [put_and_run(g) for g in groups(b)]
            print(f"   second dispatched by {now():.3f} s")
            fetch(x, "first")
            del x
            fetch(y, "second")
            w = tl.stages["wait.link"]
            print(f"   wait.link {w.calls} calls {w.seconds:.3f} s, peak in "
                  f"flight {tl.hists['link.inflight_bytes'].vmax:.0f} B of "
                  f"{device.host_link_bytes()}")

        for name, order in (
                ("warm-up (compile or cache load)", one_after_the_other),
                ("dispatched together", together),
                ("one after the other", one_after_the_other),
                ("second in once the first is ready",
                 second_once_first_is_ready),
                ("by group, released when the put is ready",
                 lambda: by_group("put")),
                ("by group, released when its program is ready",
                 lambda: by_group("program")),
                ("one after the other, again", one_after_the_other),
                ("by group, released when the put is ready, again",
                 lambda: by_group("put")),
                ("dispatched together, again", together)):
            print(f"-- {name}", flush=True)
            nonlocal t0
            t0 = time.perf_counter()
            order()
            print(f"   total {now():.3f} s", flush=True)

    print("TPU_PREMAPPED_BUFFER_SIZE",
          os.environ.get("TPU_PREMAPPED_BUFFER_SIZE"), flush=True)
    for nint in (1, 51):
        red = RawReducer(nfft=NFFT, nint=nint, chunk_frames=8)
        cb = red._channel_block((SHAPE[0], 8 * NFFT) + SHAPE[2:])
        if TOY:  # two groups, and a link that takes two of them and a half
            cb = SHAPE[0] // 2
            device.host_link_bytes = lambda: int(2.5 * a.nbytes // 2)
        kw = dict(red._channelize_kw, nint=1)
        if nint == 1:
            def run(x):
                return channelize(x, red._coeffs, **kw)
        else:
            acc = jnp.zeros((1, cb * NFFT), jnp.float32)

            def run(x):  # the open integration's new accumulator
                return integrate_carry(channelize(x, red._coeffs, **kw),
                                       acc, np.int32(0), nint=nint)[1]
        print(f"== nint {nint}: {cb} channels per dispatch", flush=True)
        orders(red, cb, run)


if __name__ == "__main__":
    main()
