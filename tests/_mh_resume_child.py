"""Child process for the 2-process RESUMABLE mesh-writer pod test
(tests/test_multiprocess.py): the pod-wide restart-offset agreement of
``reduce_scan_mesh_to_files(resume=True)`` executed for real under
``jax.distributed``.

Run as: ``python tests/_mh_resume_child.py <pid> <nproc> <port> <outdir>``.

Phases:

1. clean run → golden per-band products;
2. run with band_stream crashing on its 3rd call → both processes leave
   per-band cursor sidecars (symmetric: same call count on every
   process);
3. resume → must complete, drop the sidecars, and byte-match the golden;
4. run where the two processes crash in the SAME window's writer flush
   but on OPPOSITE sides of the append — rank 0 before writing, rank 1
   after — leaving cursors that genuinely DISAGREE (the scenario the
   pod-wide MIN agreement exists for; VERDICT r4 weak item 5).  The
   crash site is the host-side writer, after the iteration's collectives
   have been dispatched on both ranks, so no process is left blocked in
   a collective the other never joins;
5. resume → every rank must restart at the window-aligned MIN of BOTH
   cursors (asserted via the writer's start_rows on each rank: rank 1
   truncates its extra window), complete, and byte-match the golden.
"""

import os
import sys


def main() -> None:
    pid, nproc, port, outdir = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    import jax

    jax.config.update("jax_platforms", "cpu")

    from blit.parallel.multihost import init_multihost, local_players

    active = init_multihost(
        coordinator_address=f"localhost:{port}",
        num_processes=nproc,
        process_id=pid,
        cpu_collectives="gloo",
    )
    assert active and jax.process_count() == nproc

    # Bring-up barrier marker (tests/test_multiprocess.py).
    from blit.testing import signal_ready

    signal_ready(outdir, pid)

    from blit.parallel import mesh as M
    from blit.parallel.scan import reduce_scan_mesh_to_files
    from blit.testing import synth_raw

    NBAND, NBANK, NFFT, NINT, NCHAN = 2, 4, 32, 2, 2
    mesh = M.make_mesh(NBAND, NBANK)
    local = sorted(local_players(mesh))

    priv = os.path.join(outdir, f"proc{pid}")
    os.makedirs(priv, exist_ok=True)
    bank_bw = -187.5 / NBANK
    paths = [
        [os.path.join(priv, f"blc{b}{k}.raw") for k in range(NBANK)]
        for b in range(NBAND)
    ]
    for b, k in local:
        synth_raw(
            paths[b][k], nblocks=2, obsnchan=NCHAN, ntime_per_block=512,
            seed=b * 8 + k, tone_chan=k % NCHAN, obsbw=bank_bw,
            obsfreq=8000.0 + b * 500.0 + (k + 0.5) * bank_bw,
        )

    def run(tag, resume):
        d = os.path.join(priv, tag)
        os.makedirs(d, exist_ok=True)
        return d, reduce_scan_mesh_to_files(
            paths, out_dir=d, nfft=NFFT, nint=NINT, despike=False,
            window_frames=4, resume=resume, mesh=mesh,
        )

    # 1. Clean golden.
    gdir, gwritten = run("golden", resume=False)

    # 2. Symmetric crash on the 3rd window (same call count on every
    #    process — the loop is lockstep).
    real = M.band_stream
    calls = []

    def flaky(*a, **kw):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("synthetic pod crash")
        return real(*a, **kw)

    M.band_stream = flaky
    crashed = False
    try:
        run("res", resume=True)
    except RuntimeError:
        crashed = True
    M.band_stream = real
    assert crashed and len(calls) == 3, (
        "the injected 3rd-window crash did not fire (calls=%d) — the test "
        "would otherwise degrade to resume-from-zero" % len(calls)
    )
    rdir = os.path.join(priv, "res")
    cursors = [p for p in os.listdir(rdir) if p.endswith(".cursor")]
    assert cursors, "no cursor sidecars after the crash"

    # 3. Resume: completes, cleans up, matches golden byte-for-byte.
    _, written = run("res", resume=True)
    assert not any(p.endswith(".cursor") for p in os.listdir(rdir))
    for band, (path, hdr) in written.items():
        assert open(path, "rb").read() == open(gwritten[band][0], "rb").read(), (
            f"resumed band {band} != golden"
        )

    # 4. ASYMMETRIC crash: both ranks raise in the 3rd writer flush, but
    #    rank 0 before the append and rank 1 after it — cursors end up
    #    claiming different window counts.
    import json
    import time

    import blit.pipeline as P

    real_append = P.ResumableFilWriter.append
    flushes = []

    def skewed_append(self, slab):
        flushes.append(1)
        if len(flushes) == 3:
            if pid == 0:
                raise RuntimeError("asym crash before append")
            real_append(self, slab)
            raise RuntimeError("asym crash after append")
        return real_append(self, slab)

    P.ResumableFilWriter.append = skewed_append
    crashed = False
    try:
        run("asym", resume=True)
    except RuntimeError:
        crashed = True
    P.ResumableFilWriter.append = real_append
    assert crashed and len(flushes) == 3

    # Host-side barrier (both ranks are mid-failure; no collectives):
    # sentinel files signal "my cursor is on disk".
    adir = os.path.join(priv, "asym")
    open(os.path.join(outdir, f"crashed{pid}"), "w").close()
    other = os.path.join(outdir, f"crashed{1 - pid}")
    deadline = time.time() + 60
    while not os.path.exists(other):
        assert time.time() < deadline, "peer never crashed"
        time.sleep(0.05)

    def cursor_frames(rank, band):
        p = os.path.join(outdir, f"proc{rank}", "asym",
                         f"band{band}.fil.cursor")
        return json.load(open(p))["frames_done"]

    mine_frames = cursor_frames(pid, pid)  # rank r owns band r here
    peer_frames = cursor_frames(1 - pid, 1 - pid)
    rank0_frames = mine_frames if pid == 0 else peer_frames
    rank1_frames = peer_frames if pid == 0 else mine_frames
    assert rank0_frames < rank1_frames, (
        f"cursors must disagree: rank0 crashed pre-append, rank1 post-"
        f"append (got rank0={rank0_frames} rank1={rank1_frames})"
    )

    # 5. Resume: every rank restarts at the window-aligned MIN of both
    #    cursors — rank 1 must truncate its extra window.
    WF = 4  # window_frames in run()
    expected_rows = (min(mine_frames, peer_frames) // WF) * WF // NINT
    starts = []
    real_init = P.ResumableFilWriter.__init__

    def spying_init(self, path, header, nif, nchans, start_rows, nint,
                    cursor, **kw):  # kw: the scan's timeline (ISSUE 36)
        starts.append(start_rows)
        real_init(self, path, header, nif, nchans, start_rows, nint, cursor,
                  **kw)

    P.ResumableFilWriter.__init__ = spying_init
    try:
        _, awritten = run("asym", resume=True)
    finally:
        P.ResumableFilWriter.__init__ = real_init
    assert starts == [expected_rows], (
        f"rank {pid} restarted at {starts}, pod MIN demands "
        f"{expected_rows} rows"
    )
    assert not any(p.endswith(".cursor") for p in os.listdir(adir))
    for band, (path, hdr) in awritten.items():
        assert open(path, "rb").read() == open(gwritten[band][0], "rb").read(), (
            f"asym-resumed band {band} != golden"
        )
    print("CHILD-RESUME-OK", flush=True)


if __name__ == "__main__":
    main()
