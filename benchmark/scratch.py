"""Where a run may write, asked of the machine before anything is written.

Copied from ``chip_smoke.py`` (PR 21), which learned it the hard way: the
driver's chip machine caps the size of one file and the chip tool's does
not.  The benchmark sizes a pass from the answer and prints every cut as
``reduced``; it cuts duration, never width.
"""

from __future__ import annotations

import errno
import os
import resource
import shutil
import tempfile

FIL_HEADER_ROOM = 4096   # a SIGPROC header is a few hundred bytes
RAW_HEADER_ROOM = 4096   # so is one RAW block's card header


def raise_file_limit() -> None:
    """Soft RLIMIT_FSIZE up to the hard one: the run asks for nothing the
    machine's owner withheld."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    if soft != hard:
        try:
            resource.setrlimit(resource.RLIMIT_FSIZE, (hard, hard))
        except (ValueError, OSError):
            pass


def filesystem_of(path: str) -> str:
    """``"<type> on <mount point>"`` of the mount that holds ``path``, so a
    reader can tell a slow program from a slow disk."""
    best = ("", "unknown")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mnt, fstype = line.split()[:3]
                if (path == mnt or path.startswith(mnt.rstrip("/") + "/")) \
                        and len(mnt) > len(best[0]):
                    best = (mnt, fstype)
    except OSError:
        pass
    return f"{best[1]} on {best[0] or '?'}"


def host_facts(roots) -> dict:
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)

    def show(v):
        return "unlimited" if v == resource.RLIM_INFINITY else v

    facts = {"RLIMIT_FSIZE": [show(soft), show(hard)],
             "cpus": os.cpu_count()}
    for root in roots:
        if os.path.isdir(root):
            facts[root] = {"free": shutil.disk_usage(root).free,
                           "fs": filesystem_of(root)}
    return facts


def max_file_bytes(directory: str, want: int) -> int:
    """The largest single file, up to ``want`` bytes, this process may
    write in ``directory``.  A sparse ``ftruncate`` meets the checks a
    ``write`` at that offset meets (RLIMIT_FSIZE, the filesystem's own
    maximum) and moves no data."""
    with tempfile.TemporaryFile(dir=directory) as f:

        def allowed(n: int) -> bool:
            try:
                os.ftruncate(f.fileno(), n)
            except OSError as e:
                if e.errno not in (errno.EFBIG, errno.EINVAL):
                    raise
                return False
            os.ftruncate(f.fileno(), 0)
            return True

        if allowed(want):
            return want
        lo, hi = 0, want  # allowed(lo), not allowed(hi)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            lo, hi = (mid, hi) if allowed(mid) else (lo, mid)
        return lo


def scratch_dir(roots, need_bytes: int, file_bytes: int):
    """A fresh directory outside the checkout under the first of ``roots``
    that has ``need_bytes`` free and allows one file of ``file_bytes``
    (failing that, the root that allows the largest file).  Returns
    ``(directory, largest file allowed up to file_bytes)``."""
    best = None
    for root in roots:
        if not (os.path.isdir(root)
                and shutil.disk_usage(root).free > need_bytes):
            continue
        cap = max_file_bytes(root, file_bytes)
        if best is None or cap > best[1]:
            best = (root, cap)
        if cap >= file_bytes:
            break
    if best is None:
        raise RuntimeError(f"no scratch with {need_bytes} B free in "
                           f"{list(roots)}: {host_facts(roots)}")
    return tempfile.mkdtemp(prefix="blit-bench-", dir=best[0]), best[1]
