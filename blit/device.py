"""What this process runs on, and where it keeps compiled programs.

Two facts every entry point needs and none may guess:

- :func:`device_facts` — the platform, device kind and device count as
  JAX reports them.  Every command that runs device code prints them
  with its result, so a CPU run and a chip run never produce the same
  line.
- :func:`use_compile_cache` — the ONE place the persistent compilation
  cache is configured.  The hi-res programs take from seconds (the
  2^20-point Stokes-I channelizer) to minutes (its wide full-Stokes and
  drift-search relatives) to compile; without a cache every
  ``python -m blit`` invocation pays that again.

One process per chip: the first JAX call that needs a device takes the
accelerator for the life of the process, and a second process that needs
the same chip fails or hangs.  Nothing in this module initializes a
backend except :func:`device_facts` and :func:`hbm_bytes_limit`, which
are for processes that run device code anyway.  :func:`named_platform`
and :func:`pallas_interpret` keep the fallbacks honest: a process that
cannot have the chip, or a backend that cannot compile a kernel, fails by
name instead of carrying on slower.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Callable, Dict, Iterator, List, Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The accelerator platform.  On it "auto" resolves to the planar matmul DFT
# and to the compiled Pallas kernels (blit/ops/).
TPU_BACKEND = "tpu"


def pallas_interpret(backend: str) -> bool:
    """Whether Pallas kernels run interpreted on ``backend``.  The TPU
    compiles them; interpret mode exists for the CPU tests, by name.  Any
    other backend raises — it would otherwise interpret a requested kernel
    orders of magnitude slower than the XLA path and say nothing."""
    if backend == TPU_BACKEND:
        return False
    if backend == "cpu":
        return True
    raise ValueError(
        f"Pallas kernels are not supported on backend {backend!r} "
        "(the TPU compiles them; the CPU interprets them for tests)"
    )


def named_platform() -> Optional[str]:
    """The platform a process of this host is held to BY NAME, without
    touching JAX: what ``JAX_PLATFORMS`` says, else ``"tpu"`` when the PCI
    bus lists Google accelerator chips, else ``None`` (no accelerator to
    lose).  Left to choose for itself, JAX falls back to the CPU without
    an error when the chip is already taken; a process pinned by name
    fails instead."""
    named = os.environ.get("JAX_PLATFORMS")
    if named:
        return named
    import glob

    for vendor in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor) as f:
                if f.read().strip() == "0x1ae0":  # Google's PCI vendor id
                    return TPU_BACKEND
        except OSError:
            continue
    return None


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing is set in code — whoever runs the program places the cache.
    Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored):
    a fixed path, because the path is part of the cache key and a
    directory that moves never hits.  Initializes no backend.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_facts() -> Dict:
    """``{"platform", "device_kind", "device_count"}`` of this process's
    default backend (initializes it)."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def hbm_bytes_limit() -> Optional[int]:
    """Bytes of device memory one device of the default backend may hold,
    or ``None`` where the backend does not say (the CPU backend)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return None if limit is None else int(limit)


# libtpu premaps (pins) this much host memory for transfers when
# TPU_PREMAPPED_BUFFER_SIZE does not say otherwise.
_TPU_PREMAPPED_DEFAULT = 4 << 30


def host_link_bytes() -> Optional[int]:
    """Bytes the runtime's host<->device transfer path takes at speed at
    one time, or ``None`` where the backend stages nothing (the CPU).

    The TPU runtime stages every transfer through a premapped host region
    (``TPU_PREMAPPED_BUFFER_SIZE``, libtpu's own variable; 4 GiB unless
    set).  A transfer ENQUEUED while the region is taken goes another way
    and crawls: on a v5e a 2.95 GB chunk lands in 0.4 s alone and in 7 s
    when it is dispatched behind another (PERF.md section 6, PR 25: at
    12 GiB both land in 0.5 s).  :class:`HostLink` keeps what the process
    has in flight under this."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    try:
        return int(os.environ["TPU_PREMAPPED_BUFFER_SIZE"])
    except (KeyError, ValueError):
        return _TPU_PREMAPPED_DEFAULT


def _landed(handle) -> bool:
    """Is everything in ``handle`` (an array or a tree of them) ready?  (A
    deleted array is asked nothing: its ``is_ready()`` crashes the
    process.)"""
    import jax

    return all(a.is_deleted() or a.is_ready()
               for a in jax.tree_util.tree_leaves(handle))


_FETCH_DEBIT = 2  # a fetch counts twice (HostLink)


class HostLink:
    """The process's byte budget for the host<->device link, drawn on per
    transfer (ISSUE 27): a transfer of ``n`` staging bytes is admitted
    only while what is in flight plus ``n`` stays under
    :func:`host_link_bytes`, so nothing is ever enqueued behind a full
    region.  Dispatcher and readback threads draw on the one budget.

    - :meth:`put` is an H2D transfer: admit, then ``jax.device_put``.  Its
      bytes stay in flight until a handle is ready: the put array or,
      with ``then``, what the program that consumes it returned.  Both
      were measured to keep a recorder-width stream at speed (PERF.md
      section 6, PR 27).  The budget holds its handle until then, so the
      pump names the program's output (which it keeps anyway): a group's
      voltages leave device memory with their program, not at a later
      admit.
    - :meth:`fetch` brackets a D2H transfer: admitted like a put, in
      flight while it runs, and debited TWICE its bytes, so a product that
      is large beside the link has it to itself — H2D and D2H take turns,
      as they always did there — and a small one rides along.  Measured
      (PERF.md section 6, PR 27): a 2.1 GB product fetched beside 2.2 GB
      of puts took 1.5-4.5 s instead of 0.65 s, and enqueued blind behind
      4 GB of them 2.5 s.
    - An admit that must wait does so in the caller's ``wait.link`` (a
      :class:`blit.observability.StageWait`: blocked seconds only), on the
      oldest put in flight, or on a fetch of another thread.  A transfer larger
      than the link is admitted alone.  What is in flight after each
      admit is the observation ``link.inflight_bytes`` (its ``max`` is
      the pass's peak).
    - Where the backend stages nothing (``host_link_bytes()`` is ``None``:
      the CPU) everything is admitted at once and nothing is kept.
    """

    def __init__(self) -> None:
        self._cv = threading.Condition()
        self._puts: List = []  # (handle, bytes) in flight, oldest first
        self._bytes = 0        # in flight: the puts' and the running fetches'

    def _retire(self) -> None:
        """Drop the puts that have landed (under ``_cv``)."""
        flying = []
        for handle, nbytes in self._puts:
            if _landed(handle):
                self._bytes -= nbytes
            else:
                flying.append((handle, nbytes))
        self._puts = flying

    def _admit(self, nbytes: int, timeline) -> bool:
        """Block until ``nbytes`` fit, then count them in flight.  False
        where the backend has no link to budget."""
        import jax

        if timeline is not None:
            timeline.declare("wait.link")
        link = host_link_bytes()
        if link is None:
            return False
        wait = (timeline.wait("wait.link") if timeline is not None
                else contextlib.nullcontext())
        with wait as w, self._cv:
            while True:
                self._retire()
                if not self._bytes or self._bytes + nbytes < link:
                    break
                if w is not None:
                    w.block()
                if not self._puts:  # a fetch on another thread holds it
                    self._cv.wait(timeout=0.2)
                    continue
                oldest = self._puts[0][0]
                self._cv.release()
                try:
                    jax.block_until_ready(oldest)
                finally:
                    self._cv.acquire()
            self._bytes += nbytes
            if timeline is not None:
                timeline.observe("link.inflight_bytes", self._bytes)
        return True

    def _release(self, nbytes: int) -> None:
        with self._cv:
            self._bytes -= nbytes
            self._cv.notify_all()

    def put(self, host, device=None, timeline=None,
            then: Optional[Callable] = None):
        """``jax.device_put(host, device)`` through the budget, or with
        ``then`` what that program returns for what was put.  ``host`` is
        an array, or a tree of arrays admitted as one transfer (a stream's
        head with its first samples).  The ``device_put`` alone — after
        the admit, before ``then`` — is the part ``link.put`` of
        ``timeline`` (``calls`` = arrays, ``bytes`` = host->device bytes,
        seconds = what the call held its caller for), budgeted backend or
        not: the row says what a reduction sent up, whatever the link
        made of it.  The handle kept in flight must outlive its transfer:
        a program that takes a put array by donation hands back something
        else (``then``'s result), never the array."""
        import jax

        leaves = jax.tree_util.tree_leaves(host)
        nbytes = sum(a.nbytes for a in leaves)
        counted = self._admit(nbytes, timeline)
        try:
            with (timeline.part("link.put", nbytes, calls=len(leaves))
                  if timeline is not None else contextlib.nullcontext()):
                out = jax.device_put(host, device)
            if then is not None:
                out = then(out)
        except BaseException:
            if counted:
                self._release(nbytes)
            raise
        if counted:
            with self._cv:
                self._puts.append((out, nbytes))
        return out

    @contextlib.contextmanager
    def fetch(self, nbytes: int, timeline=None) -> Iterator[None]:
        """A device->host transfer of ``nbytes``, in flight for the body
        (at twice its bytes: class docstring)."""
        counted = self._admit(_FETCH_DEBIT * nbytes, timeline)
        try:
            yield
        finally:
            if counted:
                self._release(_FETCH_DEBIT * nbytes)

    @staticmethod
    def fetch_takes_all(nbytes: int) -> bool:
        """Does a fetch of ``nbytes`` leave the link no room for a put?"""
        link = host_link_bytes()
        return link is not None and _FETCH_DEBIT * nbytes >= link

    def retire(self) -> None:
        """Let go of the puts that have landed: what a caller does with
        one it has waited in and is about to DONATE (:meth:`put`: a handle
        the budget holds is never donated)."""
        with self._cv:
            self._retire()

    def inflight_bytes(self) -> int:
        """Bytes in flight now (what has landed is let go of first)."""
        with self._cv:
            self._retire()
            return self._bytes


_HOST_LINK = HostLink()


def host_link() -> HostLink:
    """The process-wide :class:`HostLink` (the link is the process's, not
    a reducer's)."""
    return _HOST_LINK
