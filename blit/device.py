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

import os
from typing import Dict, Optional

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
    12 GiB both land in 0.5 s).  Callers keep what they have in flight
    under this."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    try:
        return int(os.environ["TPU_PREMAPPED_BUFFER_SIZE"])
    except (KeyError, ValueError):
        return _TPU_PREMAPPED_DEFAULT
