"""blit — TPU-native Breakthrough Listen distributed data-product framework.

A brand-new, TPU-first (JAX/XLA/Pallas/pjit) framework with the capabilities of
the reference package ``BLDistributedDataProducts.jl`` (see ``SURVEY.md``):
distributed discovery, access, and reduction of Breakthrough Listen datasets
recorded across the BL@GBT cluster's ``(band, bank)`` node topology.

Layer map (mirrors SURVEY.md §1, rebuilt TPU-first):

- ``blit.gbt``       — main-process orchestration API (reference: src/gbt.jl).
- ``blit.workers``   — per-worker access functions (reference:
  src/gbtworkerfunctions.jl), host-side Python.
- ``blit.io``        — SIGPROC filterbank / FBH5 / GUPPI RAW codecs (reference:
  Blio.jl + HDF5.jl + H5Zbitshuffle.jl dependency layer).
- ``blit.ops``       — JAX/Pallas compute: fqav, kurtosis, despike, dequant,
  PFB channelizer, large staged FFT, Stokes detect.
- ``blit.parallel``  — the (band, bank) ``jax.sharding.Mesh``, worker pools,
  all_gather band stitching, psum beamforming, FX correlation.
- ``blit.pipeline``  — GUPPI RAW → high-resolution filterbank reduction driver.
- ``blit.faults``    — deterministic fault injection + recovery policy
  (transient-I/O retry, circuit breakers, degradation counters).
- ``blit.outplane``  — the asynchronous output plane: overlapped
  device→host readback (OutputRotation) and write-behind product sinks
  (AsyncSink) behind every streaming driver.
- ``blit.serve``     — the product service layer: priority scheduler with
  admission control, single-flight request coalescing, two-tier
  content-addressed result cache.
- ``blit.search``    — the search plane: on-device Taylor-tree
  drift-rate search (``.hits`` products alongside ``.fil``/``.h5``),
  windowed feeds + device-side threshold/top-k + ragged async hit sink.
- ``blit.stream``    — the streaming ingest plane: chunk sources
  (growing-file tailer / paced replay / queue), watermark-based
  windowing with zero-weight late/missing-chunk masking, and
  ``stream_reduce``/``stream_search`` live entry points byte-identical
  to the batch paths.
- ``blit.observability`` — the telemetry plane: spans/tracer with fan-out
  context propagation, stage timelines + log-bucketed histograms, fleet
  telemetry harvest, and the crash/stall flight recorder.
- ``blit.monitor``  — the live monitoring & SLO plane: the background
  metrics publisher (interval snapshots → spool JSONL + ``/metrics``/
  ``/healthz``/``/snapshot`` HTTP endpoint), multi-window burn-rate SLO
  evaluation with load-shed breach actions, and the ``blit top``
  terminal dashboard.
- ``blit.hostmem``   — pinned host staging: page-aligned slab allocation
  and the process-wide staging pool behind the chunk rotations and
  readback rings.
"""

from blit.version import __version__

__all__ = [
    "__version__",
    "ProductService",
    "ProductRequest",
    "ProductCache",
    "Scheduler",
    "Overloaded",
    "FleetFrontDoor",
    "DedopplerReducer",
    "Hit",
    "stream_reduce",
    "stream_search",
]

# The serving layer's front-door names re-export from blit.serve (lazily —
# `import blit` must stay light for the worker agents).
_SERVE_EXPORTS = (
    "ProductService",
    "ProductRequest",
    "ProductCache",
    "Scheduler",
    "Overloaded",
    "FleetFrontDoor",
)

# The search plane's front-door names re-export from blit.search (lazily —
# the drift kernels pull jax, which `import blit` must not).
_SEARCH_EXPORTS = (
    "DedopplerReducer",
    "Hit",
)

# The streaming ingest plane's front-door names re-export from
# blit.stream (lazily — the plane pulls the reducers, which pull jax).
_STREAM_EXPORTS = (
    "stream_reduce",
    "stream_search",
)


def __getattr__(name):
    if name in _SERVE_EXPORTS:
        import importlib

        return getattr(importlib.import_module("blit.serve"), name)
    if name in _SEARCH_EXPORTS:
        import importlib

        return getattr(importlib.import_module("blit.search"), name)
    if name in _STREAM_EXPORTS:
        import importlib

        return getattr(importlib.import_module("blit.stream"), name)
    # Lazy submodule access (keeps `import blit` light; JAX-dependent modules
    # only load when touched).
    if name in (
        "gbt",
        "workers",
        "io",
        "ops",
        "parallel",
        "pipeline",
        "inventory",
        "naming",
        "config",
        "testing",
        "faults",
        "outplane",
        "serve",
        "search",
        "stream",
        "observability",
        "monitor",
        "hostmem",
    ):
        import importlib

        try:
            return importlib.import_module(f"blit.{name}")
        except ModuleNotFoundError as e:
            if e.name == f"blit.{name}":
                # PEP 562: absent submodule surfaces as AttributeError (so
                # hasattr() works); genuine dependency failures inside an
                # existing submodule re-raise unmasked.
                raise AttributeError(
                    f"module 'blit' has no attribute {name!r}"
                ) from e
            raise
    raise AttributeError(f"module 'blit' has no attribute {name!r}")
