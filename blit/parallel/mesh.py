"""The TPU data plane: the BL@GBT ``(band, bank)`` topology as a device mesh.

SURVEY.md §2.4/§5: the reference's only parallelism is frequency-domain
sharding — 8 banks each own a contiguous 187.5 MHz slice of a 1500 MHz band,
and the sole cross-node reduction (band stitching) runs as a main-process
``vcat`` in the commented-out ``loadscan`` (src/gbt.jl:103).  Here the
topology is a ``jax.sharding.Mesh`` with axes ``('band', 'bank')``, each chip
plays one ``BLP<band><bank>`` player, the frequency axis is sharded over
``bank``, and the stitch is an ``all_gather`` over ICI — no host
materialization anywhere (BASELINE.json config 3).

Everything is built on ``shard_map`` so the collectives are explicit and the
per-chip body is exactly the single-chip reduction from
:mod:`blit.ops.channelize` — one code path from 1 chip to a 64-chip pod.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from blit.device import host_link
from blit.observability import Timeline
from blit.ops.channelize import (
    _STREAM_STATIC,
    channelize,
    head_step,
    integrate_carry,
    stream_step,
)
from blit.ops.despike import despike

BAND_AXIS = "band"
BANK_AXIS = "bank"

# -- partition rules ---------------------------------------------------------
#
# Every array role of the sharded reduction plane (ISSUE 9) names its
# PartitionSpec HERE, in one registry, instead of each call site hand-rolling
# specs: the feed (`put_local_shards`), the fold accumulators
# (:class:`ShardedAccumulator` — beamform/correlate carry these specs across
# donated windows), and the product/readback side all resolve through
# `partition_rule`, so a layout change is one edit and the specs cannot
# drift between the dispatch and the readback that interprets its shards.

PARTITION_RULES: Dict[str, P] = {
    # Ingest: int8 voltage blocks (nband, nbank, nchan, ntime, npol, 2) —
    # one (band, bank) block per chip.
    "voltages": P(BAND_AXIS, BANK_AXIS),
    # Replicated small operands (PFB coefficient banks, thresholds).
    "replicated": P(),
    # Products (nband, ntime, nif, nchans): channel axis sharded over bank
    # (pre-stitch), or replicated across each band row (post-stitch).
    "filterbank_sharded": P(BAND_AXIS, None, None, BANK_AXIS),
    "filterbank_stitched": P(BAND_AXIS, None, None, None),
    # Packed per-chip hit tables (nband, nbank, nbands, k, 4) — the search
    # plane's device-side extraction output (blit/ops/pallas_dedoppler).
    "packed_hits": P(BAND_AXIS, BANK_AXIS),
    # Fold accumulators (donated across windows).  The beamform total-power
    # accumulator is psum output, replicated; the correlator's partial
    # visibilities stay band-sharded (leading band block axis) with the
    # channel axis over bank — standard (nband, a, b, c, f, p, q) vs packed
    # (nband, c, f, a, p, b, q) layouts.
    "beamform_acc": P(),
    "vis_acc_standard": P(BAND_AXIS, None, None, BANK_AXIS),
    "vis_acc_packed": P(BAND_AXIS, BANK_AXIS),
    # The scan's open integration (nband, nif, nchans): each chip holds
    # its own bank's partial sum from window to window (band_carry) and
    # nothing of it is gathered until a row closes.
    "integration_acc": P(BAND_AXIS, None, BANK_AXIS),
    # The scan's filter state (nband, nbank, nchan, (ntap-1)*nfft), one
    # word a sample: each chip holds the last ntap-1 frames' worth of its
    # own bank's samples from window to window (band_stream); it comes up
    # from the host once per stream, as the stream's head.
    "filter_state": P(BAND_AXIS, BANK_AXIS),
    # A small-nfft leg's power and open integration in channelize_lanes'
    # layout (frames on the lanes): ``(nband, nint, C, nif, nfft, c,
    # groups)`` and ``(nband, C, nif, nfft, c)``, the coarse channels in
    # ``C`` sublane-fulls of ``c``, sharded over bank like every product.
    "lanes_power": P(BAND_AXIS, None, BANK_AXIS),
    "lanes_acc": P(BAND_AXIS, BANK_AXIS),
}

# The collective-latency histograms of the sharded plane (ISSUE 9): every
# honestly-timeable collective observes into these Timeline hists.
MESH_HISTS = ("mesh.gather_s", "mesh.psum_s")


def partition_rule(role: Union[str, P]) -> P:
    """The registry's PartitionSpec for ``role`` (a spec passes through —
    callers that already hold one can use the same entry points)."""
    if isinstance(role, str):
        try:
            return PARTITION_RULES[role]
        except KeyError:
            raise KeyError(
                f"unknown partition rule {role!r}; known roles: "
                f"{sorted(PARTITION_RULES)}"
            ) from None
    return role


def sharding_for(mesh: Mesh, role: Union[str, P]) -> NamedSharding:
    """``NamedSharding`` of ``role`` on ``mesh`` (partition-rule-driven —
    the one way array placement is spelled on the sharded plane)."""
    return NamedSharding(mesh, partition_rule(role))


def gather_ici_bytes(shard_bytes: int, axis_size: int) -> int:
    """Per-chip ICI bytes one ``all_gather`` moves: each chip RECEIVES
    every other shard of its axis row — ``(axis_size - 1) * shard_bytes``
    (ring schedule; send volume is the same, counted once)."""
    return max(0, axis_size - 1) * shard_bytes


def psum_ici_bytes(nbytes: int, axis_size: int) -> int:
    """Per-chip ICI bytes one ``psum`` moves for an ``nbytes`` operand:
    ring all-reduce = reduce-scatter + all-gather, ``2 * (n-1)/n *
    nbytes`` received per chip."""
    if axis_size <= 1:
        return 0
    return int(2 * (axis_size - 1) * nbytes // axis_size)


def record_ici(timeline, collective: str, nbytes: int,
               seconds: Optional[float] = None) -> None:
    """Account one collective on a Timeline (ISSUE 9 telemetry contract):
    cumulative per-chip ICI traffic on the ``mesh.ici`` stage, a
    per-dispatch byte histogram (``mesh.<collective>_ici_bytes``), and —
    when the caller could honestly time the collective's own dispatch
    (a probe window, the correlator's closing psum, the bench's pure
    collective legs) — a latency sample into ``mesh.<collective>_s``
    (:data:`MESH_HISTS`).  ``collective`` is ``"gather"`` or ``"psum"``."""
    s = timeline.stages["mesh.ici"]
    s.calls += 1
    s.bytes += int(nbytes)
    timeline.observe(f"mesh.{collective}_ici_bytes", float(nbytes))
    if seconds is not None:
        s.seconds += seconds
        timeline.observe(f"mesh.{collective}_s", seconds)


def make_mesh(
    nband: int = 1, nbank: int = 8, devices: Optional[list] = None
) -> Mesh:
    """A ``(band, bank)`` mesh over the first ``nband*nbank`` devices.

    The bank axis should ride ICI (it carries the stitch/beamform
    collectives); keeping it minor in the device order does that on TPU
    slices, mirroring how the racks' 8 banks share a 1500 MHz IF
    (README.md:17-24).
    """
    if devices is None:
        devices = jax.devices()
    n = nband * nbank
    if len(devices) < n:
        raise ValueError(f"need {n} devices for a {nband}x{nbank} mesh, "
                         f"have {len(devices)}")
    dev = np.asarray(devices[:n]).reshape(nband, nbank)
    return Mesh(dev, (BAND_AXIS, BANK_AXIS))


def voltage_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a global voltage array ``(nband, nbank, nchan, ntime,
    npol, 2)``: one (band, bank) block per chip."""
    return sharding_for(mesh, "voltages")


def filterbank_sharding(mesh: Mesh, stitched: bool) -> NamedSharding:
    """Sharding of the reduced product ``(nband, ntime, nif, nchans)``:
    channel axis sharded over ``bank`` (unstitched) or replicated across the
    bank axis (stitched)."""
    return sharding_for(
        mesh, "filterbank_stitched" if stitched else "filterbank_sharded"
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "nfft", "ntap", "nint", "stokes", "fft_method", "stitch",
        "despike_nfpc", "fqav_by", "dtype",
    ),
)
def band_reduce(
    voltages: jax.Array,
    coeffs: jax.Array,
    *,
    mesh: Mesh,
    nfft: int,
    ntap: int = 4,
    nint: int = 1,
    stokes: str = "I",
    fft_method: str = "auto",
    stitch: bool = True,
    despike_nfpc: int = 0,
    fqav_by: int = 1,
    dtype: str = "float32",
) -> jax.Array:
    """The full multi-chip reduction step: every chip channelizes its own
    bank's voltage block, then the 8 banks of each band stitch their fine
    spectra into a contiguous band over ICI.  ``nint`` integrates INSIDE
    this one program, so it has to fit the block; an integration longer
    than a window is the caller's to carry: the step at ``nint=1,
    stitch=False`` (no collective), :func:`band_carry` per chip, and
    :func:`stitch_despike` only for rows that closed
    (:func:`blit.parallel.scan.reduce_scan_mesh_to_files`, whose windows
    run :func:`band_stream`: this step with the filter state left on the
    chips).

    Args:
      voltages: int8 ``(nband, nbank, nchan, ntime, npol, 2)``, sharded with
        :func:`voltage_sharding` (one leading block per chip).
      stitch: gather the bank-sharded channel axis into a contiguous band on
        every chip of the band row (``all_gather`` over ``bank`` — the ICI
        rebuild of the reference's main-process ``vcat``, src/gbt.jl:103).
        When False the product stays frequency-sharded (the SP-like layout)
        and no collective runs at all.
      despike_nfpc: if >= 2, repair each coarse channel's DC fine channel
        post-stitch (src/gbt.jl:101-111 semantics, vectorized).  In OUTPUT
        channel units: with ``fqav_by > 1`` pass ``nfft // fqav_by``.
      fqav_by: on-device frequency-averaging epilogue applied per chip
        BEFORE the stitch collective — the reference's reduce-before-the-
        wire lever (src/gbtworkerfunctions.jl:16-20) mapped onto ICI: the
        all_gather moves ``fqav_by``x fewer bytes.
      dtype: working dtype of the per-chip channelizer stages ("float32"
        | "bfloat16") — the single-chip pipeline's biggest measured lever
        (DESIGN.md §3: bf16 stages halve the HBM intermediates and run
        the official bench), now reachable from the mesh path too.  The
        product stays float32 either way.

    Returns:
      float32 ``(nband, ntime_out, nif, nchans)`` where ``nchans`` is the
      full band (stitched) or the global concatenation of per-bank channels
      (unstitched, sharded over ``bank``).
    """
    def step(v, h):
        # v: (1, 1, nchan, ntime, npol, 2) — this chip's block.
        out = channelize(
            v[0, 0], h, nfft=nfft, ntap=ntap, nint=nint, stokes=stokes,
            fft_method=fft_method, fqav_by=fqav_by, dtype=dtype,
        )  # (t, nif, nchan*nfft//fqav_by)
        return _band_product(out, stitch, despike_nfpc)

    # check_vma=False on both branches: the varying-mesh-axes analysis
    # cannot see that all_gather's output is bank-invariant, and it
    # rejects the Pallas kernels the per-chip channelize resolves to on the
    # TPU (their out_shape carries no vma) — the check must not pass only
    # on the XLA path the CPU takes.
    return jax.shard_map(
        step, mesh=mesh, in_specs=(partition_rule("voltages"), P()),
        out_specs=_product_rule(stitch), check_vma=False,
    )(voltages, coeffs)


def _product_rule(stitch: bool) -> P:
    """The layout of ``band_reduce`` / ``band_stream``'s product."""
    return partition_rule(
        "filterbank_stitched" if stitch else "filterbank_sharded")


def _band_product(out: jax.Array, stitch: bool, despike_nfpc: int):
    """One chip's ``(t, nif, nchans)`` spectra as its block of the band
    product (inside ``shard_map``): gathered over ``bank`` where
    ``stitch``, DC spikes repaired where ``despike_nfpc >= 2`` (coarse
    channels never straddle banks, so the per-bank despike is exact in
    the sharded layout too)."""
    if stitch:
        out = jax.lax.all_gather(out, BANK_AXIS, axis=2, tiled=True)
    if despike_nfpc >= 2:
        out = despike(out, despike_nfpc)
    return out[None]  # leading band axis block


def _power_rule(stitch: bool, lanes: int) -> P:
    """The layout of a leg's program output: the band product, or the
    small-nfft path's power (never stitched: its rows are, once folded)."""
    return partition_rule("lanes_power") if lanes else _product_rule(stitch)


@functools.lru_cache(maxsize=None)
def band_programs(name: str):
    """``(step, head)``, the two programs of one leg of a band STREAM,
    under ``jit_<name>`` in a device trace (a leg whose seconds are to be
    read apart has a name of its own): per chip the bodies of
    :func:`blit.ops.channelize.leg_programs` — the one-chip reducer's own
    steps, not a second implementation — under ``shard_map``, each chip on
    its own bank, with no collective but the product's own stitch.

    ``step(tail, body, coeffs, *, mesh, stitch=True, despike_nfpc=0,
    frames=None, lanes=0, **kw)`` is one window:
    :func:`blit.ops.channelize.stream_step` of the first ``frames`` frames
    (all, by default) of each chip's ``concat(tail, body)`` — ``tail``
    ``(nband, nbank, nchan, (ntap-1)*nfft)`` under the ``filter_state``
    rule and ``body`` ``(nband, nbank, nchan, samples)`` under
    ``voltages``, both :func:`blit.ops.channelize.sample_words` (one word
    a sample: the form the host link carries at speed).  ``tail`` is
    DONATED (:class:`ShardedAccumulator`: a bank's filter state is held
    once); ``body`` is not, every leg of the scan reads the same array.
    Returns ``(product, next_tail)``: the product as :func:`band_reduce`
    lays it out (``stitch``, ``despike_nfpc``, ``fqav_by`` as there) or,
    with ``lanes`` (:func:`blit.ops.channelize.lanes_block`), the
    small-nfft path's power under ``lanes_power``; ``next_tail`` the
    ``(ntap-1)*nfft`` words after the frames taken (a body shorter than
    the state — a scan's one-frame last window — keeps part of the old
    tail).

    ``head(words, coeffs, *, mesh, **same)`` is a stream's first step of
    a leg whose ``nfft`` is not the scan's largest: ``words`` is the
    stream's head under ``filter_state`` (the largest leg's filter
    state), to this leg its own shorter state followed by data
    (:func:`blit.ops.channelize.head_step`); it is not donated — its
    owner takes it last."""

    def on_each_chip(run, rules, mesh, stitch, despike_nfpc, lanes, kw):
        """``run`` (a leg's per-chip step) under ``shard_map``: its
        blocks under ``rules``, the coefficients replicated."""
        assert not lanes or not (stitch or despike_nfpc)

        def per_chip(*args):
            out, nxt = run(*(a[0, 0] for a in args[:-1]), args[-1],
                           lanes=lanes, **kw)
            return _band_product(out, stitch, despike_nfpc), nxt[None, None]

        return jax.shard_map(
            per_chip, mesh=mesh,
            in_specs=tuple(map(partition_rule, rules)) + (P(),),
            out_specs=(_power_rule(stitch, lanes),
                       partition_rule("filter_state")),
            check_vma=False,  # as band_reduce
        )

    def step(tail, body, coeffs, *, mesh, stitch=True, despike_nfpc=0,
             lanes=0, **kw):
        return on_each_chip(stream_step, ("filter_state", "voltages"), mesh,
                            stitch, despike_nfpc, lanes, kw)(
                                tail, body, coeffs)

    def head(words, coeffs, *, mesh, stitch=True, despike_nfpc=0, lanes=0,
             **kw):
        return on_each_chip(head_step, ("filter_state",), mesh, stitch,
                            despike_nfpc, lanes, kw)(words, coeffs)

    for fn in (step, head):
        fn.__name__ = fn.__qualname__ = name
    static = ("mesh", "stitch", "despike_nfpc") + _STREAM_STATIC
    return (jax.jit(step, static_argnames=static, donate_argnames=("tail",)),
            jax.jit(head, static_argnames=static))


# One window of a band stream that makes ONE product (``blit scan``'s
# program since PR 31, ``jit_band_stream``), and the first leg of one that
# makes several: ``band_stream(tail, body, coeffs, *, mesh, nfft, ntap=4,
# nint=1, stokes="I", fft_method="auto", stitch=True, despike_nfpc=0,
# fqav_by=1, dtype="float32")`` -> ``(product, next_tail)``,
# :func:`band_reduce` of the gross block ``concat(tail, body)`` whose
# filter state never left the chips (:func:`band_programs`).
band_stream = band_programs("band_stream")[0]


@functools.partial(jax.jit,
                   static_argnames=("mesh", "nif", "nchans", "lanes"))
def carry_zeros(*, mesh: Mesh, nif: int = 0, nchans: int = 0,
                lanes: Optional[tuple] = None) -> jax.Array:
    """A scan's integration before its first frame: float32 zeros
    ``(nband, nif, nchans)`` laid out by the ``integration_acc`` rule,
    made on the chips (``nchans`` is the whole band's).  ``lanes``
    instead gives the shape ``(nband, C, nif, nfft, c)`` of a small-nfft
    leg's (rule ``lanes_acc``: a leg's power less its positions and its
    frame groups)."""
    import jax.numpy as jnp

    nbank = mesh.devices.shape[1]
    block = ((1, nif, nchans // nbank) if lanes is None
             else (1, lanes[1] // nbank) + tuple(lanes[2:]))
    return jax.shard_map(
        lambda: jnp.zeros(block, jnp.float32),
        mesh=mesh, in_specs=(),
        out_specs=partition_rule(
            "integration_acc" if lanes is None else "lanes_acc"),
        check_vma=False,
    )()


@functools.partial(jax.jit, static_argnames=("mesh", "nint", "lanes",
                                             "nframes"),
                   donate_argnums=0)
def band_carry(
    acc: jax.Array, power: jax.Array, filled: jax.Array, *, mesh: Mesh,
    nint: int, lanes: bool = False, nframes: Optional[int] = None,
) -> tuple:
    """One window of an integration longer than a window (or straddling
    its boundary), per chip and with NO collective: every chip folds its
    own bank's spectra into its own partial sum
    (:func:`blit.ops.channelize.integrate_carry`, the ONE fold of every
    leg on one chip and on the mesh: frame by frame in stream
    order, ``filled`` a device scalar — one program wherever the row
    boundary falls in the window grid).

    ``power`` is a leg's product at ``nint=1, stitch=False``
    (:func:`band_programs`), ``(nband, nframes, nif, nchans)``,
    bank-sharded; ``acc`` ``(nband,
    nif, nchans)`` float32 under the ``integration_acc`` rule is DONATED
    (:class:`ShardedAccumulator`: the open integration is held once).
    Returns ``(acc, rows)``: ``rows`` ``(nband, (nint - 1 + nframes) //
    nint, nif, nchans)``, still bank-sharded, of which the first ``(filled
    + nframes) // nint`` closed in this window (the rest are zeros) — what
    :func:`stitch_despike` gathers, and only then.  ``rows`` is a fresh
    output of every call, so it is also the token a caller waits on (the
    accumulator is gone with the next fold).

    With ``lanes`` the power is the small-nfft path's (rule
    ``lanes_power``, its first ``nframes`` frames real) and ``acc`` its
    accumulator (``lanes_acc``); the rows come back in the product's
    layout all the same."""

    def fold(a, x, at):
        rows, a = integrate_carry(x[0], a[0], at, nint=nint, lanes=lanes,
                                  nframes=nframes)
        return a[None], rows[None]

    held = partition_rule("lanes_acc" if lanes else "integration_acc")
    return jax.shard_map(
        fold,
        mesh=mesh,
        in_specs=(held, _power_rule(False, lanes), P()),
        out_specs=(held, partition_rule("filterbank_sharded")),
        check_vma=False,  # per-chip fold, no collectives
    )(acc, power, filled)


def stitch_bands(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Standalone stitch: gather a bank-sharded filterbank ``(nband, t, nif,
    nchans_sharded)`` into a contiguous band, replicated across each band's
    banks.  Equivalent to ``band_reduce(..., stitch=True)``'s epilogue; kept
    separate so host-read products (e.g. FBH5 slabs loaded via
    :mod:`blit.gbt`) can be stitched on-device too.  The despike-free case
    of :func:`stitch_despike` — ONE stitch program, not two to keep in
    sync."""
    return stitch_despike(x, mesh=mesh, despike_nfpc=0)


@functools.partial(jax.jit, static_argnames=("mesh", "despike_nfpc"))
def stitch_despike(x: jax.Array, *, mesh: Mesh, despike_nfpc: int = 0):
    """The sharded plane's standalone stitch program: gather a bank-sharded
    filterbank ``(nband, t, nif, nchans_sharded)`` into a contiguous band
    (replicated across each band's banks) and optionally repair the
    per-coarse-channel DC spikes post-stitch.

    This is ``band_reduce(stitch=True)``'s epilogue split into its own
    dispatch so the window loop can TIME the all_gather honestly
    (``mesh.gather_s``) and account its ICI bytes per window — the
    per-chip channelize and the collective land in separate programs,
    with the per-chip program bit-identical to the pool path's
    single-chip ``channelize`` (tests/test_sharded.py pins this)."""

    def gather(blk):
        out = jax.lax.all_gather(blk, BANK_AXIS, axis=3, tiled=True)
        if despike_nfpc >= 2:
            out = despike(out, despike_nfpc)
        return out

    return jax.shard_map(
        gather,
        mesh=mesh,
        in_specs=partition_rule("filterbank_sharded"),
        out_specs=partition_rule("filterbank_stitched"),
        check_vma=False,  # all_gather output is bank-invariant
    )(x)


def shard_voltages(
    voltages: np.ndarray, mesh: Mesh
) -> jax.Array:
    """Place a host ``(nband, nbank, ...)`` voltage array onto the mesh with
    one block per chip (the host→device feed for tests and the dry run)."""
    return jax.device_put(voltages, voltage_sharding(mesh))


def put_local_shards(
    blocks: Dict, mesh: Mesh, global_shape, role: Union[str, P] = "voltages",
    timeline=None,
) -> jax.Array:
    """``jax.device_put`` with shardings, multi-host-shaped: assemble the
    global sharded array for ``role`` from one host block per LOCALLY
    OWNED ``(band, bank)`` player — the sharded plane's replacement for
    the pool path's per-worker H2D scatter.

    ``blocks`` maps ``(band, bank)`` to that player's host block with the
    leading ``(1, 1, ...)`` block axes already present.  Each block goes
    straight onto its chip and the global array is built from the
    single-device shards, so the host never materializes the whole scan
    and no ``device_put`` targets a non-addressable device (the
    multi-process contract of :func:`blit.parallel.scan._put_window`,
    now partition-rule-driven).  Each player's put is a ``feed.put``
    stage of its own (that block's bytes) on ``timeline`` and draws on
    the process's link budget (:class:`blit.device.HostLink`): one that
    would not fit beside those in flight first waits, inside its stage,
    for the oldest to land.  The puts are issued one after the other
    from the calling thread: a put of sample words returns at once and
    the runtime copies the banks side by side behind it (four 0.54 GB
    bodies to four chips land in 0.085 s from one thread and in 0.087 s
    from four; ``tools/probe_mesh_puts.py``).  The budget holds each
    put array until it has landed: a caller that DONATES the result to a
    program first has the budget let go of it (``HostLink.put``)."""
    tl = timeline if timeline is not None else Timeline()
    shards = []
    for (b, k), blk in sorted(blocks.items()):
        with tl.stage("feed.put", blk.nbytes):
            shards.append(host_link().put(blk, mesh.devices[b, k], tl))
    return jax.make_array_from_single_device_arrays(
        tuple(global_shape), sharding_for(mesh, role), shards
    )


class ShardedAccumulator:
    """A windowed fold accumulator that CARRIES its partition rule
    (ISSUE 9 tentpole): the value pytree, the mesh, and the
    :data:`PARTITION_RULES` entry that shards it travel together, so
    every fold dispatch and the final readback agree on placement by
    construction.

    Contract (the ``correlate_stream`` fold discipline, generalized):

    - :meth:`init` installs the first window's value (already sharded by
      the producing program — its out_specs must match this rule).
    - :meth:`fold` applies a caller-jitted fold whose FIRST argument is
      the current value, **donated** (``donate_argnums=0`` on the
      caller's jit): HBM is reused in place across the whole stream and
      the accumulator never exists twice.  The fold's out_specs must
      preserve the rule — :meth:`fold` asserts the returned sharding
      still matches, so a drifted spec fails loudly at the first window
      instead of silently regathering every fold.
    - :meth:`fold_aux` is :meth:`fold` for a step that also returns a
      product (``(aux, value)``: the scan's stream program).
    - :attr:`value` holds the live pytree; ``spec``/``sharding`` expose
      the rule for finish programs (the correlator's closing band psum).
    """

    def __init__(self, mesh: Mesh, rule: Union[str, P]):
        self.mesh = mesh
        self.rule = rule
        self.spec = partition_rule(rule)
        self.value = None

    @property
    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec)

    def init(self, value):
        self.value = value
        self._check(value)
        return value

    def fold(self, fn, *args, **kw):
        """``value = fn(value, *args, **kw)`` — ``fn`` must donate its
        first argument (a donated token can no longer be waited on, so
        callers must lag-sync BEFORE the next fold, the
        :class:`blit.outplane.FoldInFlight` rule)."""
        if self.value is None:
            raise RuntimeError("ShardedAccumulator.fold before init")
        self.value = fn(self.value, *args, **kw)
        self._check(self.value)
        return self.value

    def fold_aux(self, fn, *args, **kw):
        """:meth:`fold` for an ``fn`` that returns ``(aux, value)`` — a
        step with a product beside its state (:func:`band_stream`): the
        value moves on and ``aux`` is returned."""
        if self.value is None:
            raise RuntimeError("ShardedAccumulator.fold before init")
        aux, self.value = fn(self.value, *args, **kw)
        self._check(self.value)
        return aux

    def _check(self, value) -> None:
        want = self.sharding
        for leaf in jax.tree_util.tree_leaves(value):
            got = getattr(leaf, "sharding", None)
            if got is not None and not got.is_equivalent_to(want, leaf.ndim):
                raise ValueError(
                    f"accumulator sharding drifted from rule {self.rule!r}: "
                    f"{got} != {want}"
                )
