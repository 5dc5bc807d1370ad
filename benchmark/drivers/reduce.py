"""Driver kind ``reduce``: one bank's recording through ``blit reduce``.

A pass is the CLI's own ``main(argv)`` in this process.  The traced pass
alone builds the reducer as ``blit.__main__._cmd_reduce`` does (same
constructor, same ``reduce_to_file``), because ``blit reduce`` does not
print the reducer's stage table and the table is what four per-layer
metrics read (PERF.md lists printing it under the tracing issue).
"""

from __future__ import annotations

import os

# Whether the warm-up pass may be cut to one `align_rows` of product.
WARMUP_CUT = False
# Rows of the stage table that wrap the others.
WRAPPER_STAGES = ("stream",)   # the whole pump, not a stage of it


def stem(rawdir: str, bank: int, traffic: dict) -> str:
    return os.path.join(rawdir, "blc00_guppi_59897_21221_BENCH_0001")


def new_out(outdir: str, tag: str) -> str:
    """Where pass ``tag`` is told to put its product."""
    return os.path.join(outdir, f"{tag}.rawspec.fil")


def product(out: str) -> str:
    return out


def argv(traffic: dict, inputs: dict, out: str, warm_rows=None) -> list:
    """The traffic file's argv with the recording and the product path
    filled in.  The warm-up pass is a whole pass (``warm_rows`` unused)."""
    words = []
    for w in traffic["argv"]:
        if w == "{raws}":
            words += inputs["raws"][0]
        else:
            words.append(w.format(out=out))
    return words


def traced(traffic: dict, inputs: dict, out: str, run_cli) -> dict:
    """One pass with the stage table in hand -> the table (``Timeline``
    report: stage -> calls, seconds, bytes)."""
    from blit.pipeline import RawReducer, reducer_for_product

    how = traffic["reducer"]
    kw = dict(stokes="I", fqav_by=1, dtype="float32")  # the CLI's defaults
    if "product" in how:
        red = reducer_for_product(how["product"], **kw)
    else:
        red = RawReducer(nfft=how["nfft"], nint=how["nint"], **kw)
    raws = inputs["raws"][0]
    red.reduce_to_file(raws[0] if len(raws) == 1 else raws, out,
                       compression=None)
    return red.timeline.report()
