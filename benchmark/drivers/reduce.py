"""Driver kind ``reduce``: one bank's recording through ``blit reduce``.

A pass is the CLI's own ``main(argv)`` in this process; the CLI prints
its stage table (``stages``, since PR 24), so the traced pass is the same
call.
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


def argv(traffic: dict, inputs: dict, out: str) -> list:
    """The traffic file's argv with the recording and the product path
    filled in."""
    words = []
    for w in traffic["argv"]:
        if w == "{raws}":
            words += inputs["raws"][0]
        else:
            words.append(w.format(out=out))
    return words


def run_pass(traffic: dict, inputs: dict, out: str, run_cli,
             warm_frames=None) -> dict:
    """One pass -> the JSON the command printed (``kernel_plan``, and
    ``stages``: the ``Timeline`` report, stage -> calls, seconds, bytes).
    The warm-up pass is a whole pass (``warm_frames`` unused)."""
    return run_cli(argv(traffic, inputs, out))[-1]
