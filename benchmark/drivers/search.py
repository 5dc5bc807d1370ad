"""Driver kind ``search``: one bank's recording through ``blit search``,
whose product is a ``.hits`` table (product kind ``hits``).

A pass is the CLI's own ``main(argv)`` in this process.  The command's
JSON carries ``hists`` and ``dedoppler_plan`` and, today, no ``stages``:
the harness reads a traced pass of it without them.
"""

from __future__ import annotations

import os

from drivers.reduce import WARMUP_CUT, WRAPPER_STAGES, argv, stem  # noqa: F401


def new_out(outdir: str, tag: str) -> str:
    """Where pass ``tag`` is told to put its table."""
    return os.path.join(outdir, f"{tag}.hits")


def product(out: str) -> str:
    return out


def run_pass(traffic: dict, inputs: dict, out: str, run_cli,
             warm_frames=None) -> dict:
    """One pass -> the JSON the command printed (``windows``, ``hits``,
    ``hists``, ``dedoppler_plan``, ``kernel_plan``).  The warm-up pass is
    a whole pass."""
    return run_cli(argv(traffic, inputs, out))[-1]
