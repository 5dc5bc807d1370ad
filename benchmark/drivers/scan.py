"""Driver kind ``scan``: one band's banks through ``blit scan`` on the
mesh.  A pass is the CLI's own ``main(argv)`` in this process; the CLI
prints its stage table (``stages``), so the traced pass is the same call.
"""

from __future__ import annotations

import os

# Whether the warm-up pass may be cut to one `align_rows` of product.
WARMUP_CUT = True
# Rows of the stage table that wrap the others.
WRAPPER_STAGES = ()


def stem(rawdir: str, bank: int, traffic: dict) -> str:
    d = os.path.join(rawdir, traffic["session"], "GUPPI", f"BLP0{bank}")
    os.makedirs(d, exist_ok=True)
    return os.path.join(
        d, f"blc0{bank}_guppi_59897_21221_HD_84406_{traffic['scan']}")


def new_out(outdir: str, tag: str) -> str:
    out = os.path.join(outdir, tag)
    os.makedirs(out)
    return out


def product(out: str) -> str:
    return os.path.join(out, "band0.fil")


def argv(traffic: dict, inputs: dict, out: str, warm_frames=None) -> list:
    """The warm-up pass is cut to ``warm_frames`` frames (one window): the
    same program, a quarter of the set-up."""
    words = [w.format(out=out, root=inputs["rawdir"],
                      session=traffic["session"], scan=traffic["scan"])
             for w in traffic["argv"]]
    if warm_frames is not None:
        words += ["--max-frames", str(warm_frames)]
    return words


def run_pass(traffic: dict, inputs: dict, out: str, run_cli,
             warm_frames=None) -> dict:
    """One pass -> the JSON the command printed (``kernel_plan``, and
    ``stages``: the ``Timeline`` report, stage -> calls, seconds, bytes)."""
    return run_cli(argv(traffic, inputs, out, warm_frames))[-1]
