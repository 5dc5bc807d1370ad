"""Driver kind ``reduce_many``: one bank's recording through ONE ``blit
reduce`` that makes SEVERAL products from one read (rawspec's ``-f
1048576,8,1024 -t 51,128,3072``).

What differs from ``reduce``: the command's ``-o`` is a STEM, and product
``k`` lands where the traffic file's ``products[k].path`` says
(``{out}.rawspec.000k.fil``, rawspec's naming).  A pass is still ONE call
of the CLI's own ``main(argv)``.
"""

from __future__ import annotations

import os

from drivers.reduce import (WARMUP_CUT, WRAPPER_STAGES, argv,  # noqa: F401
                            stem)


def new_out(outdir: str, tag: str) -> str:
    """The stem pass ``tag`` is told to put its products under."""
    return os.path.join(outdir, tag)


def product(out: str) -> str:
    raise ValueError("reduce_many: every product of the traffic file "
                     "names its own `path`")


def run_pass(traffic: dict, inputs: dict, out: str, run_cli,
             warm_frames=None) -> dict:
    """One pass = one command -> the JSON it printed (``kernel_plan``,
    ``stages``, ``products``).  The warm-up pass is a whole pass."""
    return run_cli(argv(traffic, inputs, out))[-1]
