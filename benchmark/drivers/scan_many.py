"""Driver kind ``scan_many``: one band's banks through ONE ``blit scan``
that makes SEVERAL band products from one read of the scan (rawspec's
``-f 1048576,8,1024 -t 51,128,3072`` on the mesh).

What differs from ``scan``: band 0's product ``k`` lands where the traffic
file's ``products[k].path`` says (``{out}/band0.rawspec.000k.fil``,
rawspec's suffix on the band's stem), and the warm-up is a whole pass (a
cut one would leave the small legs' head steps and last windows to compile
inside the measured window).  A pass is still ONE call of the CLI's own
``main(argv)``.
"""

from __future__ import annotations

from drivers.scan import WRAPPER_STAGES, argv, new_out, stem  # noqa: F401

# Whether the warm-up pass may be cut to one `align_rows` of product.
WARMUP_CUT = False


def product(out: str) -> str:
    raise ValueError("scan_many: every product of the traffic file names "
                     "its own `path`")


def run_pass(traffic: dict, inputs: dict, out: str, run_cli,
             warm_frames=None) -> dict:
    """One pass = one command -> the JSON it printed last (``kernel_plan``
    and ``stages``: the ``Timeline`` report)."""
    return run_cli(argv(traffic, inputs, out))[-1]
