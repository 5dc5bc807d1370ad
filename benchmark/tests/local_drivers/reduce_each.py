"""Test-local driver kind ``reduce_each``: a pass that makes SEVERAL
products, by running ``blit reduce`` once per entry of the traffic file's
``products`` (each entry carries its own ``argv``).  It stands in for the
one command that will make rawspec's three products from one read; the
harness cannot tell the difference.  A test copies this file into the
``drivers/`` of a temporary tree: it is no driver of the benchmark's.

``traffic["fault"]`` = ``{"product": <index>, "kind": ...}`` breaks that
product under the harness, in the timed passes only (the warm-up's is the
verified one): ``alter`` flips a payload byte, ``no_manifest`` removes the
sidecar, ``partial`` leaves a ``.partial`` beside it, ``short`` cuts the
last row off.
"""

from __future__ import annotations

import os

from drivers.reduce import (WARMUP_CUT, WRAPPER_STAGES, new_out,  # noqa: F401
                            product, stem)


def run_pass(traffic: dict, inputs: dict, out: str, run_cli,
             warm_frames=None) -> dict:
    doc, stages = {}, {}
    paths = [spec["path"].format(out=out) for spec in traffic["products"]]
    for spec, path in zip(traffic["products"], paths):
        words = []
        for w in spec["argv"]:
            words += inputs["raws"][0] if w == "{raws}" \
                else [w.format(path=path)]
        doc = run_cli(words)[-1]
        for name, row in doc.get("stages", {}).items():
            if "seconds" not in row:
                continue
            have = stages.setdefault(name, dict(row, calls=0, seconds=0.0,
                                                bytes=0))
            for k in ("calls", "seconds", "bytes"):
                have[k] += row[k]
    fault = traffic.get("fault")
    if fault and os.path.basename(out).startswith("pass"):
        break_product(paths[fault["product"]], fault["kind"])
    return dict(doc, stages=stages)


def break_product(path: str, kind: str) -> None:
    if kind == "alter":
        with open(path, "r+b") as f:
            f.seek(-5, os.SEEK_END)
            byte = f.read(1)
            f.seek(-5, os.SEEK_END)
            f.write(bytes([byte[0] ^ 0x40]))
    elif kind == "no_manifest":
        os.remove(path + ".manifest.json")
    elif kind == "partial":
        with open(path + ".partial", "wb") as f:
            f.write(b"left behind")
    elif kind == "short":
        from products import fil

        hdr, _ = fil.read_header(path)
        os.truncate(path, os.path.getsize(path) - hdr["nchans"] * 4)
    else:
        raise ValueError(f"reduce_each: unknown fault {kind!r}")
