"""Cells of BENCHMARK.json cut to sizes a CPU test holds.

Same entries, traffic shapes and comparison as on the chip; images of a
few thousand pixels, the jnp backend (Pallas would run interpreted), and
a device block that stands in for the chip whose look the tests skip.
"""

from __future__ import annotations

import time

from bench import harness

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
UPLOAD_SHAPES = {"a4": [[130, 96]], "bsds": [[33, 49], [49, 33]], "hd": [[72, 128]]}


def cell(workload: str, **traffic) -> dict:
    """``workload``'s cell at test size; ``traffic`` overrides keys of its
    mix (``rate_per_s`` turns a closed loop into an open one)."""
    c = harness.load_cell(workload)
    c["traffic"].update(traffic)
    cfg = c["config"]
    cfg["backend"] = "jnp"
    if cfg["entry"] == "upload":
        cfg["classes"] = {k: UPLOAD_SHAPES[k] for k in cfg["classes"]}
        cfg["corpus"] = {k: 2 for k in cfg["classes"]}
    else:
        cfg["height"], cfg["width"] = 72, 128
        c["traffic"]["radii"] = [20, 12]
        c["traffic"]["ring"] = 16
    return c


def run(c: dict, seed: int = 2**31 + 11, seconds: float = 1.0) -> dict:
    return harness.run_cell(c, seed, seconds, False, time.perf_counter(), DEVICE)
