"""Trace reduction: on hand-made planes, and on traces recorded on a v5e.

``data/*.xplane.pb`` were recorded on one TPU v5e chip by the benchmark's
own window (``bench.window`` and the other ``bench.*`` spans): a 0.2 s
window of the held 1080p stream and a 0.4 s window of the steady upload
mix.
"""

from __future__ import annotations

import pathlib

import numpy as np
import pytest

from bench import trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _planes(ops, modules=(), host=()):
    return [
        ("/device:TPU:0", [("XLA Ops", list(ops)), ("XLA Modules", list(modules))]),
        ("/host:CPU", [("python3", list(host))]),
    ]


def test_hand_made_trace():
    ops = [(10, 20, "%while.1 = u32[8]{0}"), (12, 5, "%fusion.2 = f32[8]{0}"),
           (40, 10, "%copy.3 = f32[8]{0}"), (95, 20, "%fusion.2 = f32[8]{0}")]
    host = [(0, 100, "bench.window"), (30, 10, "bench.submit"), (50, 45, "XlaLinearize"),
            (55, 2, "bench.feed")]
    r = tr.reduce_planes(_planes(ops, modules=[(10, 20, "jit_run(1)"), (40, 10, "jit_run(2)")], host=host))
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(35e-9)  # [10,30) + [40,50) + [95,100)
    assert r["module_s"] == pytest.approx(30e-9) and r["module_launches"] == 2
    assert dict(r["device_ops"]) == pytest.approx({
        "%while.1 = u32[8]": 15e-9, "%fusion.2 = f32[8]": 10e-9, "%copy.3 = f32[8]": 10e-9})
    # gaps [50,95), [0,10), [30,40): the first named by the host event covering it
    assert r["idle_gaps"] == [["- | XlaLinearize", pytest.approx(45e-9)],
                              ["- | -", pytest.approx(10e-9)],
                              ["bench.submit | -", pytest.approx(10e-9)]]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_planes(_planes([(0, 1, "x")], host=[(0, 5, "bench.submit")]))


def _brute(path):
    """Busy time and the longest gap on a 100 ns grid, straight from the
    profile: an independent check of the interval arithmetic."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, window = [], None
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name.startswith("/device:TPU:") and line.name == "XLA Ops":
                    ops.append((e.start_ns, e.end_ns))
                elif e.name == "bench.window":
                    window = (e.start_ns, e.end_ns)
    w0, w1 = window
    grid = np.zeros(int((w1 - w0) // 100) + 1, bool)
    for s, e in ops:
        lo, hi = max(s, w0), min(e, w1)
        if hi > lo:
            grid[int((lo - w0) // 100): int(np.ceil((hi - w0) / 100))] = True
    idle = np.flatnonzero(np.diff(np.concatenate([[1], grid.astype(int), [1]])))
    longest = max((idle[i + 1] - idle[i] for i in range(0, len(idle) - 1, 2)), default=0)
    return grid.sum() * 100e-9, longest * 100e-9, len(ops)


@pytest.mark.parametrize("name", ["stream_held", "upload_steady"])
def test_recorded_trace(name):
    path = DATA / f"{name}.xplane.pb"
    r = tr.reduce(str(path))
    busy, longest, n_ops = _brute(path)
    assert r["devices"] == 1 and n_ops > 100
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] == pytest.approx(busy, rel=0.02)
    assert r["idle_gaps"][0][1] == pytest.approx(longest, abs=2e-7)
    assert r["module_s"] >= r["busy_s"] * 0.98
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) == tr.TOP
    assert sum(times) <= r["busy_s"] * 1.001
    gaps = [t for _, t in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and sum(gaps) <= r["window_s"] - r["busy_s"] + 1e-9
    # every long gap has host work under it: the runtime's layout
    # transposes, transfers or blocking fetches
    assert all(n.split(" | ")[1] != "-" for n, _ in r["idle_gaps"])
