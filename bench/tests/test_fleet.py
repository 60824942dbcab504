"""The camera fleet cell: its interleave, its readers, and ``correct``.

Whole runs use the cell at test size (``tiny``: the jnp backend, the
chip's look skipped) with 6 cameras on the one CPU device, at 144x256
rather than tiny's 72x128: a 1-ulp tie between two gradient magnitudes
flips one pair of pixels in NMS (a 2-pixel mismatch against the
reference, which the cold detector shares), the rounding the 100 ppm
limit allows for. Tiny's frames weigh such a pair at 217 ppm, these at
54, a 1080p frame at 1.
"""

from __future__ import annotations

import collections
import itertools

import pytest

from bench import harness
from bench.tests import tiny

CELL = "camera_replicas_4chip"


def _fleet():
    return harness.load_entry("fleet")


def _cell(cameras: int = 6) -> dict:
    c = tiny.cell(CELL)
    c["config"].update(cameras=cameras, height=144, width=256)
    return c


def _order(seed: int, n: int) -> list[tuple[int, int]]:
    c = _cell(40)
    plan = _fleet().inputs(c["config"], c["traffic"], seed, 1.0)
    return list(itertools.islice(plan["clock"], n))


def test_interleave_is_deterministic_keeps_camera_order_and_runs_30_to_25():
    fleet = _fleet()
    traffic = harness.load_cell(CELL)["traffic"]
    assert fleet.camera_rates(traffic, 40) == [30.0] * 20 + [25.0] * 20
    seconds = 6
    n = seconds * (20 * 30 + 20 * 25)
    order = _order(2**31 + 5, n)
    assert order == _order(2**31 + 5, n)
    assert order != _order(2**31 + 6, n)  # the phases come from the seed
    per_camera = collections.defaultdict(list)
    for c, i in order:
        per_camera[c].append(i)
    for c, idx in per_camera.items():  # each camera's frames in its own order
        assert idx == list(range(1, len(idx) + 1)), c
    fast = [len(per_camera[c]) for c in range(20)]
    slow = [len(per_camera[c]) for c in range(20, 40)]
    assert all(abs(k - 30 * seconds) <= 1 for k in fast), fast
    assert all(abs(k - 25 * seconds) <= 1 for k in slow), slow


def _reader(name: str):
    return harness._module(harness.BENCH / "metrics" / f"{name}.py", name).read


def test_the_fleet_readers_on_hand_made_records():
    rec = {"stream": {"frames": 400, "launches": 1000, "frontend_strips": 5400,
                      "worker_ms": 8000.0},
           "cold_strips_per_frame": 36.0, "frames_in_window": 1000,
           "trace": {"window_s": 5.0, "busy_s": 0.5, "devices": 4}}
    assert _reader("strip_recompute_share.fleet")(rec) == pytest.approx(37.5)
    assert _reader("sweeps_per_frame.fleet")(rec) == pytest.approx(2.5)
    assert _reader("worker_ms_per_frame.fleet")(rec) == pytest.approx(20.0)
    assert _reader("device_idle_pct.fleet")(rec) == pytest.approx(90.0)
    # busy averaged over 4 devices, 0.5 s: 2 s of device time over 1000 frames
    assert _reader("device_ms_per_frame.fleet")(rec) == pytest.approx(2.0)
    empty = {"stream": {"frames": 0, "launches": 0, "frontend_strips": 0},
             "cold_strips_per_frame": 36.0, "frames_in_window": 0, "trace": None}
    for name in ("strip_recompute_share.fleet", "sweeps_per_frame.fleet",
                 "worker_ms_per_frame.fleet", "device_idle_pct.fleet",
                 "device_ms_per_frame.fleet"):
        assert _reader(name)(empty) is None, name


def test_sound_fleet_run_is_correct():
    r = tiny.run(_cell())
    assert r["correct"], r["checks"]
    load = r["load"]
    assert load["compared"] == 3 and load["cameras"] == 6
    assert load["sessions_opened_in_window"] == 0  # every session opened in set-up
    assert sum(load["frames_by_chip"].values()) == r["attempted"] > 0
    assert r["failed"] == 0 and load["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["answer_altered", "frame_dropped"])
def test_fleet_fault_is_caught(monkeypatch, fault):
    from repro.stream import FarmScheduler
    from repro.stream.temporal import TemporalCanny

    if fault == "answer_altered":
        step = TemporalCanny.step

        def broken(self, frame):
            edges, cost = step(self, frame)
            return edges.at[..., :4, :4].set(1 - edges[..., :4, :4]), cost

        monkeypatch.setattr(TemporalCanny, "step", broken)
    else:
        run = FarmScheduler.run_sessions

        def broken(self, source):
            for k, out in enumerate(run(self, source)):
                if k != 40:  # one frame's answer never comes back
                    yield out

        monkeypatch.setattr(FarmScheduler, "run_sessions", broken)
    r = tiny.run(_cell())
    assert not r["correct"]
