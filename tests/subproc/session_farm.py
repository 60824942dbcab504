"""Session mode and pod-rank placement on 4 (virtual) devices.

Run via tests/test_session_farm.py, which forces the device count:

  * **session mode**: 12 cameras fed interleaved through
    ``FarmScheduler.run_sessions``; every output is bit-exact, every
    camera's results come from one device throughout, all 4 devices
    serve, each session's whole state (packed words, stored frame, gate
    scalars, true-size table) lives on its camera's device, and no step,
    a session's first included, copies an array from one device to
    another (JAX's device-to-device transfer guard is on for the run);
  * **pod farm**: ``--mesh 4x1x1`` gives four one-device ranks, each
    worker pinned to its own device and its state made there, again with
    no device-to-device copy.
"""

from __future__ import annotations

import collections
import os

assert "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""), (
    "run me via tests/test_session_farm.py (or set "
    "XLA_FLAGS=--xla_force_host_platform_device_count=4)"
)

import jax
import numpy as np

from repro.core.canny import CannyParams, canny_reference
from repro.launch.mesh import dist_from_spec
from repro.stream import FarmScheduler, SyntheticStream
from repro.stream.scheduler import SessionTable

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)
CAMERAS, FRAMES, H, W, BLOCK_ROWS = 12, 4, 48, 64, 16


def state_devices(temporal) -> set:
    """The devices of every array the session's state machine holds."""
    leaves = jax.tree_util.tree_leaves(vars(temporal._impl))
    return {d for a in leaves if isinstance(a, jax.Array) for d in a.devices()}


def check_sessions(backend: str | None) -> None:
    devices = jax.devices()
    assert len(devices) == 4, devices
    streams = [list(SyntheticStream(FRAMES, H, W, seed=c)) for c in range(CAMERAS)]
    feed = [(c, streams[c][i]) for i in range(FRAMES) for c in range(CAMERAS)]
    served = collections.defaultdict(set)
    step = SessionTable.step

    def watched(self, camera, x):
        edges, cost = step(self, camera, x)
        assert edges.committed
        served[camera] |= edges.devices()
        return edges, cost

    SessionTable.step = watched
    sched = FarmScheduler(PARAMS, warm=True, skip=True, block_rows=BLOCK_ROWS,
                          backend=backend)
    # global, not a context: the workers step on threads of their own
    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    try:
        got = list(sched.run_sessions(feed))
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", "allow")
        SessionTable.step = step
    assert [c for c, _ in got] == [c for c, _ in feed]
    for (c, frame), (_, edges) in zip(feed, got):
        assert (edges == canny_reference(frame, PARAMS)).all(), f"camera {c} diverged"
    assert all(len(d) == 1 for d in served.values()), dict(served)
    for c, (d,) in served.items():
        assert d == devices[sched.route(c) % 4], (c, d)
    print(f"session mode ({backend or 'fused'}): bit-exact, each camera on one device: OK")
    by_device = sched.stats.frames_by_device
    assert sorted(by_device) == [d.id for d in devices], by_device
    assert all(n == CAMERAS * FRAMES // 4 for n in by_device.values()), by_device
    print(f"session mode ({backend or 'fused'}): all 4 devices serve {dict(by_device)}: OK")
    for table in sched.sessions:
        for t in table.table.values():
            assert state_devices(t) == {table.device}, (table.device, state_devices(t))
    print(f"session mode ({backend or 'fused'}): every session's state on its own device: OK")


def check_pod_ranks() -> None:
    frames = list(SyntheticStream(8, H, W, seed=5, hold=2))
    sched = FarmScheduler(PARAMS, warm=True, skip=True, block_rows=BLOCK_ROWS,
                          dist=dist_from_spec("4x1x1"))
    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    try:
        got = list(sched.run(frames))
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", "allow")
    for i, (f, e) in enumerate(zip(frames, got)):
        assert (np.asarray(e) == canny_reference(f, PARAMS)).all(), f"frame {i}"
    placed = [w.device for w in sched.farm.workers]
    assert placed == list(sched.dist.mesh.devices.flat), placed
    for device, temporal in zip(placed, sched.detectors):
        assert state_devices(temporal) == {device}
    assert sorted(sched.stats.frames_by_device) == [d.id for d in placed]
    print("pod farm: each one-device rank on its own device: OK")


if __name__ == "__main__":
    check_sessions(None)  # the platform's default, fused
    check_sessions("jnp")
    check_pod_ranks()
    print("ALL-OK")
