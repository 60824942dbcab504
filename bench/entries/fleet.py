"""A site's camera fleet on one host: ``FarmScheduler.run_sessions``.

Each camera's frames are made in set-up from ``(seed, camera)`` into a
ring of its own and replayed (``schedule.replay_index``). The cameras'
frames are interleaved by capture time, camera c's frame i at ``(i +
phase_c) / rate_c`` with the phases drawn from the seed, and fed as fast
as the farm takes them. The program's router pins every camera to one
chip and worker, whose session holds that camera's warm and skip state;
this entry only feeds ``(camera, frame)`` pairs and reads what comes out.
"""

from __future__ import annotations

import collections
import heapq
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from jax.profiler import TraceAnnotation

from bench import check, images, schedule

WARM_FRAMES = 3  # frames per camera fed before the window, after its cold first


def build(config: dict) -> dict:
    from repro.core.canny import CannyParams
    from repro.stream import FarmScheduler

    farm = FarmScheduler(
        CannyParams(**config["canny"]), warm=config["warm"], skip=config["skip"],
        backend=config["backend"],
    )
    # a program without session mode fails here, before the inputs are made
    return {"farm": farm, "run": farm.run_sessions}


def camera_rates(traffic: dict, cameras: int) -> list[float]:
    """Camera c's picture rate: the cameras split in order over ``rates_hz``."""
    rates = traffic["rates_hz"]
    return [float(rates[c * len(rates) // cameras]) for c in range(cameras)]


def capture_order(rates, phases, first: int = 0):
    """Endless ``(camera, frame_index)`` pairs in capture-time order, from
    each camera's frame ``first`` on; ties go to the lower camera."""
    heap = [((first + phases[c]) / rates[c], c, first) for c in range(len(rates))]
    heapq.heapify(heap)
    while True:
        _, c, i = heap[0]
        heapq.heapreplace(heap, ((i + 1 + phases[c]) / rates[c], c, i + 1))
        yield c, i


def inputs(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    cameras = config["cameras"]

    def ring(camera: int) -> list[np.ndarray]:
        scene = images.CameraScene(
            config["height"], config["width"], seed * cameras + camera,
            radii=traffic["radii"], noise=traffic["noise"], speed=traffic["speed"],
        )
        return [scene.frame(i) for i in range(traffic["ring"])]

    with ThreadPoolExecutor(min(cameras, os.cpu_count() or 1)) as pool:
        rings = list(pool.map(ring, range(cameras)))
    phases = np.random.default_rng((seed, 4)).uniform(0.0, 1.0, cameras)
    return {
        "rings": rings, "traffic": traffic, "seed": seed,
        # frame 0 of every camera opens its session in warm(); the clock
        # feeds from frame 1 on
        "clock": capture_order(camera_rates(traffic, cameras), phases, first=1),
        "fed": collections.deque(), "n_fed": 0,
    }


def _feed(plan: dict, count: int | None, stop: threading.Event | None = None):
    """``(camera, frame)`` pairs in capture order; ``count`` of them, or
    until ``stop`` is set. Each pair's ``(camera, ring index)`` goes to
    ``plan["fed"]`` before the farm sees it."""
    k = 0
    while (count is None or k < count) and not (stop is not None and stop.is_set()):
        with TraceAnnotation("bench.feed"):
            c, i = next(plan["clock"])
            r = schedule.replay_index(i, plan["traffic"])
            plan["fed"].append((c, r))
            plan["n_fed"] += 1
        k += 1
        yield c, plan["rings"][c][r]


def warm(system: dict, plan: dict) -> None:
    """Open every camera's session on its frame 0 (cold: their front-end
    strip count is what a cold front end runs), then ``WARM_FRAMES`` more
    frames per camera in capture order."""
    farm, rings = system["farm"], plan["rings"]
    for table in farm.sessions:
        table.reset()
    strips = farm.stats.frontend_strips
    first = schedule.replay_index(0, plan["traffic"])
    for _ in system["run"]((c, ring[first]) for c, ring in enumerate(rings)):
        pass
    system["cold_strips_per_frame"] = (farm.stats.frontend_strips - strips) / len(rings)
    for _ in system["run"](_feed(plan, WARM_FRAMES * len(rings))):
        pass


def _snapshot(stats) -> dict:
    return {"frames": stats.frames, "launches": stats.launches,
            "dilations": stats.dilations, "frontend_launches": stats.frontend_launches,
            "frontend_strips": stats.frontend_strips,
            "worker_ms": sum(stats.worker_ms.values()), "route_ms": stats.route_ms,
            "sessions_opened": stats.sessions_opened}


def window(system: dict, plan: dict, seconds: float, t_start: float) -> dict:
    farm, traffic, rings = system["farm"], plan["traffic"], plan["rings"]
    chip_of = {c: table.device.id for table in farm.sessions for c in table.table}
    sample = check.Reservoir(dict.fromkeys(sorted(set(chip_of.values())),
                                           traffic["sample_per_chip"]), plan["seed"])
    fed = plan["fed"]
    fed.clear()
    n_fed0 = plan["n_fed"]
    by_chip0 = dict(farm.stats.frames_by_device)
    stop = threading.Event()
    span = TraceAnnotation("bench.window")
    emitted = emitted_in_window = wrong_camera = 0
    t0 = time.perf_counter()
    before = _snapshot(farm.stats)
    t_close = t0 + seconds
    span.__enter__()
    for camera, edges in system["run"](_feed(plan, None, stop)):
        c, r = fed.popleft()  # the farm emits in feed order
        emitted += 1
        wrong_camera += camera != c
        if stop.is_set():
            continue
        if time.perf_counter() > t_close:
            span.__exit__(None, None, None)
            stop.set()
            continue
        emitted_in_window += 1
        with TraceAnnotation("bench.sample"):
            sample.offer(chip_of[c], (edges, rings[c][r]))
    if not stop.is_set():  # the feed ended inside the window
        span.__exit__(None, None, None)
    # counters taken once every frame fed has come out (stream.py's reason)
    t_end = time.perf_counter()
    after = _snapshot(farm.stats)
    by_chip = {str(d): n - by_chip0.get(d, 0)
               for d, n in sorted(farm.stats.frames_by_device.items(), key=str)}
    n_fed = plan["n_fed"] - n_fed0
    stream = {k: after[k] - before[k] for k in after}
    return {
        "setup_s": t0 - t_start,
        "t0": t0,
        "window_s": seconds,
        "attempted": n_fed,
        # a result tagged with another camera than the frame fed is no answer
        "failed": n_fed - emitted + wrong_camera,
        "frames_in_window": emitted_in_window,
        "stream": stream,
        "cold_strips_per_frame": system["cold_strips_per_frame"],
        "outputs": sample.all(),
        "load": {"compared": len(sample.all()), "cameras": len(rings),
                 "frames_by_chip": by_chip,
                 "route_share_pct": 100.0 * stream["route_ms"] / ((t_end - t0) * 1e3),
                 "sessions_opened_in_window": stream["sessions_opened"]},
    }


def close(system: dict) -> None:
    for table in system["farm"].sessions:
        table.reset()
    system.clear()


def verify(config: dict, plan: dict, rec: dict, control: str | None = None) -> dict:
    """The numbers compared against their limits (``check.compare``)."""
    return check.compare(rec.pop("outputs"), config, rec["failed"], control)
