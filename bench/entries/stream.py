"""Camera streams: ``FarmScheduler`` with temporal warm start and skip.

The frames of one camera are made in set-up from the seed into a ring and
fed from it (``schedule.replay_index``), as fast as the farm takes them:
a recorded feed replayed through the detector. Making a 1080p frame
live takes longer than detecting its edges, so frames cannot be made in
the window.
"""

from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

from bench import check, images, schedule

WARM_ROUNDS = 4  # frames per worker fed before the window, after the cold ones


def build(config: dict) -> dict:
    from repro.core.canny import CannyParams
    from repro.stream import FarmScheduler

    farm = FarmScheduler(
        CannyParams(**config["canny"]), n_workers=config["workers"],
        warm=config["warm"], skip=config["skip"], backend=config["backend"],
    )
    return {"farm": farm, "workers": len(farm.farm.workers)}


def inputs(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    cam = images.CameraScene(
        config["height"], config["width"], seed, radii=traffic["radii"],
        noise=traffic["noise"], speed=traffic["speed"],
    )
    ring = [cam.frame(i) for i in range(traffic["ring"])]
    return {"ring": ring, "traffic": traffic, "seed": seed, "next": 0}


def _feed(plan: dict, count: int | None, stop: threading.Event | None = None):
    """Frames from the ring in replay order; ``count`` of them, or until
    ``stop`` is set."""
    k = 0
    while (count is None or k < count) and not (stop is not None and stop.is_set()):
        s = plan["next"]
        with TraceAnnotation("bench.feed"):
            frame = plan["ring"][schedule.replay_index(s, plan["traffic"])]
        plan["next"] = s + 1
        k += 1
        yield frame


def warm(system: dict, plan: dict) -> None:
    """Feed one cold frame per worker (their front-end strip count is what
    a cold front end runs), then ``WARM_ROUNDS`` more per worker."""
    farm, n = system["farm"], system["workers"]
    for d in farm.detectors:
        d.reset()
    strips = farm.stats.frontend_strips
    for _ in farm.run(_feed(plan, n)):
        pass
    system["cold_strips_per_frame"] = (farm.stats.frontend_strips - strips) / n
    for _ in farm.run(_feed(plan, WARM_ROUNDS * n)):
        pass


def _snapshot(stats) -> dict:
    return {"frames": stats.frames, "launches": stats.launches,
            "dilations": stats.dilations, "frontend_launches": stats.frontend_launches,
            "frontend_strips": stats.frontend_strips}


def window(system: dict, plan: dict, seconds: float, t_start: float) -> dict:
    farm, workers = system["farm"], system["workers"]
    traffic = plan["traffic"]
    sample = check.Reservoir({"frame": traffic["sample"]}, plan["seed"])
    repeats = []  # frames equal to their worker's previous frame: front end skipped whole
    stop = threading.Event()
    span = TraceAnnotation("bench.window")
    s0 = plan["next"]
    emitted_in_window = 0
    emitted = 0
    t0 = time.perf_counter()
    before = _snapshot(farm.stats)
    t_close = t0 + seconds
    span.__enter__()
    for k, edges in enumerate(farm.run(_feed(plan, None, stop))):
        emitted += 1
        if stop.is_set():
            continue
        if time.perf_counter() > t_close:
            span.__exit__(None, None, None)
            stop.set()
            continue
        emitted_in_window += 1
        s = s0 + k
        r = schedule.replay_index(s, traffic)
        with TraceAnnotation("bench.sample"):
            if s - workers >= s0 and r == schedule.replay_index(s - workers, traffic):
                if len(repeats) < traffic["sample_repeats"]:
                    repeats.append((edges, r))
            else:
                sample.offer("frame", (edges, r))
    if not stop.is_set():  # the feed ended inside the window
        span.__exit__(None, None, None)
    # counters taken once every frame fed has come out: the workers count a
    # frame's strips before the farm emits it, so a cut at the window's
    # close would split frames from their strips
    after = _snapshot(farm.stats)
    fed = plan["next"] - s0
    return {
        "setup_s": t0 - t_start,
        "t0": t0,
        "window_s": seconds,
        "attempted": fed,
        "failed": fed - emitted,
        "frames_in_window": emitted_in_window,
        "stream": {k: after[k] - before[k] for k in after},
        "cold_strips_per_frame": system["cold_strips_per_frame"],
        "outputs": [(e, plan["ring"][r]) for e, r in sample.all() + repeats],
        "load": {"compared": len(sample.all()) + len(repeats),
                 "compared_whole_frame_skips": len(repeats)},
    }


def close(system: dict) -> None:
    for d in system["farm"].detectors:
        d.reset()
    system.clear()


def verify(config: dict, plan: dict, rec: dict, control: str | None = None) -> dict:
    """The numbers compared against their limits (``check.compare``)."""
    return check.compare(rec.pop("outputs"), config, rec["failed"], control)
