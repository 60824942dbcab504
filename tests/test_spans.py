"""Host spans (``core/spans.py``) in the batcher's dispatch and runner
threads and the stream workers, read back from a profiler trace captured
on the CPU.

The contract the trace's readers rest on: one span of each phase per lane
or per frame, and no two phase spans overlapping on one thread, so that a
trace's spans of one name add up to that phase's time.
"""

import glob
import os

import numpy as np
import pytest

import jax.profiler as prof
from jax.profiler import ProfileData

from repro.core.canny import CannyParams
from repro.core.spans import span
from repro.serve import AotCannyEngine, ContinuousBatcher
from repro.stream import FarmScheduler, SyntheticStream

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)
STREAM_PHASES = ("canny.prep", "canny.put", "canny.step", "canny.fetch", "canny.cost_sync")
LANE_PHASES = ("canny.pack", "canny.put", "canny.step", "canny.fetch")


def _traced(log_dir, work) -> list[list[tuple[int, int, str]]]:
    """Run ``work`` under a profiler session; the ``canny.*`` spans of
    each host thread, as (start_ns, end_ns, name) in start order."""
    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    prof.start_trace(str(log_dir), profiler_options=opts)
    try:
        work()
    finally:
        prof.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name.startswith("canny.")]
                if spans:
                    threads.append(sorted(spans))
    return threads


def _counts(threads) -> dict[str, int]:
    out: dict[str, int] = {}
    for spans in threads:
        for _, _, name in spans:
            out[name] = out.get(name, 0) + 1
    return out


def _assert_disjoint(threads) -> None:
    for spans in threads:
        for (_, e0, a), (s1, _, b) in zip(spans, spans[1:]):
            assert s1 >= e0, f"{b} starts inside {a} on one thread"


def test_a_lane_has_one_span_of_each_phase(tmp_path):
    engine = AotCannyEngine(PARAMS, buckets=[(32, 32)], bucket_multiple=32, max_batch=4)
    images = [np.random.default_rng(i).uniform(size=(32, 32)).astype(np.float32)
              for i in range(4)]

    def work():
        # a linger far past the test: the lane goes out when its 4 slots fill
        with ContinuousBatcher(engine, linger_ms=60_000.0, timeout=60.0) as b:
            for t in [b.submit(img) for img in images]:
                t.result(60.0)

    threads = _traced(tmp_path, work)
    assert engine.stats.batches == 1
    counts = _counts(threads)
    assert {p: counts.get(p) for p in LANE_PHASES} == dict.fromkeys(LANE_PHASES, 1)
    assert counts.get("canny.wait", 0) >= 1  # idle before the first submit
    (dispatch,) = [s for s in threads if any(n == "canny.pack" for _, _, n in s)]
    assert [n for _, _, n in dispatch if n != "canny.wait"] == ["canny.pack"]
    (runner,) = [s for s in threads if any(n == "canny.fetch" for _, _, n in s)]
    # in this order, on the runner thread the dispatch thread handed the lane
    assert [n for _, _, n in runner] == list(LANE_PHASES[1:])
    (pack_end,) = [e for _, e, n in dispatch if n == "canny.pack"]
    assert runner[0][0] >= pack_end
    _assert_disjoint(threads)


def test_a_frame_has_one_span_of_each_phase(tmp_path):
    frames = list(SyntheticStream(6, 32, 64, seed=3, hold=2))
    farm = FarmScheduler(PARAMS, n_workers=2, warm=True, skip=True, backend="jnp")
    out = []
    threads = _traced(tmp_path, lambda: out.extend(farm.run(frames)))
    assert len(out) == len(frames) == farm.stats.frames
    assert len(threads) == 2  # the two workers, and no span elsewhere
    assert _counts(threads) == dict.fromkeys(STREAM_PHASES, len(frames))
    for spans in threads:  # each worker spans each of its own frames
        assert _counts([spans]) == dict.fromkeys(STREAM_PHASES, len(frames) // 2)
    _assert_disjoint(threads)
    # the stats read the spans' own durations, traced or not
    assert len(farm.stats.prep_ms) == len(farm.stats.compute_ms) == len(frames)


def test_a_span_passes_its_duration_to_the_sink_once_its_body_completes():
    got = []
    with span("canny.test", got.append):
        pass
    assert len(got) == 1 and 0 <= got[0] < 1e3
    with pytest.raises(ValueError):
        with span("canny.test", got.append):
            raise ValueError("body failed")
    assert len(got) == 1  # a failed body records nothing


def _traced_ms(threads) -> dict[str, float]:
    out: dict[str, float] = {}
    for spans in threads:
        for s, e, name in spans:
            out[name] = out.get(name, 0.0) + (e - s) / 1e6
    return out


def test_a_routed_frame_has_one_route_span_on_the_feeder(tmp_path):
    frames = list(SyntheticStream(6, 32, 64, seed=3))
    feed = [(k % 3, f) for k, f in enumerate(frames)]  # 3 cameras, interleaved
    farm = FarmScheduler(PARAMS, warm=True, skip=True, backend="jnp")
    out = []
    threads = _traced(tmp_path, lambda: out.extend(farm.run_sessions(feed)))
    assert [c for c, _ in out] == [c for c, _ in feed]
    (feeder,) = [s for s in threads if any(n == "canny.route" for _, _, n in s)]
    assert [n for _, _, n in feeder] == ["canny.route"] * len(frames)
    assert _counts(threads) == {**dict.fromkeys(STREAM_PHASES, len(frames)),
                                "canny.route": len(frames)}
    _assert_disjoint(threads)
    # the sinks pass each span's own duration to StreamStats: its sums are
    # the trace's, the few microseconds of the annotation itself aside
    traced = _traced_ms(threads)
    summed = {**farm.stats.worker_ms, "canny.route": farm.stats.route_ms}
    assert set(summed) == set(traced)
    for name, ms in traced.items():
        assert summed[name] == pytest.approx(ms, rel=0.02, abs=2.0 * len(frames)), name
    assert farm.stats.worker_ms["canny.prep"] == pytest.approx(sum(farm.stats.prep_ms))
    assert farm.stats.worker_ms["canny.fetch"] == pytest.approx(sum(farm.stats.compute_ms))
