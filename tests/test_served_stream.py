"""Served-path conformance: the stream farm's compositions, every backend.

``test_differential.py`` pins the bare detectors (``make_canny``,
``TemporalCanny``). This file pins what the stream cells run on top of
them, for EVERY backend the registry (``backend_specs()``) lists for
that path, each output compared bit for bit with the spec's own oracle
(``ref_fn``, else ``canny_reference``) on the adversarial streams of
``tests/_conformance.py``:

  * ``FarmScheduler(n_workers=2).run``: each worker's warm and skip
    state sees every other frame; frames come back in order;
  * ``run_sessions``: cameras 0, 1 and 2 carry the three streams, fed
    round-robin or with camera 0's whole clip first; each camera's
    frames come back in its own order, and its session lives in exactly
    one worker's ``SessionTable``;
  * the operator zoo as ``canny_stream --op`` serves it: one shared
    ``make_detector(op=...)`` behind a two-worker farm.
"""

import numpy as np
import pytest

from repro.core.canny import backend_specs, canny_reference, make_detector
from repro.stream import FarmScheduler

from _conformance import PARAMS, STREAMS, all_changing

TEMPORAL = [s for s in backend_specs() if s.temporal_fn is not None]
ZOO = [s for s in backend_specs() if s.op != "canny" and s.serving_fn is not None]
MODES = ("cold", "warm", "warm+skip")
CAMERAS = list(STREAMS)  # camera k carries STREAMS' k-th stream


def _ids(spec) -> str:
    return spec.name


def _oracle(spec):
    return spec.ref_fn or canny_reference


def _scheduler(spec, mode: str) -> FarmScheduler:
    return FarmScheduler(
        PARAMS, n_workers=2, warm=mode != "cold", skip=mode == "warm+skip",
        backend=spec.name, block_rows=16,
    )


def _feed(clips: list, order: str) -> list[tuple[int, np.ndarray]]:
    """``(camera, frame)`` pairs: every camera interleaved frame by
    frame (``round_robin``), or camera 0's whole clip first and the rest
    interleaved after it (``camera_ahead``)."""
    def interleave(cams):
        n = max(len(clips[c]) for c in cams)
        return [(c, clips[c][i]) for i in range(n) for c in cams if i < len(clips[c])]

    if order == "round_robin":
        return interleave(range(len(clips)))
    return [(0, f) for f in clips[0]] + interleave(range(1, len(clips)))


@pytest.mark.parametrize("stream", list(STREAMS))
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("spec", TEMPORAL, ids=_ids)
def test_farm_run(spec, mode, stream):
    frames = STREAMS[stream]()
    fs = _scheduler(spec, mode)
    got = list(fs.run(iter(frames)))
    assert len(got) == len(frames)
    for i, (frame, edges) in enumerate(zip(frames, got)):
        assert (edges == _oracle(spec)(frame, PARAMS)).all(), (
            f"{spec.name} {mode}: {stream} frame {i} diverged"
        )
    assert fs.stats.frames == len(frames)


@pytest.mark.parametrize("order", ("round_robin", "camera_ahead"))
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("spec", TEMPORAL, ids=_ids)
def test_farm_run_sessions(spec, mode, order):
    clips = [STREAMS[name]() for name in CAMERAS]
    feed = _feed(clips, order)
    fs = _scheduler(spec, mode)
    got = list(fs.run_sessions(iter(feed)))
    assert [cam for cam, _ in got] == [cam for cam, _ in feed]
    for cam, clip in enumerate(clips):
        edges = [e for c, e in got if c == cam]
        assert len(edges) == len(clip)
        for i, (frame, e) in enumerate(zip(clip, edges)):
            assert (e == _oracle(spec)(frame, PARAMS)).all(), (
                f"{spec.name} {mode} {order}: camera {cam} "
                f"({CAMERAS[cam]}) frame {i} diverged"
            )
        holders = [k for k, t in enumerate(fs.sessions) if cam in t.table]
        assert holders == [fs.route(cam)], (cam, holders)
    assert fs.stats.sessions_opened == len(clips)


@pytest.mark.parametrize("spec", ZOO, ids=_ids)
def test_farm_zoo_op(spec):
    frames = all_changing()
    fs = FarmScheduler(
        PARAMS, n_workers=2, warm=False,
        detector=make_detector(PARAMS, op=spec.op, backend=spec.name),
    )
    got = list(fs.run(iter(frames)))
    assert len(got) == len(frames)
    for i, (frame, edges) in enumerate(zip(frames, got)):
        want = spec.ref_fn(frame, PARAMS)
        assert edges.shape == want.shape
        assert (edges == want).all(), f"{spec.op}: frame {i} diverged"
