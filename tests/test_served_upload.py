"""Served-path conformance: every serving backend behind the AOT plane.

``test_differential.py`` pins the bare detectors. This file pins the
composition the upload cells run, ``AotCannyEngine`` behind a
``ContinuousBatcher`` that packs every lane into its one host arena, for
EVERY backend with a serving entry. The backends come from
``backend_specs()``, so a backend is covered the moment it registers.
Each case serves one traffic mix and compares every result bit for bit
with the spec's own oracle (``ref_fn``, else ``canny_reference``):

  * ``full_lanes``: 8 same-size requests, two full lanes of 4 (the fill
    trigger);
  * ``mixed``: odd sizes over two buckets, each a partial lane released
    by linger, so the arena's leading view changes shape between lanes;
  * ``single``: one request, lane 1.

The batcher's clock is a hand-moved fake: a lane leaves on the fill
trigger alone until the test moves the clock past the linger, so the
lanes launched are the same on every run. Every case also pins the
no-retrace contract (``post_warmup_traces == 0``) and that every lane
launched was packed into the arena.
"""

import numpy as np
import pytest

from repro.core.canny import backend_specs, canny_reference
from repro.data.images import synthetic_image
from repro.serve import AotCannyEngine, ContinuousBatcher

from _conformance import PARAMS

SERVING = [s for s in backend_specs() if s.serving_fn is not None]
LINGER_MS = 1_000.0
# (request sizes, occupancy of the lanes launched, released by linger);
# CORPUS_SIZES' (37, 53) and (48, 64) share bucket (64, 64), (21, 33) is
# in (32, 64): lanes of 4 (3 of 4 filled) and 2
TRAFFIC = {
    "full_lanes": ([(48, 64)] * 8, [1.0, 1.0], False),
    "mixed": ([(37, 53), (21, 33), (48, 64), (21, 33), (37, 53)], [0.75, 1.0], True),
    "single": ([(21, 33)], [1.0], True),
}


class _Clock:
    """The batcher's clock, moved by hand (read by its two threads)."""

    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


@pytest.fixture(scope="module", params=SERVING, ids=lambda s: s.name)
def served(request):
    """One engine per backend for the module: the lattice of every
    traffic mix's buckets x lanes 1, 2, 4, compiled once."""
    spec = request.param
    engine = AotCannyEngine(
        PARAMS, backend=spec.name, bucket_multiple=32, max_batch=4,
        calibration=[hw for sizes, _, _ in TRAFFIC.values() for hw in sizes],
    )
    assert engine.lanes == (1, 2, 4)
    return spec, engine


@pytest.mark.parametrize("traffic", list(TRAFFIC))
def test_served_upload(served, traffic):
    spec, engine = served
    sizes, occupancy, by_linger = TRAFFIC[traffic]
    ref_fn = spec.ref_fn or canny_reference
    images = [synthetic_image(h, w, seed=300 + i) for i, (h, w) in enumerate(sizes)]
    batches = engine.stats.batches
    clock = _Clock()
    with ContinuousBatcher(
        engine, linger_ms=LINGER_MS, timeout=120.0, clock=clock
    ) as b:
        tickets = [b.submit(img) for img in images]
        if by_linger:
            clock.now += 2 * LINGER_MS / 1e3
        got = [t.result(timeout=120.0) for t in tickets]
    for i, (img, edges) in enumerate(zip(images, got)):
        want = ref_fn(img, PARAMS)
        assert edges.shape == want.shape
        assert (edges == want).all(), f"{spec.name} {traffic}: request {i} diverged"
    assert engine.post_warmup_traces == 0
    launched = engine.stats.batches - batches
    assert sorted(b.stats.slot_occupancy) == occupancy
    assert b.stats.arena_lanes == launched == len(occupancy)
