"""Traffic: one seed gives one schedule; every seed gives the same work."""

from __future__ import annotations

import collections
import itertools

import numpy as np
import pytest

from bench import harness, images, schedule

SIZES = {"a4": 2, "bsds": 8, "hd": 4}
BIG_SEED = 2**31 + 12345
# an open-loop mix of three classes, to test the generator's arithmetic
OPEN = {"rate_per_s": 42, "block": 100, "mix": {"bsds": 0.6, "hd": 0.3, "a4": 0.1}}


def test_open_loop_repeats_exactly_for_a_seed():
    t = OPEN
    assert schedule.open_loop(t, SIZES, BIG_SEED, 20) == schedule.open_loop(t, SIZES, BIG_SEED, 20)
    assert schedule.open_loop(t, SIZES, BIG_SEED, 20) != schedule.open_loop(t, SIZES, BIG_SEED + 1, 20)


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_every_seed_sends_the_same_blocks(seed):
    t = OPEN
    block = t["block"]
    reqs = list(itertools.islice(schedule.requests(t, SIZES, seed), 3 * block))
    for b in range(3):
        part = reqs[b * block:(b + 1) * block]
        assert collections.Counter(c for c, _, _ in part) == {
            c: round(w * block) for c, w in t["mix"].items()}
        gaps = sorted(g for _, _, g in part)
        assert gaps == pytest.approx(sorted(schedule.block_gaps(t["rate_per_s"], block)))
        assert sum(gaps) == pytest.approx(block / t["rate_per_s"])
    for c, n in SIZES.items():  # each class walks its whole corpus
        assert {i for k, i, _ in reqs if k == c} == set(range(n))


def test_closed_loop_has_no_gaps():
    t = dict(harness.load_cell("upload_a4")["traffic"], mix=OPEN["mix"])
    reqs = list(itertools.islice(schedule.requests(t, SIZES, BIG_SEED), 200))
    assert all(g == 0 for _, _, g in reqs)
    assert reqs == list(itertools.islice(schedule.requests(t, SIZES, BIG_SEED), 200))


def test_a_mix_that_does_not_split_a_block_is_refused():
    with pytest.raises(ValueError):
        schedule.block_classes({"a": 0.33, "b": 0.33, "c": 0.34}, 10)


def test_replay_orders():
    ping = {"ring": 4, "replay": "pingpong", "hold": 1}
    assert [schedule.replay_index(s, ping) for s in range(9)] == [0, 1, 2, 3, 2, 1, 0, 1, 2]
    cyc = {"ring": 4, "replay": "cycle", "hold": 2}
    assert [schedule.replay_index(s, cyc) for s in range(9)] == [0, 0, 1, 1, 2, 2, 3, 3, 0]


def test_inputs_repeat_exactly_for_a_seed():
    a = images.scene(40, 60, np.random.default_rng((BIG_SEED, 1)))
    b = images.scene(40, 60, np.random.default_rng((BIG_SEED, 1)))
    assert a.dtype == np.float32 and np.array_equal(a, b)
    cam = images.CameraScene(48, 64, BIG_SEED, radii=(10, 6), noise=0.01)
    again = images.CameraScene(48, 64, BIG_SEED, radii=(10, 6), noise=0.01)
    assert np.array_equal(cam.frame(5), again.frame(5))
    assert not np.array_equal(cam.frame(5), cam.frame(6))
    held = images.CameraScene(48, 64, BIG_SEED, radii=(10, 6))
    assert np.array_equal(held.frame(3), images.CameraScene(48, 64, BIG_SEED, radii=(10, 6)).frame(3))
