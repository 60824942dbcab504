"""The benchmark's plain reference decides the same pixels as the
program's serial oracle, and its bfloat16 control does not."""

from __future__ import annotations

import numpy as np
import pytest

from bench import images, reference

PARAMS = {"sigma": 1.4, "radius": 2, "low": 0.1, "high": 0.2, "l2_norm": True}


@pytest.mark.parametrize("shape,seed", [((33, 49), 1), ((49, 33), 2), ((72, 128), 3), ((5, 7), 4)])
def test_equals_the_serial_oracle(shape, seed):
    from repro.core.canny import CannyParams, canny_reference

    img = images.scene(*shape, np.random.default_rng(seed))
    want = canny_reference(img, CannyParams(**PARAMS))
    got = reference.canny(img, PARAMS)
    assert got.dtype == np.uint8 and np.array_equal(got, want)


def test_l1_magnitude_matches_too():
    from repro.core.canny import CannyParams, canny_reference

    p = dict(PARAMS, l2_norm=False, low=0.15, high=0.3)
    img = images.scene(40, 56, np.random.default_rng(9))
    assert np.array_equal(reference.canny(img, p), canny_reference(img, CannyParams(**p)))


def test_ties_keep_both_neighbours():
    mag = np.array([[0, 0, 0], [0.5, 0.5, 0.2], [0, 0, 0]], np.float32)
    dirs = np.zeros((3, 3), np.uint8)  # compare east/west
    assert reference.nms(mag, dirs)[1].tolist() == pytest.approx([0.5, 0.5, 0.0])


def test_hysteresis_follows_8_connected_weak_chains():
    m = np.zeros((5, 6), np.float32)
    m[1, 1] = 0.3  # strong
    m[2, 2] = m[3, 3] = 0.15  # weak, diagonal chain from the strong pixel
    m[1, 5] = 0.15  # weak, isolated
    out = reference.hysteresis(m, 0.1, 0.2)
    assert out[1, 1] == out[2, 2] == out[3, 3] == 1 and out[1, 5] == 0 and out.sum() == 3


def test_bfloat16_control_differs():
    img = images.scene(200, 300, np.random.default_rng(5))
    f32 = reference.canny(img, PARAMS)
    bf16 = reference.canny(img, PARAMS, "bfloat16")
    assert np.count_nonzero(f32 != bf16) > 0
