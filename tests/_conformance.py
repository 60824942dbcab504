"""Shared inputs of the conformance matrices.

The bare detectors' matrix (``test_differential.py``) and the served
compositions' matrices (``test_served_upload.py``,
``test_served_stream.py``) run the same ``PARAMS`` on the same odd sizes
and adversarial streams, so a divergence between a detector and the
composition that serves it cannot hide behind different inputs.

The streams are chosen adversarially for the temporal paths: all-static
(maximal skip), all-changing (skip must never fire wrongly) and
single-pixel flicker (destructive edits every frame: the warm gate must
fall back cold AND the strip mask must recompute exactly the touched
strips).
"""

from repro.core.canny import CannyParams
from repro.data.images import synthetic_image

PARAMS = CannyParams(sigma=1.4, radius=2, low=0.08, high=0.2)
# odd sizes on purpose: below-halo heights, non-multiple-of-32 widths
CORPUS_SIZES = [(37, 53), (64, 96), (21, 33), (48, 64)]


def all_static(frames=4, h=48, w=64):
    base = synthetic_image(h, w, seed=7)
    return [base.copy() for _ in range(frames)]


def all_changing(frames=4, h=48, w=64):
    return [synthetic_image(h, w, seed=200 + i) for i in range(frames)]


def single_pixel_flicker(frames=5, h=48, w=64):
    """One pixel toggles a strong step every frame: destructive edits
    (the warm gate must go cold) localized to one strip (the skip mask
    must recompute only the strips whose halo sees the pixel)."""
    base = synthetic_image(h, w, seed=9)
    out = []
    for i in range(frames):
        f = base.copy()
        if i % 2:
            f[h // 2, w // 2] = 1.0
        out.append(f)
    return out


STREAMS = {
    "all-static": all_static,
    "all-changing": all_changing,
    "single-pixel-flicker": single_pixel_flicker,
}
