"""Synthetic inputs drawn from a seed: upload photos/scans and camera frames.

Scenes hold what edge detection works on: straight and curved edges at
many contrasts over smooth shading, plus sensor noise. The shape count
grows with the area, so a 1080p photo and an A4 scan have about the same
edge density as a BSDS-sized image. Shapes are drawn inside their own
bounding boxes, so an A4 page costs a fraction of a second.

``CameraScene`` follows the program's ``SyntheticStream`` (a static scene,
drifting disks, a low-contrast disk whose boundary sits between the
hysteresis thresholds, a frame-invariant sub-threshold texture that breaks
magnitude ties, optional per-frame noise); it is copied here so that the
benchmark's inputs do not change when the program's generator does. The
disks' radii are the mix's, not the seed's, so every seed moves the same
area of the frame.
"""

from __future__ import annotations

import numpy as np

SHAPES_PER_MPX = 40


def scene(height: int, width: int, rng: np.random.Generator,
          noise: float = 0.03) -> np.ndarray:
    """A float32 scene in [0, 1]: shading, rectangles, disks, noise."""
    yy = np.arange(height, dtype=np.float32)[:, None]
    xx = np.arange(width, dtype=np.float32)[None, :]
    img = 0.25 + 0.15 * np.sin(xx / width * 4.0) * np.cos(yy / height * 3.0)
    img = img.astype(np.float32)
    n = max(6, int(SHAPES_PER_MPX * height * width / 1e6))
    small = min(height, width)
    for _ in range(n):
        level = np.float32(rng.uniform(0.35, 0.95))
        cy, cx = int(rng.integers(0, height)), int(rng.integers(0, width))
        r = int(rng.integers(3, max(4, small // 8)))
        y0, y1 = max(cy - r, 0), min(cy + r + 1, height)
        x0, x1 = max(cx - r, 0), min(cx + r + 1, width)
        if rng.integers(0, 2):  # rectangle
            img[y0:y1, x0:x1] = level
        else:  # disk
            box = img[y0:y1, x0:x1]
            d2 = (yy[y0:y1] - cy) ** 2 + (xx[:, x0:x1] - cx) ** 2
            box[d2 <= r * r] = level
    if noise > 0:
        img += rng.standard_normal((height, width), dtype=np.float32) * np.float32(noise)
    return np.clip(img, 0.0, 1.0, out=img)


class CameraScene:
    """Frames of one fixed camera: ``frame(i)`` is a pure function of the
    constructor's arguments and ``i``."""

    def __init__(self, height: int, width: int, seed: int, radii=(120, 72),
                 noise: float = 0.0, speed: float = 2.0):
        self.height, self.width = height, width
        self.seed, self.noise, self.speed = seed, noise, speed
        rng = np.random.default_rng((seed, 0))
        self._base = scene(height, width, rng, noise=0.0)
        self._texture = rng.uniform(-0.004, 0.004, size=(height, width)).astype(np.float32)
        self._pos = rng.uniform(0.2, 0.8, size=(len(radii), 2))
        ang = rng.uniform(0, 2 * np.pi, size=len(radii))
        self._vel = np.stack([np.cos(ang), np.sin(ang)], axis=1)
        self._size = np.asarray(radii)
        self._still = np.clip(self._base + self._texture, 0.0, 1.0)

    def frame(self, i: int) -> np.ndarray:
        h, w = self.height, self.width
        img = self._still.copy()
        for k in range(len(self._size)):
            # reflective drift keeps the objects in frame
            p = self._pos[k] + self._vel[k] * self.speed * i / max(h, w)
            p = np.abs(np.mod(p, 2.0) - 1.0)
            cy, cx = p[0] * (h - 1), p[1] * (w - 1)
            r = float(self._size[k])
            y0, y1 = max(int(cy - r) - 1, 0), min(int(cy + r) + 2, h)
            x0, x1 = max(int(cx - r) - 1, 0), min(int(cx + r) + 2, w)
            yy = np.arange(y0, y1, dtype=np.float32)[:, None]
            xx = np.arange(x0, x1, dtype=np.float32)[None, :]
            inside = (yy - cy) ** 2 + (xx - cx) ** 2 <= r * r
            base = self._base[y0:y1, x0:x1]
            tex = self._texture[y0:y1, x0:x1]
            box = img[y0:y1, x0:x1]
            if k % 2 == 0:  # hard disk: strong edges
                box[inside] = np.clip(0.9 + tex[inside], 0.0, 1.0)
            else:
                # low-contrast disk: a weak-only boundary chain, reachable
                # from a small strong anchor on it, so hysteresis has to
                # walk it
                lifted = np.clip(np.clip(base + 0.16, 0.0, 1.0) + tex, 0.0, 1.0)
                box[inside] = lifted[inside]
                ay = int(np.clip(cy + r, 1, h - 2))
                ax = int(np.clip(cx, 1, w - 2))
                img[ay - 1 : ay + 2, ax - 1 : ax + 2] = np.clip(
                    0.9 + self._texture[ay - 1 : ay + 2, ax - 1 : ax + 2], 0.0, 1.0
                )
        if self.noise > 0:
            rng = np.random.default_rng((self.seed, 1, i))
            img += rng.standard_normal((h, w), dtype=np.float32) * np.float32(self.noise)
            np.clip(img, 0.0, 1.0, out=img)
        return img.astype(np.float32, copy=False)
