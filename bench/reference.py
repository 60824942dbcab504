"""Plain Canny reference: the benchmark's own copy of the semantics.

The same four stages and the same float32 arithmetic, in the same order,
as the definition the system under test is held to (Gaussian blur with
edge-replicate borders, Sobel with edge-replicate borders and an L2 or L1
magnitude, NMS that keeps a pixel >= both neighbours along its quantized
direction with out-of-bounds neighbours read as 0, and hysteresis that
keeps the weak pixels 8-connected to a strong one). It imports nothing of
the system under test. NMS and hysteresis are vectorized (shifted arrays
and connected-component labels) so that full-size images check in about
a second; they decide the same pixels as a per-pixel loop and a BFS.

``dtype`` is the arithmetic of the blur, the gradients and the magnitude.
``float32`` is the reference; ``bfloat16`` is the control, the nearest
precision below the one the deployments state.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
from scipy import ndimage

# tan(22.5 deg), tan(67.5 deg): direction bin boundaries
_T1 = 0.41421356237309503
_T2 = 2.414213562373095

_SOBEL_X = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], dtype=np.float32)
_SOBEL_Y = np.array([[-1, -2, -1], [0, 0, 0], [1, 2, 1]], dtype=np.float32)

# (dy, dx) of the forward neighbour per direction bin
_NBR = ((0, 1), (1, 1), (1, 0), (1, -1))

DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


def gaussian_kernel1d(sigma: float, radius: int) -> np.ndarray:
    x = np.arange(-radius, radius + 1, dtype=np.float32)
    k = np.exp(-(x * x) / np.float32(2.0 * sigma * sigma))
    return (k / k.sum()).astype(np.float32)


def blur(img: np.ndarray, sigma: float, radius: int, dt) -> np.ndarray:
    """Separable blur, horizontal then vertical, accumulated in ``dt``."""
    img = img.astype(dt)
    k = gaussian_kernel1d(sigma, radius).astype(dt)
    h, w = img.shape
    padded = np.pad(img, ((0, 0), (radius, radius)), mode="edge")
    tmp = np.zeros_like(img)
    for i in range(2 * radius + 1):
        tmp += k[i] * padded[:, i : i + w]
    padded = np.pad(tmp, ((radius, radius), (0, 0)), mode="edge")
    out = np.zeros_like(img)
    for i in range(2 * radius + 1):
        out += k[i] * padded[i : i + h, :]
    return out


def _correlate3(img: np.ndarray, k: np.ndarray) -> np.ndarray:
    h, w = img.shape
    p = np.pad(img, 1, mode="edge")
    k = k.astype(img.dtype)
    out = np.zeros_like(img)
    for dy in range(3):
        for dx in range(3):
            out += k[dy, dx] * p[dy : dy + h, dx : dx + w]
    return out


def gradients(img: np.ndarray, l2_norm: bool):
    """Sobel magnitude (float32) and direction bin (uint8) of ``img``,
    computed in ``img``'s dtype."""
    gx = _correlate3(img, _SOBEL_X)
    gy = _correlate3(img, _SOBEL_Y)
    if l2_norm:
        mag = np.sqrt(gx * gx + gy * gy)
    else:
        mag = np.abs(gx) + np.abs(gy)
    mag = mag.astype(np.float32)
    gx = gx.astype(np.float32)
    gy = gy.astype(np.float32)
    ax, ay = np.abs(gx), np.abs(gy)
    horiz = ay <= _T1 * ax
    vert = ay >= _T2 * ax
    same_sign = (gx * gy) > 0
    dirs = np.where(horiz, 0, np.where(vert, 2, np.where(same_sign, 1, 3)))
    return mag, dirs.astype(np.uint8)


def nms(mag: np.ndarray, dirs: np.ndarray) -> np.ndarray:
    """Magnitudes of the pixels >= both neighbours along their bin."""
    h, w = mag.shape
    mp = np.pad(mag, 1)  # out-of-bounds neighbours read as 0

    def shifted(dy: int, dx: int) -> np.ndarray:
        return mp[1 + dy : 1 + dy + h, 1 + dx : 1 + dx + w]

    keep = np.zeros(mag.shape, bool)
    for b, (dy, dx) in enumerate(_NBR):
        keep |= (dirs == b) & (mag >= shifted(dy, dx)) & (mag >= shifted(-dy, -dx))
    return np.where(keep, mag, np.float32(0))


def hysteresis(nms_mag: np.ndarray, low: float, high: float) -> np.ndarray:
    """Weak pixels 8-connected (transitively) to a strong pixel, as uint8."""
    strong = nms_mag >= high
    weak = nms_mag >= low
    labels, n = ndimage.label(weak, structure=np.ones((3, 3), bool))
    reached = np.zeros(n + 1, bool)
    reached[labels[strong]] = True
    reached[0] = False
    return reached[labels].astype(np.uint8)


def canny(img: np.ndarray, canny_params: dict, dtype: str = "float32") -> np.ndarray:
    """Edge map (uint8 0/1) of one (h, w) image under ``canny_params``
    (sigma, radius, low, high, l2_norm)."""
    p = canny_params
    dt = DTYPES[dtype]
    blurred = blur(np.asarray(img, np.float32), p["sigma"], p["radius"], dt)
    mag, dirs = gradients(blurred, p["l2_norm"])
    return hysteresis(nms(mag, dirs), p["low"], p["high"])
