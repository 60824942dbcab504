"""The comparison that decides ``correct``.

Each number compared has its limit in the configuration's ``limits``:

- ``mismatch_ppm``: of the outputs compared, the worst one's share of
  pixels that differ from the plain reference, in parts per million (a
  missing output or a wrong shape reads 1e6);
- ``unanswered``: requests or frames of the window that never came back
  or failed (limit 0).
"""

from __future__ import annotations

import numpy as np

from bench import reference


def mismatch_ppm(got, want: np.ndarray) -> float:
    if got is None or np.shape(got) != want.shape:
        return 1e6
    return float(np.count_nonzero(np.asarray(got) != want)) * 1e6 / want.size


def compare(outputs, config: dict, failed: int, control: str | None = None) -> dict:
    """``outputs``: ``(got, image)`` pairs from the window; the reference
    runs once per distinct image. ``control`` names a lower precision
    whose reference takes the program's place (the control run)."""
    refs: dict[int, np.ndarray] = {}
    controls: dict[int, np.ndarray] = {}
    worst = 0.0
    for got, image in outputs:
        key = id(image)
        if key not in refs:
            refs[key] = reference.canny(image, config["canny"])
        if control is not None:
            if key not in controls:
                controls[key] = reference.canny(image, config["canny"], control)
            got = controls[key]
        worst = max(worst, mismatch_ppm(got, refs[key]))
    limits = config["limits"]
    return {
        "mismatch_ppm": {"value": worst, "limit": limits["mismatch_ppm"]},
        "unanswered": {"value": failed, "limit": limits["unanswered"]},
    }


class Reservoir:
    """A uniform sample of ``k`` items per class, drawn from a seed."""

    def __init__(self, k: dict, seed: int):
        self.k = k
        self.rng = np.random.default_rng((seed, 3))
        self.seen = {c: 0 for c in k}
        self.items = {c: [] for c in k}

    def offer(self, cls, item) -> None:
        n = self.seen[cls]
        self.seen[cls] = n + 1
        if n < self.k[cls]:
            self.items[cls].append(item)
        else:
            j = int(self.rng.integers(n + 1))
            if j < self.k[cls]:
                self.items[cls][j] = item

    def all(self) -> list:
        return [it for c in sorted(self.items) for it in self.items[c]]
