"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Every reader takes the run's record (what the entry's ``window`` returned,
plus ``trace``, the reduced profile, in a traced run, and ``device_kind``)
and returns one number, or None where the run holds nothing to read.
"""

from __future__ import annotations

import math
import statistics

from bench import costs


def quantile(values, q: float) -> float | None:
    """Nearest-rank ``q``-quantile; failed requests enter as ``inf``."""
    if not values:
        return None
    ranked = sorted(values)
    v = ranked[max(0, math.ceil(q * len(ranked)) - 1)]
    return None if math.isinf(v) else v


def median(values) -> float | None:
    return statistics.median(values) if values else None


def window_requests(rec: dict) -> list[dict]:
    """Requests due inside the window (open loop), or sent inside it."""
    end = rec["t0"] + rec["window_s"]
    return [r for r in rec["requests"] if r["due"] < end]


def completed_in_window(rec: dict) -> list[dict]:
    end = rec["t0"] + rec["window_s"]
    return [r for r in rec["requests"] if r["ok"] and r["complete"] <= end]


def idle_pct(rec: dict) -> float | None:
    t = rec.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def roofline_pct(rec: dict) -> float | None:
    """Algorithmic bytes of the requests dispatched in the traced window
    over the device time of every module launched in it, against peak
    HBM bandwidth."""
    t = rec.get("trace")
    if not t or t["module_s"] <= 0:
        return None
    end = rec["t0"] + rec["window_s"]
    shapes = [r["shape"] for r in rec["requests"]
              if r["dispatch"] is not None and rec["t0"] <= r["dispatch"] <= end]
    if not shapes:
        return None
    bw = costs.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * costs.canny_bytes(shapes) / t["module_s"] / bw
