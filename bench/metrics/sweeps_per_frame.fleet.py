"""Hysteresis sweep launches (``StreamStats.launches``) per frame fed in
the window."""


def read(rec):
    s = rec["stream"]
    return s["launches"] / s["frames"] if s["frames"] else None
