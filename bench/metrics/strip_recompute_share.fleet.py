"""Front-end strips recomputed (``StreamStats.frontend_strips``) over the
strips a cold front end would run (each session's first, cold frame's
count), for the frames fed in the window."""


def read(rec):
    s = rec["stream"]
    cold = rec["cold_strips_per_frame"] * s["frames"]
    return 100.0 * s["frontend_strips"] / cold if cold else None
