"""Frames emitted in order inside the window, over the window."""


def read(rec):
    return rec["frames_in_window"] / rec["window_s"] if rec["frames_in_window"] else None
