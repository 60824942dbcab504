"""True (unpadded) pixels of the requests completed in the window, in
millions, over the window."""

from bench import measures


def read(rec):
    done = measures.completed_in_window(rec)
    return sum(r["px"] for r in done) / 1e6 / rec["window_s"] if done else None
