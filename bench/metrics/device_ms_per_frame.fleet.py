"""Device busy time in the traced window, summed over the trace's devices
(``bench/trace.py`` averages it over them), over the frames emitted in
the window: the device ms a frame costs, on whichever chip it ran."""


def read(rec):
    t = rec.get("trace")
    if not t or not rec.get("frames_in_window"):
        return None
    return t["busy_s"] * t["devices"] * 1e3 / rec["frames_in_window"]
