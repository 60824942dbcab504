"""Device busy time in the traced window over the frames emitted in it."""


def read(rec):
    t = rec.get("trace")
    if not t or not rec["frames_in_window"]:
        return None
    return t["busy_s"] * 1e3 / rec["frames_in_window"]
