"""1 - true/padded pixels launched in the window (``EngineStats`` deltas):
phantom lane slots and height/width padding."""


def read(rec):
    e = rec["engine"]
    return 100.0 * (1.0 - e["true_px"] / e["padded_px"]) if e["padded_px"] else None
