"""Requests per launch over the lane it ran in, mean over the launches
dispatched in the window (tickets grouped by their dispatch stamp)."""


def read(rec):
    end = rec["t0"] + rec["window_s"]
    occ = [n / lane for t, n, lane in rec["dispatches"] if rec["t0"] <= t <= end]
    return sum(occ) / len(occ) if occ else None
