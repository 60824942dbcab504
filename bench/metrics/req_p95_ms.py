"""95th percentile over every request due in the window, from its due time
on the open-loop schedule to its result; a failed request is a miss."""

from bench import measures


def read(rec):
    lat = [(r["complete"] - r["due"]) * 1e3 if r["ok"] else float("inf")
           for r in measures.window_requests(rec)]
    lat += [float("inf")] * rec["unanswered"]
    return measures.quantile(lat, 0.95)
