"""Median launch time (H2D, device, blocking fetch) of the window's
launches: ``EngineStats.latencies_ms`` appended during the window."""

from bench import measures


def read(rec):
    return measures.median(rec["launch_ms"])
