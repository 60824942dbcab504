"""Median enqueue-to-dispatch wait of the window's requests, read from
their tickets (``SloTicket.t_enqueue``/``t_dispatch``)."""

from bench import measures


def read(rec):
    return measures.median([(r["dispatch"] - r["enqueue"]) * 1e3
                            for r in measures.window_requests(rec) if r["dispatch"] is not None])
