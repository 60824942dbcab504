"""Host ms of the five worker spans (``canny.prep``, ``.put``, ``.step``,
``.fetch``, ``.cost_sync``; ``StreamStats.worker_ms``), summed over every
worker, per frame fed in the window. A program without the spans' sinks
reports nothing."""


def read(rec):
    s = rec["stream"]
    return s["worker_ms"] / s["frames"] if s.get("worker_ms") and s["frames"] else None
