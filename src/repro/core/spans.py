"""Named host spans: one interval, seen by the profiler and by the stats.

``span(name)`` is a ``jax.profiler.TraceAnnotation``, which writes an
event into the trace only while a profiler session is active
(``jax.profiler.start_trace``, or ``start_server`` for an operator to
capture from); otherwise it costs about half a microsecond. Given
``sink``, a callable such as ``deque.append`` or
``StreamStats.record_prep``, the span also passes its duration in ms to
it once the body has completed, so a summary reads the same interval the
trace shows.

The program's spans are named ``canny.<phase>``, each covering one host
phase and never enclosing another, so a trace's spans of one name add up
to that phase's time on its thread. They sit on the host side only: never
inside a jitted or Pallas function, and never adding a device sync.

The spans and the counters their sinks feed:

- ``canny.wait``, ``canny.pack``: the serving dispatch thread
  (``serve/admission.py``), no sink; ``StreamStats.arena_lanes`` counts
  the lanes packed into the batcher's host arenas, and
  ``StreamStats.overlapped_lanes`` those launched while an earlier lane's
  ``run_packed`` had not yet returned;
- ``canny.put``, ``canny.step``, ``canny.fetch``: the serving runner
  threads (``serve/aot.py:run_packed``), no sink; and the stream workers
  (``stream/scheduler.py:StreamWorker``), with ``canny.prep`` and
  ``canny.cost_sync``: each adds its ms to ``StreamStats.worker_ms[name]``;
  ``canny.prep`` also feeds ``prep_ms``, and ``canny.fetch``
  ``compute_ms``, the watchdog and ``frames_by_device``;
- ``canny.route``: the farm's feeder in session mode
  (``core/patterns/farm.py``), the route choice, the wait for room and
  the enqueue, into
  ``StreamStats.route_ms``. ``StreamStats.sessions_opened`` counts the
  sessions ``SessionTable`` opens.
"""

from __future__ import annotations

import time
from typing import Callable

from jax.profiler import TraceAnnotation


def span(name: str, sink: Callable[[float], object] | None = None):
    return TraceAnnotation(name) if sink is None else _Timed(name, sink)


class _Timed:
    """A ``TraceAnnotation`` that passes its duration to ``sink``."""

    __slots__ = ("_annotation", "_sink", "_t0")

    def __init__(self, name: str, sink: Callable[[float], object]):
        self._annotation = TraceAnnotation(name)
        self._sink = sink

    def __enter__(self) -> None:
        self._t0 = time.perf_counter()
        self._annotation.__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None:  # a failed body records nothing
            self._sink((time.perf_counter() - self._t0) * 1e3)
