"""Pipeline pattern — staged execution over a stream of work items.

The paper pipelines images through the CED stages. Two TPU mappings:

  * ``pipeline_stages`` — function composition fused by XLA into one
    program (the common case: stages are fused so intermediates never
    round-trip to HBM; this is the "optimal" schedule).
  * ``PatternPipeline`` — software pipelining across a stream of batches
    with double buffering: while batch i computes, batch i+1's host→device
    transfer is in flight (``jax.device_put`` is async). Used by the
    corpus driver example. On a pod the same schedule becomes GPipe-style
    stage parallelism over the "pod" mesh axis (see distributed/pipeline).
"""

from __future__ import annotations

import functools
from typing import Callable, Iterable, Iterator, Sequence

import jax

from repro.core.spans import span


def pipeline_stages(*stages: Callable) -> Callable:
    """Compose stages f1..fn into one fused program (left-to-right)."""

    def run(x, *args, **kwargs):
        for s in stages:
            x = s(x, *args, **kwargs)
        return x

    return run


class PatternPipeline:
    """Double-buffered stream executor.

    ``fn`` is a jitted device function; ``feed`` yields host batches. The
    executor keeps one batch in flight: transfer(i+1) overlaps compute(i).
    Deterministic: output order == input order (paper claim C4). Each
    transfer is a ``canny.put`` span and each call of ``fn`` a
    ``canny.step`` span (``core/spans.py``); ``record(name, ms)``, when
    given, takes each span's duration.
    """

    def __init__(self, fn: Callable, sharding=None,
                 record: Callable[[str, float], object] | None = None):
        self.fn = fn
        self.sharding = sharding
        self._put_sink = self._step_sink = None
        if record is not None:
            self._put_sink = functools.partial(record, "canny.put")
            self._step_sink = functools.partial(record, "canny.step")

    def _put(self, batch):
        with span("canny.put", self._put_sink):
            return jax.device_put(batch, self.sharding)

    def run(self, feed: Iterable, ready: Callable[[], bool] | None = None) -> Iterator:
        """Yield ``fn`` of each batch, in feed order. ``ready()``, when
        given, says without blocking whether ``feed`` holds its next
        batch; when it does not, the result in hand is yielded before the
        wait for that batch, so no result waits on a later arrival
        (``core/patterns/farm.py:WorkerFeed``)."""
        it = iter(feed)
        try:
            nxt = self._put(next(it))
        except StopIteration:
            return
        while True:
            cur = nxt
            with span("canny.step", self._step_sink):
                out = self.fn(cur)  # dispatches async
            held = ready is None or ready()
            if not held:
                yield out  # nothing queued behind it: hand it back now
            try:
                nxt = self._put(next(it))  # overlaps with compute
            except StopIteration:
                if held:
                    yield out
                return
            if held:
                yield out
