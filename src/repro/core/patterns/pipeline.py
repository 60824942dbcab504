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
    ``canny.step`` span (``core/spans.py``).
    """

    def __init__(self, fn: Callable, sharding=None):
        self.fn = fn
        self.sharding = sharding

    def _put(self, batch):
        with span("canny.put"):
            return jax.device_put(batch, self.sharding)

    def run(self, feed: Iterable) -> Iterator:
        it = iter(feed)
        try:
            nxt = self._put(next(it))
        except StopIteration:
            return
        while True:
            cur = nxt
            with span("canny.step"):
                out = self.fn(cur)  # dispatches async
            try:
                nxt = self._put(next(it))  # overlaps with compute
            except StopIteration:
                yield out
                return
            yield out
