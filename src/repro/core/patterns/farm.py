"""Farm pattern — N workers drain one stream, results emitted in order.

The paper's top-level composition is a *farm of pipelines*: a stream of
images fans out to N replicated CED pipelines and the results merge back
in input order. This module is the host-side scheduler for that shape:

  * **dispatch** is round-robin over per-worker bounded queues, so the
    frame→worker assignment is a pure function of the sequence number
    (deterministic replay, and per-worker streams are contiguous strides
    — worker k sees frames k, k+N, k+2N, … which keeps any per-worker
    temporal state maximally fresh). A ``route`` of the item replaces
    ``seq % N`` (dispatch by key: every frame of one camera to the
    worker that holds its session); each routed dispatch, the route
    choice, the wait for room and the enqueue, is one ``canny.route``
    span on the feeder thread.
  * **backpressure**: the feeder blocks when a worker's queue is full, so
    at most ``n_workers · (queue_depth + 1)`` items are in flight and a
    slow consumer throttles the source instead of buffering the stream.
    Under a ``route`` the feeder also waits while ``n_workers ·
    (queue_depth + 2)`` items are fed but not yet emitted: round-robin
    spreads every stretch of the feed over all workers, a route need not.
  * **in-order emission**: results park in a reorder buffer keyed by
    sequence number; the consumer sees exactly the input order (paper
    claim C4). The buffer is bounded by the same backpressure invariant:
    ``|reorder| ≤ n_workers · (queue_depth + 2)``, with or without a
    route. A ``.stream`` worker is handed a ``WorkerFeed``; one that
    holds a finished result until its next item (``PatternPipeline``)
    hands it back first whenever ``ready()`` says no next item is queued,
    so no result waits on a later arrival and the routed window always
    drains.
  * **worker restarts** (``max_restarts > 0``): a worker that raises is
    REPLACED instead of tearing the stream down — its in-flight frames
    (dispatched but unresulted) are re-fed to the replacement first, so
    no sequence number is ever lost and emission order is unchanged.
    ``worker_factory(k)`` builds the replacement (fresh state); without
    a factory the original callable is retried (stateless workers).
  * **bounded waits** (``timeout``): the consumer's result wait polls
    under exponential backoff and raises a typed ``StreamTimeout`` once
    ``timeout`` seconds pass with NO progress — a hung worker becomes a
    catchable error, never a deadlock. The deadline is per-result:
    every emitted frame resets it.

Workers are either plain callables (item → result, run on a worker
thread) or objects with a ``stream(items) → results`` iterator method
(1:1 and order-preserving) for workers that pipeline internally, e.g. a
double-buffered ``PatternPipeline`` overlapping H2D transfer with
compute. Python threads suffice: the heavy lifting happens inside JAX
dispatch/NumPy, which release the GIL.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Sequence

from repro.core.spans import span
from repro.distributed.fault_tolerance import Backoff, FailFast, StreamTimeout


def put_cancellable(q: queue.Queue, msg, cancelled: Callable[[], bool]) -> bool:
    """Bounded put that polls ``cancelled`` instead of blocking forever —
    the backpressure primitive the farm feeder and the stream Prefetcher
    share. Returns False if cancelled before the item fit."""
    while not cancelled():
        try:
            q.put(msg, timeout=0.1)
            return True
        except queue.Full:
            continue
    return False


class WorkerFeed:
    """One worker's items, as a ``.stream`` worker receives them.

    ``ready()`` says, without blocking, whether the next item (or the end
    of the stream) is already queued. A worker that holds a finished
    result until it has its next item must hand the result back first
    when ``ready()`` is False: that item may be long in coming (a camera
    that pauses), and the results of every other worker wait in order
    behind this one.
    """

    def __init__(self, items: Iterator, ready: Callable[[], bool]):
        self._items = items
        self.ready = ready

    def __iter__(self) -> "WorkerFeed":
        return self

    def __next__(self):
        return next(self._items)


class Farm:
    """Farm executor over ``workers`` (callables or ``.stream`` objects).

    ``max_restarts`` dead workers are replaced (``worker_factory(k)``
    builds the slot-``k`` replacement; default: retry the original
    worker object) with their in-flight frames requeued; the
    ``max_restarts + 1``-th death propagates to the consumer as before.
    ``timeout`` bounds the consumer's per-result wait (exponential
    backoff, ``StreamTimeout``); ``None`` preserves the unbounded wait.
    """

    def __init__(
        self,
        workers: Sequence,
        queue_depth: int = 2,
        max_restarts: int = 0,
        worker_factory: Callable[[int], object] | None = None,
        timeout: float | None = None,
    ):
        if not workers:
            raise ValueError("farm needs at least one worker")
        if queue_depth < 1:
            raise ValueError("queue_depth must be >= 1")
        if max_restarts < 0:
            raise ValueError("max_restarts must be >= 0")
        if timeout is not None and timeout <= 0:
            raise ValueError("timeout must be positive (or None for unbounded)")
        self.workers = list(workers)
        self.queue_depth = queue_depth
        self.max_restarts = max_restarts
        self.worker_factory = worker_factory
        self.timeout = timeout
        self.restarts = 0  # cumulative across run()s, sampled by stats layers
        # live input queues, exposed for depth sampling by stats layers
        self.queues: list[queue.Queue] = []

    def queue_depths(self) -> list[int]:
        """Instantaneous input-queue depths (approximate, for stats)."""
        return [q.qsize() for q in self.queues]

    def run(
        self,
        feed: Iterable,
        route: Callable[[object], int] | None = None,
        route_sink: Callable[[float], object] | None = None,
    ) -> Iterator:
        """Yield one result per feed item, in feed order.

        ``route(item)`` names the worker of each item (default ``seq %
        n``); ``route_sink`` takes each ``canny.route`` span's ms."""
        n = len(self.workers)
        self.queues = qs = [queue.Queue(maxsize=self.queue_depth) for _ in range(n)]
        reorder: dict[int, object] = {}
        cond = threading.Condition()
        state = {"total": None, "error": None, "cancel": False, "emitted": 0}
        # routed: most items fed but not yet emitted (module docstring)
        window = n * (self.queue_depth + 2)

        def room(seq: int) -> bool:
            return state["cancel"] or seq - state["emitted"] < window

        def post_error(exc: BaseException) -> None:
            with cond:
                if state["error"] is None:
                    state["error"] = exc
                cond.notify_all()

        def cancelled() -> bool:
            return state["cancel"]

        def feeder() -> None:
            seq = 0
            try:
                for item in feed:
                    if route is None:
                        if not put_cancellable(qs[seq % n], (seq, item), cancelled):
                            return
                    else:
                        with span("canny.route", route_sink):
                            k = route(item)
                            with cond:
                                cond.wait_for(lambda: room(seq))
                            if not put_cancellable(qs[k], (seq, item), cancelled):
                                return
                    seq += 1
            except BaseException as exc:  # noqa: BLE001 — relayed to consumer
                post_error(exc)
            finally:
                with cond:
                    state["total"] = seq
                    cond.notify_all()
                for q in qs:
                    put_cancellable(q, None, cancelled)  # end-of-stream sentinels

        threads: list[threading.Thread] = []

        def worker_loop(k: int, w, preload: Sequence[tuple[int, object]]) -> None:
            # every frame pulled but not yet resulted — what a restart
            # must requeue so no sequence number is lost with the worker
            pending: collections.deque[tuple[int, object]] = collections.deque()
            left = collections.deque(preload)  # a dead predecessor's in-flight frames

            def items() -> Iterator:
                while left:
                    if state["cancel"]:
                        return
                    msg = left.popleft()
                    pending.append(msg)
                    yield msg[1]
                while True:
                    try:
                        msg = qs[k].get(timeout=0.1)
                    except queue.Empty:
                        # safety net for restarts: the predecessor may have
                        # consumed this queue's end-of-stream sentinel, so
                        # "feeder done + queue empty" must also terminate
                        if state["cancel"] or (
                            state["total"] is not None and qs[k].empty()
                        ):
                            return
                        continue
                    if msg is None or state["cancel"]:
                        return
                    pending.append(msg)
                    yield msg[1]

            feed = WorkerFeed(items(), lambda: bool(left) or not qs[k].empty())
            stream = getattr(w, "stream", None)
            results = stream(feed) if stream is not None else map(w, feed)
            try:
                for res in results:
                    with cond:
                        reorder[pending.popleft()[0]] = res
                        cond.notify_all()
            except BaseException as exc:  # noqa: BLE001 — restart or relay
                restart = False
                with cond:
                    if not state["cancel"] and self.restarts < self.max_restarts:
                        self.restarts += 1
                        restart = True
                    elif state["error"] is None:
                        state["error"] = exc
                    cond.notify_all()
                if not restart:
                    return
                try:
                    new_w = (
                        self.worker_factory(k)
                        if self.worker_factory is not None
                        else w
                    )
                    self.workers[k] = new_w
                    t = FailFast(
                        target=worker_loop,
                        args=(k, new_w, list(pending)),
                        daemon=True,
                        on_error=post_error,
                    )
                    with cond:
                        if state["cancel"]:
                            return
                        threads.append(t)
                    t.start()
                except BaseException as exc2:  # noqa: BLE001 — factory failed
                    post_error(exc2)

        # FailFast with on_error=post_error: an exception that escapes a
        # loop's OWN handling (restart machinery, bookkeeping) still posts
        # to the consumer immediately — a dead thread is never lost
        threads.append(FailFast(target=feeder, daemon=True, on_error=post_error))
        threads.extend(
            FailFast(
                target=worker_loop, args=(k, self.workers[k], ()), daemon=True,
                on_error=post_error,
            )
            for k in range(n)
        )
        for t in list(threads):
            t.start()

        def result_ready() -> bool:
            return (
                state["error"] is not None
                or nxt in reorder
                or (state["total"] is not None and nxt >= state["total"])
            )

        nxt = 0
        try:
            while True:
                with cond:
                    if self.timeout is None:
                        cond.wait_for(result_ready)
                    else:
                        # per-result deadline under exponential backoff: a
                        # hung worker raises instead of parking us forever
                        deadline = time.monotonic() + self.timeout
                        for delay in Backoff().delays():
                            if result_ready():
                                break
                            remaining = deadline - time.monotonic()
                            if remaining <= 0:
                                if result_ready():
                                    break
                                raise StreamTimeout(
                                    f"farm result for seq {nxt}", self.timeout
                                )
                            cond.wait(timeout=min(delay, remaining))
                    if state["error"] is not None:
                        raise state["error"]
                    if nxt not in reorder:  # nxt == total: stream exhausted
                        return
                    res = reorder.pop(nxt)
                    state["emitted"] = nxt + 1
                    cond.notify_all()  # room for the routed feeder
                yield res  # outside the lock: the consumer may be slow
                nxt += 1
        finally:
            with cond:
                state["cancel"] = True
                snapshot = list(threads)
                cond.notify_all()  # the routed feeder may wait for room
            for q in qs:  # unblock workers parked on q.get()
                try:
                    q.put_nowait(None)
                except queue.Full:
                    pass
            for t in snapshot:
                # reraise=False: a primary error is already propagating
                # through the consumer; errors here were posted already
                t.join(timeout=5.0, reraise=False)


def farm_map(
    fn: Callable, feed: Iterable, n_workers: int = 2, queue_depth: int = 2
) -> Iterator:
    """Convenience: farm a pure function over a stream, in-order results."""
    return Farm([fn] * n_workers, queue_depth).run(feed)
