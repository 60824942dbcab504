"""Continuous admission — requests pack into in-flight bucket slots.

``CannyEngine.drain`` runs synchronous waves: every request in a wave
waits for the WHOLE wave barrier, so tail latency under mixed load is
governed by the slowest bucket of each wave and by how long the queue
sat waiting for the wave to start. ``ContinuousBatcher`` removes the
barrier (the MaxText ``prefill_buckets`` + ``detokenize_backlog`` shape,
on Canny buckets):

  * **admission** — ``submit`` fail-fast-validates the request against
    the AOT engine's warmed lattice, stamps its enqueue time, and drops
    it into the per-bucket accumulator. Admission is bounded: more than
    ``max_pending`` unresolved requests polls under backoff and raises a
    typed ``StreamTimeout`` naming this batcher (load shedding, not
    unbounded buffering).
  * **dispatch** — a dedicated fail-fast thread packs each accumulator
    into the smallest precompiled batch lane the moment the largest lane
    FILLS or the oldest request's ``linger_ms`` deadline expires; no
    request ever waits on an unrelated bucket. Slot occupancy and queue
    depth land in ``StreamStats`` gauges.
  * **completion** — launches push onto a BOUNDED result backlog drained
    by a second fail-fast thread that crops per-request results, stamps
    completion, resolves tickets, and scores the request against the
    ``slo_ms`` bound. The bounded backlog is backpressure: a slow
    consumer throttles dispatch instead of buffering results without
    limit.

Any worker exception (dispatch or drain) POISONS the batcher: it is
recorded, every blocked call (``submit``, ``Ticket.result``, ``drain``)
re-raises it at its next poll, and ``close`` re-raises at join — a dead
background thread can never strand the caller in a silent hang
(``FailFast`` + the ``Backoff``/``wait_for`` bounded-wait plane from
``distributed/fault_tolerance.py``).

Bit-exactness is preserved by construction: a request runs the SAME
bucketed executable with the SAME ``pack_requests`` padding as the
synchronous-wave path — continuous admission only changes WHICH requests
share a launch, and the kernels' per-slot true-size border math makes
slot composition invisible to each request's output.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Callable

import numpy as np

from repro.core.patterns.farm import put_cancellable
from repro.core.spans import span
from repro.distributed.fault_tolerance import FailFast, StreamTimeout, wait_for
from repro.serve.aot import AotCannyEngine
from repro.serve.engine import pack_requests

# distinguishes "argument omitted → use the batcher default" from an
# explicit ``timeout=None`` (= wait unbounded), as in serve/engine.py
_UNSET = object()


class SloTicket:
    """Handle for one continuously-admitted request: resolves when its
    slot's launch completes, carries the enqueue→dispatch→complete
    timestamps the SLO accounting is built from."""

    __slots__ = (
        "_batcher", "_result", "_error", "_done",
        "t_enqueue", "t_dispatch", "t_complete", "shape",
    )

    def __init__(self, batcher: "ContinuousBatcher", shape: tuple[int, int],
                 t_enqueue: float):
        self._batcher = batcher
        self._result: np.ndarray | None = None
        self._error: BaseException | None = None
        self._done = False
        self.t_enqueue = t_enqueue
        self.t_dispatch: float | None = None
        self.t_complete: float | None = None
        self.shape = shape

    @property
    def done(self) -> bool:
        return self._done

    def latency_ms(self) -> float | None:
        """Enqueue→complete wall time; None while unresolved."""
        if self.t_complete is None:
            return None
        return (self.t_complete - self.t_enqueue) * 1e3

    def _resolve(self, result: np.ndarray) -> None:
        self._result = result
        self._done = True

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._done = True

    def result(self, timeout: float | None = _UNSET) -> np.ndarray:
        """The uint8 edge map; bounded wait (default: the batcher's
        ``timeout``) under exponential backoff. A poisoned batcher
        re-raises its recorded worker error instead of spinning."""
        if timeout is _UNSET:
            timeout = self._batcher.timeout

        def resolved() -> bool:
            if self._done:
                return True
            self._batcher.check()  # poisoned → raise, never hang
            return False

        wait_for(
            resolved, timeout,
            what=f"batcher {self._batcher.name!r} ticket result",
        )
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


class _Accumulator:
    """One bucket's open slot: requests waiting to be packed, oldest
    first (deque popleft order IS submission order — deterministic)."""

    __slots__ = ("waiting",)

    def __init__(self):
        self.waiting: collections.deque[SloTicket] = collections.deque()


class ContinuousBatcher:
    """Continuous admission over an ``AotCannyEngine``.

    ``submit`` → ``SloTicket``; a dispatch thread packs open bucket slots
    (fill-or-linger), a drain thread resolves results from a bounded
    backlog. ``stats`` (a ``stream.scheduler.StreamStats``) accumulates
    the per-request SLO plane: enqueue→complete latency samples,
    p50/p95/p99, queue-depth + slot-occupancy gauges, and the pass/fail
    counter against ``slo_ms``. The dispatch thread's phases are the
    ``canny.wait``, ``canny.pack`` and (in ``run_packed``) ``canny.put``,
    ``canny.step`` and ``canny.fetch`` spans (``core/spans.py``).

    Every lane is packed into one float32 host arena, allocated and
    written once here, sized to the lattice's largest cell (max of
    lane · hb · wb): 141 MB for lanes of 4 A4 pages. A fresh batch that
    large is a fresh ``mmap`` whose pages all fault on first touch, in
    every lane; the arena's faults land in construction instead. One
    arena suffices because the dispatch loop is serial: ``run_packed``
    returns only once the device result is fetched, so the transfer that
    read the last lane is over, and tickets get slices of that fetched
    result, never of the arena. ``stats.arena_lanes`` counts its lanes.

    Use as a context manager, or call ``close()``; both flush open slots,
    stop the workers, and re-raise any recorded worker error.
    """

    def __init__(
        self,
        engine: AotCannyEngine,
        linger_ms: float = 5.0,
        max_pending: int | None = None,
        backlog: int = 8,
        slo_ms: float | None = None,
        timeout: float | None = None,
        stats=None,
        name: str | None = None,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if linger_ms < 0:
            raise ValueError("linger_ms must be >= 0")
        if max_pending is not None and max_pending < 1:
            raise ValueError("max_pending must be >= 1 (or None for unbounded)")
        if backlog < 1:
            raise ValueError("backlog must be >= 1")
        if timeout is not None and timeout <= 0:
            raise ValueError("timeout must be positive (or None for unbounded)")
        if stats is None:
            from repro.stream.scheduler import StreamStats

            stats = StreamStats()
        self.engine = engine
        self.linger_s = linger_ms / 1e3
        self.max_pending = max_pending
        self.slo_ms = slo_ms
        self.timeout = timeout
        self.stats = stats
        if stats.slo_ms is None:
            stats.slo_ms = slo_ms
        self.name = name if name is not None else f"{engine.name}-batcher"
        self._clock = clock
        self._cond = threading.Condition()
        self._acc: dict[tuple[int, int], _Accumulator] = {
            hw: _Accumulator() for hw in engine.hw_buckets
        }
        self._images: dict[int, np.ndarray] = {}  # id(ticket) → request
        self._backlog: queue.Queue = queue.Queue(maxsize=backlog)
        self._error: BaseException | None = None
        self._stop = threading.Event()
        self._flush = False
        self.submitted = 0
        self.completed = 0
        self._max_lane = max(engine.lanes)
        # fill, not np.zeros: calloc maps lazy zero pages, which would
        # fault on the first pack instead of here
        self._arena = np.empty(
            max(
                lane * hb * wb
                for lane in engine.lanes for hb, wb in engine.hw_buckets
            ),
            np.float32,
        )
        self._arena.fill(0)
        self._dispatcher = FailFast(
            target=self._dispatch_loop, daemon=True,
            name=f"{self.name}-dispatch", on_error=self._poison,
        )
        self._drainer = FailFast(
            target=self._drain_loop, daemon=True,
            name=f"{self.name}-drain", on_error=self._poison,
        )
        self._dispatcher.start()
        self._drainer.start()

    # -- poisoning -----------------------------------------------------------
    def _poison(self, exc: BaseException) -> None:
        with self._cond:
            if self._error is None:
                self._error = exc
            self._stop.set()
            self._cond.notify_all()

    def check(self) -> None:
        """Raise the recorded worker error, if any — every bounded wait
        polls this so a dead worker surfaces instead of a timeout-shaped
        hang."""
        if self._error is not None:
            raise self._error

    # -- admission -----------------------------------------------------------
    def submit(self, image: np.ndarray, timeout: float | None = _UNSET) -> SloTicket:
        """Admit one (h, w) request; fail-fast on unwarmed buckets and on
        a closed/poisoned batcher; bounded by ``max_pending`` unresolved
        requests."""
        image = np.asarray(image)
        if image.ndim != 2:
            raise ValueError(f"expected (h,w), got {image.shape}")
        if timeout is _UNSET:
            timeout = self.timeout
        key = self.engine.bucket_for(*image.shape)  # UnsupportedFeature here
        ticket = SloTicket(self, image.shape, self._clock())

        def admitted() -> bool:
            self.check()
            with self._cond:
                if self._stop.is_set():
                    raise RuntimeError(f"batcher {self.name!r} is closed")
                if (
                    self.max_pending is not None
                    and self.submitted - self.completed >= self.max_pending
                ):
                    return False
                self.submitted += 1
                self._images[id(ticket)] = image
                self._acc[key].waiting.append(ticket)
                self.stats.queue_depth.append(self._undispatched_locked())
                self._cond.notify_all()
                return True

        wait_for(
            admitted, timeout,
            what=f"batcher {self.name!r} admission "
            f"(max_pending={self.max_pending})",
        )
        return ticket

    def _undispatched_locked(self) -> int:
        return sum(len(a.waiting) for a in self._acc.values())

    # -- dispatch plane ------------------------------------------------------
    def _take_ready(self, now: float):
        """Under the lock: the first bucket whose slot is full (largest
        lane) or whose oldest request out-lingered, as (key, tickets);
        otherwise (None, earliest-deadline). Accumulator iteration order
        is the warmed-bucket order — deterministic, never wall-clock."""
        next_deadline = None
        for key, acc in self._acc.items():
            if not acc.waiting:
                continue
            deadline = acc.waiting[0].t_enqueue + self.linger_s
            if len(acc.waiting) >= self._max_lane or self._flush or deadline <= now:
                take = [
                    acc.waiting.popleft()
                    for _ in range(min(len(acc.waiting), self._max_lane))
                ]
                return (key, take), None
            next_deadline = (
                deadline if next_deadline is None else min(next_deadline, deadline)
            )
        return None, next_deadline

    def _dispatch_loop(self) -> None:
        while True:
            with self._cond:
                batch, next_deadline = self._take_ready(self._clock())
                if batch is None:
                    if self._stop.is_set():
                        return
                    wait = 0.05
                    if next_deadline is not None:
                        wait = min(wait, max(next_deadline - self._clock(), 1e-4))
                    with span("canny.wait"):
                        self._cond.wait(timeout=wait)
                    continue
            (hb, wb), taken = batch
            lane = self.engine.lane_for(len(taken))
            t_dispatch = self._clock()
            for t in taken:
                t.t_dispatch = t_dispatch
            self.stats.record_occupancy(len(taken), lane)
            with span("canny.pack"):
                # the last lane's run_packed has fetched its result, so no
                # transfer still reads the arena this lane overwrites
                packed, true_hw = pack_requests(
                    [self._images[id(t)] for t in taken], hb, wb, bb=lane,
                    out=self._arena[: lane * hb * wb].reshape(lane, hb, wb),
                )
            self.stats.record_arena_lane()
            out = self.engine.run_packed(packed, true_hw)  # blocks on device
            # bounded backlog: a slow drainer (or consumer) throttles the
            # NEXT launch instead of results buffering without limit
            put_cancellable(self._backlog, (taken, out), self._stop.is_set)

    # -- completion plane ----------------------------------------------------
    def _drain_loop(self) -> None:
        while True:
            try:
                taken, out = self._backlog.get(timeout=0.05)
            except queue.Empty:
                if self._stop.is_set() and self._backlog.empty():
                    return
                if not self._dispatcher.is_alive() and self._backlog.empty():
                    return  # dispatcher died; its error is already posted
                continue
            t_complete = self._clock()
            with self._cond:
                for slot, ticket in enumerate(taken):
                    h, w = ticket.shape
                    ticket.t_complete = t_complete
                    self.stats.record_request((t_complete - ticket.t_enqueue) * 1e3)
                    self.engine.stats.true_px += h * w
                    ticket._resolve(out[slot, :h, :w])
                    del self._images[id(ticket)]
                    self.completed += 1
                self.engine.stats.requests += len(taken)
                self._cond.notify_all()

    # -- lifecycle -----------------------------------------------------------
    def drain(self, timeout: float | None = _UNSET) -> int:
        """Block until every submitted request has resolved (bounded wait
        → ``StreamTimeout``); re-raises a recorded worker error. Returns
        the number of completed requests."""
        if timeout is _UNSET:
            timeout = self.timeout

        def settled() -> bool:
            self.check()
            with self._cond:
                return self.completed >= self.submitted

        wait_for(
            settled, timeout,
            what=f"batcher {self.name!r} drain "
            f"({self.submitted - self.completed} in flight)",
        )
        return self.completed

    def close(self, timeout: float | None = 30.0) -> None:
        """Flush open slots, stop both workers, join (re-raising any
        recorded worker error). Idempotent."""
        with self._cond:
            self._flush = True
            self._cond.notify_all()
        if self._error is None:
            try:
                self.drain(timeout=timeout)
            except StreamTimeout:
                pass  # report via join below if a worker actually died
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        self._dispatcher.join(timeout=timeout)
        self._drainer.join(timeout=timeout)

    def __enter__(self) -> "ContinuousBatcher":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is None:
            self.close()
        else:  # don't mask the primary error with a flush failure
            self._stop.set()
            with self._cond:
                self._cond.notify_all()
            self._dispatcher.join(timeout=5.0, reraise=False)
            self._drainer.join(timeout=5.0, reraise=False)
