"""Pod plane — the streaming farm dispatched across hosts.

A *pod* is one detector-owning rank of the streaming farm: a whole host
(its own JAX process, optionally driving its own data×model mesh) or —
in-process — a thread owning a slice of the local device mesh via
``Dist.pod_slice``. Frame→pod assignment is round-robin by GLOBAL
sequence number, a pure function of ``seq`` (``PodCtx.owns``), so the
plane needs no coordinator:

  * every rank independently derives its slice of any deterministic
    frame source (``strided``), and
  * the merge back to global frame order is a rank-tagged reassembly
    (``reassemble``): seq ``s`` can only come from rank ``s mod P``, so
    the merged stream is deterministic and the buffer is O(1). The
    in-process farm (``core.patterns.farm.Farm``) realizes the same
    contract with its seq-keyed reorder dict; ``reassemble`` is the
    multi-process half, merging per-rank result streams produced by
    separate JAX processes (see ``tests/subproc/pod_farm.py``).

Temporal warm-start/skip state is pod-local by construction: rank r sees
frames r, r+P, … so its "previous frame" is P frames stale — staleness
can only cost hysteresis sweeps or front-end recomputes, never bits
(DESIGN.md §6/§9).

**Elasticity** (DESIGN.md §11): the healthy-path contract above assumes
every rank lives forever. The membership layer below removes that
assumption without giving up determinism:

  * ``PodMembership`` — heartbeat-based liveness with an injected clock.
    Every roster change (death, drain, join) is an **epoch** transition;
    the roster at each epoch is an explicit, ordered tuple.
  * ``owns(seq, roster)`` — ownership generalizes from ``seq % P`` to a
    pure function of (seq, epoch roster), so when rank d dies the
    orphaned sequence numbers re-own DETERMINISTICALLY across the
    survivors — every participant derives the same new owner with no
    coordination beyond agreeing on the epoch.
  * ``reassemble_elastic`` — the churn-tolerant merge: epoch-tagged
    results arrive out of order, with gaps (a dead rank's in-flight
    frames) and duplicates (a stalled zombie finishing a re-owned
    frame); the output is still the exact global seq order, bit-identical
    to the no-failure run because EVERY detector is bit-exact regardless
    of its warm state.
  * ``ElasticPodFarm`` — the in-process controller tying it together:
    per-rank worker threads under membership, fault-injected deaths and
    stalls, re-dispatch of orphans to their new owners, cold revival
    (state reset — staleness is cost-only, never bits), and every
    blocking wait bounded by timeout + exponential backoff.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.canny.params import CannyParams
from repro.core.patterns.dist import LOCAL, Dist, auto_mesh
from repro.distributed.fault_tolerance import (
    FaultInjector,
    plan_elastic_mesh,
    wait_for,
)


@dataclasses.dataclass(frozen=True)
class PodCtx:
    """One pod rank's identity in a ``size``-pod farm."""

    rank: int
    size: int

    def __post_init__(self):
        if self.size < 1 or not 0 <= self.rank < self.size:
            raise ValueError(f"bad pod rank/size: {self.rank}/{self.size}")

    def owns(self, seq: int) -> bool:
        """Round-robin frame→pod map — pure function of the sequence no.

        The healthy-roster special case of the elastic ``owns(seq,
        roster)`` below: with every rank alive the roster is
        ``(0, …, size-1)`` and ownership is ``seq % size``.
        """
        return owns(seq, tuple(range(self.size))) == self.rank


def owns(seq: int, roster: Sequence[int]) -> int:
    """The elastic frame→rank ownership function: pure in (seq, roster).

    Round-robin over the CURRENT epoch's ordered roster. Every
    participant that agrees on the epoch (and hence the roster) derives
    the same owner for every seq — the coordinator-free property the pod
    plane is built on, now surviving roster changes: when a rank dies,
    its orphaned seqs fall to ``roster_new[seq % len(roster_new)]``, the
    same survivor on every host, with no election or hand-off protocol.
    """
    if not roster:
        raise ValueError(f"no live ranks to own seq {seq}")
    if seq < 0:
        raise ValueError(f"negative seq {seq}")
    return roster[seq % len(roster)]


def session_route(camera: int, roster: Sequence[int], per_chip: int) -> int:
    """The worker that holds camera ``camera``'s session: ``owns(camera,
    roster)`` picks its chip, and its rank among that chip's cameras one
    of the chip's ``per_chip`` workers, worker ``slot * len(roster) +
    chip's place in the roster`` (worker k serves chip ``k % len(roster)``).
    Pure in (camera, roster): the same camera always reaches the same
    worker, whatever the arrival order."""
    chip = owns(camera, roster)
    slot = (camera // len(roster)) % per_chip
    return slot * len(roster) + roster.index(chip)


def strided(source: Iterable, pod: PodCtx) -> Iterator[tuple[int, np.ndarray]]:
    """Pod ``rank``'s slice of a frame stream, tagged with the global seq.

    Every rank runs this over the SAME (deterministic) source and keeps
    only its frames — no inter-host hand-off of the stream is needed.
    """
    for seq, frame in enumerate(source):
        if pod.owns(seq):
            yield seq, frame


def reassemble(streams: Sequence[Iterable[tuple[int, object]]]) -> Iterator:
    """Merge P rank-tagged ``(seq, item)`` streams into global seq order.

    ``streams[r]`` must yield pod rank r's results with increasing seq —
    exactly what ``PodWorker.run`` emits. Because seq ``s`` belongs to
    rank ``s mod P``, the merge pulls from exactly one stream per step:
    deterministic emission, O(1) buffering. Raises if any stream carries
    an unexpected seq or holds items past the global end — the ordering
    violations the pod-farm harness exists to catch.
    """
    its = [iter(s) for s in streams]
    p = len(its)
    if p == 0:
        return
    seq = 0
    while True:
        try:
            got_seq, item = next(its[seq % p])
        except StopIteration:
            break
        if got_seq != seq:
            raise RuntimeError(
                f"pod reassembly: rank {seq % p} produced seq {got_seq}, "
                f"expected {seq} (out-of-order or missing frame)"
            )
        yield item
        seq += 1
    # the stream ended at `seq`: every OTHER rank must be exhausted too
    for r, it in enumerate(its):
        leftover = next(it, None)
        if leftover is not None:
            raise RuntimeError(
                f"pod reassembly: rank {r} still holds seq {leftover[0]} "
                f"after global end {seq}"
            )


class PodMembership:
    """Heartbeat-driven pod roster with explicit epoch transitions.

    Liveness is decided from heartbeat freshness under an injectable
    clock (tests drive epochs deterministically; deployments pass
    ``time.monotonic``). Every roster change — a detected death, a
    voluntary drain, a (re)join — increments ``epoch`` and appends to
    ``history``, so "the roster at epoch e" is a well-defined, shared
    fact that ``owns(seq, roster)`` can be evaluated against by any
    participant. Dead ranks stay dead until an explicit ``join``: a
    zombie that heartbeats after being declared dead is ignored (its
    late results are handled by first-writer-wins reassembly instead).

    Thread-safe: worker threads heartbeat while a controller sweeps.
    """

    def __init__(
        self,
        ranks: Iterable[int],
        heartbeat_timeout: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
    ):
        if heartbeat_timeout <= 0:
            raise ValueError(f"heartbeat_timeout must be > 0: {heartbeat_timeout}")
        self.heartbeat_timeout = heartbeat_timeout
        self.clock = clock
        self._lock = threading.Lock()
        now = clock()
        self._beats = {int(r): now for r in ranks}
        if not self._beats:
            raise ValueError("membership needs at least one rank")
        self.epoch = 0
        self.history: list[tuple[int, tuple[int, ...], str]] = [
            (0, self._roster_locked(), "init")
        ]

    def _roster_locked(self) -> tuple[int, ...]:
        return tuple(sorted(self._beats))

    def roster(self) -> tuple[int, ...]:
        """The ordered live roster at the current epoch."""
        with self._lock:
            return self._roster_locked()

    def owner(self, seq: int) -> int:
        """Owner of ``seq`` under the current epoch's roster."""
        with self._lock:
            return owns(seq, self._roster_locked())

    def alive(self, rank: int) -> bool:
        with self._lock:
            return rank in self._beats

    def heartbeat(self, rank: int, delay: float = 0.0) -> None:
        """Record liveness for ``rank``. ``delay`` backdates the beat (a
        lagging host / an injected heartbeat-delay fault). Beats from
        ranks not on the roster are dropped — death is sticky."""
        with self._lock:
            if rank in self._beats:
                self._beats[rank] = self.clock() - delay

    def sweep(self) -> tuple[int, ...]:
        """Declare ranks whose last beat is older than the timeout dead
        (stalest first); returns the newly dead ranks (one epoch
        transition each). Staleness never EMPTIES the roster: if every
        rank is stale, the freshest one survives — an all-stale pod
        means the sweeper itself lagged (a paused process, a debugger),
        and zero owners would deadlock all in-flight work."""
        now = self.clock()
        with self._lock:
            stale = sorted(
                (
                    r
                    for r, t in self._beats.items()
                    if now - t > self.heartbeat_timeout
                ),
                key=lambda r: self._beats[r],
            )
            reason = f"heartbeat timeout ({self.heartbeat_timeout:.3g}s)"
            return tuple(
                r for r in stale if self._leave_locked(r, reason, strict=False)
            )

    def _leave_locked(self, rank: int, reason: str, strict: bool) -> bool:
        if rank not in self._beats:
            return False
        if len(self._beats) == 1:
            if strict:
                raise RuntimeError(
                    f"rank {rank} is the last live rank — cannot leave "
                    f"(epoch {self.epoch}); join a replacement first"
                )
            return False
        del self._beats[rank]
        self.epoch += 1
        self.history.append((self.epoch, self._roster_locked(), f"{rank}: {reason}"))
        return True

    def leave(self, rank: int, reason: str = "left") -> bool:
        """Remove ``rank`` (death or drain); epoch transition if it was
        live. Refuses to empty the roster — the last rank cannot leave,
        because no owner would remain for in-flight work."""
        with self._lock:
            return self._leave_locked(rank, reason, strict=True)

    def join(self, rank: int, reason: str = "joined") -> bool:
        """Add (or revive) ``rank`` with a fresh heartbeat; epoch
        transition if it was not already live. The joiner's detector
        state must be rebuilt cold — see ``ElasticPodFarm._revive``."""
        with self._lock:
            if rank in self._beats:
                return False
            self._beats[rank] = self.clock()
            self.epoch += 1
            self.history.append((self.epoch, self._roster_locked(), f"{rank}: {reason}"))
            return True


def reassemble_elastic(
    streams: Iterable[Iterable[tuple[int, int, object]]],
    expect: int | None = None,
    check_duplicates: bool = True,
) -> Iterator:
    """Merge epoch-tagged ``(seq, epoch, item)`` rank streams under churn.

    The elastic generalization of ``reassemble``: under a fixed roster
    seq s can only come from one rank, so the healthy merge polls one
    stream per step and any gap is a hard error. Under churn neither
    holds — a dead rank's stream ends early (its in-flight seqs are
    GAPS, later filled by a survivor's stream at a higher epoch) and a
    stalled zombie may emit a seq that was already re-owned (a
    DUPLICATE). This merge therefore drains every stream, buffers by
    seq, tolerates out-of-order arrival across streams, keeps the
    FIRST result per seq (duplicates must agree bit-exactly — they are
    the same pure function of the frame, so disagreement is a real bug,
    not churn), and yields items in contiguous global seq order.

    ``expect`` pins the total frame count: any seq still missing once
    every stream is drained raises, naming the gap — an orphan nobody
    re-owned, exactly the recovery bug this plane exists to prevent.
    """
    buffer: dict[int, object] = {}  # every first result, kept for dedupe
    emitted = 0
    for stream in streams:
        for seq, epoch, item in stream:
            if seq < 0 or (expect is not None and seq >= expect):
                raise RuntimeError(
                    f"elastic reassembly: seq {seq} outside the stream "
                    f"(expect {expect} frames)"
                )
            if seq in buffer:
                if check_duplicates:
                    a, b = np.asarray(buffer[seq]), np.asarray(item)
                    if a.shape != b.shape or not (a == b).all():
                        raise RuntimeError(
                            f"elastic reassembly: duplicate seq {seq} "
                            f"(epoch {epoch}) disagrees with the first "
                            "result — detectors are not bit-exact"
                        )
                continue  # first writer wins
            buffer[seq] = item
            while emitted in buffer:
                yield buffer[emitted]
                emitted += 1
    total = expect if expect is not None else (max(buffer) + 1 if buffer else 0)
    if emitted < total:
        missing = sorted(set(range(emitted, total)) - set(buffer))
        raise RuntimeError(
            f"elastic reassembly: streams drained at seq {emitted}/{total} "
            f"with gaps — seq {missing[:8]} never re-owned"
        )


class PodWorker:
    """One pod rank's end of the farm: a detector over the rank's slice.

    ``dist`` is the rank's OWN distribution (usually ``Dist.pod_slice``):

      * LOCAL → a stateful ``TemporalCanny`` — temporal warm-start (and
        the static-strip front-end skip, ``skip=True``) with pod-local
        state;
      * non-local + a ``warm_dist`` backend → a stateful ``TemporalCanny``
        whose warm/skip state is SHARDED over the rank's sub-mesh
        (``TemporalCanny(dist=...)``) — the temporal economics survive
        multi-device ranks;
      * non-local otherwise → one stateless mesh detector
        (``make_canny(dist=...)``) running cold (exactness unaffected);
        a skip request that cannot be honoured raises.

    ``run`` yields rank-tagged ``(seq, edges)`` pairs ready for
    ``reassemble``; ``step`` is the bare frame→(edges, cost) callable the
    in-process farm wraps in a ``StreamWorker`` thread.
    """

    def __init__(
        self,
        pod: PodCtx,
        params: CannyParams = CannyParams(),
        dist: Dist = LOCAL,
        warm: bool = True,
        skip: bool = False,
        backend: str | None = None,
        block_rows: int | None = None,
    ):
        if dist.pod_axis is not None:
            raise ValueError(
                "PodWorker takes the rank's OWN dist (Dist.pod_slice), "
                "not the pod-axis farm dist"
            )
        self.pod = pod
        self.temporal = None
        if dist.is_local:
            from repro.stream.temporal import TemporalCanny

            self.temporal = TemporalCanny(
                params, warm=warm, skip=skip, backend=backend, block_rows=block_rows
            )
            self.step = self.temporal.step
        else:
            from repro.core.canny.backends import (
                UnsupportedFeature,
                backend_spec,
                default_backend,
            )
            from repro.core.canny.pipeline import make_canny

            name = backend or default_backend("fused")
            if warm and backend_spec(name).supports(
                dist=True, warm=True, skip=skip
            ):
                # warm_dist backend: the rank keeps a TemporalCanny whose
                # state is sharded over its OWN sub-mesh — warm (and skip)
                # economics survive multi-device ranks
                from repro.stream.temporal import TemporalCanny

                self.temporal = TemporalCanny(
                    params, warm=warm, skip=skip, backend=name,
                    block_rows=block_rows, dist=dist,
                )
                self.step = self.temporal.step
            elif skip:
                # a skip request the backend cannot honour under a mesh
                # would be silently dropped — fail fast, unconditionally
                raise UnsupportedFeature(
                    f"skip=True on a mesh pod rank: backend {name!r} does "
                    "not claim warm_dist, so the rank would fall back to a "
                    "stateless cold make_canny(dist=...) detector — "
                    "warm/skip state needs a warm_dist backend or a LOCAL "
                    "per-rank slice"
                )
            else:
                # no warm_dist claim (or warm=False): stateless mesh
                # detector, runs cold — exactness is unaffected
                det = make_canny(params, dist, backend=name)
                self.step = lambda x: (det(x), None)

    def run(self, source: Iterable[np.ndarray]) -> Iterator[tuple[int, np.ndarray]]:
        """Process this rank's strided slice; yield ``(seq, uint8 edges)``."""
        for seq, frame in strided(source, self.pod):
            edges, _ = self.step(jnp.asarray(frame, jnp.float32))
            yield seq, np.asarray(edges)

    def reset(self) -> None:
        """Drop all temporal warm/skip state — the next frame runs cold.

        The elastic join/revive hook: a rank that re-enters the farm
        after a death must NOT trust whatever state its previous
        incarnation held (it may describe frames that were re-owned by
        others in the meantime). Cold is always correct — warm-seed
        monotonicity proves staleness is cost-only, and a reset is just
        staleness taken to the limit. Mesh detectors are stateless, so
        reset is a no-op there.
        """
        if self.temporal is not None:
            self.temporal.reset()

    def cost_totals(self) -> dict[str, int]:
        """Pod-local cumulative detector cost (zeros for mesh detectors)."""
        if self.temporal is None:
            return {}
        return self.temporal.cost_totals()


def elastic_pod_dist(
    n_ranks: int,
    devices: Sequence | None = None,
    global_batch: int = 8,
    prefer_model: int = 1,
):
    """Re-bucket the device pool into a pod-axis ``Dist`` for the CURRENT
    roster size — the elastic join/leave hook.

    When the roster shrinks or grows, the per-rank device slice changes:
    ``plan_elastic_mesh`` picks the largest valid (data, model) sub-mesh
    each surviving rank can drive (batch divisibility preserved), and
    the pod axis spans the new rank count. Returns ``(dist, plan)`` —
    the plan's note records how many devices went unused, which the
    stream CLI surfaces. A revived rank takes ``dist.pod_slice(r)`` and
    MUST rebuild its warm/skip state cold (``PodWorker.reset``).
    """
    import jax

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if n_ranks < 1:
        raise ValueError(f"need at least one rank, got {n_ranks}")
    per_rank = len(devices) // n_ranks
    if per_rank < 1:
        raise ValueError(
            f"{n_ranks} pod ranks over {len(devices)} devices: every rank "
            "needs at least one device"
        )
    plan = plan_elastic_mesh(per_rank, global_batch, prefer_model=prefer_model)
    data, model = plan.mesh_shape
    used = n_ranks * data * model
    mesh = auto_mesh((n_ranks, data, model), ("pod", "data", "model"), devices[:used])
    dist = Dist(
        mesh=mesh,
        batch_axes=("data",) if data > 1 else (),
        space_axis="model" if model > 1 else None,
        pod_axis="pod",
    )
    return dist, plan


class ElasticPodFarm:
    """In-process elastic pod farm: rank threads under ``PodMembership``.

    The churn-surviving counterpart of ``FarmScheduler``'s pod mode: one
    worker thread per live rank, frames dispatched to
    ``owns(seq, roster)`` under the current epoch, and three recovery
    paths that all end in a bit-identical output stream:

      * **death** (a worker raises — real or ``FaultInjector``-planted):
        epoch transition, the dead rank's outstanding seqs re-own to
        survivors and are re-dispatched;
      * **stall** (heartbeats go stale): ``PodMembership.sweep`` declares
        the rank dead and recovery proceeds as above; if the zombie later
        finishes, first-writer-wins reassembly drops (and cross-checks)
        its duplicate;
      * **revival** (``revive_after`` frames after a death): the rank
        rejoins at a fresh epoch with COLD state (reset — correctness
        never depended on warm state) and a fresh queue/thread.

    Every blocking wait is bounded (``timeout`` + exponential backoff →
    ``StreamTimeout``), so no churn pattern can deadlock the stream.
    Deaths beyond ``max_deaths`` re-raise the underlying failure.
    """

    def __init__(
        self,
        params: CannyParams = CannyParams(),
        ranks: int = 2,
        warm: bool = True,
        skip: bool = False,
        backend: str | None = None,
        block_rows: int | None = None,
        heartbeat_timeout: float = 60.0,
        timeout: float | None = 120.0,
        max_deaths: int = 8,
        revive_after: int | None = None,
        injector: FaultInjector | None = None,
        make_worker: Callable[[int], object] | None = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if ranks < 2:
            raise ValueError("elastic pod farm needs >= 2 ranks to survive a death")
        if make_worker is None:

            def make_worker(rank: int):
                from repro.stream.temporal import TemporalCanny

                return TemporalCanny(
                    params, warm=warm, skip=skip,
                    backend=backend, block_rows=block_rows,
                )

        self.params = params
        self.ranks = ranks
        self.timeout = timeout
        self.max_deaths = max_deaths
        self.revive_after = revive_after
        self.injector = injector
        self.make_worker = make_worker
        self.clock = clock
        self.membership = PodMembership(
            range(ranks), heartbeat_timeout=heartbeat_timeout, clock=clock
        )
        self.deaths = 0
        self.events: list[tuple[str, int, int]] = []  # (kind, rank, at-seq)
        self.recoveries_s: list[float] = []
        # mutable run state (one run() at a time)
        self._lock = threading.Lock()
        self._queues: dict[int, queue.Queue] = {}
        self._threads: dict[int, threading.Thread] = {}
        self._workers: dict[int, object] = {}
        self._assigned: dict[int, dict[int, np.ndarray]] = {}
        self._results: dict[int, np.ndarray] = {}
        self._failures: list[tuple[int, BaseException]] = []
        self._orphans = None  # collections.deque, set in run()
        self._dead_at: dict[int, int] = {}  # rank -> emitted watermark at death
        self._pending_recovery: list[tuple[float, int]] = []  # (t_death, max orphan seq)
        self._emitted = 0
        self._stop = False

    # -- rank incarnations ---------------------------------------------------
    def _spawn(self, rank: int, cold: bool) -> None:
        """Start a fresh incarnation of ``rank``: its own queue + thread.
        A zombie from a previous incarnation keeps its OLD queue, which
        receives no further work — it drains to nothing and exits."""
        worker = self._workers.get(rank) if not cold else None
        if worker is None:
            worker = self.make_worker(rank)
            self._workers[rank] = worker
        if cold and hasattr(worker, "reset"):
            worker.reset()
        q: queue.Queue = queue.Queue()
        self._queues[rank] = q
        t = threading.Thread(
            target=self._rank_loop, args=(rank, worker, q), daemon=True
        )
        self._threads[rank] = t
        t.start()

    def _rank_loop(self, rank: int, worker, q: queue.Queue) -> None:
        delay = self.injector.heartbeat_delay(rank) if self.injector else 0.0
        while not self._stop:
            try:
                msg = q.get(timeout=0.05)
            except queue.Empty:
                self.membership.heartbeat(rank, delay=delay)
                continue
            if msg is None:
                return
            seq, frame = msg
            try:
                if self.injector is not None:
                    self.injector.before_frame(rank)
                edges, _ = worker.step(jnp.asarray(frame, jnp.float32))
                out = np.asarray(edges)
            except BaseException as exc:  # noqa: BLE001 — surfaces via controller
                with self._lock:
                    self._failures.append((rank, exc))
                return
            self.membership.heartbeat(rank, delay=delay)
            with self._lock:
                # first writer wins; a zombie finishing a re-owned seq
                # after emission is simply dropped (bits are identical
                # by detector purity — pinned by reassemble_elastic)
                if seq >= self._emitted and seq not in self._results:
                    self._results[seq] = out
                self._assigned.get(rank, {}).pop(seq, None)

    # -- failure plane -------------------------------------------------------
    def _service(self) -> None:
        """One controller tick: fold failures, sweep heartbeats, re-own
        orphans, revive due ranks. Called from the emit loop's bounded
        wait — never blocks."""
        with self._lock:
            failures, self._failures = self._failures, []
        for rank, exc in failures:
            self._on_death(rank, exc)
        for rank in self.membership.sweep():
            self._on_swept(rank)
        self._redispatch()
        self._maybe_revive()

    def _on_death(self, rank: int, exc: BaseException | None) -> None:
        """Exception path: the rank is still on the roster and must leave."""
        if not self.membership.alive(rank):
            return  # already handled (e.g. sweep + exception racing)
        self._count_death(rank, exc)
        try:
            self.membership.leave(
                rank, reason=str(exc) if exc is not None else "worker death"
            )
        except RuntimeError as last:
            raise exc or last  # the last live rank died — nothing can recover
        self._reclaim(rank)

    def _on_swept(self, rank: int) -> None:
        """Heartbeat-timeout path: ``membership.sweep`` already removed
        the rank — only the death accounting and re-ownership remain."""
        self._count_death(rank, None)
        self._reclaim(rank)

    def _count_death(self, rank: int, exc: BaseException | None) -> None:
        self.deaths += 1
        if self.deaths > self.max_deaths:
            raise exc or RuntimeError(
                f"rank {rank} died and the farm is out of restarts "
                f"({self.max_deaths})"
            )

    def _reclaim(self, rank: int) -> None:
        with self._lock:
            orphans = sorted(self._assigned.pop(rank, {}).items())
            self._dead_at[rank] = self._emitted
        self.events.append(("death", rank, self._emitted))
        if orphans:
            self._pending_recovery.append(
                (self.clock(), max(seq for seq, _ in orphans))
            )
            self._orphans.extend(orphans)

    def _redispatch(self) -> None:
        """Hand every orphaned (seq, frame) to its owner under the
        CURRENT epoch roster — the deterministic re-ownership step."""
        while self._orphans:
            seq, frame = self._orphans.popleft()
            owner = self.membership.owner(seq)
            with self._lock:
                self._assigned.setdefault(owner, {})[seq] = frame
            self._queues[owner].put((seq, frame))

    def _maybe_revive(self) -> None:
        if self.revive_after is None:
            return
        for rank, at in list(self._dead_at.items()):
            if self._emitted - at >= self.revive_after:
                del self._dead_at[rank]
                # the new queue exists before the rank can own a seq: a
                # feeder that finds it on the roster must not enqueue on
                # the dead incarnation's queue
                self._spawn(rank, cold=True)  # state rebuilt cold-correct
                self.membership.join(rank, reason="revived")
                self.events.append(("join", rank, self._emitted))

    # -- stream plane --------------------------------------------------------
    def run(self, source: Iterable[np.ndarray]) -> Iterator[np.ndarray]:
        """Yield uint8 edge maps in global seq order, surviving churn."""
        import collections

        self._orphans = collections.deque()
        self._stop = False
        for rank in self.membership.roster():
            self._spawn(rank, cold=False)
        total = {"n": None}

        def feeder() -> None:
            seq = 0
            try:
                for frame in source:
                    arr = np.asarray(frame, np.float32)
                    # owner lookup, assignment and enqueue are one step
                    # under the lock that _reclaim takes: a rank leaving
                    # the roster meanwhile either never owns the seq or
                    # has it reclaimed
                    with self._lock:
                        owner = self.membership.owner(seq)
                        self._assigned.setdefault(owner, {})[seq] = arr
                        self._queues[owner].put((seq, arr))
                    seq += 1
            except BaseException as exc:  # noqa: BLE001
                with self._lock:
                    self._failures.append((-1, exc))
            finally:
                total["n"] = seq

        feed_thread = threading.Thread(target=feeder, daemon=True)
        feed_thread.start()
        try:
            while True:
                def ready():
                    # a feeder failure is not a rank death — re-raise it
                    with self._lock:
                        for rank, exc in self._failures:
                            if rank < 0:
                                raise exc
                    self._service()
                    if self._emitted in self._results:
                        return True
                    return total["n"] is not None and self._emitted >= total["n"]

                wait_for(
                    ready,
                    self.timeout,
                    what=f"pod farm result seq {self._emitted} "
                    f"(epoch {self.membership.epoch})",
                )
                with self._lock:
                    if self._emitted not in self._results:
                        return  # stream exhausted
                    out = self._results.pop(self._emitted)
                    self._emitted += 1
                now = self.clock()
                for t_death, upto in list(self._pending_recovery):
                    if self._emitted > upto:
                        self.recoveries_s.append(now - t_death)
                        self._pending_recovery.remove((t_death, upto))
                yield out
        finally:
            self._stop = True
            for q in self._queues.values():
                q.put(None)
            for t in self._threads.values():
                t.join(timeout=5.0)
            feed_thread.join(timeout=5.0)


def pod_workers(
    dist: Dist,
    params: CannyParams = CannyParams(),
    warm: bool = True,
    skip: bool = False,
    backend: str | None = None,
    block_rows: int | None = None,
) -> list[PodWorker]:
    """One ``PodWorker`` per rank of a pod-axis ``Dist`` — each over its
    own ``pod_slice`` sub-mesh. The in-process pod farm hands these to
    ``Farm`` (threads stand in for hosts); the subprocess harness runs
    ONE of them per real process."""
    p = dist.pod_size()
    if p < 2:
        raise ValueError("pod_workers needs a Dist with a pod axis of size >= 2")
    return [
        PodWorker(
            PodCtx(r, p),
            params,
            dist.pod_slice(r),
            warm=warm,
            skip=skip,
            backend=backend,
            block_rows=block_rows,
        )
        for r in range(p)
    ]
