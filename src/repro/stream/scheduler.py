"""Farm scheduler — the paper's farm-of-pipelines over a frame stream.

``FarmScheduler`` fans a frame source out to N workers and merges edge
maps back in input order (``core.patterns.farm``). Each worker is a
double-buffered ``PatternPipeline`` — transfer(i+1) overlaps compute(i)
— wrapping either its OWN ``TemporalCanny`` (stateful warm-start; worker
k sees frames k, k+N, … so its "previous frame" is N frames stale, which
only costs sweeps, never correctness) or a SHARED stateless detector
(e.g. one ``BucketedCanny``, so all workers drive one compile cache — the
single-device "shard the bucketed engine" configuration).

Because warm-start is exact and dispatch is deterministic round-robin,
a farm with any worker count emits frames bit-identical to the
single-worker (and cold) path — the property ``tests/test_stream.py``
pins.

``FarmScheduler.run_engine`` is the micro-batching alternative: frames
flow through ``CannyEngine.submit``/``drain`` waves (mixed sizes OK),
trading per-frame latency for batch-grid throughput.

``FarmScheduler.run_sessions`` is the session mode: a stream of
``(camera, frame)`` pairs from many cameras, each camera routed by
``stream/pod.py:session_route`` to one worker pinned to one local chip,
whose ``SessionTable`` keeps that camera's own ``TemporalCanny``. Every
camera's frames then meet their own previous frame, so warm start and
the skip work as they do for one camera, on as many chips as the host
has.

``StreamStats`` aggregates fps, per-stage latency (the durations of the
workers' ``canny.prep`` and ``canny.fetch`` spans), the five worker
spans' summed time (``worker_ms``), the feeder's ``canny.route`` time,
frames per device, sessions opened, farm queue depths, and the
warm-start fixpoint savings (sweep launches + in-VMEM dilations,
cumulative).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from typing import Callable, Iterable, Iterator, Sequence

import jax
import numpy as np

from repro.core.canny.params import CannyParams
from repro.core.patterns.farm import Farm
from repro.core.patterns.pipeline import PatternPipeline
from repro.core.spans import span
from repro.distributed.fault_tolerance import FaultInjector, StepWatchdog
from repro.serve.engine import percentile
from repro.stream.pod import session_route
from repro.stream.temporal import TemporalCanny

# workers a chip: the one-chip farm's default, and session mode's on every chip
WORKERS_PER_CHIP = 2


@dataclasses.dataclass
class StreamStats:
    frames: int = 0
    wall_s: float = 0.0
    launches: int = 0  # hysteresis sweep launches (see packed_fixpoint_count)
    dilations: int = 0  # productive in-VMEM dilation sweeps
    # front-end (gauss+sobel+NMS) cost: launches skipped entirely on
    # all-static frames, strips recomputed otherwise (skip mode only;
    # without skip every frame is 1 launch and strips go unreported)
    frontend_launches: int = 0
    frontend_strips: int = 0
    prep_ms: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096)
    )
    compute_ms: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096)
    )
    queue_depth: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096)
    )
    # adaptive micro-batching: chosen submit-wave size → count (the stat
    # that shows what batch sizes the queue-depth policy actually picked)
    batch_sizes: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )
    # continuous-serving SLO plane (serve/admission.py): per-request
    # enqueue→complete latency, the slot-occupancy gauge (requests packed
    # / lane size per dispatch), and the pass/fail counter against the
    # slo_ms bound
    request_ms: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096)
    )
    slot_occupancy: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096)
    )
    # lanes the batcher's dispatch thread packed into its one host arena
    arena_lanes: int = 0
    slo_ms: float | None = None
    slo_pass: int = 0
    slo_fail: int = 0
    # health plane: worker restarts (sampled from the farm), watchdog-
    # flagged slow steps, and per-worker straggler flag counts — the
    # per-host report the controller uses to exclude a sick rank
    restarts: int = 0
    slow_steps: int = 0
    straggler_counts: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )
    watchdog: StepWatchdog | None = None
    # the workers' five spans (canny.prep/put/step/fetch/cost_sync):
    # summed ms by span name, cumulative
    worker_ms: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )
    # session mode: the feeder's canny.route spans (route choice, wait
    # for room in the farm's window or a full worker queue, enqueue),
    # summed ms
    route_ms: float = 0.0
    # frames fetched, by the id of the device their worker is pinned to
    # (None: an unpinned worker)
    frames_by_device: collections.Counter = dataclasses.field(
        default_factory=collections.Counter
    )
    sessions_opened: int = 0
    _lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)

    def record_span(self, name: str, ms: float) -> None:
        with self._lock:
            self.worker_ms[name] += ms

    def record_route(self, ms: float) -> None:
        with self._lock:
            self.route_ms += ms

    def record_session(self) -> None:
        with self._lock:
            self.sessions_opened += 1

    def record_prep(self, ms: float) -> None:
        with self._lock:
            self.prep_ms.append(ms)
            self.worker_ms["canny.prep"] += ms

    def record_compute(
        self, ms: float, host: str | None = None, device: int | None = None
    ) -> None:
        with self._lock:
            self.compute_ms.append(ms)
            self.worker_ms["canny.fetch"] += ms
            self.frames_by_device[device] += 1
            if self.watchdog is not None:
                report = self.watchdog.observe(
                    ms / 1e3, {host: ms / 1e3} if host else None
                )
                if report["slow"]:
                    self.slow_steps += 1
                for h in report["stragglers"]:
                    self.straggler_counts[h] += 1

    def record_cost(
        self,
        launches: int,
        dilations: int,
        frontend_launches: int = 1,
        frontend_strips: int = 0,
    ) -> None:
        with self._lock:
            self.launches += launches
            self.dilations += dilations
            self.frontend_launches += frontend_launches
            self.frontend_strips += frontend_strips

    def record_batch_size(self, size: int) -> None:
        with self._lock:
            self.batch_sizes[size] += 1

    def record_request(self, total_ms: float) -> None:
        """One continuously-served request's enqueue→complete latency;
        scored against ``slo_ms`` when a bound is set."""
        with self._lock:
            self.request_ms.append(total_ms)
            if self.slo_ms is not None:
                if total_ms <= self.slo_ms:
                    self.slo_pass += 1
                else:
                    self.slo_fail += 1

    def record_occupancy(self, filled: int, lane: int) -> None:
        """How full a dispatched slot was (1.0 = the lane was packed)."""
        with self._lock:
            self.slot_occupancy.append(filled / lane)

    def record_arena_lane(self) -> None:
        with self._lock:
            self.arena_lanes += 1

    def latency_ms(self, q: float) -> float:
        """q-quantile of per-request enqueue→complete latency (the SLO
        metric). ``nan`` before the first request completes — a 0.0 here
        would read as a perfect latency on a scoreboard rendered early."""
        if not self.request_ms:
            return float("nan")
        return percentile(self.request_ms, q)

    @staticmethod
    def _fmt_ms(window, q: float) -> str:
        """Render a latency quantile, ``-`` for an empty window."""
        if not window:
            return "-"
        return f"{percentile(window, q):.1f}ms"

    def slo(self) -> dict:
        """The SLO scoreboard: bound, pass/fail counts, attainment."""
        total = self.slo_pass + self.slo_fail
        return {
            "slo_ms": self.slo_ms,
            "pass": self.slo_pass,
            "fail": self.slo_fail,
            "attainment": self.slo_pass / total if total else None,
        }

    def mean_batch_size(self) -> float:
        n = sum(self.batch_sizes.values())
        if not n:
            return 0.0
        return sum(s * c for s, c in self.batch_sizes.items()) / n

    def fps(self) -> float:
        return self.frames / self.wall_s if self.wall_s else 0.0

    def summary(self) -> str:
        depth = (
            sum(self.queue_depth) / len(self.queue_depth) if self.queue_depth else 0.0
        )
        line = (
            f"frames={self.frames} fps={self.fps():.2f} "
            f"prep_p50={self._fmt_ms(self.prep_ms, 0.5)} "
            f"compute_p50={self._fmt_ms(self.compute_ms, 0.5)} "
            f"compute_p95={self._fmt_ms(self.compute_ms, 0.95)} "
            f"queue_depth~{depth:.1f} "
            f"hysteresis: launches={self.launches} dilations={self.dilations} "
            f"frontend: launches={self.frontend_launches}"
        )
        if self.batch_sizes:
            line += f" micro_batch~{self.mean_batch_size():.1f}"
        if self.request_ms:
            occ = (
                sum(self.slot_occupancy) / len(self.slot_occupancy)
                if self.slot_occupancy
                else 0.0
            )
            line += (
                f" req_p50={self.latency_ms(0.50):.1f}ms"
                f" req_p95={self.latency_ms(0.95):.1f}ms"
                f" req_p99={self.latency_ms(0.99):.1f}ms"
                f" occupancy~{occ:.2f}"
            )
            if self.slo_ms is not None:
                line += (
                    f" slo<{self.slo_ms:g}ms:"
                    f" pass={self.slo_pass} fail={self.slo_fail}"
                )
        if self.restarts or self.slow_steps or self.straggler_counts:
            line += (
                f" health: restarts={self.restarts} slow_steps={self.slow_steps}"
            )
            if self.straggler_counts:
                worst = ",".join(
                    f"{h}x{c}" for h, c in self.straggler_counts.most_common(3)
                )
                line += f" stragglers={worst}"
        return line


class StreamWorker:
    """One farm worker: prep → (H2D ‖ compute) → host edges, 1:1 in order.

    ``step`` maps a device frame to ``(edges, cost)`` (cost may be None
    for stateless detectors). The inner ``PatternPipeline`` keeps one
    frame's transfer in flight while the previous frame computes.

    Each frame passes through five host spans (``core/spans.py``):
    ``canny.prep`` (the float32 copy, into ``StreamStats.prep_ms``),
    ``canny.put`` and ``canny.step`` (in the pipeline), ``canny.fetch``
    (the blocking edge fetch, into ``compute_ms``, ``frames_by_device``
    and the watchdog) and ``canny.cost_sync`` (the cost scalars read back
    to the host); each span's ms also adds to ``StreamStats.worker_ms``.

    Given ``sessions`` (session mode), items are ``(camera, frame)`` pairs,
    each stepped by its camera's session, and results ``(camera, edges)``.
    A frame's result is handed back before the wait for the next frame
    when none is queued (the farm's ``WorkerFeed.ready``).

    ``rank``/``injector`` are the fault-injection hook: the injector's
    schedule is consulted before every frame this worker computes, so a
    planted kill surfaces exactly like a real worker death (and the
    farm's restart plumbing handles both identically). ``name`` labels
    the worker in the watchdog's straggler report.
    """

    def __init__(
        self,
        step: Callable,
        stats: StreamStats,
        device=None,
        name: str | None = None,
        rank: int = 0,
        injector: FaultInjector | None = None,
        sessions: "SessionTable | None" = None,
    ):
        self.step = step
        self.stats = stats
        self.device = device
        self.name = name
        self.rank = rank
        self.injector = injector
        self.sessions = sessions
        self._device_id = None if device is None else device.id
        self._cost_sink = functools.partial(stats.record_span, "canny.cost_sync")

    def _record_fetch(self, ms: float) -> None:
        self.stats.record_compute(ms, self.name, self._device_id)

    def restarted(self) -> "StreamWorker":
        """A fresh worker for this one's farm slot: the same step, device,
        name and rank, and its sessions, if any, closed, so each reopens
        cold (a dead worker's warm/skip state is untrustworthy; cold is
        always exact, so only sweep cost is lost)."""
        if self.sessions is not None:
            self.sessions.reset()
        return StreamWorker(
            self.step, self.stats, self.device, name=self.name, rank=self.rank,
            injector=self.injector, sessions=self.sessions,
        )

    def stream(self, frames: Iterable) -> Iterator:
        cameras: collections.deque = collections.deque()  # session mode, in feed order

        def prepped():  # prep spanned here: the pipeline runs it one frame ahead
            for f in frames:
                if self.sessions is not None:
                    camera, f = f
                    cameras.append(camera)
                with span("canny.prep", self.stats.record_prep):
                    arr = np.asarray(f, np.float32)
                yield arr

        def run_step(x):  # the pipeline steps frames in feed order
            if self.injector is not None:
                self.injector.before_frame(self.rank)
            if self.sessions is None:
                camera, out = None, self.step(x)
            else:
                camera = cameras.popleft()
                out = self.sessions.step(camera, x)
            return camera, (out if isinstance(out, tuple) else (out, None))

        pipe = PatternPipeline(run_step, sharding=self.device, record=self.stats.record_span)
        for camera, (edges, cost) in pipe.run(prepped(), getattr(frames, "ready", None)):
            with span("canny.fetch", self._record_fetch):
                out = np.asarray(edges)  # blocks until the device result lands
            if cost is not None:
                with span("canny.cost_sync", self._cost_sink):  # one device sync per cost scalar
                    self.stats.record_cost(*(int(c) for c in cost))
            yield out if self.sessions is None else (camera, out)


class SessionTable:
    """One worker's sessions: camera → that camera's own ``TemporalCanny``.

    A session opens cold on its camera's first frame; its warm and skip
    state then lives on the worker's chip (``PackedTemporal`` makes state
    where the frame is). Every session shares the module-level jitted
    step (``kernels/canny_backends.py:_make_step_fn``), so opening one
    compiles nothing that an earlier session on the chip compiled.
    """

    def __init__(self, make: Callable[[], TemporalCanny], stats: StreamStats, device=None):
        self.make = make
        self.stats = stats
        self.device = device
        self.table: dict = {}

    def step(self, camera, x):
        t = self.table.get(camera)
        if t is None:
            t = self.table[camera] = self.make()
            self.stats.record_session()
        return t.step(x)

    def reset(self) -> None:
        """Close every session: the next frame of each camera opens cold."""
        self.table.clear()


class FarmScheduler:
    """Farm of warm-start Canny pipelines over any frame source.

    ``dist`` routes the stream through the mesh. With a ``warm_dist``
    backend (the Pallas ones) and ``warm=True`` the farm builds ONE
    ``TemporalCanny(dist=...)`` whose warm/skip state is sharded across
    the mesh, driven by a SINGLE worker lane — the temporal state machine
    is not thread-safe, and concurrent shard_map launches from multiple
    threads deadlock the collectives, so device parallelism comes from
    the mesh itself. Otherwise every worker shares ONE stateless
    mesh-aware detector (``make_canny(dist=...)``): frames still dispatch
    round-robin, but the shared-detector path runs cold (exactness is
    unaffected; a skip request that would be dropped raises instead).

    A ``dist`` with a POD axis selects the pod-farm mode instead: one
    worker per pod rank, each owning its OWN detector over its
    ``Dist.pod_slice`` sub-mesh (a stateful warm/skip ``TemporalCanny``
    when the slice is trivial). Frames dispatch round-robin over the
    ranks — the same seq→rank map the multi-host harness uses — and the
    farm's seq-keyed reorder buffer IS the rank-tagged reassembly, so
    emission stays globally in order and bit-identical to one host
    (``stream/pod.py``, pinned by ``tests/subproc/pod_farm.py``). A rank
    whose slice is one device has its worker pinned to that device.

    On the stateful local path (no ``dist`` mesh, no shared ``detector``)
    the scheduler also serves many cameras: ``run_sessions`` (module
    docstring) over ``WORKERS_PER_CHIP`` workers on each of ``devices``,
    ``n_workers`` applying to ``run`` alone. That farm, its workers and
    their ``sessions`` are built on the first ``run_sessions`` call, so a
    scheduler that only runs one stream holds one farm.
    """

    def __init__(
        self,
        params: CannyParams = CannyParams(),
        n_workers: int | None = None,
        warm: bool = True,
        skip: bool = False,
        queue_depth: int = 2,
        backend: str | None = None,
        block_rows: int | None = None,
        detector: Callable | None = None,
        devices=None,
        dist=None,
        max_restarts: int = 0,
        timeout: float | None = None,
        injector: FaultInjector | None = None,
        watchdog: StepWatchdog | None = None,
    ):
        devices = list(devices) if devices is not None else jax.local_devices()
        if n_workers is None:
            n_workers = max(WORKERS_PER_CHIP, len(devices))
        self.params = params
        self.warm = warm
        self.dist = dist
        self.injector = injector
        self.stats = StreamStats()
        # watchdog on by default: slow-step/straggler counts cost one
        # median over a 50-sample window per frame and feed summary()
        self.stats.watchdog = watchdog if watchdog is not None else StepWatchdog()
        self.detectors: list = []
        self.pods: list = []
        self.sessions: list[SessionTable] = []
        self.session_farm: Farm | None = None
        self._open_sessions: Callable[[], Farm] | None = None
        self._roster: tuple[int, ...] = ()
        if detector is None and dist is not None and dist.pod_size() > 1:
            # pod farm: worker k IS pod rank k (Farm's round-robin gives
            # it frames k, k+P, … — exactly PodCtx(k, P).owns). The worker
            # count is therefore the POD count and placement comes from
            # each rank's mesh slice: n_workers/devices do not apply here
            # (callers see the real count via the `pod-farm xP` banner and
            # `farm.workers`).
            from repro.stream.pod import pod_workers

            self.pods = pod_workers(
                dist, params, warm=warm, skip=skip,
                backend=backend, block_rows=block_rows,
            )
            self.detectors = [w.temporal for w in self.pods if w.temporal]
            # a one-device rank is pinned to its device; a sub-mesh rank's
            # shard_map owns placement
            rank_device = [
                devs.flat[0] if devs.size == 1 else None
                for devs in map(dist.pod_devices, range(len(self.pods)))
            ]
            workers = [
                StreamWorker(
                    w.step, self.stats, rank_device[k],
                    name=f"rank{k}", rank=k, injector=injector,
                )
                for k, w in enumerate(self.pods)
            ]

            def remake_rank(k: int) -> StreamWorker:
                # cold restart (PodWorker.reset docstring)
                self.pods[k].reset()
                return self.farm.workers[k].restarted()

            self.farm = Farm(
                workers, queue_depth=queue_depth,
                max_restarts=max_restarts, worker_factory=remake_rank,
                timeout=timeout,
            )
            return
        if detector is None and dist is not None and not dist.is_local:
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
                # warm_dist backend: ONE TemporalCanny whose warm/skip
                # state lives sharded with the mesh, driven by a SINGLE
                # worker lane. The state machine is not thread-safe, and
                # concurrent shard_map launches from multiple host
                # threads deadlock the collectives — parallelism comes
                # from the mesh, the lone worker just overlaps host prep
                # with the device step.
                t = TemporalCanny(
                    params, warm=warm, skip=skip, backend=name,
                    block_rows=block_rows, dist=dist,
                )
                self.detectors.append(t)
                detector = t.step
                devices = [None]  # shard_map owns placement
                n_workers = 1
            elif skip:
                # THIS path is a stateless shared detector and runs cold
                # no matter what was asked; a skip request would be
                # silently dropped — fail fast (warm alone keeps the
                # documented degrade-to-cold behaviour for CLI defaults)
                raise UnsupportedFeature(
                    f"skip=True under a shared mesh detector: backend "
                    f"{name!r} does not claim warm_dist, so the non-pod "
                    "mesh farm shares one stateless make_canny(dist=...) "
                    "detector, which runs cold — use a warm_dist backend "
                    "('fused'/'pallas') or a pod-axis Dist with local "
                    "per-rank slices for warm/skip state"
                )
            else:
                # device parallelism comes from the mesh (BucketedCanny
                # serializes concurrent launches internally), thread
                # overlap from per-worker host prep; make_canny validates
                # the backend's dist capability at construction
                detector = make_canny(params, dist, backend=name)
                devices = [None]  # shard_map owns placement; workers share it
        elif detector is not None and dist is not None and not dist.is_local:
            # an externally-built mesh detector (e.g. the operator zoo's
            # shared cold BucketedCanny): same rule — shard_map owns
            # placement, so workers must not commit frames to one device
            devices = [None]
        workers = []
        for k in range(n_workers):
            if detector is not None:
                step: Callable = detector  # shared: e.g. one BucketedCanny
            else:
                t = TemporalCanny(
                    params, warm=warm, skip=skip,
                    backend=backend, block_rows=block_rows,
                )
                self.detectors.append(t)
                step = t.step
            workers.append(
                StreamWorker(
                    step, self.stats, devices[k % len(devices)],
                    name=f"worker{k}", rank=k, injector=injector,
                )
            )

        def remake_worker(k: int) -> StreamWorker:
            # per-worker TemporalCanny: reset to cold before reuse
            # (detectors[k] aligns with worker k on the stateful path;
            # shared detectors are stateless, reused as-is)
            if k < len(self.detectors):
                self.detectors[k].reset()
            return self.farm.workers[k].restarted()

        self.farm = Farm(
            workers, queue_depth=queue_depth,
            max_restarts=max_restarts, worker_factory=remake_worker,
            timeout=timeout,
        )
        if detector is not None:
            return

        self._roster = tuple(range(len(devices)))

        def open_sessions() -> Farm:
            def make() -> TemporalCanny:
                return TemporalCanny(
                    params, warm=warm, skip=skip,
                    backend=backend, block_rows=block_rows,
                )

            # session worker k serves chip k % len(devices) (session_route)
            self.sessions = [
                SessionTable(make, self.stats, devices[k % len(devices)])
                for k in range(WORKERS_PER_CHIP * len(devices))
            ]
            farm = Farm(
                [
                    StreamWorker(
                        None, self.stats, table.device, name=f"session{k}",
                        rank=k, injector=injector, sessions=table,
                    )
                    for k, table in enumerate(self.sessions)
                ],
                queue_depth=queue_depth, max_restarts=max_restarts,
                worker_factory=lambda k: farm.workers[k].restarted(),
                timeout=timeout,
            )
            return farm

        self._open_sessions = open_sessions

    def _emit(self, farm: Farm, results: Iterator) -> Iterator:
        t0 = time.perf_counter()
        for out in results:
            self.stats.frames += 1
            self.stats.queue_depth.append(sum(farm.queue_depths()))
            self.stats.restarts = farm.restarts
            self.stats.wall_s = time.perf_counter() - t0
            yield out

    def run(self, source: Iterable[np.ndarray]) -> Iterator[np.ndarray]:
        """Yield uint8 edge maps in frame order; updates ``self.stats``."""
        return self._emit(self.farm, self.farm.run(source))

    def route(self, camera: int) -> int:
        """The session worker of ``camera`` (``pod.session_route`` over
        the local chips)."""
        return session_route(camera, self._roster, WORKERS_PER_CHIP)

    def run_sessions(self, source: Iterable[tuple[int, np.ndarray]]) -> Iterator[tuple[int, np.ndarray]]:
        """Session mode: ``(camera, frame)`` pairs in, ``(camera, uint8
        edges)`` out in feed order, so each camera's in that camera's
        order. Camera ids are ints >= 0; every frame of a camera goes to
        the one worker ``route`` names."""
        if self.session_farm is None:
            if self._open_sessions is None:
                raise ValueError(
                    "session mode keeps per-camera TemporalCanny state on the "
                    "local chips: it needs the stateful local path (no mesh "
                    "dist, no shared detector)"
                )
            self.session_farm = self._open_sessions()
        farm = self.session_farm
        return self._emit(farm, farm.run(
            source, route=lambda item: self.route(item[0]),
            route_sink=self.stats.record_route,
        ))

    def run_engine(
        self,
        source: Iterable[np.ndarray],
        engine=None,
        max_batch: int = 8,
        adaptive: bool = True,
        timeout: float | None = None,
        aot: bool = False,
        linger_ms: float = 5.0,
        slo_ms: float | None = None,
        buckets: Sequence[tuple[int, int]] | None = None,
    ) -> Iterator[np.ndarray]:
        """Micro-batching path: frames ride ``CannyEngine.submit``/``drain``.

        Collects frames, drains them as one bucketed batch-grid launch,
        and emits in order — higher throughput, wave latency. Mixed frame
        sizes are fine (the engine buckets them).

        ``adaptive`` picks each wave's submit batch size from the CURRENT
        source backlog instead of always waiting for ``max_batch``: when
        the source exposes ``qsize()`` (e.g. ``Prefetcher``), a wave
        flushes once it holds every frame that was already buffered —
        an idle stream drains single frames at minimum latency, a backed-
        up stream grows waves toward ``max_batch`` for throughput. The
        chosen sizes land in ``stats.batch_sizes``. Frame order and edge
        bits are identical either way (wave boundaries only group work).
        ``adaptive=False`` restores the fixed-size waves.

        ``timeout`` bounds every engine wait (drain-lock contention and
        ticket resolution) with a ``StreamTimeout``; ``None`` defers to
        the engine's own default (unbounded for a default-constructed
        engine).

        ``aot=True`` switches to the CONTINUOUS serving plane: frames are
        admitted to a ``ContinuousBatcher`` over an ``AotCannyEngine``
        the moment they arrive (no wave barrier — slots dispatch on fill
        or ``linger_ms``), compilation happens entirely at warmup
        (``buckets`` explicit, or inferred from the source's
        height/width), and per-request SLO latency lands in
        ``self.stats`` against ``slo_ms``. Emission order and edge bits
        are identical to the wave path. Pass an existing
        ``ContinuousBatcher`` as ``engine`` to reuse its warmup.
        """
        if self.dist is not None and self.dist.pod_size() > 1:
            raise ValueError(
                "run_engine batches frames through one engine queue — it "
                "does not dispatch over pods; use run() with a pod dist"
            )
        from repro.serve.admission import ContinuousBatcher

        if aot or isinstance(engine, ContinuousBatcher):
            yield from self._run_continuous(
                source, engine, max_batch, timeout, linger_ms, slo_ms, buckets
            )
            return
        if engine is None:
            from repro.core.patterns.dist import LOCAL
            from repro.serve.engine import CannyEngine

            engine = CannyEngine(
                self.params, max_batch=max_batch, dist=self.dist or LOCAL,
                timeout=timeout,
            )
        t0 = time.perf_counter()
        pending = []
        backlog = getattr(source, "qsize", None) if adaptive else None

        def flush():
            self.stats.record_batch_size(len(pending))
            if timeout is None:
                engine.drain()
            else:
                engine.drain(timeout=timeout)
            for ticket in pending:
                self.stats.frames += 1
                self.stats.wall_s = time.perf_counter() - t0
                yield ticket.result() if timeout is None else ticket.result(timeout)
            pending.clear()

        for frame in source:
            pending.append(engine.submit(np.asarray(frame, np.float32)))
            # target = frames already in hand + frames sitting in the
            # source buffer, capped at max_batch; without a backlog
            # signal, adaptive degrades to fixed max_batch waves
            target = max_batch
            if backlog is not None:
                target = min(max_batch, max(1, len(pending) + backlog()))
            if len(pending) >= target:
                yield from flush()
        if pending:
            yield from flush()

    def _run_continuous(
        self, source, batcher, max_batch, timeout, linger_ms, slo_ms, buckets
    ) -> Iterator[np.ndarray]:
        """The AOT/continuous engine mode: frames admit the moment they
        arrive, slots dispatch on fill-or-linger (no wave barrier), and
        emission stays in frame order — bits identical to the wave path
        because every frame runs the same bucketed executable."""
        import collections as _collections

        from repro.core.patterns.dist import LOCAL
        from repro.serve.admission import ContinuousBatcher
        from repro.serve.aot import AotCannyEngine

        owned = batcher is None
        if owned:
            if buckets is None:
                h = getattr(source, "height", None)
                w = getattr(source, "width", None)
                if h is None or w is None:
                    raise ValueError(
                        "aot=True needs the bucket lattice up front: pass "
                        "buckets=[(h, w), ...] or a source with "
                        "height/width attributes"
                    )
                buckets = [(int(h), int(w))]
            aot_engine = AotCannyEngine(
                self.params, buckets=buckets, max_batch=max_batch,
                dist=self.dist or LOCAL,
            )
            batcher = ContinuousBatcher(
                aot_engine, linger_ms=linger_ms, slo_ms=slo_ms,
                timeout=timeout, stats=self.stats,
            )
        t0 = time.perf_counter()
        tickets: _collections.deque = _collections.deque()
        try:
            for frame in source:
                tickets.append(batcher.submit(np.asarray(frame, np.float32)))
                # emit whatever already resolved — admission never blocks
                # behind emission, emission never waits on a wave barrier
                while tickets and tickets[0].done:
                    self.stats.frames += 1
                    self.stats.wall_s = time.perf_counter() - t0
                    yield tickets.popleft().result(timeout)
            while tickets:
                res = tickets.popleft().result(timeout)
                self.stats.frames += 1
                self.stats.wall_s = time.perf_counter() - t0
                yield res
        finally:
            if owned:
                batcher.close()
