"""Throughput serving layer for the batch-native Canny backends.

The batch-grid kernels take a whole (B, H, W) batch in one launch, but a
jitted detector still recompiles for every new (B, H, W). This module
closes that gap with **shape bucketing**: requests are padded up to a
small lattice of bucket shapes (edge-replicate — the kernels anchor
their border math at the PER-IMAGE true size carried in a (B, 2) table,
so padded outputs are bit-identical to the unpadded oracle) and cropped
on exit. Each bucket compiles exactly once; everything after that is a
cache hit.

Two entry points:

``BucketedCanny``   — a drop-in detector callable for uniform batches;
                      what ``core.canny.pipeline.make_canny`` returns
                      for serving-capable backends. Any (b, h, w) works
                      with zero recompiles after the first request per
                      bucket.
``CannyEngine``     — the request-level engine: accepts MIXED image
                      sizes, groups them into bucket batches (padding
                      the batch dim to a power of two, capped at
                      ``max_batch``), runs each group in one launch,
                      and keeps throughput/latency/compile stats.

Buffer donation is enabled on accelerators (the padded input batch is
dead after the launch) and skipped on CPU where XLA cannot donate.
"""

from __future__ import annotations

import collections
import dataclasses
import statistics
import threading
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.canny.params import CannyParams
from repro.core.patterns.dist import LOCAL, Dist
from repro.distributed.fault_tolerance import StreamTimeout, wait_for


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def bucket_batch(n: int, lane: int = 1) -> int:
    """Batch-dim bucket for ``n`` requests: the next power of two, then
    rounded up to a multiple of ``lane`` (the mesh data-axis size), so a
    bucket batch ALWAYS shards exactly over the data axes — a non-pow2
    lane (e.g. 3-way data parallel) still gets a divisible batch."""
    if n < 0:
        raise ValueError(f"negative batch {n}")
    lane = max(lane, 1)
    return round_up(max(next_pow2(n), 1), lane)


def pack_requests(
    images: Sequence[np.ndarray], hb: int, wb: int, bb: int | None = None,
    lane: int = 1,
) -> tuple[np.ndarray, np.ndarray]:
    """Pad a wave of (h, w) requests into one (bb, hb, wb) bucket batch
    plus its per-slot true-size table — the packing the lazy engine, the
    AOT engine, and the continuous batcher all share. Edge-replicate on
    h/w (what the kernels' true-size border math expects), zeros on the
    phantom batch slots. ``bb=None`` derives the batch bucket from the
    request count (pow2, then ``lane``-divisible).

    One write pass per page: the page is cast straight into its slot,
    then its last column and last row are broadcast into the pad —
    the same bytes as ``np.pad(img.astype(np.float32), mode="edge")``
    without its two intermediate copies."""
    if bb is None:
        bb = bucket_batch(len(images), lane)
    if len(images) > bb:
        raise ValueError(f"{len(images)} requests exceed batch bucket {bb}")
    batch = np.empty((bb, hb, wb), np.float32)
    batch[len(images):] = 0
    true_hw = np.full((bb, 2), (hb, wb), np.int32)
    for slot, img in enumerate(images):
        h, w = img.shape
        page = batch[slot]
        page[:h, :w] = img
        page[:h, w:] = page[:h, w - 1:w]
        page[h:, :] = page[h - 1:h, :]
        true_hw[slot] = (h, w)
    return batch, true_hw


def percentile(samples, q: float) -> float:
    """q-quantile of a bounded sample window; 0 when empty. Shared by the
    engine and stream stats so the clamp logic lives in one place."""
    if not samples:
        return 0.0
    if len(samples) == 1:  # quantiles() needs >= 2 points
        return next(iter(samples))
    qs = statistics.quantiles(samples, n=100, method="inclusive")
    return qs[min(98, max(0, int(q * 100) - 1))]


class _BucketCache:
    """(batch, height, width) bucket → compiled detector, compiled once."""

    def __init__(
        self,
        serve_fn: Callable,
        params: CannyParams,
        interpret: bool | None = None,
        donate: bool | None = None,
        dist: Dist = LOCAL,
    ):
        if donate is None:
            donate = jax.devices()[0].platform in ("tpu", "gpu")
        # jax.jit's own shape-keyed cache holds the per-bucket executables;
        # we only track which buckets have been seen to count compiles.
        self._seen: set[tuple[int, int, int]] = set()
        self.compiles = 0

        def run(imgs, true_hw):
            return serve_fn(imgs, true_hw, params, interpret, dist)

        self._jit = jax.jit(run, donate_argnums=(0,) if donate else ())

    def get(self, bb: int, hb: int, wb: int) -> Callable:
        key = (bb, hb, wb)
        if key not in self._seen:
            self._seen.add(key)
            self.compiles += 1
        return self._jit


class BucketedCanny:
    """Detector callable with a shape-bucketing compile cache.

    (h, w) or (b, h, w) in → uint8 edges of the same shape, bit-identical
    to the unbucketed detector. New exact shapes inside an existing
    (batch, height, width) bucket reuse its executable.

    ``dist`` places every bucket batch on a mesh: the batch dim is padded
    to a multiple of the data-axis size so it shards exactly, and the
    serving backend runs its kernels inside shard_map (rows over the
    space axis via halo exchange) — same outputs, whole-mesh throughput.
    """

    def __init__(
        self,
        serve_fn: Callable,
        params: CannyParams = CannyParams(),
        bucket_multiple: int = 64,
        interpret: bool | None = None,
        donate: bool | None = None,
        dist: Dist = LOCAL,
    ):
        if dist.pod_axis is not None:
            raise ValueError(
                "serving drains ONE queue across a mesh; pod ranks own "
                "separate queues — use the pod farm (stream/pod.py) with "
                "per-rank Dist.pod_slice detectors"
            )
        if not dist.is_local and bucket_multiple % 32:
            raise ValueError(
                f"mesh serving needs bucket_multiple % 32 == 0 (packed "
                f"hysteresis words), got {bucket_multiple}"
            )
        self.params = params
        self.bucket_multiple = bucket_multiple
        self.dist = dist
        self._cache = _BucketCache(serve_fn, params, interpret, donate, dist)
        # one launch owns the WHOLE mesh at a time: concurrent threads
        # racing the same shard_map program interleave its collective
        # rendezvous across devices and deadlock (single-device launches
        # need no lock — jax serializes per device)
        self._mesh_lock = None if dist.is_local else threading.Lock()

    @property
    def compiles(self) -> int:
        return self._cache.compiles

    def __call__(self, img: jax.Array) -> jax.Array:
        squeeze = img.ndim == 2
        imgs = img[None] if squeeze else img
        if imgs.ndim != 3:
            raise ValueError(f"expected (h,w) or (b,h,w), got {img.shape}")
        b, h, w = imgs.shape
        m = self.bucket_multiple
        bb = bucket_batch(b, self.dist.batch_size())
        hb, wb = round_up(h, m), round_up(w, m)
        # edge-replicate on h/w (what the true-size border math expects),
        # zeros on the phantom batch slots — an all-zero image converges in
        # one hysteresis sweep instead of paying full propagation
        padded = jnp.pad(
            imgs.astype(jnp.float32), ((0, 0), (0, hb - h), (0, wb - w)), mode="edge"
        )
        padded = jnp.pad(padded, ((0, bb - b), (0, 0), (0, 0)))
        true_hw = jnp.broadcast_to(jnp.asarray([h, w], jnp.int32), (bb, 2))
        fn = self._cache.get(bb, hb, wb)
        if self._mesh_lock is not None:
            with self._mesh_lock:
                out = jax.block_until_ready(fn(padded, true_hw))
        else:
            out = fn(padded, true_hw)
        out = out[:b, :h, :w]
        return out[0] if squeeze else out


@dataclasses.dataclass
class EngineStats:
    requests: int = 0
    batches: int = 0
    compiles: int = 0
    true_px: int = 0
    padded_px: int = 0
    wall_s: float = 0.0
    # bounded window: a long-running engine must not grow without limit
    latencies_ms: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=4096)
    )

    def throughput_mpx_s(self) -> float:
        return self.true_px / self.wall_s / 1e6 if self.wall_s else 0.0

    def latency_ms(self, q: float) -> float:
        return percentile(self.latencies_ms, q)

    def pad_overhead(self) -> float:
        return self.padded_px / self.true_px - 1.0 if self.true_px else 0.0

    def summary(self) -> str:
        return (
            f"requests={self.requests} batches={self.batches} "
            f"compiles={self.compiles} "
            f"throughput={self.throughput_mpx_s():.2f} MPx/s "
            f"p50={self.latency_ms(0.50):.1f} ms p95={self.latency_ms(0.95):.1f} ms "
            f"pad_overhead={self.pad_overhead():.1%}"
        )


# distinguishes "argument omitted → use the engine default" from an
# explicit ``timeout=None`` (= wait unbounded)
_UNSET = object()


class Ticket:
    """Handle for a ``CannyEngine.submit`` request; resolves at drain."""

    __slots__ = ("_engine", "_result", "_error", "_done")

    def __init__(self, engine: "CannyEngine"):
        self._engine = engine
        self._result: np.ndarray | None = None
        self._error: BaseException | None = None
        self._done = False

    @property
    def done(self) -> bool:
        return self._done

    def _resolve(self, result: np.ndarray) -> None:
        self._result = result
        self._done = True

    def _fail(self, exc: BaseException) -> None:
        self._error = exc
        self._done = True

    def result(self, timeout: float | None = _UNSET) -> np.ndarray:
        """The uint8 edge map; drains the engine if still pending. Raises
        the wave's exception if its ``process`` call failed.

        The wait is bounded: ``timeout`` (default: the engine's
        ``timeout``) caps how long we poll — under exponential backoff —
        for another thread's in-flight wave to resolve us, then raises
        ``StreamTimeout`` instead of spinning forever on a hung wave.
        ``timeout=None`` restores the unbounded wait.
        """
        if timeout is _UNSET:
            timeout = self._engine.timeout

        def resolved() -> bool:
            if self._done:
                return True
            # drain(0): someone else's wave holds the lock — keep polling
            self._engine.drain(timeout=0)
            return self._done

        wait_for(resolved, timeout, what="engine ticket result")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


class CannyEngine:
    """Batch-assembling Canny server for mixed-size request streams.

    ``process`` groups requests by (height, width) bucket, pads each
    group into power-of-two batches (≤ ``max_batch``), runs one batch-
    grid launch per group, and crops per-request results back out.
    Outputs are bit-identical to running each request alone.

    The async plane — ``submit`` enqueues a request and returns a
    ``Ticket``; ``drain`` flushes everything pending as one ``process``
    wave (so requests accumulated between drains share bucket batches).
    The farm scheduler's micro-batching path rides this API. Thread-safe:
    concurrent submits/drains serialize on an internal lock.

    ``dist`` makes ONE engine queue drain across a whole mesh: bucket
    batches pad to a multiple of the data-axis size and the kernels run
    inside shard_map, so every device works on every wave.

    **Bounded waits**: ``timeout`` (seconds; ``None`` = unbounded, the
    historical behaviour) is the default budget for every blocking call
    on this engine — ``drain`` waiting on another thread's in-flight
    wave, ``Ticket.result`` polling for resolution, and ``submit`` when
    ``max_pending`` caps the admission queue. All of them poll under
    exponential backoff and raise ``StreamTimeout`` when the budget runs
    out, so a hung wave (dead device, stuck collective) surfaces as a
    typed error instead of a deadlocked server.
    """

    def __init__(
        self,
        params: CannyParams = CannyParams(),
        backend: str | None = None,
        bucket_multiple: int = 64,
        max_batch: int = 8,
        interpret: bool | None = None,
        donate: bool | None = None,
        dist: Dist = LOCAL,
        timeout: float | None = None,
        max_pending: int | None = None,
        name: str = "canny-engine",
    ):
        from repro.core.canny.backends import backend_spec, default_backend

        backend = backend or default_backend("fused")
        # fail fast, feature named: a backend that cannot serve (or cannot
        # serve under THIS dist) is rejected before any request is queued
        spec = backend_spec(backend).require(
            serving=True, dist=not dist.is_local
        )
        serve_fn = spec.serving_fn
        if dist.pod_axis is not None:
            raise ValueError(
                "serving drains ONE queue across a mesh; pod ranks own "
                "separate queues — use the pod farm (stream/pod.py) with "
                "per-rank Dist.pod_slice detectors"
            )
        if not dist.is_local and bucket_multiple % 32:
            raise ValueError(
                f"mesh serving needs bucket_multiple % 32 == 0 (packed "
                f"hysteresis words), got {bucket_multiple}"
            )
        if timeout is not None and timeout <= 0:
            raise ValueError("timeout must be positive (or None for unbounded)")
        if max_pending is not None and max_pending < 1:
            raise ValueError("max_pending must be >= 1 (or None for unbounded)")
        self.params = params
        self.backend = backend
        self.bucket_multiple = bucket_multiple
        self.max_batch = max_batch
        self.dist = dist
        self.timeout = timeout
        self.max_pending = max_pending
        self.name = name
        self._cache = _BucketCache(serve_fn, params, interpret, donate, dist)
        self.stats = EngineStats()
        self._lock = threading.Lock()
        self._drain_lock = threading.Lock()
        # see BucketedCanny._mesh_lock: concurrent launches of one
        # shard_map program deadlock its cross-device rendezvous
        self._mesh_lock = None if dist.is_local else threading.Lock()
        self._pending: list[tuple[np.ndarray, Ticket]] = []

    # -- async request plane ------------------------------------------------
    def submit(self, image: np.ndarray, timeout: float | None = _UNSET) -> Ticket:
        """Enqueue one (h, w) image; resolves at the next ``drain``.

        With ``max_pending`` set, admission is bounded: a full queue
        polls (exponential backoff) for space freed by a concurrent
        drain and raises ``StreamTimeout`` when ``timeout`` (default:
        the engine's) expires — load-shedding instead of unbounded
        buffering when the drain side is stuck.
        """
        if image.ndim != 2:
            raise ValueError(f"expected (h,w), got {image.shape}")
        if timeout is _UNSET:
            timeout = self.timeout
        ticket = Ticket(self)

        def admitted() -> bool:
            with self._lock:
                if (
                    self.max_pending is not None
                    and len(self._pending) >= self.max_pending
                ):
                    return False
                self._pending.append((image, ticket))
                return True

        # the engine's name rides in ``what`` so a StreamTimeout names WHICH
        # engine shed the load, not just that some admission queue was full
        wait_for(
            admitted, timeout,
            what=f"engine {self.name!r} admission (max_pending={self.max_pending})",
        )
        return ticket

    def drain(self, timeout: float | None = _UNSET) -> int:
        """Run every pending request as one wave; returns how many ran.

        ``_drain_lock`` serializes whole waves, so concurrent drains (e.g.
        two threads calling ``Ticket.result``) never run ``process`` — and
        its stats/bucket-cache updates — in parallel. A failing wave fails
        its tickets (``result`` re-raises) instead of stranding them.

        The wait for another thread's in-flight wave is bounded by
        ``timeout`` (default: the engine's; ``None`` = unbounded) under
        exponential backoff → ``StreamTimeout``. ``timeout=0`` is the
        non-blocking probe ``Ticket.result`` polls with: if a wave is in
        flight, return 0 immediately rather than queueing behind it.
        """
        if timeout is _UNSET:
            timeout = self.timeout
        if timeout == 0:
            if not self._drain_lock.acquire(blocking=False):
                return 0
        elif timeout is None:
            self._drain_lock.acquire()
        else:
            wait_for(
                lambda: self._drain_lock.acquire(blocking=False),
                timeout,
                what="engine drain (another wave in flight)",
            )
        try:
            with self._lock:
                pending, self._pending = self._pending, []
            if not pending:
                return 0
            try:
                results = self.process([img for img, _ in pending])
            except BaseException as exc:
                for _, ticket in pending:
                    ticket._fail(exc)
                raise
            for (_, ticket), res in zip(pending, results):
                ticket._resolve(res)
            return len(pending)
        finally:
            self._drain_lock.release()

    # -- request plane -----------------------------------------------------
    def process(self, images: Sequence[np.ndarray]) -> list[np.ndarray]:
        """Run a wave of (h, w) images of possibly mixed sizes."""
        m = self.bucket_multiple
        groups: dict[tuple[int, int], list[int]] = {}
        for i, img in enumerate(images):
            if img.ndim != 2:
                raise ValueError(f"request {i}: expected (h,w), got {img.shape}")
            h, w = img.shape
            groups.setdefault((round_up(h, m), round_up(w, m)), []).append(i)

        results: list[np.ndarray | None] = [None] * len(images)
        t_wave = time.perf_counter()
        for (hb, wb), idxs in groups.items():
            for lo in range(0, len(idxs), self.max_batch):
                chunk = idxs[lo : lo + self.max_batch]
                self._run_chunk(images, chunk, hb, wb, results)
        self.stats.wall_s += time.perf_counter() - t_wave
        self.stats.requests += len(images)
        return results  # fully populated

    def _run_chunk(self, images, chunk, hb, wb, results) -> None:
        # pow2 for bucket-cache reuse, then a multiple of the data-axis
        # size so the batch ALWAYS shards exactly over the mesh
        batch, true_hw = pack_requests(
            [images[i] for i in chunk], hb, wb, lane=self.dist.batch_size()
        )
        bb = batch.shape[0]
        fn = self._cache.get(bb, hb, wb)
        t0 = time.perf_counter()
        if self._mesh_lock is not None:
            with self._mesh_lock:  # np.asarray blocks before release
                out = np.asarray(fn(jnp.asarray(batch), jnp.asarray(true_hw)))
        else:
            out = np.asarray(fn(jnp.asarray(batch), jnp.asarray(true_hw)))
        dt_ms = (time.perf_counter() - t0) * 1e3
        for slot, i in enumerate(chunk):
            h, w = images[i].shape
            results[i] = out[slot, :h, :w]
            self.stats.true_px += h * w
            self.stats.latencies_ms.append(dt_ms)
        self.stats.padded_px += bb * hb * wb
        self.stats.batches += 1
        self.stats.compiles = self._cache.compiles

    def __call__(self, image: np.ndarray) -> np.ndarray:
        return self.process([image])[0]
