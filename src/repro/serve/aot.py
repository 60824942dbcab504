"""AOT-compiled serving engine — no compile ever rides the request path.

``CannyEngine`` compiles lazily: the first request that lands in a fresh
(batch, height, width) bucket pays a trace+compile stall on the request
path, and under load that stall is exactly what governs tail latency.
``AotCannyEngine`` inverts the contract (the MaxText offline-inference
pattern: per-length executables cached ahead of time):

  * the bucket lattice is EXPLICIT — a list of (h, w) request shapes (or
    a calibration stream they are inferred from) crossed with a ladder of
    batch lanes — and every (lane, hb, wb) cell is lowered and compiled
    at construction via ``jax.jit(...).lower(...).compile()``;
  * a request whose bucket is not in the lattice is REJECTED with a
    fail-fast ``UnsupportedFeature`` (the PR 5 registry contract: named
    failure, never a silent fallback) instead of triggering a fresh
    trace;
  * a trace-counting hook (``traces`` / ``post_warmup_traces``) makes the
    no-retrace contract testable: serving any admissible stream must
    leave ``post_warmup_traces == 0``.

Outputs are bit-identical to the lazy engine's synchronous-wave path on
the same corpus: both run the SAME registered serving entry on the SAME
``pack_requests`` padding — AOT only moves WHEN compilation happens.

The continuous admission loop that feeds this engine lives in
``serve/admission.py``; ``AotCannyEngine.process`` keeps the synchronous
wave API so the two planes can be differenced request-for-request.
"""

from __future__ import annotations

import threading
import time
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.canny.backends import UnsupportedFeature
from repro.core.canny.params import CannyParams
from repro.core.patterns.dist import LOCAL, Dist
from repro.core.spans import span
from repro.serve.engine import (
    EngineStats,
    bucket_batch,
    pack_requests,
    round_up,
)


def default_lanes(max_batch: int, lane_multiple: int = 1) -> tuple[int, ...]:
    """The batch-lane ladder: powers of two up to ``max_batch``, each
    rounded up to a multiple of the mesh data-axis size so every lane
    shards exactly. Matches the lazy engine's ``bucket_batch`` choices,
    which is what keeps the two planes launching identical shapes."""
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    lanes: list[int] = []
    lane = 1
    while True:
        lanes.append(bucket_batch(lane, lane_multiple))
        if lanes[-1] >= max_batch:
            break
        lane *= 2
    return tuple(sorted(set(lanes)))


def infer_buckets(
    calibration: Iterable, bucket_multiple: int
) -> list[tuple[int, int]]:
    """Distinct (hb, wb) buckets observed in a calibration stream of
    frames or (h, w) shape pairs, in first-seen order (deterministic:
    the warmup compile order replays with the stream)."""
    seen: dict[tuple[int, int], None] = {}
    for item in calibration:
        h, w = item if isinstance(item, tuple) else np.asarray(item).shape
        seen[(round_up(int(h), bucket_multiple), round_up(int(w), bucket_multiple))] = None
    if not seen:
        raise ValueError("calibration stream produced no buckets")
    return list(seen)


class AotCannyEngine:
    """Ahead-of-time-compiled Canny server over a fixed bucket lattice.

    Construction lowers+compiles one executable per (batch-lane, height,
    width bucket) cell; after that NOTHING on the request path can trace.
    ``process`` mirrors ``CannyEngine.process`` (mixed sizes, grouped into
    bucket batches, bit-identical outputs) but raises a fail-fast
    ``UnsupportedFeature`` for any request outside the lattice.

    ``dist`` places every launch on a mesh exactly like the lazy engine:
    lanes are padded to multiples of the data-axis size and launches
    serialize on a mesh lock (concurrent shard_map launches interleave
    their collective rendezvous and deadlock).
    """

    def __init__(
        self,
        params: CannyParams = CannyParams(),
        backend: str | None = None,
        buckets: Sequence[tuple[int, int]] | None = None,
        calibration: Iterable | None = None,
        lanes: Sequence[int] | None = None,
        bucket_multiple: int = 64,
        max_batch: int = 8,
        interpret: bool | None = None,
        donate: bool | None = None,
        dist: Dist = LOCAL,
        name: str = "aot-canny",
    ):
        from repro.core.canny.backends import backend_spec, default_backend

        backend = backend or default_backend("fused")
        spec = backend_spec(backend).require(serving=True, dist=not dist.is_local)
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
        if buckets is None and calibration is None:
            raise ValueError(
                "AOT warmup needs the bucket lattice up front: pass "
                "buckets=[(h, w), ...] or calibration=<stream of frames>"
            )
        self.params = params
        self.backend = backend
        self.bucket_multiple = bucket_multiple
        self.max_batch = max_batch
        self.dist = dist
        self.name = name
        self.stats = EngineStats()
        self._mesh_lock = None if dist.is_local else threading.Lock()
        self._stats_lock = threading.Lock()
        if donate is None:
            donate = jax.devices()[0].platform in ("tpu", "gpu")

        hw: dict[tuple[int, int], None] = {}
        for h, w in buckets or ():
            hw[(round_up(int(h), bucket_multiple), round_up(int(w), bucket_multiple))] = None
        if calibration is not None:
            for b in infer_buckets(calibration, bucket_multiple):
                hw[b] = None
        self.hw_buckets: tuple[tuple[int, int], ...] = tuple(hw)
        self._hw_set = frozenset(self.hw_buckets)
        self.lanes = (
            tuple(sorted({bucket_batch(l, dist.batch_size()) for l in lanes}))
            if lanes is not None
            else default_lanes(max_batch, dist.batch_size())
        )

        # the trace hook: ``run`` executes as python exactly once per
        # trace, so this counter moving after warmup IS a retrace —
        # the property the no-retrace tests pin at zero
        self.traces = 0

        def run(imgs, true_hw):
            self.traces += 1
            return spec.serving_fn(imgs, true_hw, params, interpret, dist)

        jitted = jax.jit(run, donate_argnums=(0,) if donate else ())
        t0 = time.perf_counter()
        self._exe: dict[tuple[int, int, int], jax.stages.Compiled] = {}
        for hb, wb in self.hw_buckets:
            for lane in self.lanes:
                self._exe[(lane, hb, wb)] = jitted.lower(
                    jax.ShapeDtypeStruct((lane, hb, wb), jnp.float32),
                    jax.ShapeDtypeStruct((lane, 2), jnp.int32),
                ).compile()
        self.warmup_s = time.perf_counter() - t0
        self.warmup_traces = self.traces
        self.stats.compiles = len(self._exe)

    @property
    def post_warmup_traces(self) -> int:
        """Traces since construction finished — the no-retrace contract
        says this stays 0 for any admissible request stream."""
        return self.traces - self.warmup_traces

    # -- lattice queries -----------------------------------------------------
    def bucket_for(self, h: int, w: int) -> tuple[int, int]:
        """The (hb, wb) bucket serving an (h, w) request, or a fail-fast
        ``UnsupportedFeature`` naming the missing cell — the AOT analogue
        of the registry's named-capability rejection."""
        hb = round_up(h, self.bucket_multiple)
        wb = round_up(w, self.bucket_multiple)
        if (hb, wb) not in self._hw_set:
            raise UnsupportedFeature(
                f"AOT engine {self.name!r} has no executable for request "
                f"({h}, {w}) → bucket ({hb}, {wb}); admitting it would "
                f"trigger a fresh trace on the request path (warmed "
                f"buckets: {sorted(self.hw_buckets)})"
            )
        return hb, wb

    def lane_for(self, n: int) -> int:
        """Smallest precompiled batch lane holding ``n`` requests."""
        for lane in self.lanes:
            if lane >= n:
                return lane
        raise UnsupportedFeature(
            f"AOT engine {self.name!r} has no batch lane for {n} requests "
            f"(warmed lanes: {list(self.lanes)})"
        )

    # -- request plane -------------------------------------------------------
    def run_packed(self, batch: np.ndarray, true_hw: np.ndarray) -> np.ndarray:
        """One launch of an already-packed (lane, hb, wb) bucket batch on
        its precompiled executable. The compiled call rejects any shape it
        was not lowered for, so a packing bug surfaces as a typed error,
        never a retrace."""
        lane, hb, wb = batch.shape
        try:
            exe = self._exe[(lane, hb, wb)]
        except KeyError:
            raise UnsupportedFeature(
                f"AOT engine {self.name!r} has no executable for packed "
                f"shape {(lane, hb, wb)} (warmed buckets: "
                f"{sorted(self.hw_buckets)}, lanes: {list(self.lanes)})"
            ) from None
        t0 = time.perf_counter()
        if self._mesh_lock is not None:
            with self._mesh_lock:  # the fetch blocks before release
                out = self._launch(exe, batch, true_hw)
        else:
            out = self._launch(exe, batch, true_hw)
        dt_ms = (time.perf_counter() - t0) * 1e3
        with self._stats_lock:  # a batcher runs two lanes at once
            self.stats.batches += 1
            self.stats.padded_px += lane * hb * wb
            self.stats.latencies_ms.append(dt_ms)
        return out

    @staticmethod
    def _launch(exe, batch: np.ndarray, true_hw: np.ndarray) -> np.ndarray:
        with span("canny.put"):
            args = jnp.asarray(batch), jnp.asarray(true_hw)
        with span("canny.step"):
            out = exe(*args)
        with span("canny.fetch"):
            return np.asarray(out)  # blocks until the device result lands

    def process(self, images: Sequence[np.ndarray]) -> list[np.ndarray]:
        """Synchronous wave over mixed-size requests — same grouping and
        packing as ``CannyEngine.process`` (bit-identical outputs), every
        launch on a precompiled executable."""
        groups: dict[tuple[int, int], list[int]] = {}
        for i, img in enumerate(images):
            if img.ndim != 2:
                raise ValueError(f"request {i}: expected (h,w), got {img.shape}")
            groups.setdefault(self.bucket_for(*img.shape), []).append(i)

        results: list[np.ndarray | None] = [None] * len(images)
        t_wave = time.perf_counter()
        for (hb, wb), idxs in groups.items():
            for lo in range(0, len(idxs), self.max_batch):
                chunk = idxs[lo : lo + self.max_batch]
                reqs = [images[i] for i in chunk]
                batch, true_hw = pack_requests(
                    reqs, hb, wb, bb=self.lane_for(len(chunk))
                )
                out = self.run_packed(batch, true_hw)
                for slot, i in enumerate(chunk):
                    h, w = images[i].shape
                    results[i] = out[slot, :h, :w]
                    self.stats.true_px += h * w
        self.stats.wall_s += time.perf_counter() - t_wave
        self.stats.requests += len(images)
        return results

    def __call__(self, image: np.ndarray) -> np.ndarray:
        return self.process([image])[0]
