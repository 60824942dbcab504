"""Streaming edge detection — frame streams as a first-class workload.

Composes the farm pattern (``core.patterns.farm``) with the batch-grid
Canny kernels: frame sources behind one iterator protocol, a farm of
double-buffered per-worker pipelines with bounded-queue backpressure and
in-order emission, and temporal warm-start hysteresis that threads the
previous frame's packed edge words into the next frame's fixpoint
(bit-exact via the grow-only gate). CLI: ``python -m
repro.launch.canny_stream``.
"""

from repro.stream.sources import (
    CorpusReplay,
    NpySequence,
    Prefetcher,
    SyntheticStream,
    write_npy_sequence,
)
from repro.stream.pod import (
    ElasticPodFarm,
    PodCtx,
    PodMembership,
    PodWorker,
    elastic_pod_dist,
    owns,
    pod_workers,
    reassemble,
    reassemble_elastic,
    session_route,
    strided,
)
from repro.stream.temporal import TemporalCanny
from repro.stream.scheduler import FarmScheduler, SessionTable, StreamStats, StreamWorker

__all__ = [
    "CorpusReplay",
    "NpySequence",
    "Prefetcher",
    "SyntheticStream",
    "write_npy_sequence",
    "ElasticPodFarm",
    "PodCtx",
    "PodMembership",
    "PodWorker",
    "elastic_pod_dist",
    "owns",
    "pod_workers",
    "reassemble",
    "reassemble_elastic",
    "session_route",
    "strided",
    "TemporalCanny",
    "FarmScheduler",
    "SessionTable",
    "StreamStats",
    "StreamWorker",
]
