"""Streaming Canny demo — a farm of warm-start pipelines over a video.

``python -m repro.launch.canny_stream --frames 64``

Drives a synthetic temporally-coherent stream (static scene + moving
objects, optional per-frame hold) through the farm scheduler and prints
fps, per-stage latency, queue depth, and the warm-start hysteresis
savings. ``--no-warm`` runs the identical schedule cold — outputs are
bit-identical (the warm seed is exactness-gated), only the sweep counts
and fps move. ``--verify-every k`` checks every k-th frame against the
serial numpy oracle; ``--engine`` rides the micro-batching
``CannyEngine.submit``/``drain`` path instead of the farm.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.canny import (
    CannyParams,
    backend_spec,
    backend_specs,
    canny_reference,
    make_detector,
    registered_ops,
)
from repro.core.canny.backends import op_backend
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import dist_from_spec
from repro.stream import FarmScheduler, Prefetcher, SyntheticStream


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=64)
    ap.add_argument("--height", type=int, default=256)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--queue-depth", type=int, default=2)
    ap.add_argument("--hold", type=int, default=4, help="repeat each frame k times")
    ap.add_argument("--noise", type=float, default=0.0)
    ap.add_argument("--block-rows", type=int, default=None)
    ap.add_argument("--no-warm", action="store_true")
    ap.add_argument(
        "--skip",
        action="store_true",
        help="static-strip front-end skip: carry the previous frame and "
        "reuse its front-end output on provably-static strips "
        "(bit-exact; saves frontend launches on held/static streams)",
    )
    ap.add_argument("--engine", action="store_true", help="micro-batch via CannyEngine")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument(
        "--fixed-batch",
        action="store_true",
        help="disable adaptive micro-batching (engine path): always wait "
        "for max-batch frames per wave",
    )
    ap.add_argument(
        "--mesh",
        default=None,
        help="DATAxMODEL device mesh (e.g. 2x4): all workers share one "
        "mesh-aware detector; frames shard over data, rows over model. "
        "PODxDATAxMODEL (e.g. 2x2x2) runs the pod farm instead: frames "
        "dispatch over pod ranks, each with its OWN detector on its "
        "DATAxMODEL device slice (2x1x1 = two plain warm workers)",
    )
    # choices come from the BackendSpec registry — a new backend shows up
    # here (and is capability-validated downstream) with zero CLI edits
    ap.add_argument(
        "--op",
        default="canny",
        choices=registered_ops(),
        help="edge operator to stream; non-canny operators have no "
        "temporal plane, so they run COLD through a shared detector "
        "(and verify against the OPERATOR'S numpy oracle)",
    )
    ap.add_argument(
        "--backend",
        default=None,
        choices=[
            s.name for s in backend_specs()
            if (s.temporal_fn if s.op == "canny" else s.serving_fn)
        ],
        help="any registered backend for --op: temporal-capable for "
        "canny, serving-capable for the operator zoo (default: auto)",
    )
    ap.add_argument(
        "--timeout", type=float, default=None,
        help="seconds to wait for any single result before raising "
        "StreamTimeout (exponential-backoff polling; default: wait forever)",
    )
    ap.add_argument(
        "--max-restarts", type=int, default=0,
        help="replace up to K dead workers (in-flight frames requeued, "
        "order and bits preserved) before the failure propagates",
    )
    ap.add_argument(
        "--chaos-seed", type=int, default=None,
        help="plant a seeded FaultInjector kill schedule (demo of the "
        "restart plumbing; implies --max-restarts>=2 unless set higher)",
    )
    ap.add_argument("--sigma", type=float, default=1.4)
    ap.add_argument("--low", type=float, default=0.08)
    ap.add_argument("--high", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify-every", type=int, default=16, help="0 disables")
    args = ap.parse_args()
    use_compile_cache()

    params = CannyParams(sigma=args.sigma, low=args.low, high=args.high)
    source = SyntheticStream(
        args.frames,
        args.height,
        args.width,
        seed=args.seed,
        hold=args.hold,
        noise=args.noise,
    )
    dist = dist_from_spec(args.mesh)
    pods = dist.pod_size() if not dist.is_local else 1
    if args.skip and args.no_warm:
        raise SystemExit("--skip needs warm-start (drop --no-warm)")
    detector = None
    try:
        args.backend = op_backend(args.op, args.backend, cpu_default="fused")
    except ValueError as e:  # backend/op mismatch
        raise SystemExit(
            f"{e} (backends for {args.op!r}: "
            f"{[s.name for s in backend_specs() if s.op == args.op]})"
        )
    ref = backend_spec(args.backend).ref_fn or canny_reference
    if args.op != "canny":
        # the operator zoo streams COLD: these operators are single-pass
        # stencils with no fixpoint, so there is no temporal state to
        # warm-seed or skip from — all workers share one bucketed
        # mesh-aware detector resolved through the registry
        if args.skip:
            raise SystemExit(
                f"--skip needs a temporal plane and operator {args.op!r} "
                "has none (a single stencil pass leaves no warm state to "
                "reuse) — drop --skip"
            )
        if args.engine:
            raise SystemExit(
                "--engine drives a Canny micro-batching engine; zoo "
                "operators stream through the farm's shared detector — "
                "drop --engine"
            )
        if pods > 1:
            raise SystemExit(
                f"operator {args.op!r} has no per-rank temporal state to "
                "own, so a pod farm buys nothing — use a DATAxMODEL mesh "
                "(the shared cold detector shards over it) or run local"
            )
        try:
            detector = make_detector(
                params, dist, op=args.op, backend=args.backend
            )
        except ValueError as e:  # unclaimed dist, …
            raise SystemExit(str(e))
    if args.engine and pods > 1:
        raise SystemExit(
            "--engine batches frames through one queue and cannot dispatch "
            "over pods; drop --engine or use a DATAxMODEL mesh"
        )
    injector = None
    max_restarts = args.max_restarts
    if args.chaos_seed is not None:
        from repro.distributed import FaultInjector

        n_victims = pods if pods > 1 else args.workers
        injector = FaultInjector.seeded(
            args.chaos_seed, ranks=n_victims, frames=args.frames, kills=1
        )
        max_restarts = max(max_restarts, 2)
    sched = FarmScheduler(
        params,
        n_workers=args.workers,
        warm=not args.no_warm and args.op == "canny",
        skip=args.skip,
        queue_depth=args.queue_depth,
        backend=args.backend,
        block_rows=args.block_rows,
        detector=detector,
        dist=dist,
        max_restarts=max_restarts,
        timeout=args.timeout,
        injector=injector,
    )
    if args.engine:
        mode = "engine"
    elif pods > 1:
        mode = f"pod-farm x{pods}"
    else:
        # the non-pod mesh farm may have forced a single warm lane —
        # report the count the scheduler actually built
        mode = f"farm x{len(sched.farm.workers)}"
    mesh_desc = "" if dist.is_local else f" mesh={args.mesh}"
    # a warm_dist backend keeps temporal warm/skip state ON under a mesh
    # (sharded with it — one single-lane detector on the non-pod farm,
    # per-rank sharded detectors on the pod farm); backends without the
    # claim degrade to a stateless shared detector, warm off — say which
    # applied by looking at what the scheduler constructed
    stateful = args.op == "canny" and (dist.is_local or bool(sched.detectors))
    warm_desc = "off" if (args.no_warm or not stateful) else "on"
    if args.skip and stateful:
        warm_desc += "+skip"
    print(
        f"stream: op={args.op} backend={args.backend} {args.frames} frames "
        f"{args.height}x{args.width} hold={args.hold} "
        f"| {mode} warm={warm_desc}{mesh_desc}",
        flush=True,
    )

    feed = Prefetcher(source, depth=args.queue_depth)
    runner = (
        sched.run_engine(feed, max_batch=args.max_batch, adaptive=not args.fixed_batch)
        if args.engine
        else sched.run(feed)
    )
    t0 = time.perf_counter()
    edge_px = 0
    mismatches = 0
    for i, edges in enumerate(runner):
        edge_px += int(edges.sum())
        if args.verify_every and i % args.verify_every == 0:
            want = ref(source.frame(i), params)
            if not (edges == want).all():
                mismatches += 1
                print(f"frame {i}: MISMATCH vs numpy oracle", flush=True)
        if i % 16 == 0:
            print(f"frame {i:4d}  {sched.stats.summary()}", flush=True)
    dt = time.perf_counter() - t0

    n = sched.stats.frames
    print(f"\ndone: {n} frames in {dt:.2f}s → {n / dt:.2f} fps")
    print(sched.stats.summary())
    stragglers = (
        ", ".join(
            f"{h} (x{c})"
            for h, c in sched.stats.straggler_counts.most_common(3)
        )
        or "none"
    )
    print(
        f"health: worker_restarts={sched.stats.restarts} "
        f"slow_steps={sched.stats.slow_steps} stragglers: {stragglers}"
    )
    for k, det in enumerate(sched.detectors):
        tot = det.cost_totals()
        print(
            f"worker {k}: frames={tot['frames']} sweep_launches={tot['launches']} "
            f"dilations={tot['dilations']} "
            f"frontend_launches={tot['frontend_launches']}"
        )
    density = edge_px / max(1, n * args.height * args.width)
    print(f"mean edge density {density:.4f}")
    if mismatches:
        raise SystemExit(f"{mismatches} oracle mismatches")
    if args.verify_every:
        print("verified: sampled frames bit-exact vs numpy oracle")


if __name__ == "__main__":
    main()
