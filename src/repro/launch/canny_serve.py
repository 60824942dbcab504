"""Canny serving demo — mixed-size traffic through the CannyEngine.

``python -m repro.launch.canny_serve --waves 4 --per-wave 12``

Interleaves requests of several image sizes (default 480×640 and
512×512), feeds them to the engine in waves, and prints per-wave stats.
The headline property: the compile counter stops moving after the first
wave — every later request of ANY seen bucket is a cache hit — while
outputs stay bit-identical to the serial numpy oracle (verified on a
sample each wave).

``--aot`` switches to the continuous-batching plane: every (size,
batch-lane) executable compiles AHEAD of time (the compile counter never
moves at all — a request outside the lattice is rejected, not traced),
requests arrive continuously (``--arrival-rate`` Poisson arrivals in
req/s; default back-to-back) and pack into open bucket slots
(``--linger-ms`` fill-or-linger), and per-request latency is scored
against ``--slo-ms``.
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
    registered_ops,
)
from repro.core.canny.backends import op_backend
from repro.data.images import synthetic_image
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import dist_from_spec
from repro.serve.engine import CannyEngine


def parse_sizes(spec: str) -> list[tuple[int, int]]:
    sizes = []
    for part in spec.split(","):
        h, w = part.lower().split("x")
        sizes.append((int(h), int(w)))
    return sizes


def serve_aot(args, params, sizes, dist, ref_fn):
    """The continuous plane: AOT warmup, Poisson arrivals, SLO scoring."""
    from repro.serve.admission import ContinuousBatcher
    from repro.serve.aot import AotCannyEngine

    t0 = time.perf_counter()
    engine = AotCannyEngine(
        params,
        backend=args.backend,
        buckets=sizes,
        bucket_multiple=args.bucket,
        max_batch=args.max_batch,
        dist=dist,
    )
    mesh_desc = "local" if dist.is_local else f"mesh={args.mesh}"
    print(
        f"aot engine: op={args.op} backend={args.backend} "
        f"buckets={sorted(engine.hw_buckets)} "
        f"lanes={list(engine.lanes)} → {len(engine._exe)} executables "
        f"compiled in {engine.warmup_s:.2f}s {mesh_desc}"
    )

    total = args.waves * args.per_wave
    rng = np.random.default_rng(args.seed)
    reqs = [
        synthetic_image(*sizes[i % len(sizes)], seed=int(rng.integers(1 << 31)))
        for i in range(total)
    ]
    # seeded Poisson arrivals: exponential inter-arrival gaps at the
    # offered rate; None = back-to-back (saturation)
    gaps = (
        rng.exponential(1.0 / args.arrival_rate, size=total)
        if args.arrival_rate
        else np.zeros(total)
    )
    with ContinuousBatcher(
        engine, linger_ms=args.linger_ms, slo_ms=args.slo_ms, timeout=300.0,
    ) as batcher:
        t_start = time.perf_counter()
        tickets = []
        for req, gap in zip(reqs, gaps):
            if gap:
                time.sleep(float(gap))
            tickets.append(batcher.submit(req))
        batcher.drain()
        dt = time.perf_counter() - t_start
        stats = batcher.stats
        print(
            f"served {total} requests in {dt:.2f}s → {total / dt:.1f} req/s "
            f"(offered: "
            f"{f'{args.arrival_rate:.1f}/s poisson' if args.arrival_rate else 'saturation'})"
        )
        print(f"  {stats.summary()}")
        slo = stats.slo()
        if args.slo_ms is not None:
            print(
                f"  SLO<{args.slo_ms:g}ms: pass={slo['pass']} "
                f"fail={slo['fail']} attainment={slo['attainment']:.1%}"
            )

        if not args.no_verify:
            i = int(rng.integers(total))
            want = ref_fn(reqs[i], params)
            ok = (tickets[i].result() == want).all()
            print(f"  verify request {i} {reqs[i].shape}: "
                  f"{'bit-exact vs numpy oracle' if ok else 'MISMATCH'}")
            if not ok:
                raise SystemExit(1)

    assert engine.post_warmup_traces == 0, (
        f"{engine.post_warmup_traces} traces leaked onto the request path"
    )
    print(
        f"done: {engine.stats.requests} requests, {engine.warmup_traces} "
        f"warmup traces, 0 post-warmup traces — no compile ever rode the "
        f"request path ({time.perf_counter() - t0:.2f}s total)"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="480x640,512x512", help="h x w list, comma separated")
    ap.add_argument("--waves", type=int, default=4)
    ap.add_argument("--per-wave", type=int, default=12)
    ap.add_argument("--bucket", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8)
    # operators and serving-capable backends straight from the
    # BackendSpec registry; the engine validates dist capability at
    # construction (fail fast). The backend default resolves AFTER parse
    # — it depends on --op (``op_backend``).
    serving = [s.name for s in backend_specs() if s.serving_fn]
    ap.add_argument(
        "--op",
        default="canny",
        choices=registered_ops(),
        help="edge operator to serve; the backend resolves through the "
        "registry and sampled requests verify against the OPERATOR'S "
        "numpy oracle",
    )
    ap.add_argument(
        "--backend",
        default=None,
        choices=serving,
        help="serving backend (default: 'fused' for canny, else the "
        "operator's registered backend)",
    )
    ap.add_argument("--sigma", type=float, default=1.4)
    ap.add_argument("--low", type=float, default=0.08)
    ap.add_argument("--high", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument(
        "--mesh",
        default=None,
        help="DATAxMODEL device mesh (e.g. 2x4): bucket batches shard over "
        "data, rows over model; one queue drains across all devices",
    )
    ap.add_argument(
        "--aot",
        action="store_true",
        help="AOT continuous-batching plane: compile every (size, lane) "
        "executable at warmup, admit requests continuously into bucket "
        "slots, score per-request latency against --slo-ms",
    )
    ap.add_argument(
        "--slo-ms", type=float, default=None,
        help="per-request latency SLO bound in ms (AOT plane; default: "
        "no bound, latency still reported)",
    )
    ap.add_argument(
        "--linger-ms", type=float, default=5.0,
        help="max time a request waits for its slot to fill before the "
        "slot dispatches partially packed (AOT plane)",
    )
    ap.add_argument(
        "--arrival-rate", type=float, default=None,
        help="offered load in requests/s (seeded Poisson arrivals, AOT "
        "plane); default: submit back-to-back",
    )
    args = ap.parse_args()
    use_compile_cache()

    try:
        args.backend = op_backend(args.op, args.backend, cpu_default="fused")
    except ValueError as e:  # backend/op mismatch
        raise SystemExit(
            f"{e} (backends for {args.op!r}: "
            f"{[s.name for s in backend_specs() if s.op == args.op]})"
        )
    # every operator verifies against ITS oracle, not canny's
    ref_fn = backend_spec(args.backend).ref_fn or canny_reference

    params = CannyParams(sigma=args.sigma, low=args.low, high=args.high)
    sizes = parse_sizes(args.sizes)
    dist = dist_from_spec(args.mesh)
    if args.aot:
        return serve_aot(args, params, sizes, dist, ref_fn)
    engine = CannyEngine(
        params,
        backend=args.backend,
        bucket_multiple=args.bucket,
        max_batch=args.max_batch,
        dist=dist,
    )
    mesh_desc = "local" if dist.is_local else f"mesh={args.mesh}"
    print(
        f"engine: op={args.op} backend={args.backend} "
        f"bucket_multiple={args.bucket} "
        f"max_batch={args.max_batch} sizes={sizes} {mesh_desc}"
    )

    rng = np.random.default_rng(args.seed)
    compiles_after_warmup = None
    for wave in range(args.waves):
        # interleave sizes round-robin so every batch sees mixed traffic
        reqs = [
            synthetic_image(*sizes[i % len(sizes)], seed=int(rng.integers(1 << 31)))
            for i in range(args.per_wave)
        ]
        edges = engine.process(reqs)
        line = f"wave {wave}: {engine.stats.summary()}"
        if wave == 0:
            compiles_after_warmup = engine.stats.compiles
            line += "  (warmup: one compile per bucket)"
        elif engine.stats.compiles != compiles_after_warmup:
            line += "  !! RECOMPILED — bucket cache miss"
        else:
            line += "  (zero new compiles)"
        print(line, flush=True)

        if not args.no_verify:
            i = int(rng.integers(len(reqs)))
            want = ref_fn(reqs[i], params)
            ok = (edges[i] == want).all()
            print(f"  verify request {i} {reqs[i].shape}: "
                  f"{'bit-exact vs numpy oracle' if ok else 'MISMATCH'}")
            if not ok:
                raise SystemExit(1)

    n_buckets = len({(int(h), int(w)) for h, w in
                     ((-(-h // args.bucket) * args.bucket, -(-w // args.bucket) * args.bucket)
                      for h, w in sizes)})
    assert engine.stats.compiles == compiles_after_warmup, "bucket cache missed"
    print(
        f"done: {engine.stats.requests} requests, {engine.stats.compiles} compiles "
        f"total across {n_buckets} shape bucket(s) — zero recompiles after warmup"
    )


if __name__ == "__main__":
    main()
