"""Image uploads: ``AotCannyEngine`` behind ``ContinuousBatcher``.

Open loop (``rate_per_s`` in the mix): one thread submits each request at
its due time; a request is timed from that due time to the moment its
result resolves, so a stall delays every later request's clock. Closed
loop (``clients``): that many clients each keep one request outstanding,
sending the next the moment the last resolves.

Requests reuse a small corpus made in set-up from the seed. The system
keeps no result cache, so reuse flatters nothing; a later result cache
would need a corpus of distinct images.
"""

from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench import check, images, schedule

DRAIN_S = 60.0  # how long past the window's close an answer may still come
# How often closed-loop clients look for their answers. Part of the
# yardstick: the clients share the process (and the GIL) with the
# batcher's dispatch thread, so polling faster takes time from packing.
POLL_S = 0.005


def build(config: dict) -> dict:
    from repro.core.canny import CannyParams
    from repro.serve.admission import ContinuousBatcher
    from repro.serve.aot import AotCannyEngine

    shapes = [tuple(s) for c in sorted(config["classes"]) for s in config["classes"][c]]
    engine = AotCannyEngine(
        CannyParams(**config["canny"]), backend=config["backend"], buckets=shapes,
        max_batch=config["max_batch"], bucket_multiple=config["bucket_multiple"],
    )
    batcher = ContinuousBatcher(engine, linger_ms=config["linger_ms"])
    return {"engine": engine, "batcher": batcher}


def inputs(config: dict, traffic: dict, seed: int, seconds: float) -> dict:
    """The corpus (per class, ``corpus[c]`` images cycling over the class's
    shapes) and the arrival schedule, from the seed."""
    rng = np.random.default_rng((seed, 1))
    corpus = {}
    for c in sorted(config["classes"]):
        shapes = config["classes"][c]
        corpus[c] = [images.scene(*shapes[i % len(shapes)], rng) for i in range(config["corpus"][c])]
    sizes = {c: len(v) for c, v in corpus.items()}
    plan = {"corpus": corpus, "traffic": traffic, "seed": seed}
    if "rate_per_s" in traffic:
        plan["arrivals"] = schedule.open_loop(traffic, sizes, seed, seconds)
    else:
        plan["sequence"] = schedule.requests(traffic, sizes, seed)
    return plan


def warm(system: dict, plan: dict) -> None:
    """Run every executable of the lattice once, then one request of each
    class through the batcher."""
    from repro.serve.engine import pack_requests

    engine, batcher = system["engine"], system["batcher"]
    for c, imgs in plan["corpus"].items():
        for img in imgs[: len(set(i.shape for i in imgs))]:
            hb, wb = engine.bucket_for(*img.shape)
            for lane in engine.lanes:
                engine.run_packed(*pack_requests([img] * lane, hb, wb, bb=lane))
    tickets = [batcher.submit(imgs[0]) for imgs in plan["corpus"].values()]
    for t in tickets:
        t.result(DRAIN_S)


def _snapshot(engine) -> dict:
    s = engine.stats
    return {"batches": s.batches, "padded_px": s.padded_px, "true_px": s.true_px}


class _Book:
    """Requests in flight and done: timestamps, and a seeded sample of the
    answers for the comparison."""

    def __init__(self, plan: dict, sample: dict):
        self.plan = plan
        self.pending: list = []
        self.done: list = []
        self.sample = check.Reservoir(sample, plan["seed"])

    def add(self, due, cls, idx, ticket) -> None:
        self.pending.append((due, cls, idx, ticket, time.perf_counter()))

    def harvest(self, t_close: float | None = None) -> int:
        """Move resolved requests to ``done``; ``t_close`` (closed loop)
        keeps only answers that came inside the window in the sample."""
        still, n = [], 0
        for p in self.pending:
            due, cls, idx, ticket, t_submit = p
            if not ticket.done:
                still.append(p)
                continue
            n += 1
            try:
                out, ok = ticket.result(0), True
            except Exception:  # noqa: BLE001 — a failed request is a miss
                out, ok = None, False
            img = self.plan["corpus"][cls][idx]
            self.done.append({
                "cls": cls, "shape": img.shape, "px": img.size, "due": due, "submit": t_submit,
                "enqueue": ticket.t_enqueue, "dispatch": ticket.t_dispatch,
                "complete": ticket.t_complete, "ok": ok,
            })
            if t_close is None or ticket.t_complete <= t_close:
                self.sample.offer(cls, (out, img))
        self.pending = still
        return n


def window(system: dict, plan: dict, seconds: float, t_start: float) -> dict:
    engine, batcher = system["engine"], system["batcher"]
    book = _Book(plan, plan["traffic"]["sample"])
    span = TraceAnnotation("bench.window")
    t0 = time.perf_counter()
    before = _snapshot(engine)
    t_close = t0 + seconds
    span.__enter__()
    if "arrivals" in plan:
        for k, (offset, cls, idx) in enumerate(plan["arrivals"]):
            due = t0 + offset
            if len(book.pending) <= 256 or k % 16 == 0:
                book.harvest()
            delay = due - time.perf_counter()
            if delay > 0:
                with TraceAnnotation("bench.idle"):
                    time.sleep(delay)
            with TraceAnnotation("bench.submit"):
                ticket = batcher.submit(plan["corpus"][cls][idx])
            book.add(due, cls, idx, ticket)
        delay = t_close - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
    else:
        seq = plan["sequence"]
        for _ in range(plan["traffic"]["clients"]):
            cls, idx, _gap = next(seq)
            book.add(time.perf_counter(), cls, idx, batcher.submit(plan["corpus"][cls][idx]))
        while time.perf_counter() < t_close:
            n = book.harvest(t_close)
            if n == 0:
                time.sleep(POLL_S)
                continue
            for _ in range(n):
                if time.perf_counter() >= t_close:
                    break
                cls, idx, _gap = next(seq)
                with TraceAnnotation("bench.submit"):
                    ticket = batcher.submit(plan["corpus"][cls][idx])
                book.add(time.perf_counter(), cls, idx, ticket)
    after = _snapshot(engine)
    in_flight = len(book.pending)
    lat = list(engine.stats.latencies_ms)
    n_launch = after["batches"] - before["batches"]
    launch_ms = lat[max(len(lat) - n_launch, 0):] if n_launch else []
    span.__exit__(None, None, None)
    deadline = t_close + DRAIN_S
    closed = "arrivals" not in plan
    while book.pending and time.perf_counter() < deadline:
        if book.harvest(t_close if closed else None) == 0:
            time.sleep(0.001)
    unanswered = len(book.pending)
    groups: dict = {}
    for r in book.done:
        if r["dispatch"] is not None:
            groups[r["dispatch"]] = groups.get(r["dispatch"], 0) + 1
    dispatches = [(t, n, engine.lane_for(n)) for t, n in sorted(groups.items())]
    late = [(r["submit"] - r["due"]) * 1e3 for r in book.done] if not closed else []
    failed = unanswered + sum(not r["ok"] for r in book.done)
    return {
        "setup_s": t0 - t_start,
        "t0": t0,
        "window_s": seconds,
        "attempted": len(book.done) + unanswered,
        "failed": failed,
        "unanswered": unanswered,
        "requests": book.done,
        "dispatches": dispatches,
        "engine": {k: after[k] - before[k] for k in after},
        "launch_ms": launch_ms,
        "outputs": book.sample.all(),
        "load": {
            "generator_late_ms_max": max(late) if late else None,
            "generator_late_ms_p99": float(np.percentile(late, 99)) if late else None,
            "in_flight_at_close": in_flight,
            "compared": len(book.sample.all()),
            "post_warmup_traces": engine.post_warmup_traces,
        },
    }


def close(system: dict) -> None:
    system["batcher"].close()
    system.clear()


def verify(config: dict, plan: dict, rec: dict, control: str | None = None) -> dict:
    """The numbers compared against their limits (``check.compare``)."""
    return check.compare(rec.pop("outputs"), config, rec["failed"], control)
