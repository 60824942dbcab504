"""Reduce a profiler trace to device busy time, op times and idle gaps.

The run wraps its measured window in a ``bench.window`` host span and its
own work in ``bench.*`` spans (``jax.profiler.TraceAnnotation``). Device
planes (``/device:TPU:n``) carry the ``XLA Ops`` and ``XLA Modules``
lines; host and device events share one clock in the ``.xplane.pb``.

- busy: the union of the op intervals of each device inside the window,
  averaged over devices;
- device ops: self time (duration less the ops nested in it) per op, the
  op named by its HLO name and result shape;
- idle gaps: the stretches of the window in which no op ran, each named by
  the ``bench.*`` span open over at least half of it (``-`` if none) and
  the other host event (JAX's and the runtime's own, on any host thread)
  that covers most of it.
"""

from __future__ import annotations

import glob
import os

WINDOW = "bench.window"
TOP = 10


def start(log_dir: str) -> None:
    import jax.profiler as prof

    opts = prof.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python tracing: it slows the host
    opts.host_tracer_level = 1
    prof.start_trace(log_dir, profiler_options=opts)


def stop(log_dir: str) -> str:
    """Stop the trace; the path of the ``.xplane.pb`` it wrote."""
    import jax.profiler as prof

    prof.stop_trace()
    found = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[4,512]{1,0:T(8,128)} fusion(...)`` → ``%fusion.3 = f32[4,512]``."""
    return hlo.split("{", 1)[0].strip()[:80]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Per-name self time of possibly nested (start, end, name) events."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, duration, time of nested events]

    def close(frame):
        s_end, name, dur, child = frame
        out[name] = out.get(name, 0.0) + dur - child

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0.0])
    while stack:
        close(stack.pop())
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _busiest(spans, g0, g1, least: float = 0.0) -> str | None:
    """The span covering most of the gap, if it covers more than ``least``
    of it."""
    best, best_ov = None, least * (g1 - g0)
    for s, e, name in spans:
        ov = _overlap(s, e, g0, g1)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def reduce_planes(planes) -> dict:
    """The reduction, over ``(plane_name, [(line_name, [(start_ns,
    dur_ns, name), ...]), ...])`` tuples (what ``ProfileData`` holds)."""
    devices, bench_spans, host_spans = [], [], []
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for lname, events in lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(lname)
                if key:
                    dev[key] += [(s, s + d, n) for s, d, n in events]
            devices.append(dev)
        elif pname.startswith("/host:"):
            for _lname, events in lines:
                for s, d, n in events:
                    (bench_spans if n.startswith("bench.") else host_spans).append((s, s + d, n))
    windows = [(s, e) for s, e, n in bench_spans if n == WINDOW]
    if not windows or not devices:
        raise ValueError("trace has no bench.window span or no TPU device plane")
    w0, w1 = windows[0]
    busy_ns, modules_ns, ops_self, modules_n = [], 0.0, {}, 0
    gaps = []
    for dev in devices:
        busy = union(clip([(s, e) for s, e, _ in dev["ops"]], w0, w1))
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e in clip([(s, e) for s, e, _ in dev["modules"]], w0, w1):
            modules_ns += e - s
            modules_n += 1
        inside = [(max(s, w0), min(e, w1), op_name(n)) for s, e, n in dev["ops"] if e > w0 and s < w1]
        for name, t in self_times(inside).items():
            ops_self[name] = ops_self.get(name, 0.0) + t
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for g0, g1 in gaps[:TOP]:
        bench = _busiest([sp for sp in bench_spans if sp[2] != WINDOW], g0, g1, 0.5) or "-"
        host = _busiest(host_spans, g0, g1) or "-"
        named.append([f"{bench} | {host}", (g1 - g0) / 1e9])
    top_ops = sorted(ops_self.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "module_s": modules_ns / 1e9,
        "module_launches": modules_n,
        "devices": len(devices),
        "device_ops": [[n, t / 1e9] for n, t in top_ops],
        "idle_gaps": named,
    }


def planes_of(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not (plane.name.startswith("/device:TPU:") or plane.name.startswith("/host:")):
            continue
        out.append((plane.name, [
            (line.name, [(e.start_ns, e.duration_ns, e.name) for e in line.events])
            for line in plane.lines
        ]))
    return out


def reduce(path: str) -> dict:
    return reduce_planes(planes_of(path))
