"""The data-driven core: find a cell's pieces by name and run it.

``BENCHMARK.json`` names every piece; this module finds each by that name:

- a configuration in ``bench/configs/<config>.json`` (its ``entry`` names
  the entry module in ``bench/entries/<entry>.py``);
- a traffic mix in ``bench/traffic/<traffic>.json``;
- a metric in ``bench/metrics/<metric>.py``, a module with
  ``read(rec) -> float | None``.

A run is: set up (build the system, make the inputs from the seed, warm
every shape), measure for ``seconds`` (under the profiler when traced),
read the device's memory peak, free the system, then compare what the
window produced with the plain reference. Adding a cell, a configuration,
a mix or a metric adds files and ``BENCHMARK.json`` entries only.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: pathlib.Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, benchmark: dict | None = None, bench_dir: pathlib.Path = BENCH) -> dict:
    """Everything one cell needs, looked up by the names in the spec:
    ``{"workload", "config", "traffic", "end_to_end", "per_layer"}``."""
    if benchmark is None:
        benchmark = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = load_json(bench_dir.parent / configs[cell["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", (workload,))

    return {
        "workload": cell,
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in benchmark["end_to_end"] if applies(m)],
        "per_layer": [m for m in benchmark["per_layer"] if applies(m)],
    }


def load_entry(name: str, bench_dir: pathlib.Path = BENCH) -> ModuleType:
    return _module(bench_dir / "entries" / f"{name}.py", name)


def read_metrics(metrics: list[dict], rec: dict, bench_dir: pathlib.Path = BENCH) -> dict:
    """``{name: {"value", "unit"}}`` for every metric whose reader found
    something to read; a reader that returns None is left out."""
    out = {}
    for m in metrics:
        value = _module(bench_dir / "metrics" / f"{m['name']}.py", m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_info(jax, chips: int) -> dict:
    """The device block of the result line; raises ``NoChip`` off a TPU
    or with fewer chips than the cell asks for."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def open_chip(chips: int):
    """Place the compile cache, then ``(jax, device block)``; raises
    ``NoChip`` off a TPU. Every entry point calls this before any work."""
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    return jax, device_info(jax, chips)


def memory_peak(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts programs compiled or loaded from the compile cache while
    ``active``: a warmed window counts none."""

    def __init__(self):
        import jax.monitoring as mon

        self.active, self.count = False, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _on_event(self, event, **kw):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
             device: dict, jax=None) -> dict:
    """One run of one cell; returns the result line's object.

    ``t_start`` is the process's start on ``time.perf_counter``; set-up
    runs from there to the first timed request or frame.
    """
    from bench import trace as tr

    entry = load_entry(cell["config"]["entry"])
    if trace and "trace_seconds" in cell["config"]:
        seconds = min(seconds, cell["config"]["trace_seconds"])
    marks = [("start", t_start), ("imports", time.perf_counter())]
    system = entry.build(cell["config"])
    marks.append(("build", time.perf_counter()))
    inputs = entry.inputs(cell["config"], cell["traffic"], seed, seconds)
    marks.append(("inputs", time.perf_counter()))
    entry.warm(system, inputs)
    marks.append(("warm", time.perf_counter()))
    print("bench: set-up " + ", ".join(f"{name} {t - marks[i][1]:.2f} s" for i, (name, t)
                                       in enumerate(marks[1:])), file=sys.stderr)
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles = CompileCounter()
    try:
        if trace:
            tr.start(tmp)
        compiles.active = True
        rec = entry.window(system, inputs, seconds, t_start)
        compiles.active = False
        rec["device_kind"] = device["kind"]
        if trace:
            rec["trace"] = tr.reduce(tr.stop(tmp))
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    if jax is not None:
        device = dict(device, memory_peak_bytes=memory_peak(jax))
    entry.close(system)
    checks = entry.verify(cell["config"], inputs, rec)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics = read_metrics(cell["per_layer"] if trace else cell["end_to_end"], rec)
    result = {"correct": correct, "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device}
    if trace:
        t = rec["trace"]
        result["device"] = dict(result["device"], busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"], "idle_gaps": t["idle_gaps"]}
    result["load"] = dict(rec.get("load", {}), compiles_in_window=compiles.count)
    result["checks"] = checks
    return result


def report_checks(checks: dict, stream=sys.stderr) -> None:
    """The numbers compared, each beside its limit, as the last lines."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {verdict}", file=stream)

