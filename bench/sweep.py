"""Sweep the offered rate of an open-loop upload cell, in one process.

    python3 bench/sweep.py --config document_scans --traffic a4_bulk --seed 7 --seconds 15 --rates 8,10,12

Prints one JSON line per rate: requests offered, completed inside the
window, still in flight at its close, and the latency quantiles from due
time to result. A rate is sustained when nearly every request offered
completes inside the window and the backlog at the close stays near what
one A4 request's service time holds. An open-loop cell offers about 0.8
of the highest sustained rate.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, measures  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True, help="a file of bench/configs, by name")
    ap.add_argument("--traffic", required=True, help="an open-loop file of bench/traffic, by name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = ap.parse_args(argv)
    config = harness.load_json(harness.BENCH / "configs" / f"{args.config}.json")
    base = harness.load_json(harness.BENCH / "traffic" / f"{args.traffic}.json")
    try:
        harness.open_chip(1)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    entry = harness.load_entry(config["entry"])
    system = entry.build(config)
    warmed = False
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(base, rate_per_s=rate)
        plan = entry.inputs(config, traffic, args.seed, args.seconds)
        if not warmed:
            entry.warm(system, plan)
            warmed = True
        rec = entry.window(system, plan, args.seconds, T_START)
        lat = [(r["complete"] - r["due"]) * 1e3 if r["ok"] else float("inf")
               for r in measures.window_requests(rec)]
        done = measures.completed_in_window(rec)
        print(json.dumps({
            "rate_per_s": rate, "offered": rec["attempted"], "completed_in_window": len(done),
            "in_flight_at_close": rec["load"]["in_flight_at_close"],
            "p50_ms": measures.quantile(lat, 0.5), "p95_ms": measures.quantile(lat, 0.95),
            "p99_ms": measures.quantile(lat, 0.99), "failed": rec["failed"],
            "late_ms_p99": rec["load"]["generator_late_ms_p99"],
        }), flush=True)
    entry.close(system)
    return 0


if __name__ == "__main__":
    sys.exit(main())
