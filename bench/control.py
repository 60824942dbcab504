"""Readings for the limits of ``correct``: the program and its control.

    python3 bench/control.py --workload upload_a4 --seeds 1,2,3 --seconds 5

Builds the cell's system once, then for each seed makes that seed's
inputs, warms, runs a window at the cell's own load and sizes, and reads
every number that ``correct`` compares twice over the same sampled
outputs: once for the program, once with the reference computed in
bfloat16 (the precision below the configuration's float32) put in the
program's place. One JSON line per seed. The benchmark's own runs never
run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

CONTROL = "bfloat16"


def readings(entry, cell: dict, system: dict, seed: int, seconds: float) -> dict:
    plan = entry.inputs(cell["config"], cell["traffic"], seed, seconds)
    entry.warm(system, plan)
    rec = entry.window(system, plan, seconds, time.perf_counter())
    outputs = list(rec["outputs"])
    program = entry.verify(cell["config"], plan, rec)
    rec["outputs"] = outputs
    control = entry.verify(cell["config"], plan, rec, control=CONTROL)
    return {"seed": seed, "compared": len(outputs),
            "program": {k: v["value"] for k, v in program.items()},
            "control": {k: v["value"] for k, v in control.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.open_chip(cell["workload"]["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    entry = harness.load_entry(cell["config"]["entry"])
    system = entry.build(cell["config"])
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(entry, cell, system, seed, args.seconds)), flush=True)
    entry.close(system)
    return 0


if __name__ == "__main__":
    sys.exit(main())
