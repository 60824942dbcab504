"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload upload_a4 --seed 7 --seconds 51 --trace 0

Run from the root of a checkout. The last line of standard output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``; ``checks`` last, each
number compared beside its limit). The same checks are the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
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


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    try:
        jax, device = harness.open_chip(cell["workload"]["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              T_START, device, jax)
    print(json.dumps(result), flush=True)
    harness.report_checks(result["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
