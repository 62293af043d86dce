#!/usr/bin/env python3
"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json once, on the machine it is started on,
and prints one JSON object as the last line of stdout (see README.md).
Exit code 0 with a result line, 2 when this machine cannot give the
cell's numbers (no TPU, too few chips, unknown cell), 1 on a crash.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window (BENCHMARK.json's "
                        "run_seconds when left out)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from benchmark import cells, harness

    try:
        seconds = args.seconds
        if seconds is None:
            seconds = cells.load_benchmark()["run_seconds"]
        harness.run_cell(args.workload, args.seed, float(seconds),
                         bool(args.trace))
    except (harness.Refusal, cells.BenchmarkError) as exc:
        print(f"benchmark: refusing to run: {exc}", file=sys.stderr, flush=True)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
