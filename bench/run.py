#!/usr/bin/env python3
"""Chip benchmark of the FedOptima pod round: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU and as many chips
as the cell asks for.  The cell, its configuration and its traffic come
from ``BENCHMARK.json`` and the files under ``bench/``.  The last line of
standard output is the result as one JSON object; the numbers that decide
``correct`` are the last lines of standard error, each beside its limit.
Without a TPU, or with too few chips, the run exits non-zero and prints
no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.harness import BenchError, load_cell, run_cell
    try:
        cell = load_cell(a.workload, ROOT)
        out = run_cell(cell, seed=a.seed, seconds=a.seconds,
                       trace=bool(a.trace), t0=T0)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
