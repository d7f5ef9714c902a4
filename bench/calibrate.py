#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 7 8 9 [--variants control_bf16 mixed_bf16 ...]

For each of ``--seeds`` it makes a run of the cell as the benchmark does
(a one-second window) and prints the numbers compared: the lower
readings.  For each of ``--control-seeds`` it puts variants of the plain
reference in the program's place and prints the same numbers against
the float32 reference: the controls, one step below the configuration's
float32 (``control_bf16``: every weight and activation in bfloat16;
``mixed_bf16``: bfloat16 forward and backward over float32 weights and
updates), and two planted faults (``half_batch``: every loss over half
of each row's tokens; ``no_aggregation``: no end-of-round average).  A
state left unchanged reads 1 on ``update_gap`` and ``change_gap`` by
construction.  Each reading is one JSON line on standard output; the
last line sums them up.  Needs the cell's chips, like ``run.py``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
NUMBERS = ("loss_gap", "update_gap", "change_gap")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--variants", nargs="*", default=None,
                   help="the variants to read (default: all)")
    a = p.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax.numpy as jnp
    from bench.harness import load_cell, run_cell
    from bench.reference.check import compare, reference_readings

    cell = load_cell(a.workload, ROOT)
    R = int(cell.traffic["check_rounds"])
    seen: dict = {}

    def emit(kind, seed, nums, **extra):
        rec = {"kind": kind, "seed": seed, **{k: nums[k] for k in NUMBERS},
               **extra}
        print(json.dumps(rec), flush=True)
        for k in NUMBERS:
            seen.setdefault(kind, {}).setdefault(k, []).append(nums[k])

    for s in a.seeds:
        t = time.perf_counter()
        out = run_cell(cell, seed=s, seconds=1.0, trace=False, t0=t)
        nums = {k: out["checks"][k]["value"] for k in NUMBERS}
        emit("program", s, nums, seconds=time.perf_counter() - t,
             setup_s=out["metrics"]["setup_s"]["value"])
    variants = {"control_bf16": {"dtype": jnp.bfloat16},
                "mixed_bf16": {"compute_dtype": jnp.bfloat16},
                "half_batch": {"half_batch": True},
                "no_aggregation": {"aggregate": False}}
    if a.variants is not None:
        variants = {k: variants[k] for k in a.variants}
    for s in a.control_seeds:
        t = time.perf_counter()
        ref = reference_readings(cell, s)
        ref_s = time.perf_counter() - t
        for kind, fault in variants.items():
            hist, norms = reference_readings(cell, s, **fault)
            emit(kind, s, compare(hist, norms, *ref, R), reference_s=ref_s)
    summary = {kind: {k: (max(v) if kind == "program" else min(v))
                      for k, v in nums.items()}
               for kind, nums in seen.items()}
    print(json.dumps({"summary": summary, "seconds": time.perf_counter() - T0}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
