"""Benchmark harness: one module per paper table/figure.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run              # all
    PYTHONPATH=src python -m benchmarks.run idle comm    # subset
    PYTHONPATH=src python -m benchmarks.run --smoke idle throughput
                                         # CI wiring check (tiny configs)

``--sanitize`` runs every suite under the protocol sanitizer
(``repro.analysis.sanitize``): control-plane events are invariant-checked
online and any violation aborts the run.  Default ON under ``--smoke``
(the CI lane), off at full benchmark scale; ``--no-sanitize`` forces it
off.  A ``sanitize/<suite>`` row records events checked per suite.
"""
from __future__ import annotations

import sys

from . import (bench_ablation_aux, bench_ablation_sched, bench_accuracy,
               bench_communication, bench_faults, bench_fleet, bench_idle,
               bench_memory, bench_partition, bench_resilience,
               bench_throughput, common)

SUITES = {
    "communication": bench_communication,   # Fig. 2
    "memory": bench_memory,                 # Fig. 3 / Eq. 2-3
    "accuracy": bench_accuracy,             # Table 2, Fig. 6/7
    "idle": bench_idle,                     # Fig. 8/9
    "throughput": bench_throughput,         # Fig. 10/11
    "resilience": bench_resilience,         # Fig. 12/13
    "ablation_aux": bench_ablation_aux,     # Fig. 14
    "ablation_sched": bench_ablation_sched, # Fig. 15
    "partition": bench_partition,           # Eq. 6-8
    "fleet": bench_fleet,                   # shared-trace scenario compare
    "faults": bench_faults,                 # chaos plane: goodput under faults
}


#: Suites whose durations honor common.SMOKE / bench_duration.
SMOKE_SUITES = ("idle", "throughput", "memory", "fleet", "faults")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    argv = sys.argv[1:]
    smoke = "--smoke" in argv
    if smoke:
        argv.remove("--smoke")
        common.SMOKE = True
    sanitize = smoke                 # default: on in smoke, off at scale
    if "--sanitize" in argv:
        argv.remove("--sanitize")
        sanitize = True
    if "--no-sanitize" in argv:
        argv.remove("--no-sanitize")
        sanitize = False
    # bare --smoke runs only the smoke-aware suites: the others ignore the
    # flag and would silently run at full cost
    which = argv or (list(SMOKE_SUITES) if smoke else list(SUITES))
    ignored = [n for n in which if smoke and n not in SMOKE_SUITES]
    if ignored:
        print(f"# note: --smoke is ignored by suites {ignored} "
              "(full duration)", flush=True)
    print("name,us_per_call,derived")
    for name in which:
        mod = SUITES[name]
        if sanitize:
            from repro.analysis.sanitize import sanitized
            with sanitized() as san:
                rows = mod.main()
            for row in rows:
                print(row.csv(), flush=True)
            rep = san.report()
            print(common.Row(f"sanitize/{name}", 0.0,
                             f"events={rep['events']};"
                             f"violations={rep['n_violations']}").csv(),
                  flush=True)
        else:
            for row in mod.main():
                print(row.csv(), flush=True)


if __name__ == "__main__":
    main()
