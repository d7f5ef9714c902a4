"""Smoke-size cells for the CPU tests: the registry's smoke reductions
of both configurations under a small traffic mix."""
import json
import time
from pathlib import Path

from bench.harness import Cell, run_cell

DATA = Path(__file__).resolve().parent / "data"
ARCHS = ("smollm-135m", "mamba2-780m")
#: Loose enough for any sound run on the CPU (which reads ~1e-6), tight
#: enough for every planted fault (which reads ~1e-2 and more).
LIMITS = {"loss_gap": 1e-4, "update_gap": 1e-3, "change_gap": 1e-3}


def cell(arch: str, **traffic) -> Cell:
    config = json.loads((DATA / f"{arch}.smoke.json").read_text())
    t = json.loads((DATA / "smoke.traffic.json").read_text())
    t.update(traffic)
    return Cell(name=f"{arch}.smoke", chips=1, config=config, traffic=t,
                limits=dict(LIMITS))


def run(c: Cell, seed: int = 5, seconds: float = 0.5) -> dict:
    return run_cell(c, seed=seed, seconds=seconds, trace=False,
                    t0=time.perf_counter(), require_tpu=False)
