"""The comparison that decides ``correct``.

The run's first ``R`` rounds (R = the traffic's ``check_rounds``) are
compared with the plain reference on three numbers:

* ``loss_gap``: the largest relative gap of a round's ``d_loss`` or
  ``s_loss`` over rounds 0..R-1;
* ``update_gap``: the update of the first round, leaf by leaf: the gap
  between the program's norm of (weights after round 0 - initial
  weights) and the reference's, over the larger of the reference's norm
  of that leaf and of the median leaf (the worst leaf);
* ``change_gap``: the same for the change after R rounds.

Leaves whose reference update is under ``LEAF_FLOOR`` of the median
leaf's are left out of both: they move by round-off alone.
"""
from __future__ import annotations

import math
import statistics
import sys
import time

from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from .fedround import Reference, named_leaves

STATE_KEYS = ("dev", "aux", "srv")
LEAF_FLOOR = 1e-3
CHUNK = 1 << 22
THREADS = 8


def _sq(a, b) -> float:
    d = a.astype(np.float32) - b.astype(np.float32)
    return float(np.dot(d, d))


def change_norm(new, old, pool=None) -> float:
    """sqrt(sum((new - old)^2)) of two host arrays, in float32 chunks
    summed in float64 (the chunks on ``pool``'s threads when given)."""
    a, b = np.asarray(new).reshape(-1), np.asarray(old).reshape(-1)
    parts = [(a[i:i + CHUNK], b[i:i + CHUNK]) for i in range(0, a.size, CHUNK)]
    sums = pool.map(lambda ab: _sq(*ab), parts) if pool is not None else \
        (_sq(x, y) for x, y in parts)
    return math.sqrt(sum(sums))


def change_norms(new: dict, old: dict) -> dict:
    """{leaf: change_norm} for dicts of host arrays or lists of them."""
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        def one(k):
            xs, x0s = new[k], old[k]
            if not isinstance(xs, list):
                xs, x0s = [xs], [x0s]
            return math.sqrt(sum(change_norm(x, x0, pool) ** 2
                                 for x, x0 in zip(xs, x0s)))
        return {k: one(k) for k in new}


class ProgramTrack:
    """Host copies of the program's state at the heads of rounds 0, 1 and
    R; ``norms()`` reduces them to per-leaf change norms once the window
    has closed."""

    def __init__(self, R: int):
        self.R = R
        self.rounds = (0, 1, R)
        self._host: dict = {}
        self.seconds = {"wait": 0.0, "copy": 0.0}

    def take(self, r: int, state: dict) -> None:
        t0 = time.perf_counter()
        leaves = jax.block_until_ready(
            named_leaves({k: state[k] for k in STATE_KEYS}))
        t1 = time.perf_counter()
        self._host[r] = jax.device_get(leaves)
        self.seconds["wait"] += t1 - t0
        self.seconds["copy"] += time.perf_counter() - t1

    def norms(self) -> dict:
        h = self._host
        out = {r: change_norms(h[r], h[0]) for r in (1, self.R)}
        self._host = {}
        return out


class ReferenceTrack:
    """The same norms for the reference's state (lists of arrays)."""

    def __init__(self, ref: Reference, R: int):
        self.ref, self.R = ref, R
        self._theta0 = None
        self.norms: dict = {}

    def __call__(self, r, groups, server):
        if r not in (0, 1, self.R):
            return
        leaves = jax.device_get(self.ref.leaves(groups, server))
        if r == 0:
            self._theta0 = leaves
            return
        self.norms[r] = change_norms(leaves, self._theta0)
        if r == self.R:
            self._theta0 = None


def compare(prog_hist, prog_norms, ref_hist, ref_norms, R: int,
            log=None) -> dict:
    """The three numbers; ``log`` gets the worst round and leaves."""
    gaps = [(abs(p[k] - q[k]) / abs(q[k]), r, k)
            for r, (p, q) in enumerate(zip(prog_hist[:R], ref_hist[:R]))
            for k in ("d_loss", "s_loss") if q[k] != 0.0]
    loss_gap = max(gaps)
    missing = set(ref_norms[1]) ^ set(prog_norms[1])
    if missing:
        raise ValueError(f"program and reference leaves differ: "
                         f"{sorted(missing)[:8]}")
    base = ref_norms[1]
    med = statistics.median(base.values())
    keep = [k for k, v in base.items() if v >= LEAF_FLOOR * med]
    out = {"loss_gap": loss_gap[0]}
    for name, r in (("update_gap", 1), ("change_gap", R)):
        ref, prog = ref_norms[r], prog_norms[r]
        med_r = statistics.median(ref[k] for k in keep)
        worst = max((abs(prog[k] - ref[k]) / max(ref[k], med_r), k)
                    for k in keep)
        out[name] = worst[0]
        if log is not None:
            log(f"{name}: worst leaf {worst[1]} program {prog[worst[1]]!r} "
                f"reference {ref[worst[1]]!r}")
    if log is not None:
        log(f"loss_gap: worst at round {loss_gap[1]} {loss_gap[2]}; "
            f"{len(base) - len(keep)} of {len(base)} leaves under "
            f"{LEAF_FLOOR} of the median update left out")
        for r, (p, q) in enumerate(zip(prog_hist[:R], ref_hist[:R])):
            log(f"round {r}: program d_loss {p['d_loss']!r} s_loss "
                f"{p['s_loss']!r}; reference d_loss {q['d_loss']!r} s_loss "
                f"{q['s_loss']!r}")
    return out


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def reference_readings(cell, seed: int, **fault) -> tuple:
    """(history, norms) of the plain reference (or a planted fault)."""
    R = int(cell.traffic["check_rounds"])
    ref = Reference(cell.config, cell.traffic, **fault)
    track = ReferenceTrack(ref, R)
    hist = ref.run(seed, R, on_round=track)
    return hist, track.norms


def check(cell, seed: int, track: ProgramTrack, history) -> dict:
    """{name: (value, limit)} for the run's first rounds."""
    R = int(cell.traffic["check_rounds"])
    prog_norms = track.norms()
    ref_hist, ref_norms = reference_readings(cell, seed)
    nums = compare(history, prog_norms, ref_hist, ref_norms, R, log=_log)
    return {k: (v, float(cell.limits[k])) for k, v in nums.items()}
