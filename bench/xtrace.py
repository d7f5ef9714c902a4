"""Reduction of a profiler trace and host spans to per-layer numbers.

Intervals are ``(start_s, end_s)`` pairs on the host clock
(``time.perf_counter``).  The device planes of a ``jax.profiler`` trace
are moved onto that clock through a marker: a ``TraceAnnotation`` named
``MARKER`` whose ``t`` argument is the host clock read when it opened.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

MARKER = "bench/clock_marker"
#: HLO op names that move data between chips.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv", "collective-broadcast")


#: HLO ops whose events contain other ops' events.
CONTAINERS = ("while", "conditional", "call")


def short_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def stem(op: str) -> str:
    """``fusion.12`` -> ``fusion``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def is_container(op: str) -> bool:
    """``while.3``, ``while.3.clone``, ``call`` -> True."""
    return op.split(".", 1)[0] in CONTAINERS


def is_collective(op: str) -> bool:
    name = op.lower()
    return any(name.startswith(c) for c in COLLECTIVES)


def union(intervals) -> list:
    """Sorted, merged, non-overlapping cover of ``intervals``."""
    out: list = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(intervals, lo: float, hi: float) -> list:
    """The parts of [lo, hi] that ``intervals`` leave uncovered."""
    out, t = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def minus(intervals, cover) -> float:
    """Length of the union of ``intervals`` that ``cover`` leaves bare."""
    cov = union(cover)
    bare = 0.0
    for a, b in union(intervals):
        bare += (b - a) - total(clip(cov, a, b))
    return bare


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


@dataclass
class DeviceTrace:
    """Device operations per chip, on the host clock."""
    ops: dict = field(default_factory=dict)   # chip -> [(name, t0, t1)]

    def leaf_ops(self, chip) -> list:
        """``chip``'s ops without the containers: a ``while`` event spans
        every iteration of its loop, the idle time between them too."""
        return [(n, a, b) for n, a, b in self.ops[chip]
                if not is_container(n)]

    def intervals(self, chip) -> list:
        return [(a, b) for _, a, b in self.leaf_ops(chip)]

    def busy(self, chip, lo, hi) -> float:
        return total(clip(self.intervals(chip), lo, hi))

    def exposed_collective(self, chip, lo, hi) -> float:
        """Seconds of collective ops on ``chip`` during which no other op
        runs on it, within [lo, hi]."""
        ops = [(n, a, b) for n, a, b in self.leaf_ops(chip)
               if overlap(a, b, lo, hi) > 0]
        coll = clip([(a, b) for n, a, b in ops if is_collective(n)], lo, hi)
        comp = [(a, b) for n, a, b in ops if not is_collective(n)]
        return minus(coll, comp)

    def op_seconds(self, lo, hi) -> dict:
        """Device seconds per op stem (``fusion``, ``all-reduce``, ...)
        within [lo, hi], summed over chips and divided by their number;
        ops that contain others (``while``) are left out."""
        out: dict = {}
        for evs in self.ops.values():
            for n, a, b in evs:
                d = overlap(a, b, lo, hi)
                if d > 0 and not is_container(n):
                    out[stem(n)] = out.get(stem(n), 0.0) + d
        k = max(len(self.ops), 1)
        return {n: s / k for n, s in out.items()}


def xplane_path(profile_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return found[-1]


def load_device_trace(path: str, chips=None,
                      op_line: str = "XLA Ops") -> DeviceTrace:
    """Read the device op events of the ``/device:`` planes of an xplane
    file (those whose id is in ``chips``, or all) and move them onto the
    host clock through ``MARKER``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    offset = None
    raw: dict = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        t = dict(ev.stats).get("t")
                        if t is not None:
                            offset = float(t) - ev.start_ns * 1e-9
        elif plane.name.startswith("/device:"):
            chip = plane.name.split(":")[-1]
            if chips is not None and chip not in chips:
                continue
            for line in plane.lines:
                if line.name != op_line:
                    continue
                raw[chip] = [(short_name(ev.name), ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                             for ev in line.events]
    if offset is None:
        raise ValueError(f"{path}: no {MARKER} event with a host time")
    if not raw:
        raise ValueError(f"{path}: no device plane with a {op_line!r} line")
    return DeviceTrace({c: [(n, a + offset, b + offset) for n, a, b in evs]
                        for c, evs in raw.items()})


def label_gaps(gap_list, spans) -> list:
    """Name each idle gap by the host span (lane) that overlaps it most;
    ``spans`` are ``(lane, t0, t1)``.  A gap no span touches is
    ``"no host span"``."""
    out = []
    for a, b in gap_list:
        best, lane = 0.0, "no host span"
        for ln, s0, s1 in spans:
            o = overlap(a, b, s0, s1)
            if o > best:
                best, lane = o, ln
        out.append((lane, b - a))
    return out
