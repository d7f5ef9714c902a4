"""One run of one benchmark cell.

A run drives ``repro.launch.train.run_pod`` (the ``--mode pod`` path)
unchanged, once, from the cell's configuration and traffic files and the
seed.  Its handle is ``args.profiles``: a :class:`Recorder` that
delegates every call to the program's own ``StragglerProfiles(G)``, so
the plans are those of the unseeded default, and that

* at the heads of rounds 0, 1 and R (R = the traffic's ``check_rounds``)
  copies the train state to the host for the check, reading it from the
  executor's frame;
* timestamps every drained round: set-up ends when round R is drained
  (the seconds spent copying state for the check left out),
  the window runs from there to the first drain at or past
  ``--seconds``, and that drain ends the run by raising
  :class:`WindowClosed` out of ``run_pod``.

The losses of the first rounds are read from the executor's ``history``
in the unwound frames, which are then cleared.  After the window the
peak device memory is read, the program's state and executables are
freed, and the plain reference (``bench/reference``) recomputes the
first rounds for ``correct``.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class WindowClosed(Exception):
    """Ends ``run_pod`` at the first round drained after the window."""


class BenchError(RuntimeError):
    """The run cannot be made as the cell describes it."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list = field(default_factory=list)   # BENCHMARK.json entries

    @property
    def groups(self) -> int:
        return self.traffic["mesh"][0] * self.traffic["groups_per_shard"]

    @property
    def tokens_per_round(self) -> int:
        t = self.traffic
        return self.groups * t["per_group_batch"] * t["seq_len"]


def _read_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return Cell(name=name, chips=int(w["chips"]),
                config=_read_json(root / conf["file"]),
                traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=_read_json(BENCH / "limits" / f"{name}.json"),
                per_layer=per_layer)


# ---------------------------------------------------------------------------
# The seam inside run_pod
# ---------------------------------------------------------------------------

def _live_state(frame, depth: int = 6):
    """The train state as the executor holds it at a round head: the
    nearest caller's local ``state`` whose arrays are live."""
    import jax
    from bench.reference.check import STATE_KEYS
    for _ in range(depth):
        frame = frame.f_back
        if frame is None:
            break
        st = frame.f_locals.get("state")
        if isinstance(st, dict) and all(k in st for k in STATE_KEYS):
            leaves = jax.tree.leaves({k: st[k] for k in STATE_KEYS})
            if not any(x.is_deleted() for x in leaves):
                return st
    raise BenchError("no live train state in the executor's frames at a "
                     "round head")


def _unwind(tb, name: str):
    """The innermost local ``name`` of the frames that ``tb`` unwound
    (the handler's own frame left out); then every local of those frames,
    the program's state among them, is dropped, with the snapshots that
    reading ``f_locals`` left on them."""
    frames = []
    tb = tb.tb_next
    while tb is not None:
        frames.append(tb.tb_frame)
        tb = tb.tb_next
    found = None
    for f in frames:
        found = f.f_locals.get(name, found)
    for f in frames:
        f.clear()
        f.f_locals.clear()
    if found is None:
        raise BenchError(f"no {name!r} in the frames that WindowClosed left")
    return found


class Recorder:
    """``args.profiles`` for run_pod: the default profiles, plus the
    clock, the state copies and the end of the run."""

    def __init__(self, inner, *, seconds: float, w0: int, track=None,
                 max_rounds=None, on_window_start=None, on_before_start=None):
        self.inner = inner
        self.seconds = float(seconds)
        self.w0 = w0
        self.track = track
        self.max_rounds = max_rounds
        self.snap_s = 0.0
        self.heads = 0
        self.t_head0 = None
        self.drains: list = []
        self.t_start = None
        self.t_end = None
        self.n_window = 0
        self._on_start = on_window_start
        self._on_before = on_before_start

    def produce(self, H):
        r = self.heads
        self.heads += 1
        if r == 0:
            self.t_head0 = time.perf_counter()
        if self.track is not None and r in self.track.rounds:
            t = time.perf_counter()
            self.track.take(r, _live_state(sys._getframe()))
            self.snap_s += time.perf_counter() - t
        return self.inner.produce(H)

    def reads(self, H):
        return self.inner.reads(H)

    def observe_round(self, wall_s, H):
        t = time.perf_counter()
        k = len(self.drains)
        self.drains.append(t)
        self.inner.observe_round(wall_s, H)
        if k == self.w0 - 1 and self._on_before is not None:
            self._on_before()
        if k == self.w0:
            self.t_start = t
            if self._on_start is not None:
                self._on_start()
        elif k > self.w0 and (t - self.t_start >= self.seconds or
                              k - self.w0 == self.max_rounds):
            self.t_end = t
            self.n_window = k - self.w0
            raise WindowClosed(k)

    def summary(self):
        return self.inner.summary()

    def __getattr__(self, name):
        return getattr(self.inner, name)


class CompileCounter:
    """Counts the executables JAX compiles or loads from its cache."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.events: list = []          # (name, seconds)
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def n(self) -> int:
        return len(self.events)

    def _on(self, event, duration, fun_name=None, **_):
        if event == self._event:
            self.events.append((fun_name, duration))

    def close(self):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------

def pod_args(cell: Cell, seed: int):
    """run_pod's arguments, from launch.train's own parser."""
    from repro.launch import train
    c, t = cell.config, cell.traffic
    argv = ["--mode", "pod", "--arch", c["registry_id"],
            "--seq-len", str(t["seq_len"]), "--batch", str(t["per_group_batch"]),
            "--H", str(t["H"]), "--omega", str(t["omega"]),
            "--window", str(t["window"]), "--p-drop", str(t["p_drop"]),
            "--groups-per-shard", str(t["groups_per_shard"]),
            "--mesh-data", str(t["mesh"][0]), "--mesh-model", str(t["mesh"][1]),
            "--lr-d", str(t["lr_d"]), "--lr-s", str(t["lr_s"]),
            "--server-opt", t["server_opt"], "--l-split", str(c["device_layers"]),
            "--rounds", str(10 ** 9), "--log-every", str(10 ** 9),
            "--seed", str(seed)]
    if c.get("full", True):
        argv.append("--full")
    if c["use_kernel"]:
        argv.append("--use-kernel")
    return train.build_parser().parse_args(argv)


def device_facts(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(devices) -> int:
    """The fullest chip's peak: buffers the runtime allocated plus the
    region it reserves for the programs' scratch (on a TPU the round's
    temporaries live there, outside ``peak_bytes_in_use``)."""
    peaks = [sum((d.memory_stats() or {}).get(k, 0)
                 for k in ("peak_bytes_in_use", "peak_bytes_reserved"))
             for d in devices]
    return int(max(peaks))


def _load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t0: float, require_tpu: bool = True) -> dict:
    """One run; returns the result line's object.  ``t0`` is the host
    clock when the process started: set-up is counted from it."""
    import jax
    from repro.launch import train
    from repro.launch.compile_cache import enable_compile_cache
    from repro.core.executor import StragglerProfiles

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX's first device is "
                             f"{devices[0].platform}")
        if len(devices) < cell.chips:
            raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX "
                             f"finds {len(devices)}")
    used = devices[:cell.traffic["mesh"][0] * cell.traffic["mesh"][1]]
    enable_compile_cache()
    from bench.reference.check import ProgramTrack, check
    R = int(cell.traffic["check_rounds"])
    w0 = R                      # the snapshot at round R's head drains the
                                # pipe; round R's completion is steady
    counter = CompileCounter()
    marks: dict = {}
    prof_dir = tempfile.mkdtemp(prefix="bench_profile_") if trace else None
    tracer = None

    def before_start():
        if trace:
            jax.profiler.start_trace(prof_dir)
            from bench.xtrace import MARKER
            with jax.profiler.TraceAnnotation(MARKER, t=time.perf_counter()):
                pass

    def window_start():
        marks["compiles"] = counter.n

    args = pod_args(cell, seed)
    track = ProgramTrack(R)
    rec = Recorder(StragglerProfiles(cell.groups), seconds=seconds, w0=w0,
                   track=track,
                   max_rounds=cell.traffic["trace_rounds"] if trace else None,
                   on_window_start=window_start, on_before_start=before_start)
    args.profiles = rec
    history = None
    try:
        if trace:
            from repro.obs.trace import Tracer, traced
            tracer = Tracer(domain="wall")
            with traced(tracer):
                train.run_pod(args)
        else:
            train.run_pod(args)
        raise BenchError("run_pod returned before the window closed")
    except WindowClosed as closed:
        t_closed = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        history = list(_unwind(closed.__traceback__, "history"))
        del closed
    window_compiles = counter.n - marks.get("compiles", counter.n)
    counter.close()
    mem_peak = peak_bytes(used)
    _log(f"memory_stats after the window: "
         + "; ".join(str(d.memory_stats()) for d in used))
    gc.collect()
    n = rec.n_window
    window_s = rec.t_end - rec.t_start
    # the check's host copies of the state are its own cost, not set-up
    setup_s = rec.t_start - t0 - track.seconds["copy"]
    tokens_per_s = cell.tokens_per_round * n / window_s
    _log(f"set-up: {rec.t_head0 - t0:.3f} s to round 0's head; compiles "
         + ", ".join(f"{name} {sec:.3f} s" for name, sec in counter.events)
         + f"; rounds 0-{w0} drained at "
         + ", ".join(f"{t - t0:.3f}" for t in rec.drains[:w0 + 1])
         + f" s; state copies for the check {rec.snap_s:.3f} s ("
         + ", ".join(f"{k} {v:.3f} s" for k, v in track.seconds.items()) + ")")
    _log(f"window: {n} rounds in {window_s:.4f} s after {setup_s:.4f} s "
         f"of set-up (the check's {track.seconds['copy']:.3f} s of copies "
         f"left out); compilations inside the window: {window_compiles}")
    window_rounds = history[w0 + 1:w0 + 1 + n]
    failed = sum(1 for m in window_rounds
                 if not all(math.isfinite(v) for v in m.values()))

    result: dict = {"attempted": n, "failed": failed}
    device = device_facts(devices)
    device["memory_peak_bytes"] = mem_peak
    if trace:
        from bench import xtrace
        path = xtrace.xplane_path(prof_dir)
        dtrace = xtrace.load_device_trace(path,
                                          chips={str(d.id) for d in used})
        spans = [(lane, a, b, args_ or {}) for lane, _, a, b, args_
                 in tracer.spans]
        ctx = TraceContext(cell=cell, dtrace=dtrace, spans=spans,
                           lo=rec.t_start, hi=rec.t_end, rounds=n,
                           round_ids=range(w0 + 1, w0 + 1 + n),
                           tokens_per_s=tokens_per_s,
                           kind=devices[0].device_kind)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = window_s
        metrics = {}
        for m in cell.per_layer:
            v = _load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = ctx.breakdown()
        shutil.rmtree(prof_dir, ignore_errors=True)
    else:
        metrics = {"tokens_per_s": {"value": tokens_per_s, "unit": "tokens/s"},
                   "peak_hbm_gb": {"value": mem_peak / 1e9, "unit": "GB"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    del rec, args
    gc.collect()
    jax.clear_caches()        # the round's executable and its scratch
    _log(f"memory_stats before the reference: "
         + "; ".join(str(d.memory_stats()) for d in used))
    t_ref = time.perf_counter()
    checks = dict(check(cell, seed, track, history[:R]))
    checks["window_compiles"] = (float(window_compiles), 0.0)
    checks["failed_rounds"] = (float(failed), 0.0)
    _log(f"check: {time.perf_counter() - t_ref:.1f} s after the window "
         f"({t_ref - t_closed:.1f} s to free and read the trace)")
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    for k, (v, lim) in checks.items():
        _log(f"check {k}: {v!r} (limit {lim!r})")
    result.update(correct=correct, metrics=metrics, device=device)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


@dataclass
class TraceContext:
    """What a per-layer reader gets: the device trace and host spans of
    the traced window [lo, hi] (host clock), which holds ``rounds``
    completed rounds; ``busy_s`` is the union of the device's op
    intervals in it (containers such as ``while`` left out), averaged
    over the chips used."""
    cell: Cell
    dtrace: object
    spans: list          # (lane, t0, t1, args)
    lo: float
    hi: float
    rounds: int
    round_ids: range
    tokens_per_s: float
    kind: str
    busy_s: float = 0.0

    def __post_init__(self):
        chips = self.dtrace.ops
        self.busy_s = sum(self.dtrace.busy(c, self.lo, self.hi)
                          for c in chips) / max(len(chips), 1)

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def peak(self, key: str) -> float:
        table = _read_json(BENCH / "peaks.json")["devices"]
        if self.kind not in table:
            raise BenchError(f"no peaks for device kind {self.kind!r} in "
                             "bench/peaks.json")
        return float(table[self.kind][key])

    def breakdown(self) -> dict:
        from bench import xtrace
        ops = self.dtrace.op_seconds(self.lo, self.hi)
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        chip = sorted(self.dtrace.ops)[0]
        gaps = xtrace.gaps(self.dtrace.intervals(chip), self.lo, self.hi)
        host = [(lane, a, b) for lane, a, b, _ in self.spans
                if lane.startswith("host/")]
        labelled = sorted(xtrace.label_gaps(gaps, host), key=lambda g: -g[1])
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in labelled[:10]]}
