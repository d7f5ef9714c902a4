"""Shared benchmark harness: the paper's testbeds as simulator configs.

Testbed A: 8 devices (Raspberry Pi classes, 4 speed groups), CPU server,
50 Mbps links.  Testbed B: 16 devices (Jetson classes), GPU server,
100 Mbps links.  Speed ratios follow Table 3; absolute scales are nominal
(the figures reproduce *relative* orderings, not absolute times).

Every ``BENCH_*.json`` record should be written through
:func:`write_record`, which stamps an ``env`` block (backend, device
kind, jax/numpy versions, interpret-mode flag, smoke flag) so every
record says where it ran instead of relying on out-of-band knowledge."""
from __future__ import annotations

import json
import os
import platform
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from repro.core.control_plane import ControlPlane
from repro.core.simulation import SimModel, SimCluster, heterogeneous_cluster

#: The paper's default global activation cap (Eq. 3) used across benchmarks.
OMEGA = 8

#: Smoke mode (CI): tiny simulated durations / fewer rounds so the full
#: benchmark path runs in seconds.  Set by ``run.py --smoke`` or the
#: BENCH_SMOKE env var; results are for wiring checks, not trajectories.
SMOKE = os.environ.get("BENCH_SMOKE", "") not in ("", "0")


def bench_duration(default: float, smoke: float = 30.0) -> float:
    if SMOKE:
        return smoke
    return float(os.environ.get("BENCH_DUR", default))


def env_meta() -> dict:
    """Execution-environment stamp for benchmark records: which backend
    produced the numbers (cpu ⇒ Pallas kernels ran in interpret mode —
    shape/semantics checks, not device performance), under which jax."""
    import jax
    dev = jax.devices()[0]
    return {"jax_version": jax.__version__,
            "numpy_version": np.__version__,
            "backend": dev.platform,
            "device_kind": dev.device_kind,
            "n_devices": jax.device_count(),
            "pallas_interpret": dev.platform == "cpu",
            "python": platform.python_version(),
            "platform": platform.platform(),
            "smoke": SMOKE}


def write_record(path: str, record: dict, registry=None) -> None:
    """Write one BENCH_*.json record, stamped with :func:`env_meta`
    (callers may pre-set ``env`` to override).  ``registry`` (a
    :class:`repro.obs.metrics.MetricsRegistry`) merges its snapshot under
    a ``"metrics"`` key — the benchmark's own instruments ride the
    record instead of a second ad-hoc accounting block."""
    record.setdefault("env", env_meta())
    if registry is not None:
        record.setdefault("metrics", registry.snapshot())
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {path}")


def fedoptima_control(cluster: SimCluster, omega: int = OMEGA,
                      **kw) -> ControlPlane:
    """The integrated host control plane for a FedOptima simulation run:
    per-device flow units so Σ_k |Q_k^act| ≤ ω is the strict Eq. 3 cap
    (pass ``pool_cap=`` to admit against the tiered ω + pool budget
    instead — the server memory manager's spill tier).  Pass as
    ``simulate_fedoptima(..., control=...)`` and inspect
    ``peak_buffered`` / ``consumption`` / ``memory_summary`` afterwards."""
    return ControlPlane.for_sim(cluster.K, omega, **kw)

# device-side / server-side per-batch costs for a VGG-5-like split (batch 32)
VGG5_SPLIT = SimModel(
    dev_fwd_flops=1.2e9, dev_bwd_flops=2.4e9, full_fwd_flops=7.5e9,
    srv_flops_per_batch=1.9e10, act_bytes=2.1e6, dev_model_bytes=0.5e6,
    full_model_bytes=8e6, batch_size=32)

# MobileNetV3-Large-ish on Tiny ImageNet (batch 32)
MOBILENET_SPLIT = SimModel(
    dev_fwd_flops=2.5e9, dev_bwd_flops=5.0e9, full_fwd_flops=1.4e10,
    srv_flops_per_batch=2.6e10, act_bytes=3.2e6, dev_model_bytes=1.2e6,
    full_model_bytes=2.2e7, batch_size=32)

# Transformer-6 on SST-2 (batch 32, seq 64)
TRANSFORMER6_SPLIT = SimModel(
    dev_fwd_flops=0.8e9, dev_bwd_flops=1.6e9, full_fwd_flops=4.6e9,
    srv_flops_per_batch=1.2e10, act_bytes=0.82e6, dev_model_bytes=0.7e6,
    full_model_bytes=4.5e6, batch_size=32)

# Transformer-12 on IMDB (batch 32, seq 128)
TRANSFORMER12_SPLIT = SimModel(
    dev_fwd_flops=1.6e9, dev_bwd_flops=3.2e9, full_fwd_flops=1.05e10,
    srv_flops_per_batch=2.6e10, act_bytes=1.64e6, dev_model_bytes=0.8e6,
    full_model_bytes=9e6, batch_size=32)


def run_protocol_grid(model: SimModel, cluster: SimCluster, *,
                      duration: float, omega: int = OMEGA,
                      registry=None, trace: bool = False,
                      control_kw: dict | None = None):
    """Run FedOptima + every registered baseline once on (model, cluster).

    The shared per-protocol loop behind ``bench_idle`` and
    ``bench_throughput``: one FedOptima run through the integrated
    :class:`ControlPlane` plus each :data:`repro.core.baselines.REGISTRY`
    entry, each wall-timed through the unified metrics registry
    (``bench.us.<protocol>`` histograms — a re-run of the same grid
    accumulates instead of overwriting).

    ``trace=True`` attaches a fresh sim-domain ``Tracer`` per protocol so
    callers can feed :func:`repro.obs.idle.attribute_idle` (the tracer is
    detached between protocols: each trace covers exactly one run).

    Returns ``(results, registry, cp)``: ``results`` maps protocol name
    -> ``{"metrics", "us", "tracer"}`` (tracer ``None`` when off), and
    ``cp`` is FedOptima's control plane for ω-cap assertions.
    """
    from repro.core.baselines import REGISTRY
    from repro.core.simulation import simulate_fedoptima
    from repro.obs.metrics import MetricsRegistry
    from repro.obs.trace import Tracer, traced

    reg = registry if registry is not None else MetricsRegistry()
    cp = fedoptima_control(cluster, omega, **(control_kw or {}))
    results: dict = {}

    def one(name, fn, *args, **kw):
        tracer = Tracer(domain="sim") if trace else None
        if tracer is not None:
            with traced(tracer):
                m, us = timed(fn, *args, **kw)
        else:
            m, us = timed(fn, *args, **kw)
        # benchmark wall times span µs..minutes; widen the bucket range
        reg.histogram(f"bench.us.{name}", lo=1.0, hi=1e9).observe(us)
        results[name] = {"metrics": m, "us": us, "tracer": tracer}

    one("fedoptima", simulate_fedoptima, model, cluster,
        duration=duration, omega=omega, control=cp)
    for name, fn in REGISTRY.items():
        one(name, fn, model, cluster, duration=duration)
    return results, reg, cp


def testbed_a() -> SimCluster:
    return heterogeneous_cluster(8, base_flops=3e9,
                                 speed_groups=(1.0, 2.0, 2.0, 3.0),
                                 bw=50e6 / 8, srv_ratio=20.0)


def testbed_b() -> SimCluster:
    return heterogeneous_cluster(16, base_flops=8e9,
                                 speed_groups=(1.0, 1.33, 2.67, 3.84),
                                 bw=100e6 / 8, srv_ratio=50.0)


@dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.1f},{self.derived}"


# ---------------------------------------------------------------------------
# Executor-overlap harness: a future-backed device stand-in
# ---------------------------------------------------------------------------

class StubDevice:
    """Async-dispatch stand-in for the jit'd hybrid step.

    ``step(state, batch)`` queues a round of duration ``round_s`` on a
    single worker thread (a serialized device queue, like one mesh) and
    returns immediately; the metrics are futures whose ``float()`` blocks
    until that round completes — exactly the contract ``RoundExecutor``
    drains against.  Use as a context manager (or call ``close``) so the
    worker thread doesn't outlive the measurement.
    """

    class _Lazy:
        def __init__(self, fut):
            self._fut = fut

        def __float__(self):
            return float(self._fut.result())

    def __init__(self, round_s: float):
        self.round_s = round_s
        self._pool = ThreadPoolExecutor(max_workers=1)

    def _run(self):
        time.sleep(self.round_s)
        return 0.0

    def step(self, state, batch):
        fut = self._pool.submit(self._run)
        return state, {"d_loss": self._Lazy(fut), "s_loss": self._Lazy(fut)}

    def close(self):
        self._pool.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def executor_overlap(model: SimModel, cluster: SimCluster, *, H: int = 8,
                     rounds: int = 20, window: int = 2,
                     sim_time_scale: float = 0.004,
                     host_frac: float = 0.4,
                     host_burst_every: int = 0,
                     host_burst_frac: float = 1.0,
                     checkpoint_every: int = 0,
                     checkpoint_flush: bool = False,
                     ckpt_save_s: float | None = None,
                     state_bytes: int = 0) -> dict:
    """Measure RoundExecutor overlap on a modeled workload.

    The stub device round is the testbed's lockstep cost — H × the
    slowest device's per-iteration time from the SimModel/cluster cost
    accounting — compressed by ``sim_time_scale`` (simulated seconds →
    benchmark wall seconds, clamped to [10 ms, 100 ms] so every testbed
    finishes quickly but still dwarfs scheduler noise).  Host batch
    assembly is modeled at ``host_frac`` of the device round (the pod
    driver's Python-side shard packing); every ``host_burst_every``-th
    round costs ``host_burst_frac`` × that (periodic host spikes — re-
    partitioning, logging, GC — the load deep windows exist to amortize:
    a window shallower than the burst cadence exposes each spike).

    ``checkpoint_every`` > 0 models the save path: ``ckpt_save_s``
    (default 1.5 × the device round — np.savez of a real state dwarfs
    one round) is slept per save, after a full pipeline drain when
    ``checkpoint_flush`` else via the deferred no-flush handle path.
    ``state_bytes`` sizes a real numpy state dict so handle-ring/
    checkpoint byte accounting is measured, not modeled.

    Returns wall/round for the given window plus the executor's own
    overlap accounting (incl. steady-state exposure excluding the
    ``window`` warmup rounds, handle-ring peaks, and save counters).
    """
    from repro.core.executor import RoundExecutor

    t_iter = (model.dev_fwd_flops + model.dev_bwd_flops) / \
        np.asarray(cluster.dev_flops, float)
    round_sim_s = H * float(t_iter.max())
    round_s = float(np.clip(round_sim_s * sim_time_scale, 0.01, 0.1))
    host_s = host_frac * round_s
    save_s = 1.5 * round_s if ckpt_save_s is None else float(ckpt_save_s)
    cp = ControlPlane(cluster.K, OMEGA, H)
    state = {"params": np.zeros(max(state_bytes, 4) // 4, np.float32)} \
        if state_bytes else 0

    def batch_fn(r, plan):
        mult = host_burst_frac if host_burst_every and \
            r % host_burst_every == 0 else 1.0
        time.sleep(host_s * mult)   # modeled host batch-assembly cost
        return {}

    def checkpoint_fn(r, handle):
        time.sleep(save_s)          # modeled np.savez + fsync

    ckpt_kw = {}
    if checkpoint_every:
        ckpt_kw = dict(checkpoint_every=checkpoint_every,
                       checkpoint_fn=checkpoint_fn,
                       capture_fn=lambda r: None,
                       checkpoint_flush=checkpoint_flush)

    with StubDevice(round_s) as dev:
        ex = RoundExecutor(dev.step, cp, window=window)
        t0 = time.perf_counter()
        _, hist = ex.run(state, 0, rounds,
                         active_fn=lambda r: np.ones(cluster.K, bool),
                         batch_fn=batch_fn, **ckpt_kw)
        wall = time.perf_counter() - t0
    out = ex.summary()
    out.update(wall_s=wall, wall_s_per_round=wall / max(rounds, 1),
               rounds_per_s=max(rounds, 1) / wall,
               round_sim_s=round_sim_s, stub_round_s=round_s,
               host_s_modeled=host_s, rounds_completed=len(hist),
               plan_us=1e6 * float(np.mean([s.plan_s for s in ex.stats]))
               if ex.stats else 0.0)
    return out


def timed(fn, *args, repeat: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6
