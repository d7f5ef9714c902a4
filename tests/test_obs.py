"""Telemetry plane: tracing is bit-identical when detached AND when
attached (the tracer only records), exported Chrome traces are schema-
valid with non-overlapping per-lane spans, the idle attributor
decomposes a hand-built two-device timeline exactly, and the metrics
registry's instruments behave (percentiles, collisions, peaks)."""
import json

import numpy as np
import pytest

from repro.core.baselines import REGISTRY
from repro.core.simulation import (SimModel, heterogeneous_cluster,
                                   simulate_fedoptima)
from repro.fleet import diurnal_trace
from repro.obs import trace as trace_mod
from repro.obs.idle import attribute_idle
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import Tracer, emit_span, traced, validate_chrome_trace

MODEL = SimModel(dev_fwd_flops=1e9, dev_bwd_flops=2e9, full_fwd_flops=5e9,
                 srv_flops_per_batch=8e9, act_bytes=1e6, dev_model_bytes=4e6,
                 full_model_bytes=2e7, batch_size=32)


def _metric_tuple(m):
    return (tuple(np.asarray(m.dev_busy).tolist()), m.srv_busy,
            m.bytes_up, m.bytes_down, m.dev_samples, m.srv_batches,
            m.aggregations, m.max_buffered)


def _churn_trace(K, dur, seed=7):
    return diurnal_trace(K, horizon=dur, interval=dur / 24.0, day=dur / 2.0,
                         on_frac=0.6, bw=12.5e6, bw_jitter=0.3, seed=seed)


# ---------------------------------------------------------------------------
# bit-identity: the tracer only records
# ---------------------------------------------------------------------------

class TestBitIdentity:
    def test_detached_flag_off(self):
        assert trace_mod.TRACING is False
        assert trace_mod._STACK == []

    def test_fedoptima_traced_equals_plain(self):
        cluster = heterogeneous_cluster(6)
        fleet = _churn_trace(6, 120.0)
        kw = dict(duration=120.0, omega=4, fleet=fleet, seed=3)
        plain = simulate_fedoptima(MODEL, cluster, **kw)
        with traced(Tracer(domain="sim")) as tr:
            traced_m = simulate_fedoptima(MODEL, cluster, **kw)
        assert _metric_tuple(plain) == _metric_tuple(traced_m)
        assert len(tr.spans) > 0
        assert trace_mod.TRACING is False   # detached on exit

    @pytest.mark.parametrize("name", sorted(REGISTRY))
    def test_baselines_traced_equal_plain(self, name):
        cluster = heterogeneous_cluster(4)
        fn = REGISTRY[name]
        plain = fn(MODEL, cluster, duration=90.0)
        with traced(Tracer(domain="sim")):
            tm = fn(MODEL, cluster, duration=90.0)
        assert _metric_tuple(plain) == _metric_tuple(tm)


# ---------------------------------------------------------------------------
# Chrome export: schema validity + per-lane non-overlap
# ---------------------------------------------------------------------------

class TestChromeExport:
    def _trace_sim(self):
        cluster = heterogeneous_cluster(6)
        with traced(Tracer(domain="sim")) as tr:
            simulate_fedoptima(MODEL, cluster, duration=90.0, omega=4,
                               fleet=_churn_trace(6, 90.0), seed=5)
        return tr

    def test_valid_schema_and_lanes(self, tmp_path):
        tr = self._trace_sim()
        doc = tr.to_chrome()
        assert validate_chrome_trace(doc) == []
        lanes = tr.lanes()
        assert "srv" in lanes
        assert any(ln.startswith("dev/") for ln in lanes)
        assert any(ln.startswith("net/") for ln in lanes)
        # export round-trips through JSON
        path = tmp_path / "t.json"
        tr.export_chrome(str(path))
        with open(path) as f:
            assert validate_chrome_trace(json.load(f)) == []

    def test_pid_mapping(self):
        tr = self._trace_sim()
        doc = tr.to_chrome()
        by_tidname = {(e["pid"], e["args"]["name"])
                      for e in doc["traceEvents"]
                      if e["ph"] == "M" and e["name"] == "thread_name"}
        assert any(p == 1 and n == "srv" for p, n in by_tidname)
        assert any(p == 2 and n.startswith("device ")
                   for p, n in by_tidname)
        assert any(p == 3 and n.startswith("uplink ")
                   for p, n in by_tidname)

    def test_validator_flags_overlap(self):
        doc = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0,
             "pid": 1, "tid": 0},
            {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0,
             "pid": 1, "tid": 0}]}
        problems = validate_chrome_trace(doc)
        assert len(problems) == 1 and "overlap" in problems[0]

    def test_clip_spans_never_overlap(self):
        tr = Tracer(domain="sim")
        tr.add_span("srv", "a", 0.0, 10.0, clip=True)
        tr.add_span("srv", "b", 5.0, 15.0, clip=True)   # clips to [10, 15]
        tr.add_span("srv", "c", 6.0, 9.0, clip=True)    # fully shadowed
        assert [(s[2], s[3]) for s in tr.spans] == [(0.0, 10.0),
                                                    (10.0, 15.0)]
        assert validate_chrome_trace(tr.to_chrome()) == []


# ---------------------------------------------------------------------------
# idle attribution: synthetic two-device timeline, exact seconds
# ---------------------------------------------------------------------------

class TestIdleAttribution:
    def test_two_device_exact(self):
        tr = Tracer(domain="sim")
        tr.add_span("dev/0", "train", 0.0, 1.0)
        tr.add_span("dev/0", "train", 3.0, 4.0)
        tr.add_span("dev/1", "train", 0.0, 2.0)
        tr.add_span("srv", "aggregate", 2.0, 3.0)
        attr = attribute_idle(tr, duration=4.0)
        srv = attr["server"]
        # server: warmup [0,2) before its first busy; [3,4) a started+
        # online device (dev/1) idles while dev/0 runs -> straggler
        assert srv["busy_s"] == pytest.approx(1.0)
        assert srv["warmup_s"] == pytest.approx(2.0)
        assert srv["straggler_s"] == pytest.approx(1.0)
        assert srv["task_dependency_s"] == pytest.approx(0.0)
        dev = attr["devices"]
        # devices: [2,3) both wait on the server (task dependency, 2
        # device-seconds); [1,2) dev/0 waits on dev/1 and [3,4) dev/1
        # waits on dev/0 (straggler, 2 device-seconds)
        assert dev["busy_s"] == pytest.approx(4.0)
        assert dev["task_dependency_s"] == pytest.approx(2.0)
        assert dev["straggler_s"] == pytest.approx(2.0)
        assert dev["warmup_s"] == pytest.approx(0.0)
        # fractions normalize by total device-time (2 devices x 4 s)
        assert dev["task_dependency_frac"] == pytest.approx(0.25)

    def test_offline_device_counts_offline_not_idle(self):
        tr = Tracer(domain="sim")
        tr.add_span("dev/0", "train", 0.0, 2.0)
        tr.add_span("srv", "aggregate", 2.0, 4.0)
        tr.add_instant("dev/1", "leave", 0.0)
        tr.add_instant("dev/1", "join", 2.0)
        tr.add_span("dev/1", "train", 2.0, 4.0)
        attr = attribute_idle(tr, duration=4.0)
        assert attr["per_device"]["1"]["offline_s"] == pytest.approx(2.0)
        assert attr["devices"]["offline_s"] == pytest.approx(2.0)

    def test_rejects_bad_duration(self):
        with pytest.raises(ValueError):
            attribute_idle(Tracer(domain="sim"), duration=0.0)

    def test_sim_run_attribution_sums_to_one(self):
        cluster = heterogeneous_cluster(6)
        with traced(Tracer(domain="sim")) as tr:
            simulate_fedoptima(MODEL, cluster, duration=90.0, omega=4,
                               seed=5)
        attr = attribute_idle(tr, duration=90.0)
        srv = attr["server"]
        total = (srv["busy_s"] + srv["warmup_s"] +
                 srv["task_dependency_s"] + srv["straggler_s"])
        assert total == pytest.approx(90.0, rel=1e-6)
        assert 0.0 <= srv["idle_frac"] <= 1.0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_monotone(self):
        c = Counter()
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_peak(self):
        g = Gauge()
        g.set(5)
        g.add(-3)
        assert g.value == 2 and g.peak == 5

    def test_histogram_percentiles(self):
        h = Histogram()
        for v in (1.0, 2.0, 3.0, 4.0, 5.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["min"] == 1.0 and snap["max"] == 5.0
        # bucket-quantized percentiles stay within the observed range
        assert 1.0 <= snap["p50"] <= 5.0
        assert snap["p50"] <= snap["p95"] <= snap["p99"] <= 5.0

    def test_histogram_empty(self):
        assert Histogram().snapshot() == {"count": 0}

    def test_registry_get_or_create_and_collision(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")
        with pytest.raises(ValueError):
            reg.histogram("x")

    def test_dump_line_and_jsonl(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a.n").inc(3)
        reg.gauge("a.level").set(7)
        line = reg.dump_line(prefix="[t]")
        assert line.startswith("[t]") and "a.n=3" in line
        path = tmp_path / "m.jsonl"
        reg.write_jsonl(str(path), extra={"tag": "x"})
        rec = json.loads(path.read_text().splitlines()[0])
        assert rec["tag"] == "x"
        assert rec["metrics"]["counters"]["a.n"] == 3

    def test_sim_metrics_to_registry_and_steady(self):
        cluster = heterogeneous_cluster(6)
        m = simulate_fedoptima(MODEL, cluster, duration=90.0, omega=4,
                               seed=5)
        reg = m.to_registry()
        snap = reg.snapshot()
        assert snap["counters"]["sim.aggregations"] == m.aggregations
        steady = m.steady_summary()
        assert steady and steady["warmup_s"] >= 0.0
        assert steady["steady_s"] == pytest.approx(
            90.0 - steady["warmup_s"])


# ---------------------------------------------------------------------------
# executor instrumentation (pod wall-domain lanes)
# ---------------------------------------------------------------------------

class _AsyncStub:
    """Future-backed device stand-in: dispatch returns immediately, the
    metrics block on a worker thread — the async contract RoundExecutor
    drains against (mirrors benchmarks.common.StubDevice)."""

    class _Lazy:
        def __init__(self, fut):
            self._fut = fut

        def __float__(self):
            return float(self._fut.result())

    def __init__(self, round_s):
        from concurrent.futures import ThreadPoolExecutor
        import time
        self._sleep = lambda: time.sleep(round_s) or 0.0
        self._pool = ThreadPoolExecutor(max_workers=1)

    def step(self, state, batch):
        fut = self._pool.submit(self._sleep)
        return state, {"d_loss": self._Lazy(fut), "s_loss": self._Lazy(fut)}

    def close(self):
        self._pool.shutdown(wait=True)


class TestExecutorTrace:
    def _run(self, window, tracer=None):
        from contextlib import ExitStack

        from repro.core.control_plane import ControlPlane
        from repro.core.executor import RoundExecutor
        from repro.obs.clock import now

        G = 4
        cp = ControlPlane(G, 2, 4)
        dev = _AsyncStub(0.01)
        try:
            ex = RoundExecutor(dev.step, cp, window=window)
            with ExitStack() as stack:
                if tracer is not None:
                    stack.enter_context(traced(tracer))
                t0 = now()
                ex.run(0, 0, 6,
                       active_fn=lambda r: np.ones(G, bool),
                       batch_fn=lambda r, plan: {})
                self.run_span = (t0, now())
        finally:
            dev.close()
        return ex

    def test_window4_trace_has_host_lanes_only(self):
        """The pod trace's lanes are the host loop's: the mesh's time is
        read from a device trace, so no ``mesh`` or ``dev/`` lane is
        emitted, and the host lanes validate."""
        tr = Tracer(domain="wall")
        ex = self._run(4, tracer=tr)
        lanes = tr.lanes()
        assert {"host/plan", "host/build", "host/dispatch", "host/drain",
                "host/record"} <= set(lanes)
        assert "mesh" not in lanes
        assert not any(ln.startswith("dev/") for ln in lanes)
        assert validate_chrome_trace(tr.to_chrome()) == []
        assert ex.peak_in_flight == 4

    def test_one_dispatch_span_per_round(self):
        tr = Tracer(domain="wall")
        self._run(4, tracer=tr)
        rounds = [a["round"] for ln, _, _, _, a in tr.spans
                  if ln == "host/dispatch"]
        assert rounds == list(range(6))

    def test_host_spans_cover_the_round_loop(self):
        """From round 1's plan to the end of ``run``, the host lanes
        leave under 5% of the wall time without a span."""
        tr = Tracer(domain="wall")
        self._run(4, tracer=tr)
        lo = min(t0 for ln, _, t0, _, a in tr.spans
                 if ln == "host/plan" and a["round"] == 1)
        hi = self.run_span[1]
        covered, end = 0.0, lo
        for t0, t1 in sorted((max(t0, lo), min(t1, hi))
                             for ln, _, t0, t1, _ in tr.spans
                             if ln.startswith("host/")):
            if t1 > end:
                covered += t1 - max(t0, end)
                end = t1
        assert covered >= 0.95 * (hi - lo), (covered, hi - lo)

    def test_drain_span_survives_a_raising_hook(self):
        """A drain hook that ends the run by raising (as a benchmark's
        window does) still leaves that round's fetch under a span."""
        from repro.core.control_plane import ControlPlane
        from repro.core.executor import RoundExecutor

        class Stop(Exception):
            pass

        def stop_at_2(r, m, st):
            if r == 2:
                raise Stop

        dev = _AsyncStub(0.01)
        try:
            ex = RoundExecutor(dev.step, ControlPlane(4, 2, 4), window=2)
            with traced(Tracer(domain="wall")) as tr, pytest.raises(Stop):
                ex.run(0, 0, 6, active_fn=lambda r: np.ones(4, bool),
                       batch_fn=lambda r, plan: {}, on_metrics=stop_at_2)
        finally:
            dev.close()
        drained = [a["round"] for ln, _, _, _, a in tr.spans
                   if ln == "host/drain"]
        recorded = [a["round"] for ln, _, _, _, a in tr.spans
                    if ln == "host/record"]
        assert drained == [0, 1, 2] and recorded == [0, 1]

    def test_summary_registry_backed(self):
        ex = self._run(2)
        assert ex.metrics.counter("exec.host_s").value == ex.total_host_s
        assert ex.metrics.gauge("exec.in_flight").peak == ex.peak_in_flight
        s = ex.summary()
        assert s["peak_in_flight"] == ex.peak_in_flight


# ---------------------------------------------------------------------------
# lint RP002 extension (obs clock in hot paths)
# ---------------------------------------------------------------------------

class TestLintObsClock:
    def _lint(self, tmp_path, source, name="core/hot.py"):
        from repro.analysis.lint import lint_file
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(source)
        return lint_file(p)

    def test_perf_counter_flagged_in_hot_path(self, tmp_path):
        errs = self._lint(tmp_path,
                          "import time\nt = time.perf_counter()\n")
        assert any(e.rule == "RP002" and "obs clock" in e.message
                   for e in errs)

    def test_monotonic_flagged(self, tmp_path):
        errs = self._lint(tmp_path, "import time\nt = time.monotonic()\n")
        assert any(e.rule == "RP002" for e in errs)

    def test_waiver_by_rule_id(self, tmp_path):
        errs = self._lint(
            tmp_path,
            "import time\n"
            "t = time.perf_counter()  # lint: allow-rp002\n")
        assert not any(e.rule == "RP002" for e in errs)

    def test_waiver_by_rule_name(self, tmp_path):
        errs = self._lint(
            tmp_path,
            "import time\n"
            "t = time.perf_counter()  # lint: allow-wallclock\n")
        assert not any(e.rule == "RP002" for e in errs)

    def test_obs_clock_itself_clean(self, tmp_path):
        # the sanctioned read is not in a hot segment and stays unflagged
        errs = self._lint(tmp_path,
                          "import time\nnow = time.perf_counter\n",
                          name="obs/clock.py")
        assert not errs

    def test_repo_is_lint_clean(self):
        from repro.analysis.lint import lint_paths
        import repro
        assert lint_paths([list(repro.__path__)[0]]) == []
