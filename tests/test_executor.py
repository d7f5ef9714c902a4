"""RoundExecutor: pipelined dispatch ≡ synchronous loop (window=1, bit for
bit), host plan/build overlap at window=2, measured straggler profiles,
per-group state retention for dropped groups, ω-cap RuntimeError."""
import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.core.control_plane import ControlPlane
from repro.core.executor import RoundExecutor, StragglerProfiles
from repro.launch.mesh import make_debug_mesh
from repro.runtime.elastic import ElasticRegistry


def _setup(omega=1, n_groups=2, H=2):
    a = registry.smoke_config("smollm-135m")
    cfg = F.FedStepConfig(arch=a, l_split=1, n_groups=n_groups, seq_len=16,
                          per_group_batch=2 * H, H=H, omega=omega)
    mesh = make_debug_mesh(1, 1)
    jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh, donate=False)
    state = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0), cfg),
                    out_shardings=s_spec)()
    return cfg, jitted, state, s_spec


def _copy_state(state):
    return jax.tree.map(jnp.copy, state)


def _batch_fn(cfg):
    def fn(r, plan):
        batch = F.concrete_train_batch(jax.random.PRNGKey(r), cfg)
        batch.update(plan.batch_fields())
        return batch
    return fn


def _executor(cfg, step, s_spec, window, profiles=True, registry_=None):
    cp = ControlPlane(cfg.n_groups, cfg.omega, cfg.H)
    return cp, RoundExecutor(
        step, cp, window=window,
        profiles=StragglerProfiles(cfg.n_groups) if profiles else None,
        gather=F.gather_group_state,
        scatter=lambda st, g, p: F.scatter_group_state(
            st, g, p, state_shardings=s_spec),
        registry=registry_)


def _reference_sync_loop(cfg, step, state, actives):
    """The pre-executor run_pod round loop, verbatim semantics."""
    cp = ControlPlane(cfg.n_groups, cfg.omega, cfg.H)
    history = []
    batch_fn = _batch_fn(cfg)
    for r, active in enumerate(actives):
        plan = cp.plan_round(active=active)
        state, metrics = step(state, batch_fn(r, plan))
        cp.finish_round(active=active)
        assert cp.within_cap
        history.append({k: float(v) for k, v in metrics.items()})
    return state, history


# ---------------------------------------------------------------------------
# determinism: pipelining must not change values
# ---------------------------------------------------------------------------

def test_window1_bitforbit_matches_synchronous_loop():
    """Acceptance: executor(window=1) reproduces the synchronous round
    loop's metrics history and final state bit for bit."""
    cfg, step, state0, s_spec = _setup(omega=1, n_groups=2, H=2)
    actives = [np.ones(2, bool)] * 4
    ref_state, ref_hist = _reference_sync_loop(cfg, step,
                                               _copy_state(state0), actives)
    _, ex = _executor(cfg, step, s_spec, window=1)
    state, hist = ex.run(_copy_state(state0), 0, 4,
                         active_fn=lambda r: actives[r],
                         batch_fn=_batch_fn(cfg))
    assert hist == ref_hist            # exact float equality, round order
    for la, lb in zip(jax.tree.leaves(ref_state), jax.tree.leaves(state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_window2_history_values_equal_window1_under_churn():
    """Metric values are window-invariant (planning never reads device
    values), including across a drop/rejoin with state retention."""
    cfg, step, state0, s_spec = _setup(omega=2, n_groups=2, H=2)
    actives = [np.array([True, True]), np.array([True, False]),
               np.array([True, False]), np.array([True, True]),
               np.array([True, True])]
    results = {}
    for window in (1, 2):
        _, ex = _executor(cfg, step, s_spec, window=window)
        results[window] = ex.run(_copy_state(state0), 0, len(actives),
                                 active_fn=lambda r: actives[r],
                                 batch_fn=_batch_fn(cfg))
    s1, h1 = results[1]
    s2, h2 = results[2]
    assert h1 == h2
    for la, lb in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# overlap: window=2 hides host plan/build time behind device execution
# ---------------------------------------------------------------------------

def test_window2_overlaps_host_batch_build():
    """Acceptance: with window=2 the host plan/batch-build time is hidden
    behind device execution — host wall per round strictly below the
    synchronous (window=1) baseline.  The device is a stand-in whose
    rounds take a fixed time (``benchmarks.common.StubDevice``), so the
    two walls differ by the executor's overlap, not by how loaded the
    host's cores are."""
    from benchmarks.common import StubDevice
    G, rounds = 2, 8
    dev_s, sleep_s = 0.1, 0.05      # device round; modeled host build cost

    def slow_batch_fn(r, plan):
        time.sleep(sleep_s)
        return plan.batch_fields()

    walls = {}
    for window in (1, 2):
        with StubDevice(dev_s) as dev:
            ex = RoundExecutor(dev.step, ControlPlane(G, 1, 2),
                               window=window)
            t0 = time.perf_counter()
            ex.run(None, 0, rounds, active_fn=lambda r: np.ones(G, bool),
                   batch_fn=slow_batch_fn)
            walls[window] = time.perf_counter() - t0
        if window == 2:
            assert ex.peak_in_flight == 2
            assert ex.hidden_host_s > 0.0
    # saving ≈ rounds * min(sleep, device); demand a quarter of it
    margin = 0.25 * rounds * min(sleep_s, dev_s)
    assert walls[2] < walls[1] - margin, (walls, dev_s, sleep_s)


# ---------------------------------------------------------------------------
# per-group state retention (dropped groups rejoin from their own params)
# ---------------------------------------------------------------------------

def test_dropped_group_retains_state_and_staleness_on_rejoin():
    """Acceptance: a group dropped for k rounds keeps its retained dev/aux
    params unchanged, is NOT resynced by the aggregation broadcast, and
    rejoins from exactly those params with α reflecting the recorded
    delay."""
    cfg, step, state0, s_spec = _setup(omega=1, n_groups=2, H=2)
    k = 2                                  # dropped rounds
    actives = [np.array([True, True]), np.array([True, False]),
               np.array([True, False]), np.array([True, True]),
               np.array([True, True])]
    registry_ = ElasticRegistry()
    for g in range(2):
        registry_.join(1.0, 1.0)
    cp, ex = _executor(cfg, step, s_spec, window=1, registry_=registry_)
    scattered = {}
    real_scatter = ex.scatter

    def spy_scatter(st, g, p):
        scattered[g] = p
        return real_scatter(st, g, p)

    ex.scatter = spy_scatter

    snaps = {}
    plans = {}

    def on_metrics(r, m, st):
        plans[r] = st.plan                 # plan is dropped after this hook
        if 1 in cp.retention:              # snapshot the retained entry
            snaps[r] = copy.deepcopy(cp.retention.params_of(1))

    state, hist = ex.run(_copy_state(state0), 0, len(actives),
                         active_fn=lambda r: actives[r],
                         batch_fn=_batch_fn(cfg), on_metrics=on_metrics)

    # retained while dropped, and UNCHANGED across the drop window
    assert set(snaps) == {1, 2}
    for la, lb in zip(jax.tree.leaves(snaps[1]), jax.tree.leaves(snaps[2])):
        np.testing.assert_array_equal(la, lb)
    # the rejoin scattered exactly the retained params back
    assert list(scattered) == [1]
    for la, lb in zip(jax.tree.leaves(scattered[1]),
                      jax.tree.leaves(snaps[1])):
        np.testing.assert_array_equal(la, lb)
    assert 1 not in cp.retention           # released on rejoin
    # staleness weight on rejoin reflects the recorded delay: absent for
    # k rounds -> staleness k -> α = 1/(k+1)
    rejoin_plan = plans[3]
    np.testing.assert_allclose(rejoin_plan.agg_weight,
                               [1.0, 1.0 / (k + 1)], rtol=1e-6)
    np.testing.assert_array_equal(rejoin_plan.bcast_mask, [1.0, 1.0])
    assert ex.stats[3].plan is None        # plans are not accumulated
    # registry mirrored the churn with round timestamps
    assert registry_.devices[1].absences == 1
    assert registry_.devices[1].active and registry_.devices[1].joined_at == 3.0
    assert len(hist) == len(actives)


def test_masked_broadcast_keeps_dropped_group_params():
    """bcast_mask gates Alg. 4 line 20: masked-out groups keep their own
    params (no resync), while receiving groups sync to the aggregate."""
    cfg, step, state0, _ = _setup(omega=1, n_groups=2, H=2)
    batch = F.concrete_train_batch(jax.random.PRNGKey(0), cfg)
    batch["agg_weight"] = jnp.asarray([1.0, 0.0])

    masked, _ = step(_copy_state(state0),
                     {**batch, "bcast_mask": jnp.asarray([1.0, 0.0])})
    resync, _ = step(_copy_state(state0),
                     {**batch, "bcast_mask": jnp.asarray([1.0, 1.0])})
    w_m = np.asarray(masked["dev"]["embed"])
    w_r = np.asarray(resync["dev"]["embed"])
    # all-ones mask: broadcast resyncs the groups to identical params
    np.testing.assert_allclose(w_r[0], w_r[1], atol=1e-6)
    # masked: group 1 kept its own (locally-trained) params
    assert np.abs(w_m[0] - w_m[1]).max() > 1e-6
    # the receiving group's params are identical either way
    np.testing.assert_array_equal(w_m[0], w_r[0])


# ---------------------------------------------------------------------------
# ω-cap violation is a real error (not a strippable assert)
# ---------------------------------------------------------------------------

def test_cap_violation_raises_runtime_error_with_occupancy():
    class BrokenPlane(ControlPlane):
        @property
        def within_cap(self):
            return False

    cp = BrokenPlane(2, 1, 2)
    ex = RoundExecutor(lambda s, b: (s, {"d_loss": 0.0, "s_loss": 0.0}),
                       cp, window=1)
    with pytest.raises(RuntimeError, match=r"ring slots.*occupancy"):
        ex.run(0, 0, 1, active_fn=lambda r: np.ones(2, bool),
               batch_fn=lambda r, plan: {})


def test_executor_rejects_bad_window():
    cp = ControlPlane(2, 1, 2)
    with pytest.raises(ValueError, match="window"):
        RoundExecutor(lambda s, b: (s, {}), cp, window=0)


# ---------------------------------------------------------------------------
# measured straggler profiles
# ---------------------------------------------------------------------------

def test_profiles_unseeded_patterns_match_placeholders():
    p = StragglerProfiles(3)
    assert p.produce(4).all() and p.reads(4).all()
    cp = ControlPlane(3, 1, 4)
    planned = cp.plan_round(produce=p.produce(4), reads=p.reads(4))
    default = ControlPlane(3, 1, 4).plan_round()
    np.testing.assert_array_equal(planned.send_mask, default.send_mask)
    np.testing.assert_array_equal(planned.read_slot, default.read_slot)


def test_profiles_heterogeneous_produce_and_reads():
    p = StragglerProfiles(4, step_s=[0.01, 0.02, 0.02, 0.04],
                          server_s=0.08)
    produce = p.produce(8)
    np.testing.assert_array_equal(produce.sum(axis=0), [8, 4, 4, 2])
    assert produce[:, 0].all()             # fastest emits every iteration
    # server at half the lockstep cadence (0.04) consumes every other iter
    assert p.reads(8).sum() == 4


def test_profiles_observe_round_keeps_uniform_profile_uniform():
    """Pod path on a homogeneous mesh: measured-round EMA must never
    introduce phantom heterogeneity (bit-for-bit compat)."""
    p = StragglerProfiles(3)
    for wall in (0.5, 0.3, 0.4):
        p.observe_round(wall, H=4)
        assert p.produce(4).all() and p.reads(4).all()
    assert np.allclose(p.step_s, p.step_s[0])


def test_profiles_observe_round_preserves_relative_speeds():
    p = StragglerProfiles(2, step_s=[0.01, 0.04])
    p.observe_round(wall_s=0.8, H=4)       # slowest binds: 0.2 per iter
    np.testing.assert_allclose(p.step_s[1] / p.step_s[0], 4.0, rtol=1e-6)
    assert p.step_s[1] < 0.04 + 0.25 * 0.2 + 1e-9   # EMA moved toward scale


def test_profiles_patterns_invariant_to_wall_clock_noise():
    """Seeded heterogeneous profiles: observe_round rescales step_s and
    server_s by the same cadence factor, so the produce/reads patterns
    are pure functions of the seeds — never of measured wall times (the
    executor's determinism/window-invariance guarantee)."""
    seeds = dict(step_s=[0.01, 0.02, 0.04], server_s=0.08)
    a = StragglerProfiles(3, **seeds)
    b = StragglerProfiles(3, **seeds)
    rng = np.random.default_rng(0)
    for wall_a, wall_b in zip(rng.uniform(0.1, 2.0, 12),
                              rng.uniform(0.1, 2.0, 12)):
        a.observe_round(wall_a, H=8)       # two different noisy histories
        b.observe_round(wall_b, H=8)
        np.testing.assert_array_equal(a.produce(8), b.produce(8))
        np.testing.assert_array_equal(a.reads(8), b.reads(8))
    # and the patterns still reflect the seeded heterogeneity
    np.testing.assert_array_equal(a.produce(8).sum(axis=0), [8, 4, 2])
    assert a.reads(8).sum() == 4           # server at half the cadence


def test_simulator_measures_straggler_profiles():
    """The event simulator observes real per-device step/transfer times;
    the EMAs converge to the cluster's configured heterogeneity and the
    derived patterns schedule slow devices fewer emissions."""
    from repro.core.simulation import (SimModel, heterogeneous_cluster,
                                       simulate_fedoptima)
    model = SimModel(dev_fwd_flops=1e9, dev_bwd_flops=2e9,
                     full_fwd_flops=5e9, srv_flops_per_batch=8e9,
                     act_bytes=1e6, dev_model_bytes=4e6,
                     full_model_bytes=2e7, batch_size=32)
    cluster = heterogeneous_cluster(4, speed_groups=(1.0, 2.0, 2.0, 4.0))
    m = simulate_fedoptima(model, cluster, duration=120.0)
    prof = m.profiles
    expected = (model.dev_fwd_flops + model.dev_bwd_flops) / cluster.dev_flops
    # EMAs converge to the configured heterogeneity (constant event times)
    np.testing.assert_allclose(prof.step_s, expected, rtol=1e-3)
    np.testing.assert_allclose(prof.transfer_s,
                               model.act_bytes / cluster.dev_bw, rtol=1e-3)
    assert prof.server_s == pytest.approx(
        model.srv_flops_per_batch / cluster.srv_flops, rel=1e-3)
    # measured patterns fed into plan_round: the 4x-slower device is
    # granted about a quarter of the fastest device's emissions (EMA
    # rounding may land the stride on either side of a floor boundary)
    H = 8
    produce = prof.produce(H)
    assert produce[:, 3].all()
    assert 1 <= produce[:, 0].sum() <= 3
    sums = produce.sum(axis=0)
    assert sums[0] <= sums[1] <= sums[3] and sums[0] <= sums[2] <= sums[3]
    cp = ControlPlane(4, 2, H)
    plan = cp.plan_round(produce=produce, reads=prof.reads(H))
    sends = plan.send_mask.sum(axis=0)
    assert sends[0] <= sends[3]
    assert cp.within_cap


# ---------------------------------------------------------------------------
# retention rides the checkpoint store (metadata + extras)
# ---------------------------------------------------------------------------

def test_retention_rides_checkpoint_extras(tmp_path):
    import json

    from repro.checkpoint import store

    cp = ControlPlane(3, 2, 4)
    cp.plan_round(active=np.array([True, True, False]))     # drops group 2
    params = {"dev": {"w": np.arange(6.0).reshape(2, 3)},
              "aux": {"b": np.ones(4, np.float32)}}
    cp.retain_group(2, params)
    cp.finish_round(active=np.array([True, True, False]))

    sd = cp.state_dict()
    json.dumps(sd)                         # checkpoint-metadata safe
    store.save(str(tmp_path), 5, {"x": np.zeros(2)},
               metadata={"control_plane": sd},
               extras=cp.retention.arrays())

    meta = store.restore_metadata(str(tmp_path), 5)
    cp2 = ControlPlane(3, 2, 4)
    cp2.load_state_dict(meta["control_plane"])
    assert cp2.retention.groups == [2]
    assert cp2.retention.version_of(2) == cp.retention.version_of(2)
    assert cp2.retention.params_of(2) is None      # arrays not yet loaded
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        cp.retention.arrays())
    cp2.retention.load_arrays(store.restore_extras(str(tmp_path), 5, like))
    for la, lb in zip(jax.tree.leaves(cp.retention.params_of(2)),
                      jax.tree.leaves(cp2.retention.params_of(2))):
        np.testing.assert_array_equal(la, lb)
    # restored plane plans the rejoin identically to the original
    p1 = cp.plan_round(active=np.ones(3, bool))
    p2 = cp2.plan_round(active=np.ones(3, bool))
    assert p1.restore == p2.restore == (2,)
    np.testing.assert_array_equal(p1.agg_weight, p2.agg_weight)


def test_rejoin_without_restored_arrays_raises():
    cp = ControlPlane(2, 1, 2)
    cp.plan_round(active=np.array([True, False]))
    cp.retain_group(1, {"dev": np.zeros(2), "aux": np.zeros(2)})
    sd = cp.state_dict()
    cp2 = ControlPlane(2, 1, 2)
    cp2.load_state_dict(sd)                # metadata only, no arrays
    ex = RoundExecutor(lambda s, b: (s, {"d_loss": 0.0}), cp2, window=1,
                       gather=lambda s, g: None,
                       scatter=lambda s, g, p: s)
    with pytest.raises(RuntimeError, match="extras"):
        ex.run(0, 0, 1, active_fn=lambda r: np.ones(2, bool),
               batch_fn=lambda r, plan: {})
    # the error path must not destroy the retained entry: a fixed-up rerun
    # (extras loaded) still needs it
    assert 1 in cp2.retention


def test_churn_without_retention_wiring_raises():
    """The masked broadcast makes unwired churn unsafe (a dropped group
    would rejoin with phantom-trained params) — the executor refuses."""
    cp = ControlPlane(2, 1, 2)
    ex = RoundExecutor(lambda s, b: (s, {"d_loss": 0.0}), cp, window=1)
    rosters = [np.ones(2, bool), np.array([True, False])]
    with pytest.raises(RuntimeError, match="gather"):
        ex.run(0, 0, 2, active_fn=lambda r: rosters[r],
               batch_fn=lambda r, plan: {})


# ---------------------------------------------------------------------------
# deep pipeline (window >= 4) with DONATION: per-round handles keep
# retention/spill/checkpoint consumers off the invalidated live state
# ---------------------------------------------------------------------------

_DONATED = {}


def _donated_setup(omega=2, n_groups=2, H=2):
    """jit'd hybrid step with donate_argnums=(0,) — the deep-window
    acceptance configuration (cached: one compile per config)."""
    key = (omega, n_groups, H)
    if key not in _DONATED:
        a = registry.smoke_config("smollm-135m")
        cfg = F.FedStepConfig(arch=a, l_split=1, n_groups=n_groups,
                              seq_len=16, per_group_batch=2 * H, H=H,
                              omega=omega)
        mesh = make_debug_mesh(1, 1)
        jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh, donate=True)
        init = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0),
                                                  cfg),
                       out_shardings=s_spec)
        _DONATED[key] = (cfg, jitted, s_spec, init)
    return _DONATED[key]


class _StallThenDrain(StragglerProfiles):
    """Deterministic produce/reads: every group emits and the server never
    reads for ``stall_rounds`` plans (backlog -> spills), then emission
    stops and the server drains (fills).  Pure function of the plan call
    count, so identical for every window."""

    def __init__(self, n_groups, stall_rounds):
        super().__init__(n_groups)
        self.stall_rounds = stall_rounds
        self._planned = 0

    def produce(self, H):
        self._planned += 1
        return np.full((H, self.G), self._planned <= self.stall_rounds,
                       bool)

    def reads(self, H):
        return np.full(H, self._planned > self.stall_rounds, bool)


def _run_donated(window, actives, *, pool_cap=0, stall_rounds=0,
                 faults=None, ckpt=None):
    """One donated-step executor run; returns (history, final host state,
    executor).  ``ckpt`` = (every, flush, saves_dict) wires the
    checkpoint path with capture_fn metadata."""
    from repro.faults import PodFaultInjector, UpdateGate
    from repro.memory import ActivationStore

    cfg, step, s_spec, init = _donated_setup()
    cp = ControlPlane(cfg.n_groups, cfg.omega, cfg.H, pool_cap=pool_cap)
    kw = {}
    if pool_cap:
        kw = dict(store=ActivationStore(pool_cap),
                  gather_slot=F.gather_act_slot,
                  scatter_slot=lambda st, s, p: F.scatter_act_slot(
                      st, s, p, state_shardings=s_spec))
    if faults is not None:
        kw["faults"] = PodFaultInjector(faults, gate=UpdateGate())
    profiles = _StallThenDrain(cfg.n_groups, stall_rounds) \
        if stall_rounds else StragglerProfiles(cfg.n_groups)
    ex = RoundExecutor(
        step, cp, window=window, profiles=profiles,
        gather=F.gather_group_state,
        scatter=lambda st, g, p: F.scatter_group_state(
            st, g, p, state_shardings=s_spec), **kw)
    run_kw = {}
    if ckpt is not None:
        every, flush, saves = ckpt

        def checkpoint_fn(r, handle):
            saves[r] = {"tree": jax.tree.map(np.array, handle.host_tree()),
                        "meta": handle.meta,
                        "in_flight": len(ex._pending)}
        run_kw = dict(checkpoint_every=every, checkpoint_fn=checkpoint_fn,
                      capture_fn=lambda r: {"round": r},
                      checkpoint_flush=flush)
    state, hist = ex.run(init(), 0, len(actives),
                         active_fn=lambda r: actives[r],
                         batch_fn=_batch_fn(cfg), **run_kw)
    return hist, jax.tree.map(np.asarray, state), ex


def test_window4_donated_bitidentical_under_churn_spill_and_faults():
    """Acceptance: window=4 with donation ON, under churn (drop/rejoin
    retention through the handle ring), a spilling/filling tiered store,
    and dense injected faults, produces metrics and a final state
    bit-identical to window=1 — and the run is sanitizer-clean."""
    from repro.analysis.sanitize import sanitized
    from repro.faults import FaultEvent, FaultSchedule

    actives = [np.ones(2, bool)] * 3 + \
        [np.array([True, False])] * 2 + [np.ones(2, bool)] * 5
    sched = FaultSchedule(horizon=10.0, events=(
        FaultEvent(6.0, "timeout", device=0, param=1.0),
        FaultEvent(8.0, "corrupt_act", device=1, kind="inf")))
    results = {}
    for window in (1, 4):
        with sanitized() as san:
            results[window] = _run_donated(
                window, actives, pool_cap=2, stall_rounds=4,
                faults=FaultSchedule(horizon=sched.horizon,
                                     events=sched.events))
        assert san.n_violations == 0, san.violations
    h1, s1, ex1 = results[1]
    h4, s4, ex4 = results[4]
    assert h1 == h4                        # exact float equality
    for la, lb in zip(jax.tree.leaves(s1), jax.tree.leaves(s4)):
        np.testing.assert_array_equal(la, lb)
    # the scenario genuinely exercised every donated-handle consumer
    assert ex4.cplane.n_spills > 0
    assert ex4.summary()["faults"]["matched"] is True
    assert ex4.peak_in_flight == 4 and ex1.peak_in_flight == 1
    assert ex4.handles.n_captured > 0 and ex4.handle_bytes_peak > 0


def test_checkpoint_without_flush_bitexact_with_flush_saver():
    """Acceptance: checkpoint-without-flush (deferred handle saves, pipe
    kept full) writes byte-identical snapshots to the legacy flush saver
    at every boundary, never drains, and does not perturb training."""
    actives = [np.ones(2, bool)] * 8
    saves_f, saves_n = {}, {}
    hf, sf, exf = _run_donated(4, actives, ckpt=(2, True, saves_f))
    hn, sn, exn = _run_donated(4, actives, ckpt=(2, False, saves_n))
    assert hf == hn
    for la, lb in zip(jax.tree.leaves(sf), jax.tree.leaves(sn)):
        np.testing.assert_array_equal(la, lb)
    # same boundaries, same dispatch-time metadata, bit-identical arrays
    assert sorted(saves_f) == sorted(saves_n) == [1, 3, 5, 7]
    for r in saves_f:
        assert saves_f[r]["meta"] == saves_n[r]["meta"] == {"round": r}
        for la, lb in zip(jax.tree.leaves(saves_f[r]["tree"]),
                          jax.tree.leaves(saves_n[r]["tree"])):
            np.testing.assert_array_equal(la, lb)
    # the flush leg drained for every save; the no-flush leg never did
    assert exf.n_ckpt_flush == 4 and exf.n_ckpt_noflush == 0
    assert exn.n_ckpt_flush == 0 and exn.n_ckpt_noflush == 4
    assert all(s["in_flight"] == 0 for s in saves_f.values())
    assert any(s["in_flight"] > 0 for s in saves_n.values())
    s = exn.summary()["checkpoints"]
    assert s == {"flush_saves": 0, "noflush_saves": 4}


def test_legacy_checkpoint_contract_without_capture_fn():
    """capture_fn=None keeps the old contract: a full drain and
    checkpoint_fn(r, state) with the LIVE state object, not a handle."""
    cp = ControlPlane(2, 1, 2)
    ex = RoundExecutor(lambda s, b: (s, {"d_loss": 0.0}), cp, window=2)
    seen = []
    ex.run({"x": np.zeros(2)}, 0, 4,
           active_fn=lambda r: np.ones(2, bool),
           batch_fn=lambda r, plan: {},
           checkpoint_every=2, checkpoint_fn=lambda r, st: seen.append(st))
    assert [isinstance(s, dict) for s in seen] == [True, True]
    assert ex.n_ckpt_flush == 2 and ex.n_ckpt_noflush == 0


def test_summary_reports_steady_state_exposure_excluding_warmup():
    """The first ``window`` dispatches have nothing in flight to hide
    behind; summary() excludes them from the steady-state exposure."""
    cp = ControlPlane(2, 1, 2)
    ex = RoundExecutor(lambda s, b: (s, {"d_loss": 0.0}), cp, window=3)
    ex.run(0, 0, 7, active_fn=lambda r: np.ones(2, bool),
           batch_fn=lambda r, plan: {})
    s = ex.summary()
    assert s["warmup_rounds_excluded"] == 3
    assert s["rounds"] == 7
    assert 0.0 <= s["host_s_exposed_steady"] <= s["host_s_exposed"] + 1e-9
    assert 0.0 <= s["hidden_host_frac_steady"] <= 1.0
    assert s["handles"]["depth"] == 4
    # fewer rounds than the window: everything is warmup
    ex2 = RoundExecutor(lambda s, b: (s, {"d_loss": 0.0}),
                        ControlPlane(2, 1, 2), window=4)
    ex2.run(0, 0, 2, active_fn=lambda r: np.ones(2, bool),
            batch_fn=lambda r, plan: {})
    s2 = ex2.summary()
    assert s2["warmup_rounds_excluded"] == 2
    assert s2["host_s_exposed_steady"] == 0.0


def test_pipeline_window_validation():
    """--window 0 is a typed error, not a silent remap to the default
    (the old ``or 2`` idiom swallowed it); unset still defaults to 2."""
    import argparse

    from repro.launch.train import _pipeline_window

    assert _pipeline_window(argparse.Namespace()) == 2
    assert _pipeline_window(argparse.Namespace(window=None)) == 2
    assert _pipeline_window(argparse.Namespace(window=1)) == 1
    assert _pipeline_window(argparse.Namespace(window=4)) == 4
    for bad in (0, -3):
        with pytest.raises(ValueError, match="window must be >= 1"):
            _pipeline_window(argparse.Namespace(window=bad))
