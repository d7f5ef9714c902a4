"""Chaos plane + crash-consistent recovery: seeded fault schedules,
poison-update quarantine, torn-snapshot fallback, injected-crash resume,
and the SIGKILL crash sweep (slow).

Every injected fault must be matched to a recovery counter — the report's
``matched`` flag is the acceptance contract: scheduled − injected events
are accounted ``unfired``, injected ones must equal recovered per class.
"""
import json
import os

import numpy as np
import pytest

from repro.checkpoint import store
from repro.core.baselines import REGISTRY
from repro.core.simulation import (SimModel, heterogeneous_cluster,
                                   simulate_fedoptima)
from repro.faults import (BASELINE_CLASSES, CORRUPT_KINDS, SIM_CLASSES,
                          FaultEvent, FaultSchedule, InjectedCrash,
                          PodFaultInjector, UpdateGate, make_fault_schedule,
                          make_payload, tear_snapshot)

MODEL = SimModel(dev_fwd_flops=1e9, dev_bwd_flops=2e9, full_fwd_flops=5e9,
                 srv_flops_per_batch=8e9, act_bytes=1e6, dev_model_bytes=4e6,
                 full_model_bytes=2e7, batch_size=32)


# ---------------------------------------------------------------------------
# schedules: deterministic, serializable, validated
# ---------------------------------------------------------------------------

def test_schedule_seeded_determinism():
    a = make_fault_schedule(16, 600.0, seed=3, density=2.0)
    b = make_fault_schedule(16, 600.0, seed=3, density=2.0)
    c = make_fault_schedule(16, 600.0, seed=4, density=2.0)
    assert a.events == b.events
    assert a.events != c.events
    assert all(a.events[i].t <= a.events[i + 1].t
               for i in range(len(a) - 1))
    assert set(a.counts()) == set(SIM_CLASSES)


def test_schedule_json_roundtrip(tmp_path):
    sched = make_fault_schedule(8, 300.0, seed=1)
    path = str(tmp_path / "faults.json")
    sched.save(path)
    with open(path) as f:
        assert json.load(f)["format"] == "fault-schedule-v1"
    back = FaultSchedule.load(path)
    assert back.events == sched.events
    assert back.horizon == sched.horizon


def test_schedule_validation():
    with pytest.raises(ValueError):
        FaultEvent(1.0, "meteor_strike")
    with pytest.raises(ValueError):
        FaultEvent(1.0, "corrupt_act", device=0, kind="soggy")
    with pytest.raises(ValueError):
        FaultEvent(1.0, "torn_checkpoint", kind="nan")
    with pytest.raises(ValueError):      # event at the horizon never fires
        FaultSchedule(horizon=5.0,
                      events=(FaultEvent(5.0, "delay", device=0),))


# ---------------------------------------------------------------------------
# quarantine gate: finite-check + norm fence, strikes, backoff
# ---------------------------------------------------------------------------

def test_gate_rejects_every_poison_kind():
    gate = UpdateGate()
    for kind in CORRUPT_KINDS:
        ok, reason = gate.validate(make_payload(kind, seed=2))
        assert not ok, kind
        assert reason in ("non_finite", "norm_fence")
    ok, reason = gate.validate(make_payload("", seed=2))  # clean payload
    assert ok and reason == ""


def test_gate_strikes_backoff_and_readmission():
    gate = UpdateGate(strike_limit=2, backoff=10.0, backoff_growth=2.0)
    assert gate.may_send(0, t=0.0)
    assert gate.note_reject(0, t=0.0) == 0.0       # strike 1: under the limit
    assert gate.note_reject(0, t=1.0) == pytest.approx(10.0)   # at the limit
    d = gate.note_reject(0, t=2.0)                  # strike 3: one over
    assert d == pytest.approx(20.0)                 # backoff * growth^(3-2)
    assert not gate.may_send(0, t=2.0 + d - 1e-6)
    assert gate.may_send(0, t=2.0 + d + 1e-6)       # re-admitted after backoff
    gate.note_accept(0)                             # good update heals a strike
    assert gate.strikes[0] == 2
    assert gate.may_send(1, t=0.0)                  # other devices unaffected
    s = gate.summary()
    assert s["devices_struck"] == 1 and s["max_strikes"] == 2


# ---------------------------------------------------------------------------
# flow-token conservation under quarantine
# ---------------------------------------------------------------------------

def test_flow_quarantine_withdraws_exactly_one_inflight_unit():
    from repro.analysis.sanitize import sanitized
    from repro.core.flow_control import FlowController
    with sanitized() as san:
        flow = FlowController(omega=2)
        flow.register(0)
        flow.register(1)
        assert flow.can_send(0)
        flow.mark_sent(0)
        assert flow.inflight_of(0) == 1
        flow.on_quarantined(0)                 # poisoned arrival withdrawn
        assert flow.inflight_of(0) == 0
        assert flow.buffered == 0              # never buffered
        assert flow.n_spilled == 0 and flow.n_filled == 0
        assert flow.can_send(0) or flow.can_send(1)  # budget re-granted
        # the freed budget is usable end-to-end: a clean send still admits
        k = 0 if flow.can_send(0) else 1
        flow.mark_sent(k)
        assert flow.on_enqueue(k)
        flow.on_dequeue(k)
    assert san.report()["n_violations"] == 0


# ---------------------------------------------------------------------------
# dense-fault acceptance: K=32 diurnal sim, every fault matched
# ---------------------------------------------------------------------------

def test_sim_dense_faults_all_matched_and_sanitizer_clean():
    from repro.analysis.sanitize import sanitized
    from repro.fleet import make_trace
    K, dur = 32, 900.0
    cluster = heterogeneous_cluster(K)
    trace = make_trace("diurnal", K, dur, interval=dur / 24.0, seed=7,
                       day=dur / 2.0, on_frac=0.6)
    sched = make_fault_schedule(K, dur, seed=5, density=1.0)
    with sanitized() as san:
        m = simulate_fedoptima(MODEL, cluster, duration=dur, fleet=trace,
                               faults=sched, seed=0)
    assert san.report()["n_violations"] == 0
    fr = m.faults
    assert fr is not None and fr["matched"] is True
    assert sum(fr["injected"].values()) > 0
    for cls in SIM_CLASSES:
        assert fr["injected"].get(cls, 0) == fr["recovered"].get(cls, 0), \
            (cls, fr)
        # unfired events are the scheduled ones that never reached a seam
        assert fr["unfired"][cls] == \
            fr["scheduled"][cls] - fr["injected"].get(cls, 0)
    assert fr["gate"]["n_rejected"] > 0     # poison actually hit the gate
    assert m.srv_batches > 0                # training still made progress


def test_sim_gate_off_consumes_poison_honestly():
    """The no-recovery leg: with the gate disabled, poisoned uploads are
    consumed (badput) and the report says so — matched must be False, not
    silently green."""
    K, dur = 8, 600.0
    cluster = heterogeneous_cluster(K)
    sched = make_fault_schedule(K, dur, seed=2, density=2.0,
                                classes=("corrupt_act", "corrupt_model"))
    m = simulate_fedoptima(MODEL, cluster, duration=dur, faults=sched,
                           fault_gate=False, seed=0)
    fr = m.faults
    assert fr["matched"] is False
    assert fr["gate"] is None
    badput = fr["disposition"].get("consumed_poisoned_act", 0) + \
        fr["disposition"].get("consumed_poisoned_model", 0) + \
        fr["disposition"].get("admitted_poisoned_act", 0)
    assert badput > 0


def test_all_baselines_inject_and_match():
    K, dur = 8, 400.0
    cluster = heterogeneous_cluster(K)
    sched = make_fault_schedule(K, dur, seed=9, density=2.0,
                                classes=BASELINE_CLASSES)
    for name, fn in REGISTRY.items():
        m = fn(MODEL, cluster, duration=dur, faults=sched)
        fr = m.faults
        assert fr is not None and fr["matched"] is True, (name, fr)
        assert sum(fr["injected"].values()) > 0, name


# ---------------------------------------------------------------------------
# torn snapshots: verified fallback, never half-loads
# ---------------------------------------------------------------------------

def _tree(v):
    return {"w": np.full((4, 3), float(v)), "step": np.asarray(v, np.int64)}


@pytest.mark.parametrize("mode", ["truncate", "bitflip", "manifest"])
def test_restore_torn_snapshot_raises_not_half_loads(tmp_path, mode):
    d = str(tmp_path)
    store.save(d, 1, _tree(1))
    tear_snapshot(d, 1, mode)
    ok, reason = store.verify_snapshot(d, 1)
    assert not ok and reason
    with pytest.raises(store.CorruptSnapshotError):
        store.restore(d, 1, _tree(0))


@pytest.mark.parametrize("mode", ["truncate", "bitflip", "manifest"])
def test_resume_falls_back_to_previous_verified_snapshot(tmp_path, mode):
    from repro.runtime.fault_tolerance import CheckpointPolicy, resume_or_init
    d = str(tmp_path)
    for s in (1, 2, 3):
        store.save(d, s, _tree(s))
    tear_snapshot(d, 3, mode)
    step, skipped = store.latest_verified_step(d)
    assert step == 2
    assert [s for s, _ in skipped] == [3]
    policy = CheckpointPolicy(d, every_steps=10)
    state, start = resume_or_init(d, lambda: _tree(0), policy=policy)
    assert start == 2
    np.testing.assert_array_equal(state["w"], _tree(2)["w"])
    assert policy._last_step == 2           # cadence seeded from the resume
    assert not policy.should_save(2)
    assert policy.should_save(12)


def test_resume_all_torn_initializes_fresh(tmp_path):
    from repro.runtime.fault_tolerance import resume_or_init
    d = str(tmp_path)
    store.save(d, 1, _tree(1), retain=1)
    tear_snapshot(d, 1, "truncate")
    state, start = resume_or_init(d, lambda: _tree(0))
    assert start == 0
    np.testing.assert_array_equal(state["w"], _tree(0)["w"])


def test_crash_sweep_parent_never_starts_a_backend(tmp_path):
    """The sweep's parent reads snapshots through numpy and the filesystem
    only.  A parent that started a JAX backend would hold the chip, and
    its ``launch.train`` children would then fail or hang on it."""
    import subprocess
    import sys
    store.save(str(tmp_path), 2, _tree(2), metadata={"rng_state": [7]})
    code = (
        "import sys\n"
        "from jax._src import xla_bridge\n"
        "from repro.faults import crash_harness\n"
        "fp = crash_harness._final_fingerprint(sys.argv[1], 2)\n"
        "assert fp['rng_state'] == [7], fp\n"
        "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_churn_draw_is_time_indexed_not_call_ordered():
    """Satellite pin: ChurnModel.draw(t) is a pure function of
    (seed, interval index) — call order and call count must not matter."""
    from repro.runtime.fault_tolerance import ChurnModel
    cm1 = ChurnModel(n_devices=32, p_drop=0.3, interval=100.0, seed=5)
    cm2 = ChurnModel(n_devices=32, p_drop=0.3, interval=100.0, seed=5)
    for _ in range(4):                      # burn "calls" on cm1 only
        cm1.draw(0.0)
    a1, b1 = cm1.draw(250.0)
    a2, b2 = cm2.draw(250.0)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)
    # same interval, any t within it: identical; different interval: differs
    a3, _ = cm2.draw(299.0)
    np.testing.assert_array_equal(a1, a3)
    a4, b4 = cm2.draw(300.0)
    assert not (np.array_equal(a1, a4) and np.array_equal(b1, b4))


# ---------------------------------------------------------------------------
# pod path: timeout -> retention -> rejoin, injected crash -> resume
# ---------------------------------------------------------------------------

def _pod_setup(n_groups=2, H=2):
    import jax
    from repro.configs import registry
    from repro.core import fedopt_step as F
    from repro.launch.mesh import make_debug_mesh

    a = registry.smoke_config("smollm-135m")
    cfg = F.FedStepConfig(arch=a, l_split=1, n_groups=n_groups, seq_len=16,
                          per_group_batch=2 * H, H=H, omega=1)
    mesh = make_debug_mesh(1, 1)
    jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh, donate=False)
    state = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0), cfg),
                    out_shardings=s_spec)()
    return cfg, jitted, state, s_spec


def _pod_executor(cfg, step, s_spec, injector):
    from repro.core import fedopt_step as F
    from repro.core.control_plane import ControlPlane
    from repro.core.executor import RoundExecutor, StragglerProfiles

    cp = ControlPlane(cfg.n_groups, cfg.omega, cfg.H)
    ex = RoundExecutor(
        step, cp, window=1, profiles=StragglerProfiles(cfg.n_groups),
        gather=F.gather_group_state,
        scatter=lambda st, g, p: F.scatter_group_state(
            st, g, p, state_shardings=s_spec),
        faults=injector)
    return cp, ex


def _pod_batch_fn(cfg):
    from repro.core import fedopt_step as F
    import jax

    def fn(r, plan):
        batch = F.concrete_train_batch(jax.random.PRNGKey(r), cfg)
        batch.update(plan.batch_fields())
        return batch
    return fn


def test_pod_timeout_reclaims_slot_and_rejoins():
    cfg, step, state, s_spec = _pod_setup(n_groups=2, H=2)
    sched = FaultSchedule(horizon=6.0, events=(
        FaultEvent(1.0, "timeout", device=0, param=2.0),))
    inj = PodFaultInjector(sched, gate=UpdateGate())
    cp, ex = _pod_executor(cfg, step, s_spec, inj)
    rosters = []
    _, hist = ex.run(state, 0, 6,
                     active_fn=lambda r: np.ones(2, bool),
                     batch_fn=_pod_batch_fn(cfg),
                     on_metrics=lambda r, m, st: rosters.append(
                         np.asarray(st.plan.bcast_mask) > 0.5))
    assert len(hist) == 6
    fr = inj.report()
    assert fr["matched"] is True
    assert fr["injected"]["timeout"] == 1
    assert fr["disposition"].get("timeout_rejoined") == 1
    # rounds 1..2 ran without group 0 (slot retired), round 3 rejoined it
    assert not rosters[1][0] and not rosters[2][0]
    assert rosters[3][0] and rosters[0][0]
    assert 0 not in cp.retention.groups            # restored, not leaked


def test_pod_injected_crash_resumes_from_snapshot(tmp_path):
    import jax
    cfg, step, state, s_spec = _pod_setup(n_groups=2, H=2)
    d = str(tmp_path)
    events = (FaultEvent(1.0, "server_crash", param=1.0),
              FaultEvent(2.0, "timeout", device=0, param=1.0),
              FaultEvent(3.0, "corrupt_act", device=1, kind="inf"),
              FaultEvent(3.0, "torn_checkpoint", kind="bitflip"))
    sched = FaultSchedule(horizon=6.0, events=events)

    def run_leg(state0, start, injector, cp, ex):
        def ckpt(r, st):
            store.save(d, r + 1, jax.tree.map(np.asarray, st),
                       metadata={"control_plane": cp.state_dict()})
            injector.on_checkpoint(r, d, r + 1)
        return ex.run(state0, start, 6,
                      active_fn=lambda r: np.ones(2, bool),
                      batch_fn=_pod_batch_fn(cfg),
                      checkpoint_every=1, checkpoint_fn=ckpt)

    inj1 = PodFaultInjector(sched, gate=UpdateGate())
    cp1, ex1 = _pod_executor(cfg, step, s_spec, inj1)
    with pytest.raises(InjectedCrash) as exc:
        run_leg(state, 0, inj1, cp1, ex1)
    assert exc.value.round_index == 1
    assert sorted(inj1.fired_crashes) == [1]

    # "process restart": resume from the newest verified snapshot with the
    # fired boundary carried over — the crash must not re-fire
    start, skipped = store.latest_verified_step(d)
    assert start == 1 and skipped == []
    state2 = store.restore(d, start, jax.eval_shape(lambda: state))
    inj2 = PodFaultInjector(sched, gate=UpdateGate(),
                            fired_crashes=sorted(inj1.fired_crashes))
    cp2, ex2 = _pod_executor(cfg, step, s_spec, inj2)
    cp2.load_state_dict(store.restore_metadata(d, start)["control_plane"])
    state2, hist = run_leg(state2, start, inj2, cp2, ex2)
    assert len(hist) == 5                          # rounds 1..5
    fr = inj2.report()
    assert fr["matched"] is True, fr
    assert fr["recovered"]["server_crash"] == 1    # crash_resumed
    assert fr["injected"]["timeout"] == 1
    assert fr["injected"]["corrupt_act"] == 1
    assert fr["injected"]["torn_checkpoint"] == 1
    # the torn snapshot is detectable and was skipped by any later resume
    torn = [s for s in store.committed_steps(d)
            if not store.verify_snapshot(d, s)[0]]
    assert torn == [4]


# ---------------------------------------------------------------------------
# SIGKILL crash sweep (subprocess; reduced boundaries for the smoke lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_crash_sweep_sigkill_bit_exact_smoke():
    from repro.faults.crash_harness import sweep
    out = sweep(boundaries=[1], rounds=2, ckpt_every=1,
                kill_modes=("after", "mid"))
    assert out["cases"] == {"after@1": "bit-exact", "mid@1": "bit-exact"}


@pytest.mark.slow
def test_crash_sweep_window4_checkpoint_without_flush():
    """Acceptance for the deep pipeline: SIGKILL sweep at window=4 with
    checkpoint-without-flush — children save from dispatch-time handles
    while rounds stay in flight (the sweep asserts flush_saves=0 on the
    reference and every resumed run), and resume is still bit-exact."""
    from repro.faults.crash_harness import sweep
    out = sweep(boundaries=[1], rounds=2, ckpt_every=1,
                kill_modes=("after", "mid"), window=4)
    assert out["window"] == 4 and out["ckpt_flush"] is False
    assert out["cases"] == {"after@1": "bit-exact", "mid@1": "bit-exact"}
