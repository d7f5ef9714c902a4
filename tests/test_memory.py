"""Tiered activation store (server memory manager, ``repro.memory``):
spill→fill round-trips (bit-exact fp32 / bounded-error int8), eviction
policies, the pool_cap=0 ≡ hard-ω pin, K ≫ ω admission past the old
cap, executor wiring, and checkpoint riding (state_dict v3 + extras,
v2 compatibility)."""
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core.control_plane import ControlPlane
from repro.core.executor import RoundExecutor, StragglerProfiles
from repro.memory import ActivationStore, make_eviction_policy

OMEGA, G4 = 2, 8        # K = 4ω acceptance scale (host-level tests)


# ---------------------------------------------------------------------------
# spill → fill round-trips (the store itself)
# ---------------------------------------------------------------------------

def _payload(rng, n, scale):
    return {"acts": (scale * rng.standard_normal((3, n))).astype(np.float32),
            "labels": rng.integers(0, 1000, (3, 4)).astype(np.int32)}


@settings(max_examples=25)
@given(st.integers(1, 64), st.floats(1e-3, 1e3))
def test_spill_fill_roundtrip_fp32_bitexact(n, scale):
    """fp32 spill is lossless: fill returns the gathered slot bit-for-bit."""
    rng = np.random.default_rng(n)
    store = ActivationStore(2, quant=False)
    p = _payload(rng, n, scale)
    store.spill(0, p)
    out = store.fill(0)
    np.testing.assert_array_equal(out["acts"], p["acts"])
    np.testing.assert_array_equal(out["labels"], p["labels"])
    assert out["acts"].dtype == np.float32
    assert out["labels"].dtype == np.int32


@settings(max_examples=25, deadline=None)   # each new n compiles the quantizer
@given(st.integers(1, 64), st.floats(1e-3, 1e3))
def test_spill_fill_roundtrip_int8_tolerance(n, scale):
    """int8 spill: float leaves within the per-tensor quantization bound
    (max|x|/254 per element); integer leaves (labels) stay exact."""
    rng = np.random.default_rng(1000 + n)
    store = ActivationStore(2, quant=True)
    p = _payload(rng, n, scale)
    store.spill(5, p)
    out = store.fill(5)
    bound = np.abs(p["acts"]).max() / 254.0 + 1e-7
    assert np.abs(out["acts"] - p["acts"]).max() <= bound
    np.testing.assert_array_equal(out["labels"], p["labels"])


def test_store_cap_counts_and_bytes():
    rng = np.random.default_rng(0)
    store = ActivationStore(1, quant=False)
    store.spill(0, _payload(rng, 8, 1.0))
    assert len(store) == 1 and store.n_spills == 1
    assert store.pool_bytes == store.peak_pool_bytes > 0
    with pytest.raises(RuntimeError, match="pool full"):
        store.spill(1, _payload(rng, 8, 1.0))
    with pytest.raises(KeyError):
        store.spill(0, _payload(rng, 8, 1.0))   # key already held
    store.fill(0)
    assert len(store) == 0 and store.n_fills == 1 and store.pool_bytes == 0
    # int8 spill shrinks the float payload ~4x
    big = {"acts": rng.standard_normal((64, 64)).astype(np.float32)}
    fp = ActivationStore(1, quant=False)
    q8 = ActivationStore(1, quant=True)
    fp.spill(0, big)
    q8.spill(0, big)
    assert fp.pool_bytes > 3.5 * q8.pool_bytes


def test_eviction_policies_pick_expected_victims():
    """share: evict the slot whose contributors are best-served; lru:
    evict the least-recently-touched slot — over the same candidates."""
    share_of = {0: 0.7, 1: 0.1, 2: 0.4}.get
    groups_of = {10: {0}, 11: {1}, 12: {2}}.get     # slot -> contributors
    touch = {10: 5, 11: 9, 12: 1}
    lru = make_eviction_policy("lru")
    sh = make_eviction_policy("share")
    assert lru.victim([10, 11, 12], groups_of=groups_of, share=share_of,
                      touch=touch) == 12          # oldest touch
    assert sh.victim([10, 11, 12], groups_of=groups_of, share=share_of,
                     touch=touch) == 10           # best-served contributor
    # fills: share promotes the most-underserved entry first
    assert sh.fill_order([10, 11, 12], groups_of=groups_of,
                         share=share_of) == [11, 12, 10]
    assert lru.fill_order([12, 10, 11], groups_of=groups_of,
                          share=share_of) == [10, 11, 12]
    with pytest.raises(ValueError, match="unknown eviction"):
        make_eviction_policy("mru")


def test_fifo_withdraw_preserves_unspilled_arrival_order():
    """Evicting a NEWER contribution must not demote the group's older,
    unspilled one: withdraw_slot retires the arrival entry matching the
    withdrawn message, not the group's oldest."""
    from repro.core.scheduler import Message, TaskScheduler
    sched = TaskScheduler(3, policy="fifo")
    sched.put(Message("activation", 0, content="A"))   # g0 slot A (oldest)
    sched.put(Message("activation", 1, content="A"))
    sched.put(Message("activation", 2, content="B"))
    sched.put(Message("activation", 0, content="B"))   # g0 slot B (newer)
    sched.withdraw_slot("B", [0, 2])                   # evict slot B
    # g0's slot-A contribution kept arrival position 1: it is served first
    served = [sched.get().origin for _ in range(2)]
    assert served == [0, 1]
    assert sched.total_buffered == 0
    # the withdrawn messages re-enter at the back on fill
    sched.put(Message("activation", 2, content="C"))
    sched.put(Message("activation", 0, content="C"))
    assert [sched.get().origin, sched.get().origin] == [2, 0]


# ---------------------------------------------------------------------------
# control-plane planning: pool_cap=0 pin + K >= 4ω admission
# ---------------------------------------------------------------------------

def _stress(cp, rounds, stalled):
    """Two-phase workload: while ``stalled(r)`` the groups produce but the
    server never reads (pressure builds); afterwards production stops and
    the server drains the backlog.  Returns the plan trace."""
    H = cp.H
    plans = []
    for r in range(rounds):
        if stalled(r):
            produce, reads = None, np.zeros(H, bool)
        else:
            produce, reads = np.zeros((H, cp.G), bool), np.ones(H, bool)
        plans.append(cp.plan_round(produce=produce, reads=reads))
        assert cp.within_cap
        cp.finish_round()
    return plans


def test_pool_cap_zero_plans_are_hard_omega_behavior():
    """pool_cap=0 (the pod default): no spill/fill is ever planned, the
    flow budget is exactly ω·G, and a full ring gates sends — the plan
    trace is the pre-tiered hard-cap behavior, regardless of the
    eviction policy knob."""
    for eviction in ("share", "lru"):
        cp = ControlPlane(G4, OMEGA, 4, pool_cap=0, eviction=eviction)
        assert cp.flow.cap == cp.flow.omega == OMEGA * G4
        plans = _stress(cp, 6, stalled=lambda r: r < 3)
        assert all(p.spill == () and p.fill == () for p in plans)
        # ring full after ω write-iterations: every later stalled-round
        # send is gated (the ω cap as a strict invariant)
        stalled_sends = sum(int(p.send_mask.sum()) for p in plans[:3])
        assert stalled_sends == OMEGA * G4
        assert cp.n_spills == cp.n_fills == 0 and cp.pool_live == 0
        assert cp.peak_buffered <= OMEGA * G4


def test_k_4omega_admits_past_the_omega_ring():
    """K = 4ω groups with a stalled server: the tiered plane admits
    ω + pool slots of contributions (4× the old ceiling) while
    ``within_cap`` holds on the tiered budget; the same buffering level
    under the old ω-only cap is exactly the state the executor's
    RuntimeError refuses."""
    pool = 3 * OMEGA
    cp = ControlPlane(G4, OMEGA, 2, pool_cap=pool)
    _stress(cp, 4, stalled=lambda r: True)
    assert cp.peak_buffered == (OMEGA + pool) * G4    # 4x the old budget
    assert cp.peak_buffered > cp.flow.omega           # past the ω ring
    assert cp.pool_live == pool and cp.within_cap
    # the old path: same buffering with no spill tier violates ω —
    # RoundExecutor._check_cap raises the ω-cap RuntimeError
    ex = RoundExecutor(lambda s, b: (s, {}), cp)
    cp.flow.pool_cap = 0          # the old, un-tiered budget
    old_cap = cp.pool_cap
    cp.pool_cap = 0
    with pytest.raises(RuntimeError, match="activation cap"):
        ex._check_cap(3)
    cp.flow.pool_cap = pool * G4  # restore the tiered budget
    cp.pool_cap = old_cap
    assert cp.within_cap
    # server catches up: the pool drains back through fills
    _stress(cp, 12, stalled=lambda r: False)
    assert cp.n_fills == cp.n_spills > 0
    assert cp.pool_live == 0 and cp.flow.buffered == 0


# ---------------------------------------------------------------------------
# executor wiring (host-level stub mesh)
# ---------------------------------------------------------------------------

class _StalledProfiles(StragglerProfiles):
    """Deterministic two-phase pattern: for the first ``stall_rounds``
    plans every group emits and the server never reads (backlog builds,
    spills); afterwards emission stops and the server drains (fills)."""

    def __init__(self, n_groups, stall_rounds):
        super().__init__(n_groups)
        self.stall_rounds = stall_rounds
        self._planned = 0

    def produce(self, H):
        self._planned += 1          # produce() is called first each round
        stalled = self._planned <= self.stall_rounds
        return np.full((H, self.G), stalled, bool)

    def reads(self, H):
        return np.full(H, self._planned > self.stall_rounds, bool)


class _StubMesh:
    """Host-array ring standing in for the jit'd step: applies the plan's
    writes, stamping each written slot with (round, h)."""

    def __init__(self, omega):
        self.t = 0

    def step(self, state, plan):
        ring = list(state["ring"])
        for h in range(len(plan.write_slot)):
            if plan.send_mask[h].any():
                ring[int(plan.write_slot[h])] = {
                    "acts": np.full(4, 100.0 * self.t + h, np.float32)}
        self.t += 1
        return {"ring": ring}, {"d_loss": 0.0}


def _slot_ops():
    def gather(state, s):
        return state["ring"][s]

    def scatter(state, s, payload):
        ring = list(state["ring"])
        ring[s] = payload
        return {"ring": ring}
    return gather, scatter


def test_executor_runs_k_4omega_spills_and_fills():
    pool = 3 * OMEGA
    H = 2
    cp = ControlPlane(G4, OMEGA, H, pool_cap=pool)
    store = ActivationStore(pool)
    mesh = _StubMesh(OMEGA)
    gather, scatter = _slot_ops()
    profiles = _StalledProfiles(G4, stall_rounds=5)
    ex = RoundExecutor(mesh.step, cp, window=2, profiles=profiles,
                       store=store, gather_slot=gather,
                       scatter_slot=scatter)

    def on_metrics(r, m, stats):
        assert cp.within_cap
        # store payloads and control-plane bookkeeping track each other
        assert store.keys == sorted(cp.pool_occupancy)

    state = {"ring": [{"acts": np.zeros(4, np.float32)}] * OMEGA}
    state, hist = ex.run(state, 0, 14,
                         active_fn=lambda r: np.ones(G4, bool),
                         batch_fn=lambda r, plan: plan,
                         on_metrics=on_metrics)
    assert len(hist) == 14
    mem = ex.summary()["memory"]
    assert mem["spills"] == mem["store_spills"] > 0
    assert mem["fills"] == mem["store_fills"] == mem["spills"]
    assert mem["peak_pool"] > 0 and len(store) == 0
    assert cp.peak_buffered > OMEGA * G4      # admitted past the old cap


def test_executor_refuses_spills_without_store_wiring():
    cp = ControlPlane(G4, OMEGA, 2, pool_cap=2)
    profiles = _StalledProfiles(G4, stall_rounds=10)
    ex = RoundExecutor(_StubMesh(OMEGA).step, cp, profiles=profiles)
    with pytest.raises(RuntimeError, match="ActivationStore"):
        ex.run({"ring": [None] * OMEGA}, 0, 3,
               active_fn=lambda r: np.ones(G4, bool),
               batch_fn=lambda r, plan: plan)


# ---------------------------------------------------------------------------
# prefetch-ahead staging: pre-decoded fills, bit-identical and advisory
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [False, True])
def test_prefetch_staged_fill_is_bitexact(quant):
    """A prefetch-staged fill returns exactly what an unstaged fill
    would (the decode is pure in the stored payload) — including through
    the int8 quantized path — and the staging counters balance."""
    rng = np.random.default_rng(3)
    payloads = {k: _payload(rng, 8, 1.0) for k in (0, 1)}
    a = ActivationStore(2, quant=quant)     # staged leg
    b = ActivationStore(2, quant=quant)     # plain leg
    for k, p in payloads.items():
        a.spill(k, p)
        b.spill(k, p)
    a.prefetch(0)
    assert a.n_prefetched == 1 and a.staged_bytes > 0
    a.prefetch(0)                           # idempotent: already staged
    assert a.n_prefetched == 1
    a.prefetch(99)                          # advisory: unknown key ignored
    assert a.n_prefetched == 1
    for k in (0, 1):
        fa, fb = a.fill(k), b.fill(k)
        for leaf in fa:
            np.testing.assert_array_equal(fa[leaf], fb[leaf])
    assert a.prefetch_hits == 1 and a.staged_bytes == 0
    assert a.peak_staged_bytes > 0
    s = a.summary()
    assert s["n_prefetched"] == 1 and s["prefetch_hits"] == 1


def test_prefetch_ignores_payloadless_restored_entries():
    """Post-restore, pre-load_arrays entries hold metadata only; a
    prefetch hint against them must be a no-op, not a crash."""
    src = ActivationStore(1)
    src.spill(0, _payload(np.random.default_rng(0), 4, 1.0))
    dst = ActivationStore(1)
    dst.load_meta(src.meta_dict())          # keys known, payloads absent
    dst.prefetch(0)
    assert dst.n_prefetched == 0 and dst.staged_bytes == 0


def test_executor_prefetch_stages_ahead_without_changing_values():
    """The executor's lookahead (= window) pre-stages pooled entries and
    the fills consume the staged decodes; the metric history is
    bit-identical across windows (prefetch is plan-neutral)."""
    hists = {}
    for window in (1, 2):
        cp = ControlPlane(G4, OMEGA, 2, pool_cap=3 * OMEGA)
        store = ActivationStore(3 * OMEGA)
        gather, scatter = _slot_ops()
        ex = RoundExecutor(_StubMesh(OMEGA).step, cp, window=window,
                           profiles=_StalledProfiles(G4, stall_rounds=5),
                           store=store, gather_slot=gather,
                           scatter_slot=scatter)
        state = {"ring": [{"acts": np.zeros(4, np.float32)}] * OMEGA}
        _, hists[window] = ex.run(
            state, 0, 14, active_fn=lambda r: np.ones(G4, bool),
            batch_fn=lambda r, plan: plan)
        mem = ex.summary()["memory"]
        assert mem["n_prefetched"] > 0
        assert mem["prefetch_hits"] > 0
        assert mem["fills"] == mem["spills"] > 0
    assert hists[1] == hists[2]


# ---------------------------------------------------------------------------
# checkpoint riding: state_dict v3 + extras, v2 compatibility
# ---------------------------------------------------------------------------

def _occupied_plane(pool=2, quant=False):
    """A plane + store mid-run with a genuinely occupied spill pool."""
    rng = np.random.default_rng(7)
    cp = ControlPlane(4, OMEGA, 2, pool_cap=pool)
    store = ActivationStore(pool, quant=quant)
    ring = [_payload(rng, 6, 1.0) for _ in range(OMEGA)]
    for r in range(2 + pool):
        plan = cp.plan_round(reads=np.zeros(2, bool))
        for key, s in plan.fill:
            ring[s] = store.fill(key)
        for s, key in plan.spill:
            store.spill(key, ring[s])
        for h in range(2):
            if plan.send_mask[h].any():
                ring[int(plan.write_slot[h])] = _payload(rng, 6, 1.0)
        cp.finish_round()
    assert cp.pool_live == pool and len(store) == pool
    return cp, store, ring


def test_state_dict_v3_roundtrip_with_occupied_pool():
    import json
    cp, store, _ = _occupied_plane()
    sd = cp.state_dict()
    json.dumps(sd)                                 # metadata-safe
    assert sd["version_tag"] == 3 and len(sd["pool"]) == 2
    cp2 = ControlPlane(4, OMEGA, 2, pool_cap=2)
    cp2.load_state_dict(sd)
    assert cp2.within_cap and cp2.pool_occupancy == cp.pool_occupancy
    assert cp2.flow.buffered == cp.flow.buffered   # pooled units counted
    # lockstep planning through the drain (fills included)
    quiet = np.zeros((2, 4), bool)
    for r in range(6):
        p1 = cp.plan_round(produce=quiet, reads=np.ones(2, bool))
        p2 = cp2.plan_round(produce=quiet, reads=np.ones(2, bool))
        np.testing.assert_array_equal(p1.read_slot, p2.read_slot)
        np.testing.assert_array_equal(p1.send_mask, p2.send_mask)
        assert p1.fill == p2.fill and p1.spill == p2.spill
        cp.finish_round()
        cp2.finish_round()
    assert cp.n_fills == cp2.n_fills > 0


def test_load_rejects_undersized_pool_and_policy_mismatch():
    cp, _, _ = _occupied_plane()
    sd = cp.state_dict()
    small = ControlPlane(4, OMEGA, 2, pool_cap=1)
    with pytest.raises(ValueError, match="pool_cap"):
        small.load_state_dict(sd)
    other = ControlPlane(4, OMEGA, 2, pool_cap=2, eviction="lru")
    with pytest.raises(ValueError, match="eviction"):
        other.load_state_dict(sd)


def test_v2_snapshot_without_spill_metadata_still_loads():
    """Snapshots from before the tiered store (no pool/eviction keys)
    restore into a pool-capable plane: empty tier, same plans."""
    cp = ControlPlane(3, OMEGA, 2)
    for _ in range(3):
        cp.plan_round(reads=np.array([True, False]))
        cp.finish_round()
    sd = cp.state_dict()
    for k in ("version_tag", "pool_cap", "eviction", "pool",
              "next_pool_key", "slot_touch", "tick", "n_spills",
              "n_fills", "peak_pool"):
        sd.pop(k)                                  # what a v2 writer wrote
    cp2 = ControlPlane(3, OMEGA, 2, pool_cap=4)
    cp2.load_state_dict(sd)
    assert cp2.within_cap and cp2.pool_live == 0
    p1 = cp.plan_round()
    p2 = cp2.plan_round()
    np.testing.assert_array_equal(p1.read_slot, p2.read_slot)
    np.testing.assert_array_equal(p1.send_mask, p2.send_mask)


@pytest.mark.parametrize("quant", [False, True])
def test_checkpoint_extras_roundtrip_occupied_pool(tmp_path, quant):
    """The spilled payloads ride the snapshot's extras.npz next to the
    retention params and restore losslessly (fp32) / within quantization
    tolerance (int8)."""
    import jax
    from repro.checkpoint import store as ckpt
    cp, astore, ring = _occupied_plane(quant=quant)
    originals = {k: astore._pool[k]["payload"] for k in astore.keys}
    extras = {"spill": astore.arrays()}
    ckpt.save(str(tmp_path), 1, {"x": np.arange(3.0)},
              metadata={"control_plane": cp.state_dict(),
                        "spill_store": astore.meta_dict()},
              extras=extras)

    meta = ckpt.restore_metadata(str(tmp_path), 1)
    cp2 = ControlPlane(4, OMEGA, 2, pool_cap=2)
    cp2.load_state_dict(meta["control_plane"])
    astore2 = ActivationStore(2, quant=quant)
    astore2.load_meta(meta["spill_store"])
    assert astore2.keys == astore.keys
    slot_like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in ring[0].items()}
    ex = ckpt.restore_extras(str(tmp_path), 1,
                             {"spill": astore2.like_tree(slot_like)})
    astore2.load_arrays(ex["spill"], dtypes=astore2.slot_dtypes(slot_like))
    for key in list(astore2.keys):
        a = astore.fill(key)
        b = astore2.fill(key)
        np.testing.assert_array_equal(a["labels"], b["labels"])
        # identical stored form (int8 q + scale for quant) -> identical
        # dequantized fill, so the round-trip through the snapshot is
        # lossless relative to the in-memory store either way
        np.testing.assert_array_equal(a["acts"], b["acts"])
        if quant:
            np.testing.assert_array_equal(originals[key]["acts"]["q"],
                                          np.asarray(ex["spill"][str(key)]
                                                     ["acts"]["q"]))
    assert len(astore2) == 0 and astore2.pool_bytes == 0


# ---------------------------------------------------------------------------
# real jit'd step: spill rounds train, pool_cap=0 parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def jit_setup():
    import jax
    from repro.configs import registry
    from repro.core import fedopt_step as F
    from repro.launch.mesh import make_debug_mesh
    a = registry.smoke_config("smollm-135m")
    cfg = F.FedStepConfig(arch=a, l_split=1, n_groups=2, seq_len=16,
                          per_group_batch=4, H=2, omega=OMEGA)
    mesh = make_debug_mesh(1, 1)
    jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh, donate=False)

    def fresh_state():
        return jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0),
                                                  cfg),
                       out_shardings=s_spec)()
    return cfg, jitted, s_spec, fresh_state


def _run_real(cfg, jitted, s_spec, state, *, pool_cap, quant=False,
              rounds=6, wire_store=True):
    import jax
    from repro.core import fedopt_step as F
    cp = ControlPlane(cfg.n_groups, cfg.omega, cfg.H, pool_cap=pool_cap)
    store = ActivationStore(pool_cap, quant=quant)
    kw = {}
    if wire_store:
        kw = dict(store=store, gather_slot=F.gather_act_slot,
                  scatter_slot=lambda st, s, p: F.scatter_act_slot(
                      st, s, p, state_shardings=s_spec))
    ex = RoundExecutor(jitted, cp, window=2,
                       profiles=_StalledProfiles(cfg.n_groups,
                                                 stall_rounds=3), **kw)

    def batch_fn(r, plan):
        batch = F.concrete_train_batch(jax.random.PRNGKey(r), cfg)
        batch.update(plan.batch_fields())
        return batch

    state, hist = ex.run(state, 0, rounds,
                         active_fn=lambda r: np.ones(cfg.n_groups, bool),
                         batch_fn=batch_fn)
    return cp, store, state, hist


def test_real_step_spill_rounds_train_and_drain(jit_setup):
    """ω=2 + pool_cap=2 on the real hybrid step: a stalled server forces
    real host↔mesh slot transfers; training stays finite, the tiered cap
    holds, and the pool drains once reads resume."""
    cfg, jitted, s_spec, fresh_state = jit_setup
    cp, store, state, hist = _run_real(cfg, jitted, s_spec, fresh_state(),
                                       pool_cap=2)
    assert len(hist) == 6
    assert all(np.isfinite(m["d_loss"]) and np.isfinite(m["s_loss"])
               for m in hist)
    assert cp.n_spills > 0 and cp.n_fills == cp.n_spills
    assert store.n_spills == cp.n_spills and len(store) == 0
    assert cp.within_cap
    assert cp.peak_buffered > cfg.omega * cfg.n_groups   # past the ring


def test_real_step_pool_cap_zero_is_bitforbit_storeless(jit_setup):
    """pool_cap=0 with the store wired is bit-for-bit the storeless
    (pre-tiered) executor run: same metric history, same final state."""
    import jax
    cfg, jitted, s_spec, fresh_state = jit_setup
    _, store, st_a, hist_a = _run_real(cfg, jitted, s_spec, fresh_state(),
                                       pool_cap=0, wire_store=True)
    _, _, st_b, hist_b = _run_real(cfg, jitted, s_spec, fresh_state(),
                                   pool_cap=0, wire_store=False)
    assert store.n_spills == store.n_fills == 0
    assert [m["d_loss"] for m in hist_a] == [m["d_loss"] for m in hist_b]
    assert [m["s_loss"] for m in hist_a] == [m["s_loss"] for m in hist_b]
    for la, lb in zip(jax.tree.leaves(st_a), jax.tree.leaves(st_b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
