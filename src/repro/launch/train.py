"""Training driver: FedOptima end-to-end.

Two modes:

``--mode pod``   — the datacenter hybrid step (core/fedopt_step) on a local
                   mesh: every FL device group trains its device-side block
                   on its own non-IID synthetic shard; the server block
                   trains centrally on the activation stream.  Rounds are
                   driven by the pipelined RoundExecutor (core/executor):
                   host planning + batch assembly for round r+1 overlap
                   round r's device execution (--window in-flight rounds;
                   --window 1 is the synchronous loop bit-for-bit), each
                   round is planned by the host ControlPlane
                   (core/control_plane) — the ω-deep activation ring
                   schedule (--omega), flow-control send masks, straggler
                   produce/reads patterns (relative speeds seeded via
                   ``args.profiles``, absolute scale from measured round
                   walls; uniform default ≡ placeholder patterns), and
                   staleness-derived aggregation weights all come from
                   real Alg. 2-4 state.
                   Supports checkpoint/restart (atomic store, retention
                   extras included) and elastic group dropout (--p-drop):
                   dropped groups are retained host-side and rejoin from
                   their OWN params at their recorded staleness (the
                   aggregation broadcast is masked — no resync-everyone).
                   Any ``--arch`` runs at its smoke reduction (--full uses
                   the real config; CPU-feasible only for the smallest
                   archs).

``--mode sim``   — the paper's lab-testbed experiment: the event-driven
                   cluster simulator drives real JAX training in event
                   order (Alg. 1-4), reproducing idle-time/throughput/
                   accuracy behaviour of §6.

Examples::

    python -m repro.launch.train --mode pod --arch smollm-135m --rounds 20
    python -m repro.launch.train --mode sim --devices 8 --duration 600
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.configs import registry
from repro.core import fedopt_step as F
from repro.core.control_plane import ControlPlane
from repro.core.executor import RoundExecutor, StragglerProfiles
from repro.data.partitioner import dirichlet_partition
from repro.data.synthetic import lm_dataset
from repro.faults import (POD_CLASSES, SIM_CLASSES, FaultSchedule,
                          InjectedCrash, PodFaultInjector, UpdateGate,
                          make_fault_schedule)
from repro.fleet import (FleetTrace, SelectionContext, balance_summary,
                         make_selection_policy, make_trace, sample_cluster)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh, n_groups_of
from repro.memory import ActivationStore
from repro.obs.metrics import MetricsRegistry
from repro.runtime.elastic import ElasticRegistry


def _fleet_trace(args, K: int, horizon: float, interval: float,
                 bw=None) -> FleetTrace | None:
    """Resolve --fleet-trace: a JSON artifact path, or a generator kind
    (diurnal | weibull | flaky | uniform) seeded by --seed with scenario
    scales derived from the run horizon.  ``bw`` (scalar or per-device
    array, e.g. a tier-sampled cluster's dev_bw) sets the generated
    trace's base bandwidths so --fleet-tiers heterogeneity survives."""
    spec = getattr(args, "fleet_trace", None)
    if spec is None:
        return None
    if spec.endswith(".json") or os.path.exists(spec):
        trace = FleetTrace.load(spec)
        if trace.K != K:
            raise ValueError(f"--fleet-trace describes {trace.K} devices, "
                             f"this run has {K}")
        return trace
    kw = {}
    if spec == "diurnal":
        kw = dict(day=horizon / 2.0, on_frac=0.6)   # two "days" per run
    elif spec == "weibull":
        kw = dict(on_scale=horizon / 4.0, off_scale=horizon / 8.0)
    if bw is not None and spec != "flaky":   # flaky re-draws bw per tick
        kw["bw"] = bw
    return make_trace(spec, K, horizon, interval=interval,
                      seed=args.seed, **kw)


def _fault_schedule(args, K: int, horizon: float,
                    classes) -> FaultSchedule | None:
    """Resolve --faults: a JSON artifact path (fault-schedule-v1), or
    ``random[:density]`` — a seeded schedule over the mode's supported
    fault classes (sim: time axis seconds; pod: time axis round index)."""
    spec = getattr(args, "faults", None)
    if spec is None:
        return None
    if spec.endswith(".json") or os.path.exists(spec):
        return FaultSchedule.load(spec)
    kind, _, dens = spec.partition(":")
    if kind != "random":
        raise ValueError(f"unknown --faults spec {spec!r}: expected a "
                         "schedule JSON path or 'random[:density]'")
    return make_fault_schedule(K, horizon, seed=args.seed, classes=classes,
                               density=float(dens) if dens else 1.0)


# ---------------------------------------------------------------------------
# pod mode
# ---------------------------------------------------------------------------

def _group_streams(cfg: F.FedStepConfig, seed: int = 0):
    """Per-group non-IID token streams (distinct synthetic grammars)."""
    streams = []
    for g in range(cfg.n_groups):
        toks = lm_dataset(200_000, cfg.arch.vocab, seed=seed + g,
                          structure=0.75 + 0.2 * (g % 3) / 2)
        streams.append(toks)
    return streams


def _make_batch(cfg: F.FedStepConfig, streams, rng: np.random.Generator,
                plan, put=None):
    """One round's inputs: per-group token shards + the ControlPlane's
    schedule/weight fields (ring slots, send masks, staleness weights).

    ``put`` (the jit step's batch sharding dict) pre-stages the host
    arrays with one ``jax.device_put`` — the H2D transfers start
    immediately and overlap the in-flight rounds instead of riding the
    dispatch.  Values are bit-identical to the lazy ``jnp.asarray``
    default; only when the copy happens changes."""
    G, H, b, S = cfg.n_groups, cfg.H, cfg.micro_batch, cfg.seq_len
    tokens = np.zeros((G, H, b, S), np.int32)
    labels = np.zeros((G, H, b, S), np.int32)
    for g in range(G):
        n = len(streams[g]) - S - 1
        idx = rng.integers(0, n, size=(H, b))
        for h in range(H):
            for i in range(b):
                j = idx[h, i]
                tokens[g, h, i] = streams[g][j:j + S]
                labels[g, h, i] = streams[g][j + 1:j + S + 1]
    batch = {"tokens": tokens, "labels": labels}
    batch.update(plan.batch_fields())
    arch = cfg.arch
    if arch.frontend_len:
        batch["frontend"] = np.zeros(
            (G, H, b, arch.frontend_len, arch.d_model),
            np.dtype(cfg.param_dtype))
    if put is not None:
        return jax.device_put(batch, {k: put[k] for k in batch})
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _pipeline_window(args) -> int:
    """Resolve the pipeline window with explicit validation: an unset
    attribute (programmatic bare Namespace) defaults to 2; anything set
    must be an int >= 1 — ``--window 0`` is an error, not a silent remap
    to the default (``or 2`` used to swallow it)."""
    w = getattr(args, "window", None)
    if w is None:
        return 2
    w = int(w)
    if w < 1:
        raise ValueError(
            f"--window must be >= 1, got {w}: 1 is the synchronous loop, "
            ">= 2 keeps that many rounds in flight")
    return w


def run_pod(args) -> dict:
    arch = registry.smoke_config(args.arch) if not args.full \
        else registry.get(args.arch)
    mesh = make_debug_mesh(args.mesh_data, args.mesh_model)
    G = n_groups_of(mesh) * args.groups_per_shard
    # control-plane knobs default for programmatic callers' bare Namespaces
    omega = getattr(args, "omega", None) or 1
    window = _pipeline_window(args)
    H = getattr(args, "H", None) or 4
    # tiered-store knobs (pod default: spill disabled — bit-for-bit the
    # hard-ω ring; raise --pool-cap to admit past the ring)
    pool_cap = getattr(args, "pool_cap", None)
    pool_cap = 0 if pool_cap is None else pool_cap
    spill_quant = bool(getattr(args, "spill_quant", False))
    eviction = getattr(args, "eviction", None) or "share"
    cfg = F.FedStepConfig(
        arch=arch, l_split=args.l_split or F.default_l_split(arch),
        n_groups=G, seq_len=args.seq_len, per_group_batch=args.batch,
        H=H, lr_d=args.lr_d, lr_s=args.lr_s,
        server_opt=args.server_opt, omega=omega,
        use_kernel=getattr(args, "use_kernel", False))
    jitted, _, s_spec, b_spec = F.jit_train_step(cfg, mesh, donate=True)
    cplane = ControlPlane(G, omega, cfg.H,
                          policy=getattr(args, "policy", "counter"),
                          max_delay=getattr(args, "max_delay", 16),
                          pool_cap=pool_cap, eviction=eviction)
    # one registry backs the executor, spill store, and fault gate — the
    # per-round dump and final snapshot see every component's instruments
    reg = MetricsRegistry()
    act_store = ActivationStore(pool_cap, quant=spill_quant, metrics=reg)

    # chaos plane (pod axis: round index) — built before resume so a
    # restarted run replays the SAME schedule, minus already-fired crashes
    faults_sched = _fault_schedule(args, G, float(max(args.rounds, 1)),
                                   POD_CLASSES)
    injector, fired_path = None, None
    if faults_sched is not None:
        needs_store = any(e.cls in ("server_crash", "torn_checkpoint")
                          for e in faults_sched.events)
        if needs_store and not args.ckpt_dir:
            raise ValueError(
                "--faults schedules server_crash/torn_checkpoint events: "
                "--ckpt-dir is required so fired crash boundaries persist "
                "across restarts and recovery has a store to resume from")
        fired = ()
        if args.ckpt_dir:
            # a crash can fire before the first snapshot creates the dir
            os.makedirs(args.ckpt_dir, exist_ok=True)
            fired_path = os.path.join(args.ckpt_dir, "FAULTS_FIRED.json")
            if os.path.exists(fired_path):
                with open(fired_path) as f:
                    fired = tuple(json.load(f))
        injector = PodFaultInjector(faults_sched,
                                    gate=UpdateGate(metrics=reg),
                                    fired_crashes=fired)

    like = jax.eval_shape(lambda: F.init_train_state(
        jax.random.PRNGKey(args.seed), cfg))
    start_round = 0
    resumed_meta = None
    verified_step = None
    if args.ckpt_dir:
        verified_step, skipped = store.latest_verified_step(args.ckpt_dir)
        for bad_step, reason in skipped:
            print(f"resume: skipping torn snapshot step {bad_step}: "
                  f"{reason}")
    if verified_step is not None:
        start_round = verified_step
        state = store.restore(args.ckpt_dir, start_round, like)
        ring = jax.tree.leaves(state["act_buf"])[0].shape[0]
        if ring != omega:
            raise ValueError(
                f"checkpoint has an ω={ring} activation ring but "
                f"--omega={omega}; out-of-range slot indices would be "
                f"silently clamped — restart with --omega {ring}")
        meta = store.restore_metadata(args.ckpt_dir, start_round)
        if "control_plane" in meta:
            # restore the host plan with the ring it describes, or slot
            # occupancy and staleness history silently reset on resume
            cplane.load_state_dict(meta["control_plane"])
            slice_like = {
                k: jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                    like[k]) for k in ("dev", "aux")}
            if "spill_store" in meta:
                # v3 layout: extras.npz is namespaced {"retention", "spill"}
                # — spilled ring slots ride the snapshot next to the
                # retained per-group params
                act_store.load_meta(meta["spill_store"])
                if sorted(cplane.pool_occupancy) != act_store.keys:
                    raise ValueError(
                        f"snapshot pool bookkeeping ({sorted(cplane.pool_occupancy)}) "
                        f"disagrees with its spill store ({act_store.keys})")
                like_extras, slot_like = {}, None
                if len(cplane.retention):
                    like_extras["retention"] = {
                        str(g): slice_like
                        for g in cplane.retention.groups}
                if len(act_store):
                    slot_like = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                        like["act_buf"])
                    like_extras["spill"] = act_store.like_tree(slot_like)
                if like_extras:
                    ex = store.restore_extras(args.ckpt_dir, start_round,
                                              like_extras)
                    if "retention" in like_extras:
                        cplane.retention.load_arrays(ex["retention"])
                    if "spill" in like_extras:
                        act_store.load_arrays(
                            ex["spill"],
                            dtypes=act_store.slot_dtypes(slot_like))
            elif len(cplane.retention):
                # v2 layout: extras.npz holds the retention tree bare
                cplane.retention.load_arrays(store.restore_extras(
                    args.ckpt_dir, start_round,
                    {str(g): slice_like for g in cplane.retention.groups}))
        state = jax.device_put(state, s_spec)
        resumed_meta = meta
        print(f"resumed from round {start_round}")
    else:
        # the key is an argument, not a constant, so the compiled init
        # (minutes at full width) is one cache entry for every seed
        state = jax.jit(lambda key: F.init_train_state(key, cfg),
                        out_shardings=s_spec)(jax.random.PRNGKey(args.seed))

    streams = _group_streams(cfg, seed=args.seed)
    rng = np.random.default_rng(args.seed + start_round)
    if resumed_meta and "rng_state" in resumed_meta:
        # bit-exact continuation: restore the batch RNG mid-stream instead
        # of reseeding (reseeding resumes a DIFFERENT run than the one
        # that crashed — same distribution, different batches)
        rng.bit_generator.state = resumed_meta["rng_state"]

    # Fleet emulation (repro.fleet): --fleet-trace maps one trace tick to
    # one round (the pod roster for round r is trace row r, wrapping past
    # the horizon); --fleet-tiers samples per-group capabilities whose
    # relative speeds seed the straggler profiles; --selection picks the
    # participating cohort from each round's available groups, fed the
    # live Alg. 3 consumption counters + staleness accounting.
    fleet = _fleet_trace(args, G, horizon=float(max(args.rounds, 1)),
                         interval=1.0)
    sel = make_selection_policy(getattr(args, "selection", None),
                                seed=args.seed)
    caps = None
    if getattr(args, "fleet_tiers", None):
        tier_cluster = sample_cluster(G, args.fleet_tiers, seed=args.seed)
        caps = np.asarray(tier_cluster.dev_flops, float)

    registry_ = ElasticRegistry()
    for g in range(G):       # one pod "device" per mesh group
        registry_.join(flops_per_s=float(caps[g]) if caps is not None
                       else 1.0, bandwidth=1.0)
    # Straggler profiles: the lockstep mesh can only measure the round's
    # absolute scale, so RELATIVE group speeds come from the seeds —
    # programmatic callers inject a cost-model-seeded profile via
    # args.profiles (e.g. StragglerProfiles.from_sim_model), and
    # --fleet-tiers seeds one from the sampled capability mix (step time
    # inversely proportional to flops) to activate straggler-aware
    # produce/reads planning; the unseeded default is uniform, whose
    # patterns equal the placeholder defaults (that degeneracy is what
    # keeps homogeneous runs bit-for-bit reproducible).
    profiles = getattr(args, "profiles", None)
    if profiles is None and caps is not None:
        profiles = StragglerProfiles(G, step_s=1.0 / caps)
    if profiles is None:
        profiles = StragglerProfiles(G)
    if resumed_meta and "profiles" in resumed_meta:
        # restore the measured EMAs so the resumed run plans the same
        # produce/reads patterns the crashed run would have
        ps = resumed_meta["profiles"]
        profiles = StragglerProfiles(
            G, beta=ps.get("beta", 0.25), step_s=ps.get("step_s"),
            transfer_s=ps.get("transfer_s"), server_s=ps.get("server_s"))
        profiles.n_obs = int(ps.get("n_obs", 0))
    executor = RoundExecutor(
        jitted, cplane, window=window,
        profiles=profiles,
        gather=F.gather_group_state,
        scatter=lambda st, g, p: F.scatter_group_state(
            st, g, p, state_shardings=s_spec),
        registry=registry_,
        store=act_store,
        gather_slot=F.gather_act_slot,
        scatter_slot=lambda st, s, p: F.scatter_act_slot(
            st, s, p, state_shardings=s_spec),
        faults=injector, metrics=reg)

    if sel is not None and resumed_meta and "selection_rng" in resumed_meta \
            and hasattr(sel, "_rng"):
        sel._rng.bit_generator.state = resumed_meta["selection_rng"]

    def active_fn(r):
        if fleet is not None:
            roster = fleet.roster(r)
        else:
            roster = rng.random(G) >= args.p_drop
            if not roster.any():
                roster[rng.integers(0, G)] = True
        if sel is not None and not sel.trivial and roster.any():
            ctx = SelectionContext(t=float(r),
                                   counters=cplane.scheduler.counters,
                                   staleness=cplane.version - cplane.versions,
                                   capability=caps)
            chosen = sel.select(np.flatnonzero(roster), ctx)
            roster = np.zeros(G, bool)
            roster[np.asarray(chosen, int)] = True
        return roster

    def batch_fn(r, plan):
        return _make_batch(cfg, streams, rng, plan, put=b_spec)

    t0 = time.time()
    metrics_every = int(getattr(args, "metrics_every", 0) or 0)

    def on_metrics(r, m, st):
        nonlocal t0
        if (r + 1) % args.log_every == 0:
            tok_s = cfg.global_batch * cfg.seq_len * args.log_every / \
                (time.time() - t0)
            n_active = int(np.sum(np.asarray(st.plan.bcast_mask) > 0.5))
            print(f"round {r+1:4d}  d_loss {m['d_loss']:.4f}  "
                  f"s_loss {m['s_loss']:.4f}  active {n_active}/{G}"
                  f"  {tok_s:,.0f} tok/s")
            t0 = time.time()
        if metrics_every and (r + 1) % metrics_every == 0:
            print(executor.metrics.dump_line(prefix=f"[round {r+1}]"))

    def capture_fn(r):
        """Dispatch-time host bookkeeping for round r's checkpoint —
        snapshotted at the SAME boundary as the handle's arrays, so the
        eventual (possibly deferred) save describes exactly round r.
        The extras dicts are built fresh here and the payload pytrees
        they reference are never mutated in place (retention release
        pops; store fill pops), so a later save sees round-r values."""
        # v3 extras layout: retention params and spilled ring slots ride
        # the same atomic snapshot under their own namespaces
        extras = {}
        if cplane.retention.arrays():
            extras["retention"] = cplane.retention.arrays()
        if act_store.arrays():
            extras["spill"] = act_store.arrays()
        metadata = {"round": r + 1, "arch": arch.name,
                    "control_plane": cplane.state_dict(),
                    "spill_store": act_store.meta_dict(),
                    # host-loop continuation state: what a resumed run
                    # needs for bit-exact replay past this snapshot
                    "rng_state": rng.bit_generator.state,
                    "profiles": profiles.summary()}
        if sel is not None and hasattr(sel, "_rng"):
            metadata["selection_rng"] = sel._rng.bit_generator.state
        return {"metadata": metadata, "extras": extras or None}

    def checkpoint_fn(r, handle):
        """Save round r from its RoundHandle: donation-safe host copies
        of the captured arrays + the dispatch-time metadata.  In the
        no-flush path this runs while rounds r+1..r+window are still in
        flight; in the flush path the handle wraps the drained live
        state — the save itself is identical."""
        meta = handle.meta
        store.save(args.ckpt_dir, r + 1, handle.host_tree(),
                   metadata=meta["metadata"], extras=meta["extras"])
        if injector is not None:
            injector.on_checkpoint(r, args.ckpt_dir, r + 1)

    try:
        state, history = executor.run(
            state, start_round, args.rounds,
            active_fn=active_fn, batch_fn=batch_fn, on_metrics=on_metrics,
            checkpoint_every=args.ckpt_every if args.ckpt_dir else 0,
            checkpoint_fn=checkpoint_fn if args.ckpt_dir else None,
            capture_fn=capture_fn if args.ckpt_dir else None,
            checkpoint_flush=bool(getattr(args, "ckpt_flush", False)))
    except InjectedCrash as crash:
        # persist the fired boundary FIRST, then die: the restarted run
        # resumes from the newest verified snapshot and must not re-fire
        if fired_path is not None:
            with open(fired_path, "w") as f:
                json.dump(sorted(injector.fired_crashes), f)
        print(f"faults: {crash} (fired boundaries "
              f"{sorted(injector.fired_crashes)}) — restart to resume")
        raise
    xs = executor.summary()
    print(f"checkpoints: flush_saves={xs['checkpoints']['flush_saves']} "
          f"noflush_saves={xs['checkpoints']['noflush_saves']}  "
          f"handle_bytes_peak={xs['handle_bytes_peak']}")
    mem = {**cplane.memory_summary(), **act_store.summary()}
    print(f"memory: spills {mem['spills']}  fills {mem['fills']}  "
          f"evictions {mem['evictions']}  peak pool "
          f"{mem['peak_pool']}/{pool_cap} slots "
          f"({mem['peak_pool_bytes']/1e6:.1f} MB"
          f"{', int8 spill' if spill_quant else ''})")
    consumed = np.array([cplane.consumption.get(g, 0) for g in range(G)],
                        np.int64)
    bal = balance_summary(consumed)
    print(f"contribution balance: consumed={consumed.tolist()}  "
          f"gini={bal['gini']:.3f}  cv={bal['cv']:.3f}  "
          f"participants={bal['participants']}/{G}")
    if fleet is not None:
        absences = sum(i.absences for i in registry_.devices.values())
        print(f"fleet: trace={fleet.meta.get('kind', 'custom')}  "
              f"roster events={absences}  "
              f"selection={sel.describe() if sel else 'all'}")
    out = {"history": history, "final": history[-1] if history else None,
           "state": state,
           "round_wall_s": [s.round_wall_s for s in executor.stats],
           "executor": xs, "memory": mem,
           "consumed": consumed.tolist(), "contribution_balance": bal,
           "registry": executor.metrics.snapshot()}
    if metrics_every:
        print(executor.metrics.dump_line(prefix="[final]"))
    if getattr(args, "metrics_out", None):
        executor.metrics.write_jsonl(args.metrics_out,
                                     extra={"mode": "pod",
                                            "rounds": args.rounds})
    if injector is not None:
        fr = injector.report()
        print(f"faults: injected={fr['injected']}  "
              f"recovered={fr['recovered']}  matched={fr['matched']}")
        out["faults"] = fr
    return out


# ---------------------------------------------------------------------------
# sim mode (paper testbed)
# ---------------------------------------------------------------------------

def run_sim(args) -> dict:
    from repro.core.learning import FedOptimaLearner, ModelAdapter
    from repro.core.simulation import (SimModel, heterogeneous_cluster,
                                       simulate_fedoptima)
    from repro.data.pipeline import DeviceDataset
    from repro.data.synthetic import classification_dataset
    from repro.models import cnn

    # sim-mode control-plane knobs: honor the CLI flags (the paper's lab
    # defaults ω=8, H=10 apply only when the flags are left unset)
    omega = getattr(args, "omega", None) or 8
    H = getattr(args, "H", None) or 10
    policy = getattr(args, "policy", "counter")
    max_delay = getattr(args, "max_delay", 16)
    # sim default pool = ω: the lab testbed showcases the tiered budget
    # (2ω admission), versus the pod default of 0 (spill off)
    pool_cap = getattr(args, "pool_cap", None)
    pool_cap = omega if pool_cap is None else pool_cap

    data = classification_dataset(4096, 10, img_size=16, seed=args.seed)
    parts = dirichlet_partition(data.y, args.devices, alpha=0.5,
                                seed=args.seed)
    mcfg = cnn.vgg5_config(n_classes=10, img_size=16)
    adapter = ModelAdapter(cnn, mcfg)
    datasets = [DeviceDataset(data.x[ix], data.y[ix], batch=32, seed=g)
                for g, ix in enumerate(parts)]
    learner = FedOptimaLearner(adapter, datasets, l_split=1,
                               lr_d=0.05, lr_s=0.05)
    sim_model = SimModel(dev_fwd_flops=2e9, dev_bwd_flops=4e9,
                         full_fwd_flops=6e9, srv_flops_per_batch=1.2e10,
                         act_bytes=2e6, dev_model_bytes=1e6,
                         full_model_bytes=4e6, batch_size=32)
    # fleet emulation: --fleet-tiers samples the cluster from a weighted
    # capability mix (default: the paper's 4 uniform speed groups), and
    # --fleet-trace/--selection drive availability + cohort choice
    if getattr(args, "fleet_tiers", None):
        cluster = sample_cluster(args.devices, args.fleet_tiers,
                                 seed=args.seed)
    else:
        cluster = heterogeneous_cluster(args.devices)
    fleet = _fleet_trace(args, args.devices, args.duration,
                         interval=max(args.duration / 12.0, 1.0),
                         bw=cluster.dev_bw)
    control = ControlPlane.for_sim(args.devices, omega, policy=policy,
                                   max_delay=max_delay, pool_cap=pool_cap)
    profiles = StragglerProfiles(args.devices)
    faults_sched = _fault_schedule(args, args.devices, args.duration,
                                   SIM_CLASSES)
    metrics = simulate_fedoptima(sim_model, cluster, duration=args.duration,
                                 omega=omega, H=H, policy=policy,
                                 max_delay=max_delay, pool_cap=pool_cap,
                                 seed=args.seed, fleet=fleet,
                                 selection=getattr(args, "selection", None),
                                 hooks=learner, control=control,
                                 profiles=profiles, faults=faults_sched,
                                 metrics_every=float(
                                     getattr(args, "metrics_every", 0) or 0))
    xte, yte = data.x[:512], data.y[:512]
    acc = learner.eval_accuracy(xte, yte)
    # the measured per-device profiles drive a straggler-aware plan: slow
    # devices are scheduled fewer emissions per round, the server reads at
    # its measured cadence — the same patterns run_pod feeds per round
    produce, reads = profiles.produce(H), profiles.reads(H)
    print(f"sim: {args.devices} devices, {args.duration}s simulated | "
          f"srv idle {metrics.srv_idle_frac:.1%}  dev idle "
          f"{metrics.dev_idle_frac:.1%}  throughput {metrics.throughput:.0f} "
          f"samples/s  train-set acc {acc:.3f}")
    print(f"measured straggler profile: emissions/round "
          f"{produce.sum(axis=0).tolist()} of H={H}, server reads "
          f"{int(reads.sum())}/{H}")
    mem = control.memory_summary()
    print(f"memory: tiered budget ω={omega}+pool={pool_cap}, peak buffered "
          f"{mem['peak_buffered']} batches, spills {mem['spills']}  "
          f"fills {mem['fills']}")
    bal = metrics.contribution_balance()
    print(f"contribution balance: consumed={metrics.dev_consumed.tolist()}  "
          f"gini={bal['gini']:.3f}  cv={bal['cv']:.3f}  "
          f"participants={bal['participants']}/{args.devices}")
    steady = metrics.steady_summary()
    if steady:
        print(f"steady state (post-warmup {steady['warmup_s']:.1f}s): "
              f"srv idle {steady['srv_idle_frac_steady']:.1%}  dev idle "
              f"{steady['dev_idle_frac_steady']:.1%}  throughput "
              f"{steady['throughput_steady']:.0f} samples/s")
    if metrics.registry is not None:
        absences = sum(i.absences
                       for i in metrics.registry.devices.values())
        kind = fleet.meta.get("kind", "custom") if fleet is not None \
            else "identity"     # selection-only runs get an identity trace
        print(f"fleet: trace={kind}  roster events={absences}  active now "
              f"{len(metrics.registry.active_ids)}/{args.devices}")
    reg = metrics.to_registry()
    out = {"accuracy": acc, "srv_idle": metrics.srv_idle_frac,
           "dev_idle": metrics.dev_idle_frac,
           "throughput": metrics.throughput,
           "profiles": profiles.summary(),
           "produce_per_round": produce.sum(axis=0).tolist(),
           "reads_per_round": int(reads.sum()),
           "memory": mem,
           "consumed": metrics.dev_consumed.tolist(),
           "contribution_balance": bal,
           "steady": steady, "registry": reg.snapshot()}
    if getattr(args, "metrics_every", 0):
        print(reg.dump_line(prefix="[final]"))
    if getattr(args, "metrics_out", None):
        reg.write_jsonl(args.metrics_out,
                        extra={"mode": "sim", "duration": args.duration,
                               "devices": args.devices})
    if metrics.faults is not None:
        fr = metrics.faults
        print(f"faults: injected={fr['injected']}  "
              f"recovered={fr['recovered']}  matched={fr['matched']}")
        out["faults"] = fr
    return out


def build_parser() -> argparse.ArgumentParser:
    """The CLI; ``build_parser().parse_args([...])`` gives programmatic
    callers the same defaults as the command line."""
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", default="pod", choices=("pod", "sim"))
    p.add_argument("--arch", default="smollm-135m")
    p.add_argument("--full", action="store_true",
                   help="use the full config (not the smoke reduction)")
    p.add_argument("--rounds", type=int, default=10)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch", type=int, default=8, dest="batch",
                   help="sequences per group per round")
    p.add_argument("--H", type=int, default=None,
                   help="local iterations per round (pod default 4, "
                        "sim default 10)")
    p.add_argument("--l-split", type=int, default=0)
    p.add_argument("--lr-d", type=float, default=0.05)
    p.add_argument("--lr-s", type=float, default=0.05)
    p.add_argument("--server-opt", default="sgd", choices=("sgd", "adamw"))
    p.add_argument("--omega", type=int, default=None,
                   help="activation cap ω (scheduled batches, Eq. 3; pod "
                        "ring default 1, sim default 8)")
    p.add_argument("--pool-cap", type=int, default=None, dest="pool_cap",
                   help="host spill-pool depth backing the ω ring (tiered "
                        "activation store, repro.memory): admission runs "
                        "against ω + pool_cap.  Pod default 0 (spill off, "
                        "bit-for-bit the hard-ω ring), sim default ω")
    p.add_argument("--spill-quant", action="store_true", dest="spill_quant",
                   help="int8-quantize spilled activation slots (per-tensor"
                        "; labels/tokens stay exact) — pool bytes / ~4 for "
                        "a bounded dequantization error on refill")
    p.add_argument("--eviction", default="share", choices=("share", "lru"),
                   help="spill-victim policy: 'share' protects least-"
                        "consumption-share contributions (scheduler-aware)"
                        ", 'lru' evicts the least-recently-touched slot")
    p.add_argument("--window", type=int, default=2,
                   help="pipelined rounds in flight (pod mode): 1 = "
                        "synchronous host loop, 2 = double-buffered "
                        "planning (host plan/batch-build overlaps device "
                        "execution; metric values are window-invariant)")
    p.add_argument("--policy", default="counter", choices=("counter", "fifo"),
                   help="Task Scheduler consumption policy (Alg. 3)")
    p.add_argument("--max-delay", type=int, default=16,
                   help="staleness cap D for aggregation (Alg. 4)")
    p.add_argument("--use-kernel", action="store_true",
                   help="run attention/SSD through the fused Pallas kernels "
                        "(differentiable; interpret mode on CPU)")
    p.add_argument("--mesh-data", type=int, default=1)
    p.add_argument("--mesh-model", type=int, default=1)
    p.add_argument("--groups-per-shard", type=int, default=4)
    p.add_argument("--p-drop", type=float, default=0.0)
    p.add_argument("--fleet-trace", default=None, dest="fleet_trace",
                   help="device availability trace (repro.fleet): a JSON "
                        "artifact saved by FleetTrace.save, or a generator "
                        "kind — diurnal | weibull | flaky | uniform — "
                        "seeded by --seed.  Sim mode drives join/leave "
                        "from trace ticks; pod mode maps one tick to one "
                        "round (trace-driven churn exercises per-group "
                        "retention end-to-end, superseding --p-drop)")
    p.add_argument("--fleet-tiers", default=None, dest="fleet_tiers",
                   help="capability-tier mix for the fleet, e.g. "
                        "'low,mid,high,premium' or 'low:3,premium:1' "
                        "(repro.fleet.devices).  Sim mode samples the "
                        "cluster from it; pod mode seeds the straggler "
                        "profiles with the sampled relative speeds")
    p.add_argument("--selection", default=None,
                   help="participant-selection policy: random | refl | "
                        "score, optionally ':fraction' (e.g. refl:0.5 "
                        "runs the most-stale half each tick).  Fed the "
                        "Alg. 3 consumption counters + staleness "
                        "accounting; default: every available device")
    p.add_argument("--faults", default=None,
                   help="chaos plane (repro.faults): a fault-schedule JSON "
                        "path, or 'random[:density]' — a seeded schedule "
                        "of corrupt uploads, duplicates, delays, device "
                        "timeouts, server crashes and checkpoint tears.  "
                        "Sim mode injects at the event seams (time axis "
                        "seconds); pod mode at round boundaries (crash/"
                        "tear faults need --ckpt-dir; an injected crash "
                        "kills the run — rerun the same command to resume)")
    p.add_argument("--sanitize", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="run under the protocol sanitizer "
                        "(repro.analysis.sanitize): control-plane events "
                        "are checked online against the invariant "
                        "catalogue and any violation aborts the run with "
                        "the offending event window")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="record a span trace of the run and export Chrome "
                        "trace-event JSON to PATH (open in Perfetto or "
                        "chrome://tracing).  Pod mode traces the host loop "
                        "on the wall clock; sim mode traces per-device/"
                        "server/network lanes in simulated time.  Off = "
                        "zero-instrumentation run (bit-identical)")
    p.add_argument("--metrics-every", type=float, default=0,
                   dest="metrics_every", metavar="N",
                   help="periodically dump the unified metrics registry: "
                        "every N rounds (pod) or every N simulated "
                        "seconds (sim); 0 = final summary only")
    p.add_argument("--metrics-out", default=None, dest="metrics_out",
                   metavar="PATH",
                   help="append the final metrics-registry snapshot to "
                        "PATH as one JSON line")
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=5)
    p.add_argument("--ckpt-flush", action="store_true", dest="ckpt_flush",
                   help="drain the pipeline at every checkpoint boundary "
                        "(the pre-handle saver) instead of the default "
                        "checkpoint-without-flush, which saves round r "
                        "from its dispatch-time handle while rounds "
                        "r+1..r+window stay in flight")
    p.add_argument("--log-every", type=int, default=1)
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--duration", type=float, default=300.0)
    p.add_argument("--seed", type=int, default=0)
    return p


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    run = run_pod if args.mode == "pod" else run_sim

    def _run_traced():
        if not args.trace:
            run(args)
            return
        from repro.obs.trace import Tracer, traced
        tracer = Tracer(domain="wall" if args.mode == "pod" else "sim")
        with traced(tracer):
            run(args)
        tracer.export_chrome(args.trace)
        print(f"trace: {len(tracer.spans)} spans on "
              f"{len(tracer.lanes())} lanes -> {args.trace}")

    # the sanitizer and tracer seams are independent and compose
    if args.sanitize:
        from repro.analysis.sanitize import sanitized
        with sanitized() as san:
            _run_traced()
        rep = san.report()
        print(f"sanitizer: {rep['events']} events checked, "
              f"{rep['n_violations']} violations")
    else:
        _run_traced()


if __name__ == "__main__":
    main()
