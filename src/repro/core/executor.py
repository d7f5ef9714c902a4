"""Pipelined round executor: overlap host planning with device execution.

The paper eliminates dependency idle time *on the mesh* (the device and
server halves of the jit'd step have no data dependency), but a naive
driver reintroduces it on the HOST: plan round r, build its batch,
dispatch, then block on the metrics fetch before planning r+1 — the host
and the mesh strictly alternate.  :class:`RoundExecutor` removes that
alternation with a double-buffered loop riding JAX's async dispatch:

* ``step(state, batch)`` returns *futures* immediately; nothing blocks
  until a concrete value is read.  The executor keeps up to ``window``
  dispatched rounds in flight and fetches each round's metrics lazily,
  one drain behind the dispatch frontier — so the host plans round r+1
  and assembles its batch while round r executes on the mesh.
* ``window=1`` drains immediately after every dispatch, which is exactly
  the old synchronous loop — same plans, same batches, same metrics, bit
  for bit.  ``window=2`` is classic double buffering; deeper windows
  trade checkpoint/retention latency for more slack.  Planning consumes
  only host state (ControlPlane bookkeeping + the driver's RNG), never
  device values, and the profile patterns are pure functions of the
  profile seeds (``observe_round`` rescales without perturbing ratios),
  so metric *values* are window-invariant; only wall time changes.

The executor also owns the two host↔mesh consistency duties that the
round loop used to interleave by hand:

* **measured straggler profiles** — each drained round updates a
  :class:`StragglerProfiles` EMA from the measured wall time; the
  resulting ``produce``/``reads`` patterns feed the next
  ``ControlPlane.plan_round`` instead of host-supplied placeholders
  (REFL/Apodotiko-style: schedule from observed speeds, not assumed).
* **per-group state retention** — when a plan retires a dropped group,
  the executor gathers its dev/aux slices into the ControlPlane's
  RetentionStore before dispatch; when a group rejoins, its retained
  params are scattered back on-mesh so it resumes from its OWN state at
  its recorded staleness (the aggregation broadcast is masked via
  ``bcast_mask``, so the dropped rows were never resynced).

The ω-cap invariant is enforced with a real ``RuntimeError`` (asserts
are stripped under ``python -O``), surfacing the violating ring-slot
occupancy.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field

import numpy as np

from repro.analysis import sanitize as _san
from repro.core.handles import HandleRing, RoundHandle
from repro.obs import trace as _tr
from repro.obs.clock import now as _now
from repro.obs.metrics import MetricsRegistry


# ---------------------------------------------------------------------------
# Measured straggler profiles
# ---------------------------------------------------------------------------

class StragglerProfiles:
    """EMA over *measured* per-group step/transfer times + server batch time.

    The profile is observed, never assumed: the event simulator feeds it
    per-device iteration/transfer durations as they complete, and the pod
    executor feeds it each drained round's wall time (SimModel-style cost
    accounting sets the relative per-group speeds; the measurement sets
    the absolute scale — on a lockstep mesh the slowest group binds the
    micro-iteration).  From the EMAs it derives the two patterns
    ``ControlPlane.plan_round`` consumes:

    ``produce(H)`` — (H, G) bool: group g emits at micro-iteration h when
    its cumulative progress at its measured speed crosses a new whole
    batch (the fastest group emits every iteration; a group at half speed
    every other one).

    ``reads(H)`` — (H,) bool: the server consumes a new scheduled batch at
    iteration h when its measured per-batch time keeps up with the
    micro-iteration cadence; a slower server consumes on a strided
    subset (the skipped iterations replay the last slot — Fig. 1(d)'s
    never-idle server, without phantom consumption events).

    Unseeded profiles yield all-true patterns — identical to the
    placeholder defaults, so homogeneous runs are bit-for-bit unchanged.
    """

    def __init__(self, n_groups: int, *, beta: float = 0.25,
                 step_s=None, transfer_s=None, server_s: float | None = None):
        if n_groups < 1:
            raise ValueError(f"need n_groups >= 1, got {n_groups}")
        self.G = n_groups
        self.beta = beta
        self.step_s = None if step_s is None else \
            np.asarray(step_s, float).copy()        # (G,) s / micro-iter
        self.transfer_s = None if transfer_s is None else \
            np.asarray(transfer_s, float).copy()    # (G,) s / act batch
        self.server_s = server_s                    # s / scheduled batch
        self.n_obs = 0

    @classmethod
    def from_sim_model(cls, model, cluster, **kw) -> "StragglerProfiles":
        """Seed from SimModel-style cost accounting (FLOPs / rates); the
        measured observations then correct the seeds in place."""
        step = (model.dev_fwd_flops + model.dev_bwd_flops) / \
            np.asarray(cluster.dev_flops, float)
        transfer = model.act_bytes / np.asarray(cluster.dev_bw, float)
        server = model.srv_flops_per_batch / float(cluster.srv_flops)
        return cls(cluster.K, step_s=step, transfer_s=transfer,
                   server_s=server, **kw)

    # -- observations ---------------------------------------------------
    def _ema(self, old, new):
        return new if old is None else (1.0 - self.beta) * old + \
            self.beta * new

    def observe_group(self, g: int, *, step_s: float | None = None,
                      transfer_s: float | None = None):
        """One measured device event (simulator path): an iteration took
        ``step_s`` and/or an activation upload took ``transfer_s``."""
        if step_s is not None:
            if self.step_s is None:
                self.step_s = np.full(self.G, float(step_s))
            else:
                self.step_s[g] = self._ema(self.step_s[g], float(step_s))
        if transfer_s is not None:
            if self.transfer_s is None:
                self.transfer_s = np.full(self.G, float(transfer_s))
            else:
                self.transfer_s[g] = self._ema(self.transfer_s[g],
                                               float(transfer_s))
        self.n_obs += 1

    def observe_server(self, batch_s: float):
        self.server_s = self._ema(self.server_s, float(batch_s))
        self.n_obs += 1

    def observe_round(self, wall_s: float, H: int):
        """Pod path: one lockstep round of H micro-iterations measured at
        ``wall_s`` on the mesh.  The slowest group binds the lockstep
        cadence, so the measurement rescales the profile to put the
        slowest group at ``wall_s/H`` while preserving the relative
        speeds already observed/seeded (uniform when unseeded).

        ``step_s`` and ``server_s`` are rescaled by the SAME cadence
        factor, so every ratio the derived patterns depend on is an exact
        invariant of the seeds — ``produce``/``reads`` are pure functions
        of the profile's relative speeds, never of wall-clock noise.
        That is what makes pod plans deterministic and window-invariant
        even for heterogeneously seeded profiles."""
        per_iter = max(wall_s / max(H, 1), 1e-12)
        if self.step_s is None:
            self.step_s = np.full(self.G, per_iter)
        else:
            cadence = max(float(self.step_s.max()), 1e-12)
            self.step_s = self._ema(self.step_s,
                                    self.step_s / cadence * per_iter)
            if self.server_s is not None:
                self.server_s = self._ema(self.server_s,
                                          self.server_s / cadence * per_iter)
        if self.server_s is None:
            # the fused step trains the server every micro-iteration: its
            # per-batch time IS the (post-update) cadence, keeping rho=1
            # exactly for any seeding combination
            self.server_s = float(self.step_s.max())
        self.n_obs += 1

    # -- derived patterns ------------------------------------------------
    @staticmethod
    def _stride(rate: np.ndarray, H: int) -> np.ndarray:
        """(H, ...) bool: True at h when cumulative progress at ``rate``
        (batches per micro-iteration, in (0, 1]) crosses a whole batch."""
        h = np.arange(H, dtype=float)[:, None] if rate.ndim else \
            np.arange(H, dtype=float)
        return np.floor((h + 1.0) * rate) > np.floor(h * rate)

    def produce(self, H: int) -> np.ndarray:
        """(H, G) bool straggler emission pattern for plan_round."""
        if self.step_s is None:
            return np.ones((H, self.G), bool)
        t = np.maximum(self.step_s, 1e-12)
        speed = t.min() / t                       # (G,) relative, in (0, 1]
        return self._stride(speed[None, :], H)

    def reads(self, H: int) -> np.ndarray:
        """(H,) bool server-consumption pattern for plan_round."""
        if self.server_s is None or self.step_s is None:
            return np.ones(H, bool)
        cadence = max(float(self.step_s.max()), 1e-12)
        rho = np.asarray(min(1.0, cadence / max(self.server_s, 1e-12)))
        return self._stride(rho, H)

    def summary(self) -> dict:
        """JSON-able snapshot for logs / benchmark records."""
        out = {"n_obs": int(self.n_obs), "beta": self.beta}
        if self.step_s is not None:
            out["step_s"] = [float(v) for v in self.step_s]
        if self.transfer_s is not None:
            out["transfer_s"] = [float(v) for v in self.transfer_s]
        if self.server_s is not None:
            out["server_s"] = float(self.server_s)
        return out


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

@dataclass
class RoundStats:
    """Per-round host/device accounting (times in seconds)."""
    round: int
    plan_s: float = 0.0          # plan_round + retention transfers
    build_s: float = 0.0         # host batch assembly
    in_flight_at_dispatch: int = 0
    hidden_host_s: float = 0.0   # host work done while the mesh was busy
                                 # (set at drain: clamped by the in-flight
                                 # round's observed completion)
    round_wall_s: float = 0.0    # measured device wall (set at drain)
    plan: object = None          # the RoundPlan this round ran under —
                                 # available in the on_metrics drain hook,
                                 # dropped afterwards (memory)
    _host_t0: float = field(default=0.0, repr=False)
    _dispatch_t: float = field(default=0.0, repr=False)


class RoundExecutor:
    """Bounded-window pipelined driver for ``step(state, batch)`` programs.

    Parameters
    ----------
    step : callable(state, batch) -> (state, metrics)
        The jit'd hybrid round (or any async-dispatching stand-in whose
        metric values support ``float()`` lazily).
    cplane : ControlPlane
        Host planner; its ``plan_round``/``finish_round`` bookkeeping is
        committed at DISPATCH time (host order), never at drain time.
    window : int
        Max dispatched-but-undrained rounds.  1 = synchronous (bit-for-bit
        the old loop), 2 = double buffering.
    profiles : StragglerProfiles | None
        Measured straggler profiles; when given, every plan uses
        ``profiles.produce/reads`` and every drained round feeds the EMA.
    gather / scatter : callables for per-group retention
        ``gather(state, g) -> params`` (host copies) and
        ``scatter(state, g, params) -> state``; see
        ``fedopt_step.gather_group_state`` / ``scatter_group_state``.
    store / gather_slot / scatter_slot : tiered activation store wiring
        ``store`` is a ``repro.memory.ActivationStore`` (host spill
        pool); ``gather_slot(state, s) -> payload`` and
        ``scatter_slot(state, s, payload) -> state`` move one ring
        slot host↔mesh (``fedopt_step.gather_act_slot`` /
        ``scatter_act_slot``).  Planned ``fill``/``spill`` moves run at
        the round boundary, inside the in-flight window.  Fills and the
        host-side bookkeeping stay fully async; a SPILL gathers
        pre-round ring content from the previous round's HANDLE (the
        donation-safe ``jnp.copy`` snapshot taken at dispatch) when one
        exists, so deep windows never synchronize on the live ring —
        the live-state ``np.asarray`` sync remains only as the
        window=1 / unwired fallback.  Fills run before spills, so the
        pool never transiently exceeds its cap; a slot filled and
        re-spilled at the same boundary spills the fill payload itself
        (the handle predates the fill).
    registry : ElasticRegistry | None
        Optional roster mirror: drops/rejoins are recorded with the round
        index as the timestamp.
    faults : repro.faults.PodFaultInjector | None
        Chaos plane for pod-mode runs.  At each round head the injector
        may raise ``InjectedCrash`` (server crash at a round boundary —
        the driver persists the fired-crash set and resumes from the
        checkpoint store), mask timed-out groups out of ``active`` (their
        slots are reclaimed by the normal plan_round retire path and the
        retained state rejoins at the recorded α), and veto poisoned
        activation production via the update-validation gate.  ``None``
        (the default) is a strict no-op: no branch of the round loop
        changes.
    """

    def __init__(self, step, cplane, *, window: int = 1, profiles=None,
                 gather=None, scatter=None, registry=None,
                 store=None, gather_slot=None, scatter_slot=None,
                 faults=None, metrics=None):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.step = step
        self.cplane = cplane
        self.window = window
        self.profiles = profiles
        self.gather = gather
        self.scatter = scatter
        self.registry = registry
        self.store = store
        self.gather_slot = gather_slot
        self.scatter_slot = scatter_slot
        self.faults = faults
        self.stats: list[RoundStats] = []
        # -- instruments (pure bookkeeping; legacy names are properties) --
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._g_in_flight = self.metrics.gauge("exec.in_flight")
        self._c_host_s = self.metrics.counter("exec.host_s")
        self._c_hidden_s = self.metrics.counter("exec.hidden_host_s")
        self._c_ckpt_flush = self.metrics.counter("exec.ckpt_flush")
        self._c_ckpt_noflush = self.metrics.counter("exec.ckpt_noflush")
        self._g_handle_bytes = self.metrics.gauge("exec.handle_bytes")
        self._h_plan = self.metrics.histogram("exec.plan_s")
        self._h_build = self.metrics.histogram("exec.build_s")
        self._h_wall = self.metrics.histogram("exec.round_wall_s")
        self._pending: deque = deque()     # (RoundStats, metrics futures)
        self._last_drain_t: float | None = None
        self._last_completion_t: float | None = None
        # -- donation-safe per-round handle ring --------------------------
        # With window > 1 the donated step invalidates older rounds' state
        # references, so every leaf a LATER boundary may need (retention
        # gathers read dev/aux; spill gathers read act_buf) is snapshotted
        # into the ring at dispatch (one fused on-device copy; D2H happens
        # lazily per consumed slice).  Capture is ADAPTIVE so workloads
        # that never consume a handle never pay for one: act_buf is
        # captured only while a spill pool is active, and dev/aux only
        # once churn has been observed — the first churned boundary falls
        # back to the live-state gather (value-identical: the live state
        # at a boundary IS the previous round's output, not yet donated).
        # window=1 consumers always read the live state synchronously.
        self._churn_seen = False
        self.handles = HandleRing(depth=window + 1)
        self._deferred: deque[RoundHandle] = deque()   # no-flush saves
        self._op_table_sent = False

    # legacy counter names, read-only over the registry instruments
    @property
    def peak_in_flight(self) -> int:
        return int(self._g_in_flight.peak)

    @property
    def total_host_s(self) -> float:
        return self._c_host_s.value

    @property
    def hidden_host_s(self) -> float:
        return self._c_hidden_s.value

    @property
    def n_ckpt_flush(self) -> int:
        return int(self._c_ckpt_flush.value)

    @property
    def n_ckpt_noflush(self) -> int:
        return int(self._c_ckpt_noflush.value)

    @property
    def handle_bytes_peak(self) -> int:
        return int(self._g_handle_bytes.peak)

    # ------------------------------------------------------------------
    def run(self, state, start_round: int, end_round: int, *, active_fn,
            batch_fn, on_metrics=None, checkpoint_every: int = 0,
            checkpoint_fn=None, capture_fn=None, checkpoint_flush=None):
        """Drive rounds [start_round, end_round).

        active_fn(r) -> (G,) bool roster for round r (host RNG lives with
        the caller, consumed in dispatch order — window-invariant).
        batch_fn(r, plan) -> jit batch for round r.
        on_metrics(r, metrics, stats) fires at drain, in round order.

        Checkpointing comes in two shapes:

        * **legacy flush** (``capture_fn=None``): the pipeline is fully
          drained at the due boundary and ``checkpoint_fn(r, state)`` is
          called with the live post-round-r state — the synchronous
          loop's save point exactly.
        * **checkpoint-without-flush** (``capture_fn`` given): at the due
          boundary a donation-safe :class:`RoundHandle` of the full state
          is captured at DISPATCH (on-device copies + async D2H), with
          ``capture_fn(r)`` providing the dispatch-time host metadata
          (ControlPlane snapshot, RNG state, extras) so arrays and
          bookkeeping describe the same round.  ``checkpoint_fn(r,
          handle)`` then runs once the handle's copies are ready — rounds
          r+1..r+window stay in flight the whole time, and the save never
          lags more than ``window`` rounds behind (forced at the end of
          the run).  Pass ``checkpoint_flush=True`` to keep the drain
          while still receiving handles (the flush-vs-no-flush A/B).
        """
        flush = (capture_fn is None) if checkpoint_flush is None \
            else bool(checkpoint_flush)
        history: list[dict] = []
        for r in range(start_round, end_round):
            t0 = _now()
            active = np.asarray(active_fn(r), bool)
            H = self.cplane.H
            produce = self.profiles.produce(H) if self.profiles is not None \
                else None
            reads = self.profiles.reads(H) if self.profiles is not None \
                else None
            if self.faults is not None:
                # crash faults raise BEFORE any round-r bookkeeping, so a
                # resumed run replans round r from identical state
                self.faults.on_round_start(r)
                active = self.faults.mask_active(r, active)
                if produce is None:
                    produce = np.ones((H, self.cplane.G), bool)
                produce = self.faults.mask_produce(r, produce, active)
            plan = self.cplane.plan_round(
                active=active, produce=produce, reads=reads,
                lookahead=self.window if self.store is not None else 0)
            state = self._apply_retention(state, plan, r)
            state = self._apply_memory(state, plan, r)
            t1 = _now()
            batch = batch_fn(r, plan)
            t2 = _now()
            if _tr.TRACING:
                _tr.emit_span("host/plan", "plan_round", t0, t1, round=int(r))
                _tr.emit_span("host/build", "build_batch", t1, t2,
                              round=int(r))
                if not self._op_table_sent and hasattr(self.step, "lower"):
                    self._emit_op_table(r, state, batch)
            st = RoundStats(round=r, plan_s=t1 - t0, build_s=t2 - t1,
                            in_flight_at_dispatch=len(self._pending),
                            plan=plan, _host_t0=t0, _dispatch_t=t2)
            state, metrics = self.step(state, batch)
            self.cplane.finish_round(active=active)
            self._check_cap(r)
            if _tr.TRACING:
                _tr.emit_span("host/dispatch", "dispatch", t2, _now(),
                              round=int(r))
            if _san.TRACING:
                _san.emit("exec.round", cp=self.cplane, store=self.store,
                          round=int(r), in_flight=len(self._pending))
            self._pending.append((st, metrics))
            self._g_in_flight.set(len(self._pending))
            due = checkpoint_fn is not None and checkpoint_every and \
                (r + 1) % checkpoint_every == 0
            self._capture_round(r, state, due and not flush, capture_fn)
            while len(self._pending) >= self.window:
                self._drain_one(history, on_metrics)
            if due and flush:
                while self._pending:          # flush: state == round r
                    self._drain_one(history, on_metrics)
                tc0 = _now() if _tr.TRACING else 0.0
                if capture_fn is None:
                    checkpoint_fn(r, state)   # legacy (r, state) contract
                else:
                    # drained pipe: the live tree is stable until the next
                    # dispatch, so the handle wraps it without copying
                    checkpoint_fn(r, RoundHandle.capture(
                        r, state, meta=capture_fn(r), copy=False))
                if _tr.TRACING:
                    _tr.emit_span("host/ckpt", "ckpt_flush", tc0, _now(),
                                  round=int(r))
                self._c_ckpt_flush.inc()
            self._service_deferred(checkpoint_fn, now=r)
        while self._pending:
            self._drain_one(history, on_metrics)
        self._service_deferred(checkpoint_fn, force=True)
        if self.faults is not None:
            self.faults.finalize(end_round)
        return state, history

    # ------------------------------------------------------------------
    def _emit_op_table(self, r: int, state, batch):
        """One ``host/compile`` span naming the round program's parts:
        ``op_scope`` maps each instruction of the compiled step (the
        executable the dispatch runs: ``lower().compile()`` fills the
        jit's own cache) to its named scope (``repro.obs.scopes``) or
        None, the key a device trace's op names are joined on.

        The persistent compilation cache keys programs without their
        metadata, so an executable it holds may carry the op names of
        an older build of the same program; this compile keys them in,
        and the table names the scopes of the code that runs."""
        import jax

        from repro.obs.scopes import op_scopes
        self._op_table_sent = True
        tc0 = _now()
        key = "jax_compilation_cache_include_metadata_in_key"
        prev = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            text = self.step.lower(state, batch).compile().as_text()
        finally:
            jax.config.update(key, prev)
        _tr.emit_span("host/compile", "op_table", tc0, _now(), round=int(r),
                      op_scope=op_scopes(text))

    def _light_keys(self) -> tuple:
        """Leaves the NEXT boundary's consumers may slice from this
        round's handle.  Adaptive: no spill pool and no churn so far
        means no keys — and no per-round copy cost."""
        if self.window <= 1:
            return ()
        keys = []
        if self.gather is not None and self._churn_seen:
            keys += ["dev", "aux"]
        if self.store is not None and \
                getattr(self.cplane, "pool_cap", 0) > 0:
            keys += ["act_buf"]
        return tuple(keys)

    def _capture_round(self, r: int, state, ckpt_due: bool, capture_fn):
        """Dispatch-time handle capture: the light per-round snapshot of
        retention-/spill-referenced leaves into the ring, plus (when a
        no-flush checkpoint is due) a full-state handle with async D2H
        staging queued for the deferred saver."""
        keys = self._light_keys()
        light = keys and isinstance(state, dict)
        if not (light or ckpt_due):
            return
        tc0 = _now() if _tr.TRACING else 0.0
        if ckpt_due:
            meta = capture_fn(r) if capture_fn is not None else None
            h = RoundHandle.capture(r, state, meta=meta, to_host=True)
            self._deferred.append(h)
        if light:
            self.handles.push(RoundHandle.capture(r, state, keys=keys))
        if _tr.TRACING:
            _tr.emit_span("host/capture", "capture_handle", tc0, _now(),
                          round=int(r))
        self._g_handle_bytes.set(
            self.handles.nbytes + sum(h.nbytes for h in self._deferred))

    def _service_deferred(self, checkpoint_fn, *, now=None,
                          force: bool = False):
        """Run deferred no-flush saves whose device copies completed.
        A save is forced once its round falls a full window behind (or
        at the end of the run), bounding checkpoint lag — in-order
        execution means the copy is all but certainly done by then, so
        the force is a consistency backstop, not a stall in practice."""
        while self._deferred:
            h = self._deferred[0]
            if not (force or h.ready()
                    or (now is not None and now - h.round >= self.window)):
                break
            self._deferred.popleft()
            tc0 = _now() if _tr.TRACING else 0.0
            checkpoint_fn(h.round, h)
            if _tr.TRACING:
                _tr.emit_span("host/ckpt", "ckpt_deferred", tc0, _now(),
                              round=int(h.round))
            self._c_ckpt_noflush.inc()

    # ------------------------------------------------------------------
    def _apply_retention(self, state, plan, r: int):
        # the plan's bcast_mask already excludes dropped groups from the
        # aggregation broadcast, so running churn WITHOUT retention wiring
        # would hand a rejoining group phantom-trained params — refuse
        # loudly rather than silently skip the transfers
        cp = self.cplane
        if plan.retire and self.gather is None:
            raise RuntimeError(
                f"round {r} drops groups {plan.retire} but this executor "
                "has no gather fn — per-group retention must be wired "
                "(fedopt_step.gather_group_state/scatter_group_state) for "
                "runs with churn")
        if plan.restore and self.scatter is None:
            raise RuntimeError(
                f"round {r} restores groups {plan.restore} but this "
                "executor has no scatter fn — per-group retention must be "
                "wired for runs with churn")
        if plan.retire or plan.restore:
            # from here on, dev/aux ride the handle ring (this boundary's
            # gathers use the ring when a handle exists, else the live
            # state — the same values either way)
            self._churn_seen = True
        h = self.handles.get(r - 1) if plan.retire else None
        for g in plan.retire:
            if h is not None and h.has("dev"):
                # donation-safe: slice the previous round's handle (its
                # post-step dev/aux copies ARE this boundary's pre-round
                # values) instead of syncing the live, soon-donated state
                cp.retain_group(g, h.group_state(g))
            else:
                cp.retain_group(g, self.gather(state, g))
            if self.registry is not None:
                self.registry.leave(g, t=float(r))
        for g in plan.restore:
            # validate before popping: the error path must not destroy the
            # retained metadata (a fixed-up rerun still needs the entry)
            if cp.retention.params_of(g) is None:
                raise RuntimeError(
                    f"group {g} rejoins but its retained params are "
                    "missing — a resumed run must restore the checkpoint's "
                    "extras into ControlPlane.retention.load_arrays first")
            entry = cp.release_group(g)
            state = self.scatter(state, g, entry["params"])
            if self.registry is not None:
                self.registry.rejoin(g, t=float(r))
        return state

    def _apply_memory(self, state, plan, r: int):
        """Perform the plan's tiered-store moves (host↔mesh ring-slot
        transfers) before dispatch.  Fills first — a fill frees the pool
        entry a same-boundary spill may need — then spills of pre-round
        ring content into the host pool, then plan-neutral prefetch
        staging of lookahead pool entries."""
        if not (plan.fill or plan.spill or plan.prefetch):
            return state
        tm0 = _now() if _tr.TRACING else 0.0
        if self.store is None or self.gather_slot is None or \
                self.scatter_slot is None:
            raise RuntimeError(
                f"round {r} plans spill/fill moves "
                f"(fill={plan.fill}, spill={plan.spill}) but this executor "
                "has no ActivationStore wiring — pass store=/gather_slot=/"
                "scatter_slot= (fedopt_step.gather_act_slot/"
                "scatter_act_slot) for runs with pool_cap > 0")
        filled: dict[int, dict] = {}
        for key, s in plan.fill:
            payload = self.store.fill(key)
            filled[s] = payload
            state = self.scatter_slot(state, s, payload)
        h = self.handles.get(r - 1) if plan.spill else None
        for s, key in plan.spill:
            if s in filled:
                # fill-then-spill of the same slot at one boundary: the
                # handle predates the fill, so the ring content being
                # spilled IS the fill payload just scattered — reuse it
                # (bit-identical to a live gather-after-scatter)
                self.store.spill(key, filled[s])
            elif h is not None and h.has("act_buf"):
                # donation-safe: slice the previous round's ring handle
                # instead of syncing the live (about-to-donate) ring
                self.store.spill(key, h.act_slot(s))
            else:
                self.store.spill(key, self.gather_slot(state, s))
        for key in plan.prefetch:
            self.store.prefetch(key)
        if _tr.TRACING:
            _tr.emit_span("host/memory", "fill_spill", tm0, _now(),
                          round=int(r), fills=len(plan.fill),
                          spills=len(plan.spill),
                          prefetch=len(plan.prefetch))
        return state

    def _check_cap(self, r: int):
        cp = self.cplane
        if not cp.within_cap:
            raise RuntimeError(
                f"activation cap ω={cp.omega}+pool={cp.pool_cap} violated "
                f"after round {r}: {cp.live_slots}/{cp.omega} live ring "
                f"slots (occupancy={cp.slot_occupancy}), "
                f"{cp.pool_live}/{cp.pool_cap} pool entries, flow "
                f"promised={cp.flow.promised} of cap={cp.flow.cap} "
                f"(buffered={cp.flow.buffered}, "
                f"inflight={cp.flow.inflight}, "
                f"tokens={cp.flow.active_tokens})")

    def _drain_one(self, history, on_metrics):
        st, metrics = self._pending.popleft()
        t_fetch = _now()
        m = {k: float(v) for k, v in metrics.items()}   # blocks here only
        t = _now()
        if _tr.TRACING:
            # emitted before the hooks, which may raise out of the drain
            _tr.emit_span("host/drain", "drain", t_fetch, t,
                          round=int(st.round))
        # device-completion estimate: a blocking fetch pins the completion
        # at its return; a non-blocking fetch means the round finished at
        # some unobservable earlier point — fall back to its dispatch time
        # so overlap is only ever credited on evidence (a lower bound:
        # hidden time is never overstated)
        completion = t if (t - t_fetch) > 1e-4 else st._dispatch_t
        # hidden host time for THIS round's plan+build: it overlapped the
        # mesh only while the previously-dispatched round was still
        # executing — clamp by that round's observed completion (a host
        # interval outlasting the device work is exposed, not hidden)
        if st.in_flight_at_dispatch and self._last_completion_t is not None:
            st.hidden_host_s = max(
                0.0, min(st._dispatch_t, self._last_completion_t)
                - st._host_t0)
        self._last_completion_t = completion
        # device wall estimate: dispatch→done is exact when nothing was
        # queued ahead; under pipelining the completion-to-completion gap
        # is the steady-state round time — take the tighter of the two
        wall = t - st._dispatch_t
        if self._last_drain_t is not None:
            wall = min(wall, max(t - self._last_drain_t, 1e-9))
        self._last_drain_t = t
        st.round_wall_s = wall
        if self.profiles is not None:
            self.profiles.observe_round(wall, self.cplane.H)
        self._c_host_s.inc(st.plan_s + st.build_s)
        self._c_hidden_s.inc(st.hidden_host_s)
        self._h_plan.observe(st.plan_s)
        self._h_build.observe(st.build_s)
        self._h_wall.observe(wall)
        self.stats.append(st)
        history.append(m)
        if on_metrics is not None:
            on_metrics(st.round, m, st)
        if _tr.TRACING:
            # the round's accounting, profile update and drain hook
            _tr.emit_span("host/record", "record_round", t, _now(),
                          round=int(st.round))
        # the full RoundPlan (H×G schedule arrays) is only needed through
        # the drain hook; keep the per-round stats list O(scalars) so long
        # runs don't accumulate plans
        st.plan = None

    # ------------------------------------------------------------------
    def summary(self) -> dict:
        """JSON-able overlap accounting for logs / benchmarks.

        Besides whole-run totals, reports STEADY-STATE exposure excluding
        the first ``window`` dispatches: those warmup rounds have no (or
        a partial) in-flight round to hide behind, so including them
        biases deep-window comparisons against exactly the windows they
        are meant to evaluate."""
        n = len(self.stats)
        warmup = min(n, self.window)
        steady = self.stats[warmup:]
        host_steady = sum(s.plan_s + s.build_s for s in steady)
        hidden_steady = sum(s.hidden_host_s for s in steady)
        out = {
            "rounds": n,
            "window": self.window,
            "peak_in_flight": self.peak_in_flight,
            "host_s_total": self.total_host_s,
            "host_s_hidden": self.hidden_host_s,
            "host_s_exposed": self.total_host_s - self.hidden_host_s,
            "host_ms_hidden_per_round":
                1e3 * self.hidden_host_s / max(n, 1),
            "device_s_per_round":
                float(np.mean([s.round_wall_s for s in self.stats]))
                if n else 0.0,
            "warmup_rounds_excluded": warmup,
            "host_s_exposed_steady": host_steady - hidden_steady,
            "hidden_host_frac_steady":
                hidden_steady / host_steady if host_steady > 0 else 0.0,
            "handles": self.handles.summary(),
            "handle_bytes_peak": int(self.handle_bytes_peak),
            "checkpoints": {"flush_saves": self.n_ckpt_flush,
                            "noflush_saves": self.n_ckpt_noflush},
        }
        if self.profiles is not None:
            out["profiles"] = self.profiles.summary()
        if self.store is not None:
            out["memory"] = {**self.cplane.memory_summary(),
                             **self.store.summary()}
        if self.faults is not None:
            out["faults"] = self.faults.report()
        return out
