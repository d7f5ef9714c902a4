"""Datacenter-scale FedOptima: the paper's split-training pipeline as one
pjit program per (arch × shape × mesh).

Mapping: an FL "device" becomes a *device group* — one index of the
mesh's data-parallel axes (pod × data), owning a ``model``-axis (TP) slice
of chips.  Each group trains its own copy of the device-side block (params
stacked on a leading group axis, sharded over dp) on its local non-IID
shard, with gradients from the *auxiliary network* only — no
gradient ever flows server→device (``stop_gradient`` on the activation
hand-off).  The server-side block is ONE centrally-trained model (TP over
``model``, FSDP over dp) consuming the activation stream.

Idle-time elimination carries over: the server trains on *previously
scheduled* activations (the paper's queue semantics), so the device half
and the server half of the XLA program have no data dependency, which is
Fig. 1(d) at pod scale.

The activation hand-off is an ω-deep ring of scheduled batches (Eq. 3's
bounded buffer realized on-mesh): ``state["act_buf"]`` holds ω slots, each
one micro-iteration's combined (all-groups) activation batch.  The *host
control plane* (core/control_plane.py — TaskScheduler + FlowController +
staleness accounting) plans each round and feeds the jit'd step three
schedule fields per micro-iteration:

    read_slot[h]    which ring slot the server trains on (Alg. 3's pick)
    write_slot[h]   which slot this iteration's emission lands in
    send_mask[h,g]  which groups' rows refresh in that slot (flow-control
                    token grants; unsent rows keep the slot's old content)

plus two per-group fields: ``agg_weight`` derived from real staleness
counters (Alg. 4 line 16) instead of placeholder ones, and ``bcast_mask``
gating which groups receive the aggregated global model back (Alg. 4
line 20 applies to *participants*; a dropped group's rows keep their
current params so it can rejoin from its host-retained state at its
recorded staleness — see ``ControlPlane``'s RetentionStore).  With ω=1, an
identity schedule, uniform weights and an all-ones ``bcast_mask`` this
reduces bit-for-bit to the original single-buffer pipeline.

Structure of one hybrid step::

    devices (vmapped over G groups)          server (centralized)
    ───────────────────────────────          ─────────────────────
    fwd device block + aux head              train on act_buf (prev step)
    local SGD on (θ_dk, θ̃_dk)               SGD/AdamW on θ_s
    emit activations ──────────────▶ act_buf (next step)
    every H steps: staleness-weighted async aggregation over groups
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.api import ArchConfig
from repro.obs.scopes import SCOPES
from repro.optim.optimizers import make_optimizer
from repro.parallel.sharding import Parallelism, param_specs, _param_spec, _validate

Params = Any

#: ``jax.named_scope`` names of the round's parts (``repro.obs.scopes``;
#: the last, ``ssd``, is put on by the Mamba-2 mixer)
DEVICE_HALF, SERVER_HALF, RING, AGGREGATE = SCOPES[:4]


# ---------------------------------------------------------------------------
# Step configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FedStepConfig:
    arch: ArchConfig
    l_split: int                      # device-side periods (split point, Eq. 8)
    n_groups: int                     # FL device groups (= mesh dp size)
    seq_len: int
    per_group_batch: int              # sequences per group
    H: int = 8                        # local iterations per round (Alg. 1);
                                      # one jit step = one round of H
                                      # micro-iterations + aggregation
    lr_d: float = 0.05
    lr_s: float = 0.05
    server_opt: str = "sgd"           # paper Alg. 4 line 10 (adamw optional)
    param_dtype: Any = jnp.float32
    omega: int = 1                    # activation-ring depth in scheduled
                                      # batches (Eq. 3 cap ω); slots are
                                      # read/written per the host schedule
    remat: Any = "selective"          # True | False | "selective" (save
                                      # post-TP-collective outputs only);
                                      # not timed on the chip
    use_kernel: bool = False          # Pallas kernels for attn/SSD hot spots
                                      # (differentiable: custom_vjp backward
                                      # kernels, so both halves' value_and_grad
                                      # run through the fused path; composes
                                      # with remat="selective", which saves
                                      # the kernels' (o, lse)/state residuals)
    agg_compress: bool = False        # int8 aggregation payload (cross-pod)

    @property
    def global_batch(self) -> int:
        return self.n_groups * self.per_group_batch

    @property
    def micro_batch(self) -> int:
        """Sequences per group per local iteration (Alg. 1 line 4)."""
        if self.per_group_batch % self.H != 0:
            raise ValueError(
                f"per_group_batch={self.per_group_batch} is not divisible "
                f"by H={self.H}; Alg. 1 consumes per_group_batch/H "
                "sequences per local iteration")
        return self.per_group_batch // self.H

    @property
    def frontend_dtype(self):
        return self.param_dtype


def default_l_split(arch: ArchConfig) -> int:
    """Paper Eq. 8 with edge-device profiles puts the split early (devices
    are weak); at pod scale we default to 1/8 of the periods on the device
    side, clamped to a valid boundary."""
    return max(1, min(arch.n_periods - 1, arch.n_periods // 8))


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------

def _init_one_group(rng, arch: ArchConfig, l_split: int, dtype):
    full = tfm.init_params(rng, arch, dtype)
    dev, srv = tfm.split_params(full, arch, l_split)
    aux = tfm.make_aux_params(jax.random.fold_in(rng, 1), arch, dtype,
                              regression=bool(arch.n_decoder_layers))
    return dev, aux, srv


def init_train_state(rng, cfg: FedStepConfig) -> Params:
    """Concrete training state (smoke-scale; full configs use eval_shape)."""
    dev1, aux1, srv = _init_one_group(rng, cfg.arch, cfg.l_split,
                                      cfg.param_dtype)
    G = cfg.n_groups
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), t)
    s_init, _ = make_optimizer(cfg.server_opt)
    return {
        "dev": stack(dev1),
        "aux": stack(aux1),
        "srv": srv,
        "srv_opt": s_init(srv),
        "step": jnp.zeros((), jnp.int32),
        "version": jnp.zeros((), jnp.int32),
        "act_buf": _empty_act_buf(cfg),
    }


def _empty_act_slot(cfg: FedStepConfig) -> Params:
    """One scheduled activation batch (one micro-iteration's output).
    ``valid`` marks the rows some group's emission has been written to:
    the server loss masks the rest out.  A never-written row holds zeros,
    and at full depth the gradient through a stack of RMSNorms of an
    all-zero input overflows to NaN."""
    arch = cfg.arch
    B = cfg.n_groups * cfg.micro_batch
    S = arch.frontend_len if arch.n_decoder_layers else cfg.seq_len
    buf = {"acts": jnp.zeros((B, S, arch.d_model), cfg.param_dtype),
           "labels": jnp.zeros((B, cfg.seq_len), jnp.int32),
           "valid": jnp.zeros((B,), jnp.int32)}
    if arch.n_decoder_layers:
        buf["tokens"] = jnp.zeros((B, cfg.seq_len), jnp.int32)
    if arch.family == "vlm":
        buf["frontend"] = jnp.zeros((B, arch.frontend_len, arch.d_model),
                                    cfg.frontend_dtype)
    return buf


def _empty_act_buf(cfg: FedStepConfig) -> Params:
    """ω-deep ring of scheduled activation batches: Σ|Q_act| ≤ ω on-mesh."""
    return jax.tree.map(
        lambda x: jnp.zeros((cfg.omega,) + x.shape, x.dtype),
        _empty_act_slot(cfg))


def abstract_train_state(cfg: FedStepConfig) -> Params:
    """ShapeDtypeStruct state — no allocation."""
    return jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs for every model input)
# ---------------------------------------------------------------------------

#: Host-control-plane fields: per-micro-iteration ring schedule (leading H
#: axis, NOT per-group) + per-group staleness weights (leading G axis).
SCHEDULE_KEYS = ("read_slot", "write_slot", "send_mask")

#: Per-group (G,) control fields consumed once per round (not scanned over
#: the H micro-iterations): aggregation weights + broadcast receive mask.
PER_GROUP_KEYS = ("agg_weight", "bcast_mask")


def train_input_specs(cfg: FedStepConfig) -> dict:
    """Batch stand-ins: tokens/labels per group per local iteration (one
    round = H micro-iterations); agg weights + the activation-ring schedule
    from the host control plane (staleness-derived, §Alg. 4 line 16)."""
    arch = cfg.arch
    G, H, b, S = cfg.n_groups, cfg.H, cfg.micro_batch, cfg.seq_len
    sds = jax.ShapeDtypeStruct
    batch = {"tokens": sds((G, H, b, S), jnp.int32),
             "labels": sds((G, H, b, S), jnp.int32),
             "agg_weight": sds((G,), jnp.float32),
             "bcast_mask": sds((G,), jnp.float32),
             "read_slot": sds((H,), jnp.int32),
             "write_slot": sds((H,), jnp.int32),
             "send_mask": sds((H, G), jnp.float32)}
    if arch.frontend_len:
        batch["frontend"] = sds((G, H, b, arch.frontend_len, arch.d_model),
                                cfg.frontend_dtype)
    return batch


def identity_schedule(cfg: FedStepConfig) -> dict:
    """The uncontrolled default plan: every group sends every iteration and
    slot h%ω is consumed then overwritten — with ω=1 this is exactly the
    original single-buffer pipeline."""
    slots = jnp.arange(cfg.H, dtype=jnp.int32) % max(cfg.omega, 1)
    return {"read_slot": slots, "write_slot": slots,
            "send_mask": jnp.ones((cfg.H, cfg.n_groups), jnp.float32)}


def _stable_fold(rng, name: str):
    """fold_in with a process-stable salt (builtin hash() varies with
    PYTHONHASHSEED, breaking run-to-run benchmark reproducibility)."""
    return jax.random.fold_in(rng, zlib.crc32(name.encode()) % 97)


def concrete_train_batch(rng, cfg: FedStepConfig) -> dict:
    arch = cfg.arch
    out = dict(identity_schedule(cfg))
    for k, s in train_input_specs(cfg).items():
        if k in out:
            continue
        if k in PER_GROUP_KEYS:
            out[k] = jnp.ones(s.shape, s.dtype)
        elif s.dtype == jnp.int32:
            out[k] = jax.random.randint(_stable_fold(rng, k),
                                        s.shape, 0, arch.vocab, jnp.int32)
        else:
            out[k] = jax.random.normal(_stable_fold(rng, k), s.shape, s.dtype)
    return out


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

def _stacked_specs(params: Params, par: Parallelism) -> Params:
    """Specs for group-stacked device/aux params: leading G axis over the
    dp axes; inner dims per the standard rules (FSDP off — dp is taken).

    Exception: the device-side *input* embedding shards d_model (not
    vocab) over ``model`` — the token gather and the scatter-add of its
    gradient are then chip-local (no all-reduce of a (V, D) table per
    micro-iteration).  Vocab-sharding only pays off on the logits path,
    which the device block doesn't have (the aux head is factorized)."""
    inner_par = replace(par, fsdp=False)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        if key.endswith("embed") and leaf.ndim == 3:
            inner = _validate(P(None, par.tp_axis), leaf.shape[1:], inner_par)
        else:
            inner = _param_spec(key, leaf.shape[1:], inner_par)
            inner = _validate(inner, leaf.shape[1:], inner_par)
        specs.append(P(tuple(par.dp_axes), *tuple(inner)))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _act_buf_specs(buf: Params, par: Parallelism, ring: bool = False) -> Params:
    """Slot-shaped activation specs: batch over dp, the sequence of (B, S, D)
    activations over ``model`` (Megatron-SP); ``ring=True`` for the
    ω-stacked state buffer (leading slot axis replicated, inner dims as one
    slot)."""
    dp = tuple(par.dp_axes)
    tp = par.tp_axis
    tp_size = par.mesh.shape[tp]

    def spec(k, leaf):
        shape = leaf.shape[1:] if ring else leaf.shape
        b = dp if shape[0] % par.dp_size == 0 else None
        if len(shape) == 3:     # (B, S, D) or (B, F, D)
            s = tp if shape[1] % tp_size == 0 else None
            inner = (b, s, None)
        elif len(shape) == 2:
            inner = (b, None)   # (B, S) int labels/tokens
        else:
            inner = (b,)        # (B,) row validity
        return P(None, *inner) if ring else P(*inner)
    return {k: spec(k, v) for k, v in buf.items()}


def state_specs(state: Params, par: Parallelism) -> Params:
    specs = {
        "dev": _stacked_specs(state["dev"], par),
        "aux": _stacked_specs(state["aux"], par),
        "srv": param_specs(state["srv"], par),
        "step": P(),
        "version": P(),
        "act_buf": _act_buf_specs(state["act_buf"], par, ring=True),
    }
    # optimizer state mirrors its parameters (ZeRO); scalars replicated
    so = {}
    for k, v in state["srv_opt"].items():
        so[k] = specs["srv"] if k in ("mu", "nu", "velocity") else P()
    specs["srv_opt"] = so
    return specs


def batch_specs(cfg: FedStepConfig, par: Parallelism) -> dict:
    dp = tuple(par.dp_axes)
    out = {"tokens": P(dp, None, None, None),
           "labels": P(dp, None, None, None),
           "agg_weight": P(dp),
           "bcast_mask": P(dp),
           # ring schedule: tiny host-planned control tensors, replicated
           "read_slot": P(None),
           "write_slot": P(None),
           "send_mask": P(None, dp)}
    if cfg.arch.frontend_len:
        out["frontend"] = P(dp, None, None, None, None)
    return out


def to_named(specs: Params, mesh) -> Params:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# The hybrid train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: FedStepConfig, par: Parallelism):
    """Returns step(state, batch) -> (state, metrics), pure & jit-ready.

    One step = one FL round: a ``lax.scan`` over H local micro-iterations
    (Alg. 1 lines 3-12 on every device group in parallel; Alg. 4 lines 5-10
    on the server against the *previous* iteration's scheduled activation
    batch, so the two halves have no data dependency and overlap), followed
    by the end-of-round asynchronous aggregation (Alg. 4 lines 12-19).
    Micro-iterating also bounds activation memory to one iteration's worth.
    """
    arch = cfg.arch
    _, s_update = make_optimizer(cfg.server_opt)
    # Activation sharding: Megatron-SP carries in both halves.  Inside the
    # vmapped device half the group axis has consumed dp, so act_batch=None
    # there; the server half (not vmapped) shards batch over dp, and routes
    # MoE tokens to each ``model`` shard's local experts (shard_map EP).
    dev_par = replace(par, ep=False, constraints=True, seq_shard=True,
                      act_batch=None)
    srv_par = replace(par, ep=True, constraints=True, seq_shard=True,
                      act_batch=tuple(par.dp_axes))
    kw = dict(use_kernel=cfg.use_kernel, remat=cfg.remat)

    @jax.named_scope(DEVICE_HALF)
    def device_half(dev, aux, batch_g):
        """One FL device group: local-loss training (Alg. 1 lines 3-12).
        Runs under vmap over the group axis — no cross-group collectives."""
        if arch.n_decoder_layers:        # whisper: encoder on frame stubs
            inputs, aux_labels = batch_g["frontend"], batch_g["frontend"]
        else:
            inputs, aux_labels = batch_g["tokens"], batch_g["labels"]
        frontend = batch_g.get("frontend") if arch.family == "vlm" else None

        def loss_fn(d, a):
            loss, acts = tfm.device_train_loss(d, a, arch, inputs, aux_labels,
                                               frontend=frontend,
                                               parallelism=dev_par, **kw)
            return loss, acts

        (d_loss, acts), (gd, ga) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(dev, aux)
        dev = jax.tree.map(lambda p, g: p - cfg.lr_d * g.astype(p.dtype),
                           dev, gd)
        aux = jax.tree.map(lambda p, g: p - cfg.lr_d * g.astype(p.dtype),
                           aux, ga)
        return dev, aux, acts, d_loss

    def server_half(srv, srv_opt, buf):
        """One server iteration on the scheduled activation batch (Alg. 4
        lines 5-10) — the single global model, never stale."""
        def loss_fn(s):
            if arch.n_decoder_layers:
                return tfm.server_encdec_loss(s, arch, buf["acts"],
                                              buf["tokens"], buf["labels"],
                                              parallelism=srv_par,
                                              row_mask=buf["valid"], **kw)
            return tfm.server_forward_loss(s, arch, buf["acts"],
                                           buf["labels"],
                                           frontend=buf.get("frontend"),
                                           parallelism=srv_par,
                                           row_mask=buf["valid"], **kw)
        s_loss, gs = jax.value_and_grad(loss_fn)(srv)
        srv, srv_opt = s_update(srv, gs, srv_opt, cfg.lr_s)
        return srv, srv_opt, s_loss

    @jax.named_scope(AGGREGATE)
    def aggregate(dev_aux, weights, recv_mask):
        """Async staleness-weighted aggregation over the group axis (Alg. 4
        lines 12-19 telescoped: the sequential α-lerps over one round equal
        a normalized weighted average with per-group staleness weights
        supplied by the host control plane).  All-zero weights mean every
        update was rejected (too stale / absent — Alg. 4 line 13): the
        groups keep their current params instead of being zeroed.  The
        broadcast back (Alg. 4 line 20) is masked by ``recv_mask``:
        dropped groups do NOT receive the global model — their rows keep
        current params so a rejoin scatters their host-retained state in,
        preserving true per-group staleness."""
        w_sum = jnp.sum(weights)
        w = weights / jnp.maximum(w_sum, 1e-9)

        def mean_bcast(x):
            xw = x.astype(jnp.float32) if cfg.agg_compress is False else \
                _dequant(_quant(x))
            g = jnp.tensordot(w, xw, axes=1).astype(x.dtype)
            rows = (recv_mask > 0.5).reshape((-1,) + (1,) * (x.ndim - 1))
            out = jnp.where(rows, jnp.broadcast_to(g[None], x.shape), x)
            return jnp.where(w_sum > 0, out, x)

        return jax.tree.map(mean_bcast, dev_aux)

    @jax.named_scope(RING)
    def exchange(acts, batch_g, batch_h, ring):
        """This micro-iteration's activation batch into the ω ring, and
        the batch the server trains on out of it."""
        G, b = acts.shape[0], acts.shape[1]
        new_buf = {"acts": acts.reshape((G * b,) + acts.shape[2:]),
                   "labels": batch_g["labels"].reshape(G * b, -1),
                   "valid": jnp.ones((G * b,), jnp.int32)}
        if arch.n_decoder_layers:
            new_buf["tokens"] = batch_g["tokens"].reshape(G * b, -1)
        if arch.family == "vlm":
            new_buf["frontend"] = batch_g["frontend"].reshape(
                (G * b,) + batch_g["frontend"].shape[2:])
        spec = _act_buf_specs({"acts": new_buf["acts"]}, par)["acts"]
        new_buf["acts"] = jax.lax.with_sharding_constraint(
            new_buf["acts"], NamedSharding(par.mesh, spec))

        # server consumes the host-scheduled slot (ring state from BEFORE
        # this iteration's write, matching the control plane's
        # read-then-write bookkeeping) ...
        read_slot = batch_h["read_slot"]
        train_buf = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(
                x, read_slot, 0, keepdims=False), ring)
        # ... while token-holding groups' rows refresh the written slot;
        # groups without a flow-control grant keep the slot's previous
        # content (their emission is not shipped)
        write_slot = batch_h["write_slot"]
        keep = batch_h["send_mask"] > 0.5            # (G,)
        rows = jnp.repeat(keep, b)                   # (G*b,) grouped
        old = jax.tree.map(
            lambda x: jax.lax.dynamic_index_in_dim(
                x, write_slot, 0, keepdims=False), ring)
        merged = jax.tree.map(
            lambda n, o: jnp.where(
                rows.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
            new_buf, old)
        ring = jax.tree.map(
            lambda r, m: jax.lax.dynamic_update_index_in_dim(
                r, m, write_slot, 0), ring, merged)
        return train_buf, ring

    def step(state, batch):
        def body(carry, batch_h):
            dev, aux, srv, srv_opt, ring = carry
            batch_g = {k: v for k, v in batch_h.items()
                       if k not in SCHEDULE_KEYS}

            dev, aux, acts, d_loss = jax.vmap(device_half)(dev, aux, batch_g)
            # the server half starts once the device half is done: left
            # free, XLA interleaves the two and keeps the device half's
            # residuals live through the server forward (+0.34 GB peak at
            # smollm-135m G4 S2048)
            dev, aux, acts, d_loss, ring = jax.lax.optimization_barrier(
                (dev, aux, acts, d_loss, ring))
            train_buf, ring = exchange(acts, batch_g, batch_h, ring)

            with jax.named_scope(SERVER_HALF):
                srv, srv_opt, s_loss = server_half(srv, srv_opt, train_buf)
            s_live = jnp.any(train_buf["valid"] > 0).astype(jnp.float32)
            return (dev, aux, srv, srv_opt, ring), \
                (jnp.mean(d_loss), s_loss, s_live)

        # (G, H, ...) -> scan-major (H, G, ...); the schedule fields already
        # carry H on the leading axis and pass through unchanged; the
        # per-group (G,) control fields are consumed once after the scan
        xs = {k: v if k in SCHEDULE_KEYS else jnp.moveaxis(v, 1, 0)
              for k, v in batch.items() if k not in PER_GROUP_KEYS}
        carry = (state["dev"], state["aux"], state["srv"], state["srv_opt"],
                 state["act_buf"])
        carry, (d_losses, s_losses, s_live) = jax.lax.scan(body, carry, xs)
        dev, aux, srv, srv_opt, ring = carry

        # ---- end-of-round async aggregation (Alg. 1 l.13, Alg. 4 l.12-19)
        dev, aux = aggregate((dev, aux), batch["agg_weight"],
                             batch["bcast_mask"])

        new_state = dict(state, dev=dev, aux=aux, srv=srv, srv_opt=srv_opt,
                         act_buf=ring, step=state["step"] + 1,
                         version=state["version"] + 1)
        # s_loss: mean over the micro-iterations whose read held any row
        metrics = {"d_loss": jnp.mean(d_losses),
                   "s_loss": jnp.sum(s_losses * s_live)
                   / jnp.maximum(jnp.sum(s_live), 1.0)}
        return new_state, metrics

    return step


def _quant(x):
    """Per-tensor int8 quantization of the aggregation payload (cross-pod
    model upload compression; see parallel/compression.py for the
    error-feedback gradient variant).  Also reused by the tiered
    activation store (repro.memory.store) for int8 spill encoding of
    float activation leaves."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    return jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8), scale


def _dequant(qs):
    q, scale = qs
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Jit assembly (train)
# ---------------------------------------------------------------------------

def jit_train_step(cfg: FedStepConfig, mesh, *, donate: bool = True):
    """jit(step) with explicit in/out shardings for the given mesh.
    Returns (jitted, abstract_state, state_shardings, batch_shardings)."""
    dp = tuple(a for a in mesh.axis_names if a != "model")
    par = Parallelism(mesh=mesh, dp_axes=dp)
    step = make_train_step(cfg, par)
    state = abstract_train_state(cfg)
    s_spec = to_named(state_specs(state, par), mesh)
    b_spec = to_named(batch_specs(cfg, par), mesh)
    m_spec = {"d_loss": NamedSharding(mesh, P()),
              "s_loss": NamedSharding(mesh, P())}
    jitted = jax.jit(step, in_shardings=(s_spec, b_spec),
                     out_shardings=(s_spec, m_spec),
                     donate_argnums=(0,) if donate else ())
    return jitted, state, s_spec, b_spec


# ---------------------------------------------------------------------------
# Per-group state retention (dropped groups — §3.4.2)
# ---------------------------------------------------------------------------

def snapshot_state(state: Params, keys=None, *, to_host: bool = False) \
        -> Params:
    """Donation-safe snapshot of the train state (or the ``keys`` subset):
    ``jnp.copy`` per device leaf enqueues fresh, never-donated buffers in
    dispatch order, so the copy reads the current round's output before
    the next donated dispatch aliases it (see ``core/handles.py`` for the
    full contract).  With ``to_host=True`` every leaf also starts its
    async D2H transfer immediately (checkpoint staging).

    This is THE way to keep a reference into a past round's state under
    ``jit_train_step(..., donate=True)`` at window > 1 — a plain Python
    reference is invalid the moment the next round dispatches."""
    from repro.core.handles import snapshot_tree
    src = state if keys is None else \
        {k: state[k] for k in keys if k in state}
    return snapshot_tree(src, to_host=to_host)


def gather_act_slot(state: Params, s: int) -> dict:
    """Host copies of activation-ring slot ``s`` (spill path of the tiered
    store, ``repro.memory``): one scheduled batch — acts, labels and any
    tokens/frontend leaves — lifted off the mesh for the host pool.

    Blocks only until the act_buf leaves are materialized: under
    pipelined dispatch this waits for the rounds already in flight, and
    only on the ring (one slot's read is sliced host-side), never on the
    model params.  With donation at window > 1 the executor gathers from
    a :class:`~repro.core.handles.RoundHandle` (``handle.act_slot``)
    instead — this live-state sync remains the window=1 / unwired
    fallback, where the values are identical."""
    return jax.tree.map(lambda x: np.asarray(x[s]), state["act_buf"])


def scatter_act_slot(state: Params, s: int, payload: dict,
                     state_shardings=None) -> Params:
    """Functionally write one spilled slot's payload back into the on-mesh
    ring (fill path).  ``state_shardings`` (the jit step's state spec
    dict) re-pins the updated ring so the next dispatch sees the same
    shardings it was compiled for."""
    spec = None if state_shardings is None else state_shardings["act_buf"]

    def one(x, v, sh=None):
        y = x.at[s].set(jnp.asarray(v, x.dtype))
        return jax.device_put(y, sh) if sh is not None else y

    new = dict(state)
    new["act_buf"] = jax.tree.map(one, state["act_buf"], payload) \
        if spec is None else jax.tree.map(one, state["act_buf"], payload,
                                          spec)
    return new


def gather_group_state(state: Params, g: int) -> dict:
    """Host copies of one group's dev/aux slices for the retention store.

    Blocks until those leaves are materialized (a targeted device→host
    sync): under pipelined dispatch this waits only for the rounds already
    in flight, and only on the small device-side block, not the server
    params.  With donation at window > 1 the executor gathers from a
    :class:`~repro.core.handles.RoundHandle` (``handle.group_state``)
    instead — this live-state sync remains the window=1 / unwired
    fallback, where the values are identical."""
    take = lambda tree: jax.tree.map(lambda x: np.asarray(x[g]), tree)
    return {"dev": take(state["dev"]), "aux": take(state["aux"])}


def scatter_group_state(state: Params, g: int, retained: dict,
                        state_shardings=None) -> Params:
    """Functionally write one group's retained dev/aux slices back into the
    stacked state (rejoin path).  ``state_shardings`` (the jit step's state
    spec dict) re-pins the updated stacks so the next dispatch sees the
    same shardings it was compiled for."""
    def put(stacked, sl, spec):
        def one(x, v, s=None):
            y = x.at[g].set(jnp.asarray(v, x.dtype))
            return jax.device_put(y, s) if s is not None else y
        if spec is None:
            return jax.tree.map(one, stacked, sl)
        return jax.tree.map(one, stacked, sl, spec)

    new = dict(state)
    for key in ("dev", "aux"):
        spec = None if state_shardings is None else state_shardings[key]
        new[key] = put(state[key], retained[key], spec)
    return new
