"""Feed-forward blocks: dense (SwiGLU / GELU) and mixture-of-experts.

MoE design (TPU-native):
  * experts' weights are stacked on a leading ``experts`` axis and sharded
    over the ``model`` mesh axis (expert parallelism);
  * routing uses top-k gating with softmax-renormalised weights;
  * dispatch is dense "einsum-style" (one-hot combine) — on TPU this lowers
    to an all-to-all across the expert axis when sharded.  A capacity factor
    bounds per-expert work for the dropping variant; the default path is
    dropless dense-dispatch which is exactly what the oracle computes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from .common import dense_init, gelu, silu

Params = Any


@dataclass(frozen=True)
class MlpConfig:
    d_model: int
    d_ff: int
    activation: str = "swiglu"   # "swiglu" | "gelu"


@dataclass(frozen=True)
class MoeConfig:
    d_model: int
    d_ff: int                    # per-expert hidden dim
    n_experts: int
    top_k: int
    router_jitter: float = 0.0
    activation: str = "swiglu"


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------

GATED = ("swiglu", "geglu")


def _act(name):
    return silu if name == "swiglu" else gelu


def mlp_init(rng, cfg: MlpConfig, dtype=jnp.float32) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    if cfg.activation in GATED:
        return {
            "w_gate": dense_init(k1, cfg.d_model, cfg.d_ff, dtype),
            "w_up": dense_init(k2, cfg.d_model, cfg.d_ff, dtype),
            "w_down": dense_init(k3, cfg.d_ff, cfg.d_model, dtype),
        }
    return {
        "w_up": dense_init(k1, cfg.d_model, cfg.d_ff, dtype),
        "w_down": dense_init(k2, cfg.d_ff, cfg.d_model, dtype),
    }


def mlp_apply(params: Params, cfg: MlpConfig, x: jnp.ndarray,
              parallelism=None) -> jnp.ndarray:
    con = parallelism.ffn_hidden if parallelism is not None else (lambda t: t)
    if cfg.activation in GATED:
        a = _act(cfg.activation)
        h = a(con(x @ params["w_gate"])) * con(x @ params["w_up"])
        return h @ params["w_down"]
    return gelu(con(x @ params["w_up"])) @ params["w_down"]


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------

def moe_init(rng, cfg: MoeConfig, dtype=jnp.float32) -> Params:
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale_in = 1.0 / D ** 0.5
    scale_out = 1.0 / F ** 0.5
    p = {
        "router": dense_init(k0, D, E, dtype),
        "we_gate": jax.random.normal(k1, (E, D, F), dtype) * scale_in,
        "we_up": jax.random.normal(k2, (E, D, F), dtype) * scale_in,
        "we_down": jax.random.normal(k3, (E, F, D), dtype) * scale_out,
    }
    if cfg.activation not in GATED:
        del p["we_gate"]
    return p


def moe_routing(params: Params, cfg: MoeConfig, x: jnp.ndarray):
    """x: (T, D) -> (weights (T, E) sparse in top-k, aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)                       # (T, k)
    top_w = top_w / jnp.clip(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # dense combine weights: scatter top-k back to (T, E)
    combine = jnp.zeros_like(probs)
    combine = jax.vmap(lambda c, i, w: c.at[i].set(w))(combine, top_idx, top_w)
    # load-balancing auxiliary loss (Switch-style): E * sum_e f_e * p_e
    f = jnp.mean((combine > 0).astype(jnp.float32), axis=0)   # fraction routed
    p = jnp.mean(probs, axis=0)                               # mean router prob
    aux = cfg.n_experts * jnp.sum(f * p)
    return combine, aux


def moe_apply(params: Params, cfg: MoeConfig, x: jnp.ndarray):
    """x: (B, S, D) -> (out (B, S, D), aux_loss). Dense (dropless) dispatch.

    einsum formulation: per-expert FFN applied to the full token set,
    weighted by the sparse combine matrix.  XLA's SPMD partitioner turns the
    (T, E) contraction into an all-to-all when experts are sharded on the
    ``model`` axis.  FLOP-accurate for roofline purposes in the dense form;
    MODEL_FLOPS accounting uses top_k/E of it (active experts only).
    """
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    combine, aux = moe_routing(params, cfg, xt)               # (T, E)
    combine = combine.astype(x.dtype)

    # Gather tokens per expert via dense einsum (dropless).
    # h_e = act(x W_gate^e) * (x W_up^e);  y = sum_e combine[:, e] * h_e W_down^e
    if cfg.activation in GATED:
        gate = jnp.einsum("td,edf->tef", xt, params["we_gate"])
        up = jnp.einsum("td,edf->tef", xt, params["we_up"])
        h = _act(cfg.activation)(gate) * up
    else:
        h = gelu(jnp.einsum("td,edf->tef", xt, params["we_up"]))
    y = jnp.einsum("tef,efd,te->td", h, params["we_down"], combine)
    return y.reshape(B, S, D), aux


def _top_k_route(params: Params, cfg: MoeConfig, xt: jnp.ndarray):
    """xt: (T, D) -> (top_idx (T,k) int32, top_w (T,k) f32, aux scalar)."""
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.clip(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # load-balance aux loss on the full distribution
    oh = jax.nn.one_hot(top_idx, cfg.n_experts, dtype=jnp.float32).sum(axis=1)
    f = jnp.mean(oh, axis=0) / cfg.top_k
    p = jnp.mean(probs, axis=0)
    aux = cfg.n_experts * jnp.sum(f * p)
    return top_idx.astype(jnp.int32), top_w, aux


def moe_apply_grouped(params: Params, cfg: MoeConfig, x: jnp.ndarray, *,
                      expert_offset: int = 0, n_local_experts: int | None = None,
                      capacity_factor: float = 1.0, psum_axis: str | None = None):
    """Capacity-bounded grouped-matmul MoE (FLOPs ∝ top_k, not E).

    Scalable dispatch: no (T, E, C) one-hot.  Tokens hitting a local expert
    are scattered into per-expert slot queues (gather/scatter of indices),
    the experts run as one batched matmul (E_l, C, D) x (E_l, D, F), and
    contributions are combined back per token.  Overflow beyond the static
    capacity C is dropped (GShard/Switch semantics).

    Expert parallelism: call under ``shard_map`` with tokens replicated over
    the ``model`` axis and ``params`` holding only this shard's experts
    (leading E axis pre-sliced).  Pass ``expert_offset``/``n_local_experts``
    for this shard's range and ``psum_axis="model"`` to sum partial outputs.
    Without those arguments this is a standalone exact (modulo drops)
    single-host MoE.
    """
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    E = cfg.n_experts
    E_l = n_local_experts if n_local_experts is not None else E
    k = cfg.top_k
    N = T * k
    C = max(1, int(capacity_factor * T * k / E))

    top_idx, top_w, aux = _top_k_route(params, cfg, xt)       # router is replicated
    eflat = top_idx.reshape(N)                                 # expert id per assignment
    tflat = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)      # token id per assignment
    wflat = top_w.reshape(N)

    # Sort-based dispatch: stable-sort assignments by (local) expert id —
    # position within the sorted run is the slot index.  O(N log N) with no
    # (N, E) one-hot/cumsum intermediates, which grow with the expert
    # count.  Stable sort keeps earlier tokens first, so capacity drops
    # match the cumsum formulation.
    e_rel = eflat - expert_offset
    own = (e_rel >= 0) & (e_rel < E_l)
    e_key = jnp.where(own, e_rel, E_l).astype(jnp.int32)       # foreign -> end
    order = jnp.argsort(e_key, stable=True)
    e_sorted = e_key[order]
    counts = jnp.zeros((E_l + 1,), jnp.int32).at[e_sorted].add(1)
    starts = jnp.cumsum(counts) - counts                       # (E_l + 1,)
    pos_sorted = jnp.arange(N, dtype=jnp.int32) - starts[e_sorted]
    keep_sorted = (e_sorted < E_l) & (pos_sorted < C)
    slot_sorted = jnp.where(keep_sorted, e_sorted * C + pos_sorted, E_l * C)
    # back to assignment order for the combine
    inv = jnp.zeros((N,), jnp.int32).at[order].set(
        jnp.arange(N, dtype=jnp.int32))
    keep = keep_sorted[inv]
    slot = slot_sorted[inv]

    # scatter token ids into slots; sentinel row (index T) stays zero
    slot_token = jnp.full((E_l * C + 1,), T, dtype=jnp.int32)
    slot_token = slot_token.at[slot].set(tflat, mode="drop")
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
    xe = xt_pad[slot_token[:-1]].reshape(E_l, C, D)            # (E_l, C, D)

    if cfg.activation in GATED:
        h = _act(cfg.activation)(jnp.einsum("ecd,edf->ecf", xe, params["we_gate"])) * \
            jnp.einsum("ecd,edf->ecf", xe, params["we_up"])
    else:
        h = gelu(jnp.einsum("ecd,edf->ecf", xe, params["we_up"]))
    ye = jnp.einsum("ecf,efd->ecd", h, params["we_down"]).reshape(E_l * C, D)

    # combine back: each kept assignment adds w * ye[slot] to its token
    ye_pad = jnp.concatenate([ye, jnp.zeros((1, D), ye.dtype)], axis=0)
    contrib = ye_pad[jnp.where(keep, slot, E_l * C)]           # (N, D)
    y = jnp.zeros((T, D), x.dtype).at[tflat].add(
        contrib * jnp.where(keep, wflat, 0.0)[:, None].astype(x.dtype))
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
        aux = jax.lax.psum(aux, psum_axis) / jax.lax.psum(1, psum_axis)
    return y.reshape(B, S, D), aux
