"""Mamba2 block (state-space duality / SSD), pure JAX.

Follows arXiv:2405.21060.  The sequence mixer is the chunked SSD algorithm:
within-chunk quadratic (attention-like) term + across-chunk linear
recurrence, which is the TPU-friendly form (big matmuls for the MXU, scan
only over T/Q chunks).  A step-by-step recurrence is provided for decode,
and `repro.kernels.ref.ssd_reference` holds the naive oracle.

Shapes (per mamba2 conventions):
  x      (B, T, H, P)   inputs per head      (P = head_dim)
  dt     (B, T, H)      per-head step size (after softplus + bias)
  A      (H,)           negative decay rates (stored as A_log)
  B, C   (B, T, G, N)   input/output projections (G groups, N = ssm state)
  state  (B, H, N, P)
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs import trace as _obs
from repro.obs.clock import now as _now

from .common import dense_init, rmsnorm_apply, rmsnorm_init, silu

Params = Any


@dataclass(frozen=True)
class MambaConfig:
    d_model: int
    d_state: int = 128          # N
    head_dim: int = 64          # P
    expand: int = 2
    n_groups: int = 1
    conv_kernel: int = 4
    chunk: int = 256            # SSD chunk length Q
    dt_min: float = 0.001
    dt_max: float = 0.1

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def mamba_init(rng, cfg: MambaConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(rng, 5)
    H, G, N = cfg.n_heads, cfg.n_groups, cfg.d_state
    d_in_proj = 2 * cfg.d_inner + 2 * G * N + H  # z, x, B, C, dt
    # dt bias so softplus(dt_bias) spans [dt_min, dt_max] log-uniformly
    u = jax.random.uniform(ks[3], (H,), jnp.float32)
    dt_init = jnp.exp(u * (jnp.log(cfg.dt_max) - jnp.log(cfg.dt_min)) + jnp.log(cfg.dt_min))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))  # inverse softplus
    return {
        "in_proj": dense_init(ks[0], cfg.d_model, d_in_proj, dtype),
        "conv_w": jax.random.normal(ks[1], (cfg.conv_kernel, cfg.conv_dim), dtype) * 0.2,
        "conv_b": jnp.zeros((cfg.conv_dim,), dtype),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": rmsnorm_init(cfg.d_inner, dtype),
        "out_proj": dense_init(ks[4], cfg.d_inner, cfg.d_model, dtype),
    }


def _split_in_proj(cfg: MambaConfig, zxbcdt: jnp.ndarray):
    H, G, N, Di = cfg.n_heads, cfg.n_groups, cfg.d_state, cfg.d_inner
    z, xBC, dt = jnp.split(zxbcdt, [Di, Di + cfg.conv_dim], axis=-1)
    return z, xBC, dt  # dt: (..., H)


def _causal_conv(xBC: jnp.ndarray, conv_w, conv_b, cache=None):
    """Depthwise causal conv over time.  xBC: (B, T, Cd); conv_w: (K, Cd)."""
    K = conv_w.shape[0]
    if cache is None:
        pad = jnp.zeros((xBC.shape[0], K - 1, xBC.shape[2]), xBC.dtype)
    else:
        pad = cache  # (B, K-1, Cd) — the last K-1 inputs
    xp = jnp.concatenate([pad, xBC], axis=1)
    new_cache = xp[:, -(K - 1):, :]
    # sum_k w[k] * x[t - (K-1) + k]
    out = sum(xp[:, k:k + xBC.shape[1], :] * conv_w[k][None, None, :] for k in range(K))
    return silu(out + conv_b), new_cache


def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked SSD scan (single pass over chunks, remat'd body).

    x: (b, T, H, P); dt: (b, T, H); A: (H,); B, C: (b, T, G, N).
    Returns (y (b, T, H, P), final_state (b, H, N, P)).
    T must be divisible by ``chunk``.

    Per chunk: the quadratic intra-chunk term (C_t·B_s masked-decay matmul),
    the inter-chunk contribution from the carried state, and the state
    update — one ``lax.scan`` over T/Q chunks carrying the state.  The
    body is checkpointed so the (Q, Q) decay matrix is never live across
    chunks; this is the same schedule the Pallas ``ssd`` kernel runs on TPU
    (grid over chunks, state in VMEM).

    Heads are grouped: head h = g·rep + r reads group g's B and C
    (H = G·rep).  C·Bᵀ is formed once per group and chunk, and scaled by
    each head's decay; the (Q, Q) tiles, x and y are head-major,
    (b·G, rep, Q, ·), so each tile is a matmul operand as it lies.  The
    two products that contract the state dim, C·h and the state update
    Bᵀ·x, take a group's heads together, folded into one (rep·P) dim:
    the carried state is (b·G, N, rep·P).

    Every decay is ``exp`` of a non-positive log decay.  Above the
    diagonal ``Lcum[t] - Lcum[s]`` is positive (hundreds over a 256-step
    chunk at the published dt/A ranges), so the mask is applied to the
    exponent, not to ``exp``'s result: ``where(mask, exp(diff), 0)``
    would select 0 in the forward pass but give ``0 * inf = NaN`` in the
    backward one.
    """
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = chunk
    nc = T // Q
    rep = H // G
    # one batch dim for (sequence, group): with G = 1 as a dim of its own,
    # XLA's CPU simplifier rebuilds the C·Bᵀ dot without its op_name (scope)
    k = b * G

    xb = (x * dt[..., None]).astype(jnp.float32)                  # dt-weighted input
    la = (dt * A[None, None, :]).astype(jnp.float32)              # log decay per step (<0)

    # chunked, scan-major, head-major layouts: (nc, k, [rep,] Q, ...)
    xb = xb.reshape(b, nc, Q, G, rep, P).transpose(1, 0, 3, 4, 2, 5)
    la = la.reshape(b, nc, Q, G, rep).transpose(1, 0, 3, 4, 2)
    Bc = B.reshape(b, nc, Q, G, N).astype(jnp.float32).transpose(1, 0, 3, 2, 4)
    Cc = C.reshape(b, nc, Q, G, N).astype(jnp.float32).transpose(1, 0, 3, 2, 4)
    xb, la, Bc, Cc = (a.reshape(nc, k, *a.shape[3:]) for a in (xb, la, Bc, Cc))
    mask = jnp.tril(jnp.ones((Q, Q), bool))

    def fold(a):        # (k, rep, L, P) -> (k, L, rep·P)
        return jnp.swapaxes(a, 1, 2).reshape(k, a.shape[2], rep * P)

    def unfold(a):      # (k, L, rep·P) -> (k, rep, L, P)
        return jnp.swapaxes(a.reshape(k, a.shape[1], rep, P), 1, 2)

    h0 = (jnp.zeros((b, H, N, P), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    h0 = fold(h0.reshape(k, rep, N, P))

    @jax.checkpoint
    def chunk_fn(h, xb_c, la_c, B_c, C_c):
        Lcum = jnp.cumsum(la_c, axis=-1)                          # (k,rep,Q)
        Ltot = Lcum[..., -1:]                                     # (k,rep,1)
        # intra-chunk quadratic term
        diff = Lcum[..., :, None] - Lcum[..., None, :]            # (k,rep,Q,Q)
        decay = jnp.exp(jnp.where(mask, diff, -jnp.inf))
        CB = jnp.einsum("ktn,ksn->kts", C_c, B_c)                 # once per group
        scores = CB[:, None] * decay
        y = scores @ xb_c                                         # (k,rep,Q,P)
        # inter-chunk contribution from the carried state
        y = y + jnp.exp(Lcum)[..., None] * unfold(C_c @ h)
        # state update
        w_state = jnp.exp(Ltot - Lcum)[..., None]                 # (k,rep,Q,1)
        S_c = jnp.swapaxes(B_c, 1, 2) @ fold(w_state * xb_c)      # (k,N,rep·P)
        h_decay = jnp.broadcast_to(jnp.exp(Ltot), (k, rep, P))
        h = h * h_decay.reshape(k, 1, rep * P) + S_c
        return h, y

    def body(h, inp):
        return chunk_fn(h, *inp)

    h_final, ys = jax.lax.scan(body, h0, (xb, la, Bc, Cc))
    y = ys.reshape(nc, b, G, rep, Q, P).transpose(1, 0, 4, 2, 3, 5)
    return y.reshape(b, T, H, P), unfold(h_final).reshape(b, H, N, P)


def mamba_apply(params: Params, cfg: MambaConfig, x: jnp.ndarray,
                use_kernel: bool = False, return_state: bool = False):
    """Full-sequence forward.  x: (B, T, d_model) -> (B, T, d_model).
    With return_state=True also returns the decode state ({"ssm","conv"})
    after the last position — used by prefill to prime caches."""
    Bb, T, _ = x.shape
    H, G, N, P = cfg.n_heads, cfg.n_groups, cfg.d_state, cfg.head_dim
    zxbcdt = x @ params["in_proj"]
    z, xBC_raw, dt = _split_in_proj(cfg, zxbcdt)
    xBC, _ = _causal_conv(xBC_raw, params["conv_w"], params["conv_b"])
    xi, Bm, Cm = jnp.split(xBC, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xi = xi.reshape(Bb, T, H, P)
    Bm = Bm.reshape(Bb, T, G, N)
    Cm = Cm.reshape(Bb, T, G, N)
    Q = min(cfg.chunk, T)
    pad = (-T) % Q              # T padded to a chunk multiple (zero dt =>
                                # identity decay, zero input)
    kernel = use_kernel and not return_state
    if _obs.TRACING:
        _obs.emit_instant_once(
            "host/compile", "ssd_chunks", _now(), shape=[Bb, T, H, P, N],
            chunk=Q, n_chunks=(T + pad) // Q, pad=pad,
            path="kernel" if kernel else "jnp", groups=G,
            cb_per_chunk=H if kernel else G)   # the kernel forms C·Bᵀ per head
    with jax.named_scope("ssd"):
        if kernel:
            from repro.kernels import ops as kops
            # differentiable (custom_vjp); ops.ssd clamps chunk to T and pads
            y = kops.ssd(xi, dt, A, Bm, Cm, chunk=cfg.chunk)
            state = None
        else:
            if pad:
                widen = lambda a: jnp.pad(
                    a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                y, state = ssd_chunked(widen(xi), widen(dt), A, widen(Bm),
                                       widen(Cm), Q)
                y = y[:, :T]
            else:
                y, state = ssd_chunked(xi, dt, A, Bm, Cm, Q)
    y = y + params["D"][None, None, :, None] * xi.astype(jnp.float32)
    y = y.reshape(Bb, T, cfg.d_inner).astype(x.dtype)
    y = rmsnorm_apply(params["norm"], y * silu(z))
    out = y @ params["out_proj"]
    if return_state:
        K = cfg.conv_kernel
        pad = jnp.zeros((Bb, K - 1, xBC_raw.shape[-1]), xBC_raw.dtype)
        conv_cache = jnp.concatenate([pad, xBC_raw], axis=1)[:, -(K - 1):, :]
        return out, {"ssm": state, "conv": conv_cache}
    return out


# ---------------------------------------------------------------------------
# Decode (single token, recurrent state)
# ---------------------------------------------------------------------------

def mamba_state_init(cfg: MambaConfig, batch: int, dtype=jnp.float32):
    return {
        "ssm": jnp.zeros((batch, cfg.n_heads, cfg.d_state, cfg.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_kernel - 1, cfg.conv_dim), dtype),
    }


def mamba_decode(params: Params, cfg: MambaConfig, x: jnp.ndarray, state):
    """One-step decode.  x: (B, 1, d_model) -> (y (B, 1, d_model), new state)."""
    Bb = x.shape[0]
    H, G, N, P = cfg.n_heads, cfg.n_groups, cfg.d_state, cfg.head_dim
    zxbcdt = x @ params["in_proj"]
    z, xBC, dt = _split_in_proj(cfg, zxbcdt)
    xBC, conv_cache = _causal_conv(xBC, params["conv_w"], params["conv_b"],
                                   cache=state["conv"])
    xi, Bm, Cm = jnp.split(xBC, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]   # (B,H)
    A = -jnp.exp(params["A_log"])
    xi = xi.reshape(Bb, H, P).astype(jnp.float32)
    Bm = jnp.repeat(Bm.reshape(Bb, G, N), H // G, axis=1).astype(jnp.float32)  # (B,H,N)
    Cm = jnp.repeat(Cm.reshape(Bb, G, N), H // G, axis=1).astype(jnp.float32)

    a = jnp.exp(dt * A[None, :])                                  # (B,H)
    h = state["ssm"] * a[..., None, None] + \
        jnp.einsum("bhn,bh,bhp->bhnp", Bm, dt, xi)
    y = jnp.einsum("bhn,bhnp->bhp", Cm, h) + params["D"][None, :, None] * xi
    y = y.reshape(Bb, 1, cfg.d_inner).astype(x.dtype)
    y = rmsnorm_apply(params["norm"], y * silu(z))
    return y @ params["out_proj"], {"ssm": h, "conv": conv_cache}
