"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

Algorithm (per batch × head, chunk length Q):
  intra-chunk:  Y_intra = ((C B^T) ⊙ decay_tril) (dt ⊙ X)       — MXU matmuls
  chunk state:  S_c     = B^T diag(w) (dt ⊙ X),  w_s = e^{L_Q - L_s}
  recurrence:   h_c     = e^{L_Q} h_{c-1} + S_c                 — VMEM carry
  inter-chunk:  Y_inter = (C ⊙ e^{L})  h_{c-1}

TPU adaptation: the chunk dimension is the innermost grid axis; TPU grid
steps run sequentially, so the (N × P) state lives in VMEM scratch and is
carried across chunks — this replaces the GPU implementation's separate
state-passing kernel + inter-block sync.  All matmuls are (Q×N)(N×P)-style
MXU shapes; Q, N, P default to 128/128/64.

Backward: the forward also emits each chunk's *entry* state h_{c-1}
(an (nc, N, P) residual per batch × head — the linear-recurrence analogue
of flash attention's LSE), and the backward kernel walks the chunks in
REVERSE grid order carrying dh (the gradient of the carried state) in VMEM
scratch, recomputing the decay/score tiles per chunk to produce
dx/ddt/dA/dB/dC.  dB/dC come out per *head* and are group-summed to the
(B, T, G, N) layout by the JAX wrapper; dA accumulates per (batch, head)
in scratch and is reduced outside.

Layouts are head-major, so that every block's last two dims are a
(chunk, width) tile (Mosaic needs them (8, 128)-aligned or whole; a
(chunk, 1) slice over H is neither): x (B, H, T, P); dt (B, H, T);
A (H,); Bm/Cm (B, G, T, N); out (B, H, T, P).  T % Q == 0; ops.py pads
and transposes from the models' (B, T, H, P).  Inside the kernels dt
arrives as a (1, Q) row, A as an SMEM scalar, and per-step vectors are
(Q, 1) columns or (1, Q) rows: cumulative sums are masked reductions over
a (Q, Q) tile, and a row turns into a column through the identity mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128        # lane width of a TPU vreg: per-(b, h) dA fills one row


def _col(row, eye):
    """(1, Q) row -> (Q, 1) column (diagonal of the broadcast tile)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    """(Q, 1) column -> (1, Q) row."""
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _chunk_tiles(dt_row, a, Bm, Cm, *, chunk: int):
    """Shared forward recomputation: log-decay cumsum (as a column and a
    row) and the masked decay / score tiles every term of the chunk
    algebra is built from.  Masks: ``tri[t, s]`` is s <= t, ``triu`` its
    transpose, ``eye`` the diagonal."""
    i0 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    i1 = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri, triu, eye = i0 >= i1, i0 <= i1, i0 == i1
    dt = _col(dt_row, eye)                             # (Q, 1)
    la_row, la = dt_row * a, dt * a                    # log-decay, <= 0
    Lcum = jnp.sum(jnp.where(tri, la_row, 0.0), axis=1, keepdims=True)
    Lcum_row = jnp.sum(jnp.where(triu, la, 0.0), axis=0, keepdims=True)
    Ltot = jnp.sum(la_row, axis=1, keepdims=True)      # (1, 1)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q, Q)
    decay = jnp.where(tri, jnp.exp(Lcum - Lcum_row), 0.0)   # e^{L_t - L_s}
    return dt, Lcum, Ltot, scores, decay, (tri, triu, eye)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, h_scr, *,
                chunk: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)                # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)          # (1, Q)
    a = a_ref[pl.program_id(1)]                        # scalar A_h (negative)
    Bm = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)               # (Q, N)

    dt, Lcum, Ltot, scores, decay, _ = _chunk_tiles(dt_row, a, Bm, Cm,
                                                    chunk=chunk)

    xb = x * dt                                        # dt-weighted input (Q, P)

    # intra-chunk quadratic term
    y_intra = jax.lax.dot_general(scores * decay, xb, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk contribution from carried state
    h_prev = h_scr[...]                                # (N, P)
    st_ref[0, 0, 0] = h_prev                           # backward residual
    y_inter = jax.lax.dot_general(Cm * jnp.exp(Lcum), h_prev,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # state update: h = e^{Ltot} h + B^T diag(e^{Ltot - Lcum}) xb
    w = jnp.exp(Ltot - Lcum)                           # (Q, 1)
    S_c = jax.lax.dot_general(Bm * w, xb, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)     # (N, P)
    h_scr[...] = jnp.exp(Ltot) * h_prev + S_c

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


def _smem_spec():
    """Whole (H,) A vector in scalar memory; the kernel reads A[h]."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def ssd_fwd_chunked_pallas(x, dt, A, Bm, Cm, *, chunk=128, interpret=False):
    """x: (B, H, T, P); dt: (B, H, T); A: (H,); Bm, Cm: (B, G, T, N).
    Returns (y (B, H, T, P), states (B, H, nc, N, P)) where states[..., c]
    is the carried state *entering* chunk c.  T % chunk == 0 (ops.py pads).
    """
    Bb, H, T, P = x.shape
    G, N = Bm.shape[1], Bm.shape[3]
    assert T % chunk == 0, (T, chunk)
    rep = H // G
    nc = T // chunk
    grid = (Bb, H, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, states = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            _smem_spec(),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, nc, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt.reshape(Bb, H, 1, T), A.astype(jnp.float32), Bm, Cm)
    return y, states


def ssd_chunked_pallas(x, dt, A, Bm, Cm, *, chunk=128, interpret=False):
    """Forward-only wrapper returning y (B, H, T, P)."""
    y, _ = ssd_fwd_chunked_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                                  interpret=interpret)
    return y


# ---------------------------------------------------------------------------
# Backward (reverse chunk scan carrying dh in VMEM)
# ---------------------------------------------------------------------------

def _ssd_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, dy_ref,
                    dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                    dh_scr, da_scr, *, chunk: int):
    """One reverse grid step = one chunk.  dh_scr carries ∂L/∂h_c from the
    chunks *after* this one (the reverse of the forward's VMEM state carry);
    da_scr accumulates the per-(batch, head) scalar ∂L/∂A over all chunks,
    replicated across one row of lanes."""
    c_idx = pl.program_id(2)        # 0 == LAST chunk (index maps reverse)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        da_scr[...] = jnp.zeros_like(da_scr)

    x = x_ref[0, 0].astype(jnp.float32)                # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)          # (1, Q)
    a = a_ref[pl.program_id(1)]
    Bm = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)               # (Q, N)
    h_prev = st_ref[0, 0, 0].astype(jnp.float32)       # (N, P) entry state
    dy = dy_ref[0, 0].astype(jnp.float32)              # (Q, P)
    dh = dh_scr[...]                                   # (N, P) ∂L/∂h_c

    dt, Lcum, Ltot, scores, decay, (tri, triu, eye) = _chunk_tiles(
        dt_row, a, Bm, Cm, chunk=chunk)
    xb = x * dt
    expL = jnp.exp(Lcum)                               # (Q, 1)
    w = jnp.exp(Ltot - Lcum)                           # (Q, 1)

    def mm(lhs, rhs, contract):
        return jax.lax.dot_general(lhs, rhs, (contract, ((), ())),
                                   preferred_element_type=jnp.float32)

    def total(t):
        return jnp.sum(jnp.sum(t, axis=1, keepdims=True), axis=0,
                       keepdims=True)                  # (1, 1)

    # y = (scores ⊙ decay) xb + (C ⊙ e^{L}) h_prev
    dM = mm(dy, xb, ((1,), (1,)))                      # (Q, Q)
    dxb = mm(scores * decay, dy, ((0,), (0,)))         # Mᵀ dy   (Q, P)
    dscores = dM * decay
    dCm = mm(dscores, Bm, ((1,), (0,)))                # (Q, N)
    dBm = mm(dscores, Cm, ((0,), (0,)))                # dscoresᵀ C (Q, N)
    ddiff = jnp.where(tri, dM * scores * decay, 0.0)   # decay = e^{diff} ⊙ tri
    dLcum = jnp.sum(ddiff, axis=1, keepdims=True) - \
        _col(jnp.sum(ddiff, axis=0, keepdims=True), eye)

    dyh = mm(dy, h_prev, ((1,), (1,)))                 # dy h_prevᵀ (Q, N)
    dCm += dyh * expL
    dLcum += jnp.sum(dyh * Cm, axis=1, keepdims=True) * expL
    dh_prev = mm(Cm * expL, dy, ((0,), (0,)))          # (N, P)

    # h = e^{Ltot} h_prev + (B ⊙ w)ᵀ xb,   ∂L/∂h = dh
    dxb += mm(Bm * w, dh, ((1,), (0,)))                # (Q, P)
    dBw = mm(xb, dh, ((1,), (1,)))                     # xb dhᵀ (Q, N)
    dBm += dBw * w
    dw = jnp.sum(dBw * Bm, axis=1, keepdims=True)      # (Q, 1)
    dLtot = jnp.exp(Ltot) * total(dh * h_prev) + total(dw * w)
    dLcum -= dw * w
    dh_prev += jnp.exp(Ltot) * dh

    # Lcum = cumsum(la), Ltot = Lcum[-1] ⇒ dla_s = Σ_{t≥s} dLcum_t + dLtot
    dla = jnp.sum(jnp.where(triu, _row(dLcum, eye), 0.0), axis=1,
                  keepdims=True) + dLtot

    # la = dt·a; xb = x·dt
    ddt = dla * a + jnp.sum(dxb * x, axis=1, keepdims=True)
    da_scr[...] += jnp.broadcast_to(
        jnp.sum(dla * dt, axis=0, keepdims=True), da_scr.shape)
    dx = dxb * dt

    dx_ref[0, 0] = dx
    ddt_ref[0, 0] = _row(ddt, eye)
    db_ref[0, 0] = dBm
    dc_ref[0, 0] = dCm
    dh_scr[...] = dh_prev

    @pl.when(c_idx == nc - 1)
    def _finalize():
        da_ref[0, 0] = da_scr[...]


def ssd_bwd_chunked_pallas(x, dt, A, Bm, Cm, states, dy, *, chunk=128,
                           interpret=False):
    """Reverse-scan backward, head-major like the forward.  states:
    (B, H, nc, N, P) chunk entry states from the forward.  Returns
    (dx, ddt, dA, dBm, dCm) — dBm/dCm already group-summed to
    (B, G, T, N), everything float32."""
    Bb, H, T, P = x.shape
    G, N = Bm.shape[1], Bm.shape[3]
    assert T % chunk == 0, (T, chunk)
    rep = H // G
    nc = T // chunk
    grid = (Bb, H, nc)

    # grid step c processes chunk nc-1-c: the reverse scan is pure index
    # arithmetic, the kernel body only sees "its" chunk.
    def rev(c, n=nc):
        return n - 1 - c

    kernel = functools.partial(_ssd_bwd_kernel, chunk=chunk)
    dx, ddt, dbh, dch, dab = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, rev(c), 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, rev(c))),
            _smem_spec(),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, r=rep: (b, h // r, rev(c), 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, r=rep: (b, h // r, rev(c), 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, h, c: (b, h, rev(c), 0, 0)),
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, rev(c), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, rev(c), 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, rev(c))),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, rev(c), 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, c: (b, h, rev(c), 0)),
            pl.BlockSpec((1, 1, 1, LANES), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, T, P), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, 1, T), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, T, N), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, T, N), jnp.float32),
            jax.ShapeDtypeStruct((Bb, H, 1, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((N, P), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt.reshape(Bb, H, 1, T), A.astype(jnp.float32), Bm, Cm, states, dy)

    dA = jnp.sum(dab[:, :, 0, 0], axis=0)                   # (H,)
    # B/C are shared across each group's rep = H//G heads: sum the group.
    dBm = dbh.reshape(Bb, G, rep, T, N).sum(axis=2)
    dCm = dch.reshape(Bb, G, rep, T, N).sum(axis=2)
    return dx, ddt.reshape(Bb, H, T), dA, dBm, dCm
