"""Fused flash attention for TPU (Pallas), with GQA, causal masking,
sliding-window ("local") attention, and Gemma-2 logit soft-capping.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * tiling targets VMEM: one (block_q × head_dim) query tile and one
    (block_k × head_dim) K/V tile resident per grid step; the MXU consumes
    (block_q × head_dim) @ (head_dim × block_k) matmuls, so block sizes are
    multiples of 128 and head_dim is the contracting dim;
  * the online-softmax running state (m, l, acc) lives in VMEM scratch and
    is carried across the innermost grid dimension (TPU grid steps execute
    sequentially, which replaces CUDA's per-CTA shared-memory loop);
  * causal/window block skipping is a `pl.when` guard on whole tiles (the
    TPU equivalent of warp-level early exit).

Backward pass (FlashAttention-2 style recompute): the forward additionally
emits the per-row LSE (logsumexp of the masked logits), and the backward
kernels rebuild each attention tile from (q, k, lse) — never materialising
the (S × Skv) score matrix — to produce dq (one kernel, kv blocks innermost)
and dk/dv (a second kernel, query blocks innermost, accumulating over the
H//Hkv GQA query-head group in VMEM scratch).  Soft-capping contributes the
tanh-derivative factor (1 - (z/cap)²) to dS.

Layout: q (B, H, S, hd); k, v (B, Hkv, Skv, hd).  `ops.flash_attention`
wraps the (B, S, H, hd) public layout.  Per-row statistics (m, l, lse, Δ)
are held as (rows, LANES) tiles, replicated across the 128 lanes as in
JAX's own TPU flash attention: Mosaic needs a block's last two dims
(8, 128)-aligned or whole, which a (1, block_q) slice of a (B, H, S)
array is not.  The kernels read them back as (rows, 1) columns.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128        # lane width of a TPU vreg: row statistics fill one tile


def _lanes(col):
    """(rows, 1) column -> (rows, LANES) lane-replicated tile."""
    return jnp.broadcast_to(col, (col.shape[0], LANES))


def _tile_mask(q_start, k_start, *, causal, window, block_q, block_k, seq_k):
    """The (block_q, block_k) validity mask of one attention tile."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k                             # padding
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    return mask


def _tile_relevant(q_start, k_start, *, causal, window, block_q, block_k):
    """Whole-tile skip predicate: False iff every entry is masked by the
    causal/window structure (padding is handled by the entry mask)."""
    relevant = jnp.bool_(True)
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + block_q - 1)
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window)
    return relevant


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int | None,
               logit_cap: float | None, block_q: int, block_k: int,
               seq_q: int, seq_k: int):
    i = pl.program_id(2)          # query block
    j = pl.program_id(3)          # kv block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)

        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, seq_k=seq_k)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                      # (bq, 1) columns
        l_prev = l_scr[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                 # NEG_INF-safe: exp(-inf)≈0
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_scr[...] = _lanes(m_new)
        l_scr[...] = _lanes(l_new)

    # tile-level skip: in causal/window mode many (i, j) tiles are fully
    # masked — skip their compute entirely (TPU analogue of early exit).
    if causal or window is not None:
        pl.when(_tile_relevant(q_start, k_start, causal=causal, window=window,
                               block_q=block_q, block_k=block_k))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)
        # LSE of the masked row; fully-masked rows keep NEG_INF so the
        # backward's exp(z - lse) stays mask-zeroed rather than NaN.
        lse_ref[0, 0] = _lanes(jnp.where(l > 0.0,
                                         m_scr[...][:, :1] + jnp.log(safe),
                                         NEG_INF))


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def flash_attention_fwd_bhsd(q, k, v, *, causal=True, window=None,
                             logit_cap=None, block_q=128, block_k=128,
                             interpret=False):
    """q: (B, H, S, hd); k, v: (B, Hkv, Skv, hd).
    Returns (out (B, H, S, hd), lse (B, H, S) float32)."""
    B, H, S, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, Skv)

    # pad sequences to block multiples (mask handles the tail)
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    Sp, Skvp = qp.shape[2], kp.shape[2]
    if Sp % block_q or Skvp % block_k:
        raise ValueError(
            f"padded seq lengths ({Sp}, {Skvp}) not divisible by blocks "
            f"({block_q}, {block_k}); the grid would drop the tail")
    grid = (B, H, Sp // block_q, Skvp // block_k)

    kernel = functools.partial(
        _fa_kernel, scale=1.0 / math.sqrt(hd), causal=causal, window=window,
        logit_cap=logit_cap, block_q=block_q, block_k=block_k,
        seq_q=S, seq_k=Skv)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sp, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :S], lse[:, :, :S, 0]


def flash_attention_bhsd(q, k, v, *, causal=True, window=None, logit_cap=None,
                         block_q=128, block_k=128, interpret=False):
    """Forward-only convenience wrapper: returns just (B, H, S, hd)."""
    out, _ = flash_attention_fwd_bhsd(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out


# ---------------------------------------------------------------------------
# Backward kernels (recompute from q, k, v, lse — FlashAttention-2 schedule)
# ---------------------------------------------------------------------------

def _tile_p_ds(q, k, v, do, lse, delta, mask, *, scale, logit_cap):
    """Rebuild one attention tile's probabilities p and logit-gradient dS.

    z = softcap(scale·qkᵀ); p = exp(z - lse); dS = p·(doᵀv - Δ) with the
    tanh-derivative factor (1 - (z/cap)²) when soft-capped.  ``lse`` and
    ``delta`` are (bq, 1) columns.  Fully-masked rows carry lse = NEG_INF;
    the mask zeroes p there before any use.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if logit_cap is not None:
        z = logit_cap * jnp.tanh(s / logit_cap)
    else:
        z = s
    p = jnp.exp(z - lse)
    p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    if logit_cap is not None:
        ds = ds * (1.0 - jnp.square(z / logit_cap))    # d softcap / d s
    return p, ds


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dq_scr, *, scale: float, causal: bool,
                      window: int | None, logit_cap: float | None,
                      block_q: int, block_k: int, seq_k: int):
    """dq = Σ_j dS_ij · K_j · scale; kv blocks innermost, dq in VMEM."""
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = i * block_q
    k_start = j * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, seq_k=seq_k)
        _, ds = _tile_p_ds(q, k, v, do, lse_ref[0, 0][:, :1],
                           delta_ref[0, 0][:, :1], mask,
                           scale=scale, logit_cap=logit_cap)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal or window is not None:
        pl.when(_tile_relevant(q_start, k_start, causal=causal, window=window,
                               block_q=block_q, block_k=block_k))(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                       causal: bool, window: int | None,
                       logit_cap: float | None, block_q: int, block_k: int,
                       seq_k: int, group: int):
    """dk = Σ_i dS_ijᵀ · Q_i · scale, dv = Σ_i P_ijᵀ · dO_i; query blocks
    innermost, accumulating over the GQA query-head group g in VMEM —
    grid (B, Hkv, nk, group, nq), so one (kv head, kv block) owns its
    dk/dv tile across all g·nq sequential steps."""
    j = pl.program_id(2)
    g = pl.program_id(3)
    i = pl.program_id(4)
    nq = pl.num_programs(4)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = i * block_q
    k_start = j * block_k

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, seq_k=seq_k)
        p, ds = _tile_p_ds(q, k, v, do, lse_ref[0, 0][:, :1],
                           delta_ref[0, 0][:, :1], mask,
                           scale=scale, logit_cap=logit_cap)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal or window is not None:
        pl.when(_tile_relevant(q_start, k_start, causal=causal, window=window,
                               block_q=block_q, block_k=block_k))(_compute)
    else:
        _compute()

    @pl.when(jnp.logical_and(g == group - 1, i == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd_bhsd(q, k, v, o, lse, do, *, causal=True, window=None,
                             logit_cap=None, block_q=128, block_k=128,
                             interpret=False):
    """Recompute backward.  q/o/do: (B, H, S, hd); k, v: (B, Hkv, Skv, hd);
    lse: (B, H, S).  Returns (dq, dk, dv) in float32."""
    B, H, S, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = H // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, Skv)
    scale = 1.0 / math.sqrt(hd)

    # Δ = rowsum(dO ⊙ O): the softmax-normalisation term of dS (the cheap
    # "preprocess" pass; padded rows are zero because dO pads with zeros).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qp, op, dop = (_pad_to(t, 2, block_q) for t in (q, o, do))
    kp, vp = (_pad_to(t, 2, block_k) for t in (k, v))
    lsep, deltap = (jnp.broadcast_to(_pad_to(t, 2, block_q)[..., None],
                                     (B, H, qp.shape[2], LANES))
                    for t in (lse, delta))
    Sp, Skvp = qp.shape[2], kp.shape[2]
    if Sp % block_q or Skvp % block_k:
        raise ValueError(
            f"padded seq lengths ({Sp}, {Skvp}) not divisible by blocks "
            f"({block_q}, {block_k}); the grid would drop the tail")
    nq, nk = Sp // block_q, Skvp // block_k
    del op  # o only feeds Δ

    common = dict(scale=scale, causal=causal, window=window,
                  logit_cap=logit_cap, block_q=block_q, block_k=block_k,
                  seq_k=Skv)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **common),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, **common, group=group),
        grid=(B, Hkv, nk, group, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, kh, j, g, i, gr=group: (b, kh * gr + g, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, kh, j, g, i: (b, kh, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, kh, j, g, i: (b, kh, j, 0)),
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, kh, j, g, i, gr=group: (b, kh * gr + g, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, kh, j, g, i, gr=group: (b, kh * gr + g, i, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b, kh, j, g, i, gr=group: (b, kh * gr + g, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, kh, j, g, i: (b, kh, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, kh, j, g, i: (b, kh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Skvp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, Skvp, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, deltap)

    return dq[:, :, :S], dk[:, :, :Skv], dv[:, :, :Skv]
