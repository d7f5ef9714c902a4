"""Multi-head attention with the variants needed by the assigned archs.

Supported features (all composable):
  * grouped-query attention (n_kv_heads < n_heads)
  * qk-norm (Qwen3)
  * attention logit soft-capping (Gemma-2)
  * sliding-window ("local") attention (Gemma-2 alternating layers)
  * cross-attention (Llama-3.2-Vision image layers, Whisper decoder)
  * KV-cache single-token decode path

The public entry point dispatches to the Pallas flash-attention kernel
(`repro.kernels.ops.flash_attention`) when enabled, otherwise to
:func:`sdpa_blockwise`, exact attention in plain jnp for XLA.  Both paths
share parameter layout and the same residual contract: a ``jax.custom_vjp``
whose forward saves (o, lse) as ``kernel_out`` and whose backward
recomputes the score tiles from (q, k, v, o, lse).  Under the
selective-remat policy the backward therefore never re-runs the attention
forward.  :func:`sdpa_blockwise` cuts the queries into blocks of at most
``chunk_q`` rows (smaller where a causal or window mask hides part of each
row) and forms only the tiles a block can see: at S = 2048, causal, blocks
of 256 rows compute 36 of the square's 64 tiles.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.obs import trace as _obs
from repro.obs.clock import now as _now

from .common import (apply_rope, dense_init, rmsnorm_apply, rmsnorm_init,
                     softcap)

Params = Any


@dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int | None = None          # default d_model // n_heads
    qk_norm: bool = False                # Qwen3
    attn_softcap: float | None = None    # Gemma-2 (e.g. 50.0)
    window: int | None = None            # sliding-window size; None = global
    rope_theta: float = 10000.0
    causal: bool = True
    use_bias: bool = False
    chunk_q: int = 1024                  # most query rows per block (memory bound)

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads


def attention_init(rng, cfg: AttentionConfig, dtype=jnp.float32) -> Params:
    hd = cfg.hd
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype,
                         scale=1.0 / (cfg.n_heads * hd) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def _project_qkv(params, cfg: AttentionConfig, x, xkv=None):
    """x: (B, S, D) -> q (B,S,H,hd), k/v (B,Skv,Hkv,hd)."""
    hd = cfg.hd
    xkv = x if xkv is None else xkv
    B, S, _ = x.shape
    Skv = xkv.shape[1]
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (xkv @ params["wk"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    v = (xkv @ params["wv"]).reshape(B, Skv, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm_apply(params["q_norm"], q)
        k = rmsnorm_apply(params["k_norm"], k)
    return q, k, v


def sdpa_reference(q, k, v, *, causal: bool, window: int | None,
                   logit_cap: float | None, q_positions=None, kv_positions=None):
    """Pure-jnp scaled dot-product attention with GQA.

    q: (B, S, H, hd); k, v: (B, Skv, Hkv, hd).  Grouped heads are expanded
    by reshaping q into (Hkv, group) and contracting per kv head.
    """
    B, S, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    qg = q.reshape(B, S, Hkv, group, hd)
    # logits: (B, Hkv, group, S, Skv)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logit_cap is not None:
        logits = softcap(logits, logit_cap)

    if q_positions is None:
        q_positions = jnp.arange(S)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv)
    qpos = q_positions[:, None]      # (S, 1)
    kpos = kv_positions[None, :]     # (1, Skv)
    mask = jnp.ones((S, Skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None, None], logits, -1e30)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


#: Query blocks a causal or windowed sequence is cut into (at most
#: ``chunk_q`` rows each, at least ``_MIN_BLOCK``): (n - 1) / 2n of the
#: causal square is then never formed.  On a TPU v5e the smollm-135m round
#: at S = 2048 took 1.895 s with 8 blocks and 2.095 s with 4.
_BAND_BLOCKS = 8
_MIN_BLOCK = 128
_MASKED = -1e30


def block_size(S: int, *, causal: bool, window: int | None,
               chunk_q: int = 1024) -> int:
    """Query rows per block of :func:`sdpa_blockwise`: ``min(chunk_q, S)``,
    cut to about ``S / _BAND_BLOCKS`` rows where a causal or window mask
    hides part of each row, so the masked tiles can be left out."""
    cq = min(chunk_q, S)
    if causal or window is not None:
        cq = min(cq, max(_MIN_BLOCK, -(-S // _BAND_BLOCKS)))
    return cq


def _plan(S: int, Skv: int, cq: int, causal: bool, window: int | None):
    """Static tiling: per query block ``(a, b, lo, hi, masked, full)``,
    rows [a, b) and the key range [lo, hi) they can see.  ``masked``: some
    pair in the block is hidden (diagonal/window edge tiles).  ``full``: a
    row of the block sees no key at all, so the block takes every key and
    keeps the -1e30 convention (uniform weights) of :func:`sdpa_reference`
    (a windowed row past ``Skv + window - 1``; never in self-attention)."""
    blocks = []
    for a in range(0, S, cq):
        b = min(a + cq, S)
        full = window is not None and b - 1 >= Skv + window - 1
        lo = max(0, a - window + 1) if window is not None and not full else 0
        hi = min(b, Skv) if causal and not full else Skv
        masked = ((causal and hi - 1 > a)
                  or (window is not None and lo <= b - 1 - window))
        blocks.append((a, b, lo, hi, masked, full))
    return tuple(blocks)


def tile_counts(S: int, Skv: int, cq: int, causal: bool,
                window: int | None) -> tuple[int, int]:
    """(cq × cq tiles computed, tiles of the full S × Skv square)."""
    done = sum(-(-hi // cq) - lo // cq
               for _, _, lo, hi, _, _ in _plan(S, Skv, cq, causal, window))
    return done, -(-S // cq) * -(-Skv // cq)


def _block_scores(qb, kb, a, lo, masked, causal, window, logit_cap, scale):
    """f32 scores (B, H, rows, keys) of one block of (B, ·, H, hd) inputs,
    softcapped and masked
    like :func:`sdpa_reference`; also the mask (None: nothing hidden) and
    the softcap's derivative (None: no cap)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) * scale
    dcap = None
    if logit_cap is not None:
        t = jnp.tanh(s / logit_cap)
        s, dcap = logit_cap * t, 1.0 - t * t
    mask = None
    if masked:
        qpos = a + jnp.arange(qb.shape[1])[:, None]
        kpos = lo + jnp.arange(kb.shape[1])[None, :]
        mask = jnp.ones(s.shape[-2:], bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, _MASKED)
    return s, mask, dcap


def _blockwise_fwd(q, k, v, causal, window, logit_cap, cq):
    """Forward over the visible tiles: (o (B, S, H, hd), lse (B, H, S))."""
    S, Skv, hd = q.shape[1], k.shape[1], q.shape[3]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qt, kt, vt = (t.astype(jnp.float32) for t in (q, k, v))
    outs, lses = [], []
    for a, b, lo, hi, masked, _ in _plan(S, Skv, cq, causal, window):
        s, _, _ = _block_scores(qt[:, a:b], kt[:, lo:hi], a, lo, masked,
                                causal, window, logit_cap, scale)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vt[:, lo:hi])
        outs.append(pv / jnp.swapaxes(l, 1, 2))
        lses.append((m + jnp.log(l))[..., 0])
    o = jnp.concatenate(outs, axis=1).astype(q.dtype)
    return o, jnp.concatenate(lses, axis=2)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _blockwise(q, k, v, causal, window, logit_cap, cq):
    return _blockwise_fwd(q, k, v, causal, window, logit_cap, cq)[0]


def _blockwise_vjp_fwd(q, k, v, causal, window, logit_cap, cq):
    o, lse = _blockwise_fwd(q, k, v, causal, window, logit_cap, cq)
    # the selective-remat policy saves these: the backward never re-runs
    # the forward over the tiles
    o = checkpoint_name(o, "kernel_out")
    lse = checkpoint_name(lse, "kernel_out")
    return o, (q, k, v, o, lse)


def _blockwise_vjp_bwd(causal, window, logit_cap, cq, res, do):
    """One pass per visible tile: P from the saved lse, D = rowsum(dO∘o)."""
    q, k, v, o, lse = res
    S, Skv, hd = q.shape[1], k.shape[1], q.shape[3]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qt, kt, vt, dot = (t.astype(jnp.float32) for t in (q, k, v, do))
    D = jnp.swapaxes(jnp.sum(dot * o.astype(jnp.float32), axis=-1), 1, 2)
    dqs = []
    dk = jnp.zeros(kt.shape, jnp.float32)
    dv = jnp.zeros(vt.shape, jnp.float32)
    for a, b, lo, hi, masked, full in _plan(S, Skv, cq, causal, window):
        qb, kb, vb, dob = (qt[:, a:b], kt[:, lo:hi], vt[:, lo:hi],
                           dot[:, a:b])
        s, mask, dcap = _block_scores(qb, kb, a, lo, masked, causal, window,
                                      logit_cap, scale)
        p = (jax.nn.softmax(s, axis=-1) if full
             else jnp.exp(s - lse[:, :, a:b, None]))
        dp = jnp.einsum("bqhd,bkhd->bhqk", dob, vb)
        ds = p * (dp - D[:, :, a:b, None]) * scale
        if dcap is not None:
            ds = ds * dcap
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)
        dqs.append(jnp.einsum("bhqk,bkhd->bqhd", ds, kb))
        dk = dk.at[:, lo:hi].add(jnp.einsum("bhqk,bqhd->bkhd", ds, qb))
        dv = dv.at[:, lo:hi].add(jnp.einsum("bhqk,bqhd->bkhd", p, dob))
    dq = jnp.concatenate(dqs, axis=1)
    return tuple(g.astype(t.dtype) for g, t in ((dq, q), (dk, k), (dv, v)))


_blockwise.defvjp(_blockwise_vjp_fwd, _blockwise_vjp_bwd)


def sdpa_blockwise(q, k, v, *, causal: bool, window: int | None,
                   logit_cap: float | None, chunk_q: int = 1024):
    """Exact attention over the visible tiles only, with its own VJP.

    q: (B, S, H, hd); k, v: (B, Skv, Hkv, hd).  Query blocks of
    :func:`block_size` rows each attend to the key range they can see
    (causal: keys up to the block's last row; a window trims the start);
    masked tiles are never formed, and the mask is applied only in the
    edge tiles.  Scores, softmax statistics and accumulators are f32; the
    forward saves (o, lse) as ``kernel_out``, so under the selective-remat
    policy the backward (one pass per visible tile, P = exp(S − lse))
    never re-runs the forward.  K/V are expanded to H heads so the head
    dim stays cleanly shardable under TP (GQA kv counts rarely divide the
    ``model`` axis; q heads do); the expansion's transpose sums the
    groups' K/V gradients back to Hkv heads."""
    B, S, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    cq = block_size(S, causal=causal, window=window, chunk_q=chunk_q)
    if _obs.TRACING:
        done, total = tile_counts(S, Skv, cq, causal, window)
        _obs.emit_instant_once(
            "host/compile", "attention_tiles", _now(),
            shape=[B, S, Skv, H, hd], causal=causal, window=window,
            tiles=done, of=total, cq=cq)
    group = H // Hkv
    kf = jnp.repeat(k, group, axis=2)       # (B, Skv, H, hd)
    vf = jnp.repeat(v, group, axis=2)
    return _blockwise(q, kf, vf, causal, window, logit_cap, cq)


def attention_apply(params: Params, cfg: AttentionConfig, x, *, xkv=None,
                    positions=None, use_kernel: bool = False,
                    return_kv: bool = False, parallelism=None):
    """Full-sequence attention (training / prefill). x: (B, S, D).
    With return_kv=True also returns the rotated {"k","v"} for cache
    priming (prefill)."""
    B, S, _ = x.shape
    con = parallelism.heads if parallelism is not None else (lambda t: t)
    q, k, v = _project_qkv(params, cfg, x, xkv)
    q, k, v = con(q), con(k), con(v)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if xkv is None:  # self-attention: RoPE on q and k
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        causal = cfg.causal
    else:            # cross-attention: no RoPE, no causal mask
        causal = False
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=cfg.window,
                                   logit_cap=cfg.attn_softcap)
    else:
        out = sdpa_blockwise(q, k, v, causal=causal, window=cfg.window,
                             logit_cap=cfg.attn_softcap, chunk_q=cfg.chunk_q)
    out = con(out).reshape(B, S, cfg.n_heads * cfg.hd) @ params["wo"]
    if return_kv:
        return out, {"k": k, "v": v}
    return out


# ---------------------------------------------------------------------------
# KV-cache decode path
# ---------------------------------------------------------------------------

def kv_cache_init(cfg: AttentionConfig, batch: int, max_len: int, dtype=jnp.float32):
    hd = cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
    }


def attention_decode(params: Params, cfg: AttentionConfig, x, cache, position,
                     ring: bool = False):
    """Single-token decode step.

    x: (B, 1, D); cache: {"k","v"}: (B, T, Hkv, hd); position: scalar int —
    the index of the new token (same for the whole batch; per-request offsets
    are handled a level above by the serving layer).
    Returns (out (B, 1, D), new_cache).

    ring=True treats the cache as a ring buffer of length T (sliding-window
    layers keep only the last ``window`` K/V): the write index is
    ``position % T`` and slot j holds position p_j = position-((position-j)%T),
    valid iff p_j >= 0.  RoPE uses absolute positions, so ring slots stay
    correctly rotated.
    """
    B = x.shape[0]
    T = cache["k"].shape[1]
    q, k, v = _project_qkv(params, cfg, x)
    pos = jnp.full((B, 1), position, dtype=jnp.int32)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    write_idx = position % T if ring else position
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), write_idx, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), write_idx, axis=1)

    kv_positions = jnp.arange(T)
    if ring:
        # slot j holds absolute position p_j; valid once written (p_j >= 0);
        # the ring length IS the window, so no further window mask is needed.
        p_j = position - jnp.mod(position - kv_positions, T)
        valid = p_j >= 0
    else:
        # valid: kv slot <= current position (and within window if local)
        valid = kv_positions <= position
        if cfg.window is not None:
            valid &= kv_positions > position - cfg.window
    hd = cfg.hd
    Hkv = cfg.n_kv_heads
    group = cfg.n_heads // Hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    qg = q.reshape(B, 1, Hkv, group, hd)
    logits = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32),
                        ck.astype(jnp.float32)) * scale
    if cfg.attn_softcap is not None:
        logits = softcap(logits, cfg.attn_softcap)
    logits = jnp.where(valid[None, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, cv.astype(jnp.float32))
    out = out.reshape(B, 1, cfg.n_heads * hd).astype(x.dtype) @ params["wo"]
    return out, {"k": ck, "v": cv}
