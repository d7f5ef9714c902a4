"""Backbone assembly: decoder LMs, hybrids, enc-dec, VLM — scan over periods.

Public API
----------
  init_params(rng, cfg, dtype)                  -> params pytree
  forward(params, cfg, tokens, ...)             -> final hidden (B, S, D)
  lm_loss(params, cfg, tokens, labels, ...)     -> (scalar loss, aux)
  init_decode_state(cfg, batch, max_len, dtype) -> caches
  decode_step(params, cfg, state, token, pos)   -> (logits, new state)

FedOptima split API (device/server halves + auxiliary network):
  split_params(params, cfg, l_split)            -> (device_params, server_params)
  device_forward(dev_params, cfg, tokens, l_split)  -> activations
  aux_head_loss(dev_params, cfg, acts, labels)  -> scalar local loss
  server_forward_loss(srv_params, cfg, acts, labels, l_split) -> scalar loss
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .api import ArchConfig
from .attention import (attention_apply, attention_decode, attention_init,
                        kv_cache_init, sdpa_reference)
from .common import (dense_init, embed_init, rmsnorm_apply, rmsnorm_init,
                     softcap)
from .mamba import (mamba_apply, mamba_decode, mamba_init, mamba_state_init)
from .mlp import mlp_apply, mlp_init, moe_apply_grouped, moe_init

Params = Any


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _block_init(rng, cfg: ArchConfig, mixer: str, ffn: str, dtype) -> Params:
    k1, k2 = jax.random.split(rng)
    p: dict = {"ln1": rmsnorm_init(cfg.d_model, dtype)}
    if mixer in ("attn", "local"):
        p["mixer"] = attention_init(k1, cfg.attn_cfg(mixer), dtype)
    elif mixer == "cross":
        p["mixer"] = attention_init(k1, cfg.cross_cfg(), dtype)
        p["gate"] = jnp.zeros((), dtype)      # zero-init gated cross-attn
    elif mixer == "mamba":
        p["mixer"] = mamba_init(k1, cfg.mamba_cfg(), dtype)
    elif mixer != "none":
        raise ValueError(mixer)
    if ffn == "dense":
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = mlp_init(k2, cfg.mlp_cfg(), dtype)
    elif ffn == "moe":
        p["ln2"] = rmsnorm_init(cfg.d_model, dtype)
        p["ffn"] = moe_init(k2, cfg.moe_cfg(), dtype)
    elif ffn != "none":
        raise ValueError(ffn)
    return p


def _stack_init(rng, cfg: ArchConfig, n_periods: int, dtype) -> list:
    """Per-position-in-period param stacks, leaves shaped (n_periods, ...)."""
    stacks = []
    for pos, (mixer, ffn) in enumerate(cfg.pattern):
        rngs = jax.random.split(jax.random.fold_in(rng, pos), n_periods)
        per = [_block_init(r, cfg, mixer, ffn, dtype) for r in rngs]
        stacks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    return stacks


def init_params(rng, cfg: ArchConfig, dtype=jnp.float32) -> Params:
    ke, kb, kh, kd = jax.random.split(rng, 4)
    params: dict = {
        "embed": embed_init(ke, cfg.vocab, cfg.d_model, dtype),
        "blocks": _stack_init(kb, cfg, cfg.n_periods, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, cfg.d_model, cfg.vocab, dtype)
    if cfg.n_decoder_layers:  # enc-dec (audio family): decoder stack
        dec_cfg = _decoder_cfg(cfg)
        params["dec_blocks"] = _stack_init(kd, dec_cfg, dec_cfg.n_periods, dtype)
        params["dec_norm"] = rmsnorm_init(cfg.d_model, dtype)
    return params


def _decoder_cfg(cfg: ArchConfig) -> ArchConfig:
    """Decoder stack of an enc-dec model: self-attn + cross-attn + mlp."""
    return cfg.scaled(n_layers=cfg.n_decoder_layers,
                      pattern=(("attn", "none"), ("cross", "dense")),
                      n_decoder_layers=0)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------

def _attn_out(y, parallelism):
    """An attention block's output: both attention paths save their
    output o ("kernel_out"), and y = o @ wo is one small matmul to
    recompute, so y is saved ("tp_out") only where it took a TP
    all-reduce that the recompute would issue again."""
    from jax.ad_checkpoint import checkpoint_name
    if parallelism is not None and parallelism.mesh.shape.get(
            parallelism.tp_axis, 1) > 1:
        return checkpoint_name(y, "tp_out")
    return y


def _apply_block(p: Params, cfg: ArchConfig, mixer: str, ffn: str, h, *,
                 positions, frontend=None, use_kernel=False, parallelism=None,
                 return_state=False):
    """One block.  Mixer/FFN outputs are `checkpoint_name`d "tp_out": with
    the selective remat policy these post-TP-collective tensors are saved,
    so the backward pass never re-runs the forward all-reduces (attention
    outputs only where they took one: `_attn_out`)."""
    from jax.ad_checkpoint import checkpoint_name
    aux = jnp.zeros((), jnp.float32)
    state = {}
    if mixer in ("attn", "local"):
        y = attention_apply(p["mixer"], cfg.attn_cfg(mixer),
                            rmsnorm_apply(p["ln1"], h),
                            positions=positions, use_kernel=use_kernel,
                            return_kv=return_state, parallelism=parallelism)
        if return_state:
            y, state = y
        h = h + _attn_out(y, parallelism)
    elif mixer == "cross":
        y = attention_apply(p["mixer"], cfg.cross_cfg(),
                            rmsnorm_apply(p["ln1"], h), xkv=frontend,
                            return_kv=return_state, parallelism=parallelism)
        if return_state:
            y, state = y
        h = h + jnp.tanh(p["gate"]) * _attn_out(y, parallelism)
    elif mixer == "mamba":
        y = mamba_apply(p["mixer"], cfg.mamba_cfg(),
                        rmsnorm_apply(p["ln1"], h), use_kernel=use_kernel,
                        return_state=return_state)
        if return_state:
            y, state = y
        h = h + checkpoint_name(y, "tp_out")
    if ffn == "dense":
        y = mlp_apply(p["ffn"], cfg.mlp_cfg(),
                      rmsnorm_apply(p["ln2"], h), parallelism=parallelism)
        h = h + checkpoint_name(y, "tp_out")
    elif ffn == "moe":
        y, aux = _moe_dispatch(p["ffn"], cfg, rmsnorm_apply(p["ln2"], h),
                               parallelism)
        h = h + checkpoint_name(y, "tp_out")
    if return_state:
        return h, aux, state
    return h, aux


def _moe_dispatch(p, cfg: ArchConfig, x, parallelism):
    """MoE ffn, optionally expert-parallel over the mesh 'model' axis.

    With a `parallelism` spec, runs under shard_map: tokens sharded over the
    dp axes and replicated over 'model'; each model shard holds E/tp experts
    and computes only tokens routed to them; partial outputs are psum'd over
    'model' (expert parallelism fused onto the TP axis).
    """
    mcfg = cfg.moe_cfg()
    if parallelism is None or not parallelism.ep:
        return moe_apply_grouped(p, mcfg, x,
                                 capacity_factor=cfg.moe_capacity_factor)
    P = jax.sharding.PartitionSpec
    mesh = parallelism.mesh
    tp = mesh.shape[parallelism.tp_axis]
    E_l = mcfg.n_experts // tp
    dp_spec = P(parallelism.dp_axes, None, None)
    expert_spec = jax.tree.map(lambda _: P(parallelism.tp_axis), p)
    expert_spec["router"] = P()  # router replicated

    def local_moe(p_l, x_l):
        idx = jax.lax.axis_index(parallelism.tp_axis)
        y, aux = moe_apply_grouped(
            p_l, mcfg, x_l, expert_offset=idx * E_l, n_local_experts=E_l,
            capacity_factor=cfg.moe_capacity_factor,
            psum_axis=parallelism.tp_axis)
        return y, aux

    from repro.parallel.sharding import shard_map
    y, aux = shard_map(
        local_moe, mesh=mesh, in_specs=(expert_spec, dp_spec),
        out_specs=(dp_spec, P()))(p, x)
    return y, aux


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _run_stack(blocks, cfg: ArchConfig, h, *, positions, frontend=None,
               use_kernel=False, parallelism=None, remat=True):
    def period_fn(h, stacks_slice):
        aux_total = jnp.zeros((), jnp.float32)
        for pos, (mixer, ffn) in enumerate(cfg.pattern):
            h, aux = _apply_block(stacks_slice[pos], cfg, mixer, ffn, h,
                                  positions=positions, frontend=frontend,
                                  use_kernel=use_kernel, parallelism=parallelism)
            aux_total = aux_total + aux
        return h, aux_total

    if remat == "selective":
        # full remat EXCEPT the post-TP-collective block outputs: backward
        # recompute stops at the saved tensors, so the forward's TP
        # all-reduces are never re-issued (collective term / ~1.5).
        # "kernel_out" additionally saves the attention paths' (o, lse)
        # and the SSD kernel's chunk-state residuals — O(S·hd), never the
        # (S×S) scores — so the custom_vjp backward doesn't re-run the
        # forward either.
        fn = jax.checkpoint(
            period_fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "tp_out", "kernel_out"))
    elif remat:
        fn = jax.checkpoint(period_fn)
    else:
        fn = period_fn

    def body(carry, stacks_slice):
        h, aux_sum = carry
        if parallelism is not None:
            h = parallelism.resid(h)   # seq-parallel saved carries
        h, aux = fn(h, stacks_slice)
        return (h, aux_sum + aux), ()

    (h, aux_sum), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                   tuple(blocks))
    if parallelism is not None:
        h = parallelism.resid(h)
    return h, aux_sum


def forward(params: Params, cfg: ArchConfig, tokens, *, frontend=None,
            use_kernel=False, parallelism=None, remat=True):
    """tokens: (B, S) int32 (or (B, S, D) pre-embedded frontend stub for
    audio encoders).  Returns final hidden states (B, S, D)."""
    if tokens.ndim == 2:
        h = params["embed"][tokens]
    else:
        h = tokens
    S = h.shape[1]
    positions = jnp.arange(S)[None, :]
    h, aux = _run_stack(params["blocks"], cfg, h, positions=positions,
                        frontend=frontend, use_kernel=use_kernel,
                        parallelism=parallelism, remat=remat)
    return rmsnorm_apply(params["final_norm"], h), aux


def _lm_logits(params, cfg: ArchConfig, h):
    w = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T
    logits = h @ w
    if cfg.final_softcap is not None:
        logits = softcap(logits, cfg.final_softcap)
    return logits


def _chunked_ce(logits_fn, h, labels, mask, s_chunk: int):
    """Sequence-chunked CE on (B, S, D) hidden states: scan over S-chunks;
    per step the (B, sc, V) logits keep batch sharded over dp and vocab over
    ``model`` (all chips busy), and the remat'd body means the chunk logits
    are never live across steps.  The gold logit uses a masked sum (not a
    gather) so vocab-sharding reduces with one psum."""
    B, S, D = h.shape
    sc = min(s_chunk, S)
    n = S // sc
    rem = S - n * sc

    @jax.checkpoint
    def chunk_loss(hc, lc, mc):
        logits = logits_fn(hc).astype(jnp.float32)          # (B, sc, V)
        lse = jax.nn.logsumexp(logits, axis=-1)             # (B, sc)
        hit = lc[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 2)
        gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        return jnp.sum((lse - gold) * mc), jnp.sum(mc)

    def body(acc, xs):
        loss, cnt = chunk_loss(*xs)
        return (acc[0] + loss, acc[1] + cnt), ()

    xs = (jnp.moveaxis(h[:, : n * sc].reshape(B, n, sc, D), 1, 0),
          jnp.moveaxis(labels[:, : n * sc].reshape(B, n, sc), 1, 0),
          jnp.moveaxis(mask[:, : n * sc].reshape(B, n, sc), 1, 0))
    (loss, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),) * 2, xs)
    if rem:
        l2, c2 = chunk_loss(h[:, n * sc:], labels[:, n * sc:], mask[:, n * sc:])
        loss, cnt = loss + l2, cnt + c2
    return loss / jnp.maximum(cnt, 1.0)


def chunked_ce_loss(params, cfg: ArchConfig, h, labels, mask=None):
    """Cross-entropy over (B, S, D) hidden states without materialising the
    full (B, S, V) logits: scan over sequence chunks (memory-roofline win
    for vocab 256k).  Labels: (B, S) int32; mask optional (B, S) {0,1}."""
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    w = params["lm_head"] if not cfg.tie_embeddings else params["embed"].T

    def logits_fn(hc):
        logits = hc @ w
        if cfg.final_softcap is not None:
            logits = softcap(logits, cfg.final_softcap)
        return logits

    return _chunked_ce(logits_fn, h, labels, mask.astype(jnp.float32),
                       cfg.ce_chunk)


def lm_loss(params: Params, cfg: ArchConfig, tokens, labels, *, frontend=None,
            use_kernel=False, parallelism=None, aux_weight=0.01, remat=True):
    """Next-token loss.  For enc-dec (audio): tokens is the decoder input,
    frontend the encoder input embeddings."""
    if cfg.n_decoder_layers:
        enc_h, aux_e = forward(params, cfg, frontend, use_kernel=use_kernel,
                               parallelism=parallelism, remat=remat)
        dec_cfg = _decoder_cfg(cfg)
        h = params["embed"][tokens]
        positions = jnp.arange(h.shape[1])[None, :]
        h, aux_d = _run_stack(params["dec_blocks"], dec_cfg, h,
                              positions=positions, frontend=enc_h,
                              use_kernel=use_kernel, parallelism=parallelism,
                              remat=remat)
        h = rmsnorm_apply(params["dec_norm"], h)
        aux = aux_e + aux_d
    else:
        h, aux = forward(params, cfg, tokens, frontend=frontend,
                         use_kernel=use_kernel, parallelism=parallelism,
                         remat=remat)
    loss = chunked_ce_loss(params, cfg, h, labels)
    return loss + aux_weight * aux, (loss, aux)


# ---------------------------------------------------------------------------
# Decode (one token, full cache)
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.float32,
                      frontend_len: int | None = None):
    """Per-(period, position) cache stacks for the mixers that need state."""
    n = cfg.n_periods
    caches = []
    for mixer, _ in cfg.pattern:
        if mixer in ("attn", "local"):
            L = min(max_len, cfg.window) if (mixer == "local" and cfg.window) else max_len
            c = kv_cache_init(cfg.attn_cfg(mixer), batch, L, dtype)
        elif mixer == "mamba":
            c = mamba_state_init(cfg.mamba_cfg(), batch, dtype)
        elif mixer == "cross":
            fl = frontend_len or cfg.frontend_len
            c = kv_cache_init(cfg.cross_cfg(), batch, fl, dtype)
        else:
            c = {}
        caches.append(jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), c))
    return caches


def decode_step(params: Params, cfg: ArchConfig, caches, token, position, *,
                frontend=None):
    """token: (B, 1) int32; position: scalar int32.  Returns (logits (B, V),
    new caches).  For enc-dec models, `params["dec_blocks"]`/decoder caches
    should be passed through cfg=_decoder_cfg(cfg) by the serving layer."""
    h = params["embed"][token]

    # scan over periods, threading h as carry, caches as xs -> ys
    def period_fn(h, inp):
        stacks_slice, cache_slice = inp
        new_cache = []
        for pos, (mixer, ffn) in enumerate(cfg.pattern):
            p = stacks_slice[pos]
            c = cache_slice[pos]
            if mixer in ("attn", "local"):
                acfg = cfg.attn_cfg(mixer)
                ring = mixer == "local" and cfg.window is not None
                y, c = attention_decode(p["mixer"], acfg,
                                        rmsnorm_apply(p["ln1"], h), c, position,
                                        ring=ring)
                h = h + y
            elif mixer == "mamba":
                y, c = mamba_decode(p["mixer"], cfg.mamba_cfg(),
                                    rmsnorm_apply(p["ln1"], h), c)
                h = h + y
            elif mixer == "cross":
                q = rmsnorm_apply(p["ln1"], h)
                # cached cross K/V (precomputed from frontend at prefill)
                y = _cross_decode(p["mixer"], cfg.cross_cfg(), q, c)
                h = h + jnp.tanh(p["gate"]) * y
            if ffn == "dense":
                h = h + mlp_apply(p["ffn"], cfg.mlp_cfg(), rmsnorm_apply(p["ln2"], h))
            elif ffn == "moe":
                y, _aux = moe_apply_grouped(
                    p["ffn"], cfg.moe_cfg(), rmsnorm_apply(p["ln2"], h),
                    capacity_factor=max(4.0, cfg.moe_capacity_factor))
                h = h + y
            new_cache.append(c)
        return h, tuple(new_cache)

    h, new_caches = jax.lax.scan(period_fn, h, (tuple(params["blocks"]), tuple(caches)))
    h = rmsnorm_apply(params["final_norm"], h)
    logits = _lm_logits(params, cfg, h)[:, 0]
    return logits, list(new_caches)


def _cross_decode(p, acfg, q_in, cache):
    """Cross-attn during decode: K/V from the (static) frontend cache."""
    B = q_in.shape[0]
    hd = acfg.hd
    q = (q_in @ p["wq"]).reshape(B, 1, acfg.n_heads, hd)
    out = sdpa_reference(q, cache["k"], cache["v"], causal=False, window=None,
                         logit_cap=None)
    return out.reshape(B, 1, acfg.n_heads * hd) @ p["wo"]


# ---------------------------------------------------------------------------
# Prefill (full sequence -> decode caches + last-token logits)
# ---------------------------------------------------------------------------

def _state_to_cache(cfg: ArchConfig, mixer: str, st, S: int, max_len: int):
    """Convert a per-block prefill state into the decode-cache layout of
    init_decode_state (so decode_step continues seamlessly at position S)."""
    if mixer in ("attn", "local"):
        W = min(max_len, cfg.window) if (mixer == "local" and cfg.window) else max_len
        k, v = st["k"], st["v"]

        def place(x):
            B, _, Hkv, hd = x.shape
            if S >= W:
                # ring layout: slot j holds position p with p % W == j
                last = x[:, S - W:]
                idx = jnp.mod(jnp.arange(W) - (S % W), W)
                return last[:, idx]
            pad = jnp.zeros((B, W - S, Hkv, hd), x.dtype)
            return jnp.concatenate([x, pad], axis=1)

        return {"k": place(k), "v": place(v)}
    if mixer in ("mamba", "cross"):
        return st
    return {}


def prefill(params: Params, cfg: ArchConfig, tokens, *, max_len=None,
            frontend=None, use_kernel=False, parallelism=None, remat=True):
    """Run the full forward over ``tokens`` collecting decode caches.

    Returns (last_logits (B, V), caches) with caches in the layout of
    init_decode_state, primed so decode continues at position S.  For
    enc-dec archs (audio) the encoder runs on ``frontend`` and the decoder
    prefills on ``tokens`` with cross caches from the encoder output.
    """
    if cfg.n_decoder_layers:
        enc_h, _ = forward(params, cfg, frontend, use_kernel=use_kernel,
                           parallelism=parallelism, remat=remat)
        dec_cfg = _decoder_cfg(cfg)
        dec_params = {"embed": params["embed"], "blocks": params["dec_blocks"],
                      "final_norm": params["dec_norm"]}
        if "lm_head" in params:
            dec_params["lm_head"] = params["lm_head"]
        return prefill(dec_params, dec_cfg, tokens, max_len=max_len,
                       frontend=enc_h, use_kernel=use_kernel,
                       parallelism=parallelism, remat=remat)

    h = params["embed"][tokens] if tokens.ndim == 2 else tokens
    B, S = h.shape[0], h.shape[1]
    L = max_len or S
    positions = jnp.arange(S)[None, :]

    def period_fn(h, stacks_slice):
        caches = []
        for pos, (mixer, ffn) in enumerate(cfg.pattern):
            h, _aux, st = _apply_block(stacks_slice[pos], cfg, mixer, ffn, h,
                                       positions=positions, frontend=frontend,
                                       use_kernel=use_kernel,
                                       parallelism=parallelism,
                                       return_state=True)
            caches.append(_state_to_cache(cfg, mixer, st, S, L))
        return h, tuple(caches)

    fn = jax.checkpoint(period_fn) if remat else period_fn

    def body(h, stacks_slice):
        if parallelism is not None:
            h = parallelism.resid(h)
        return fn(h, stacks_slice)

    h, caches = jax.lax.scan(body, h, tuple(params["blocks"]))
    h = rmsnorm_apply(params["final_norm"], h[:, -1:])
    logits = _lm_logits(params, cfg, h)[:, 0]
    return logits, list(caches)


def serve_decode_step(params: Params, cfg: ArchConfig, caches, token,
                      position):
    """decode_step that also handles enc-dec archs (uses the decoder stack;
    cross caches must have been primed by ``prefill``)."""
    if cfg.n_decoder_layers:
        dec_params = {"embed": params["embed"], "blocks": params["dec_blocks"],
                      "final_norm": params["dec_norm"]}
        if "lm_head" in params:
            dec_params["lm_head"] = params["lm_head"]
        return decode_step(dec_params, _decoder_cfg(cfg), caches, token,
                           position)
    return decode_step(params, cfg, caches, token, position)


def init_serve_state(cfg: ArchConfig, batch: int, max_len: int,
                     dtype=jnp.float32):
    """init_decode_state that routes enc-dec archs to their decoder stack."""
    if cfg.n_decoder_layers:
        return init_decode_state(_decoder_cfg(cfg), batch, max_len, dtype,
                                 frontend_len=cfg.frontend_len)
    return init_decode_state(cfg, batch, max_len, dtype,
                             frontend_len=cfg.frontend_len or None)


def prefill_cross_cache(params, cfg: ArchConfig, frontend):
    """Precompute cross-attention K/V from frontend embeddings for decode."""
    caches = []
    hd = cfg.cross_cfg().hd
    B, F, _ = frontend.shape
    for pos, (mixer, _f) in enumerate(cfg.pattern):
        if mixer != "cross":
            caches.append(None)
            continue
        p = params["blocks"][pos]  # stacked (n_periods, ...)

        def kv(px):
            k = (frontend @ px["mixer"]["wk"]).reshape(B, F, cfg.n_kv_heads, hd)
            v = (frontend @ px["mixer"]["wv"]).reshape(B, F, cfg.n_kv_heads, hd)
            return {"k": k, "v": v}

        caches.append(jax.lax.map(kv, p))
    return caches


# ---------------------------------------------------------------------------
# FedOptima split API
# ---------------------------------------------------------------------------
# The DNN is split at a *period* boundary l_split (so alternation patterns
# like gemma2 local/global or jamba 1:7 stay intact).  The device half is
# ``embed + blocks[:l_split]`` plus an auxiliary network (one extra block of
# the same type as the last device block + a factorized classifier head,
# §3.2.2 default).  The server half is ``blocks[l_split:] + final_norm +
# lm_head`` and trains *centrally* on activations (§3.3.2).

def _slice_stacks(blocks, lo, hi):
    return [jax.tree.map(lambda x: x[lo:hi], s) for s in blocks]


def make_aux_params(rng, cfg: ArchConfig, dtype=jnp.float32, *,
                    regression: bool = False) -> Params:
    """Auxiliary network: one block (same type as last device-side block,
    i.e. the last pattern position) + factorized dense classifier.  With
    ``regression=True`` (continuous-input device blocks, e.g. the whisper
    encoder) the head projects back to d_model for next-frame MSE."""
    mixer, ffn = cfg.pattern[-1]
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {
        "block": _block_init(k1, cfg, mixer, ffn, dtype),
        "norm": rmsnorm_init(cfg.d_model, dtype),
        "head_in": dense_init(k2, cfg.d_model, cfg.aux_dim, dtype),
    }
    if regression:
        p["head_reg"] = dense_init(k3, cfg.aux_dim, cfg.d_model, dtype)
    else:
        p["head_out"] = dense_init(k3, cfg.aux_dim, cfg.vocab, dtype)
    return p


def split_params(params: Params, cfg: ArchConfig, l_split: int):
    """Split at period boundary l_split in [1, n_periods - 1].

    Enc-dec (audio): the device block is the *encoder prefix* (input = the
    frontend frame embeddings, so no token embedding on device); the whole
    decoder stays server-side (it cross-attends to the *final* encoder
    states, which only the server produces)."""
    dev = {"blocks": _slice_stacks(params["blocks"], 0, l_split)}
    srv = {"blocks": _slice_stacks(params["blocks"], l_split, cfg.n_periods),
           "final_norm": params["final_norm"]}
    if not cfg.n_decoder_layers:
        dev["embed"] = params["embed"]
    if not cfg.tie_embeddings:
        srv["lm_head"] = params["lm_head"]
    else:
        srv["embed_out"] = params["embed"]  # tied head lives server-side
    if cfg.n_decoder_layers:
        srv["dec_blocks"] = params["dec_blocks"]
        srv["dec_norm"] = params["dec_norm"]
    return dev, srv


def merge_params(dev: Params, srv: Params, cfg: ArchConfig) -> Params:
    blocks = [jax.tree.map(lambda a, b: jnp.concatenate([a, b]), d, s)
              for d, s in zip(dev["blocks"], srv["blocks"])]
    out = {"embed": dev.get("embed", srv.get("embed_out")), "blocks": blocks,
           "final_norm": srv["final_norm"]}
    if "lm_head" in srv:
        out["lm_head"] = srv["lm_head"]
    if "dec_blocks" in srv:
        out["dec_blocks"] = srv["dec_blocks"]
        out["dec_norm"] = srv["dec_norm"]
    return out


def device_forward(dev_params: Params, cfg: ArchConfig, tokens, *,
                   frontend=None, use_kernel=False, parallelism=None,
                   remat=True):
    """Run the device-side block; returns activations (B, S, D).

    For enc-dec (whisper) the device block is the *encoder* prefix, so the
    input is the frontend frame embeddings (tokens is (B, F, D) floats).
    For VLM the device block may contain cross-attn layers: `frontend`
    carries the local image-patch embeddings (devices own their data)."""
    h = dev_params["embed"][tokens] if tokens.ndim == 2 else tokens
    positions = jnp.arange(h.shape[1])[None, :]
    h, aux = _run_stack(dev_params["blocks"], cfg, h, positions=positions,
                        frontend=frontend, use_kernel=use_kernel,
                        parallelism=parallelism, remat=remat)
    return h, aux


def aux_head_loss(aux_params: Params, cfg: ArchConfig, acts, labels, *,
                  frontend=None):
    """Local loss f_d through the auxiliary network (Alg. 1 lines 7-8).

    Default (§3.2.2): one block of the same type as the last device-side
    layer + a factorized dense classifier; CE against the local labels.
    For continuous-input device blocks (whisper encoder: no token labels at
    frame granularity) the head regresses the next frame embedding and the
    loss is MSE — labels is then the (B, S, D) input embedding stream."""
    mixer, ffn = cfg.pattern[-1]
    positions = jnp.arange(acts.shape[1])[None, :]
    h, _ = _apply_block(aux_params["block"], cfg, mixer, ffn, acts,
                        positions=positions, frontend=frontend)
    h = rmsnorm_apply(aux_params["norm"], h)
    if labels.ndim == 3:  # regression: predict next input frame
        pred = (h @ aux_params["head_in"]) @ aux_params["head_reg"]
        target = jnp.roll(labels, -1, axis=1)
        err = (pred[:, :-1] - target[:, :-1]).astype(jnp.float32)
        return jnp.mean(jnp.square(err))
    return _chunked_ce(
        lambda hc: (hc @ aux_params["head_in"]) @ aux_params["head_out"],
        h, labels, jnp.ones(labels.shape, jnp.float32), cfg.ce_chunk)


def device_train_loss(dev_params: Params, aux_params: Params, cfg: ArchConfig,
                      tokens, labels, *, frontend=None, use_kernel=False,
                      parallelism=None, remat=True):
    """Device-side objective F_d (Eq. 4): aux-head CE on local data.
    Returns (loss, activations) — activations are what gets shipped to the
    server (detached there; the server never sends gradients back)."""
    acts, moe_aux = device_forward(dev_params, cfg, tokens, frontend=frontend,
                                   use_kernel=use_kernel,
                                   parallelism=parallelism, remat=remat)
    loss = aux_head_loss(aux_params, cfg, acts, labels, frontend=frontend) \
        + 0.01 * moe_aux
    return loss, acts


def _row_mask(row_mask, labels):
    """(B,) row mask -> the (B, S) token mask of ``chunked_ce_loss``."""
    if row_mask is None:
        return None
    return jnp.broadcast_to(row_mask[:, None], labels.shape)


def server_forward_loss(srv_params: Params, cfg: ArchConfig, acts, labels, *,
                        frontend=None, use_kernel=False, parallelism=None,
                        remat=True, aux_weight=0.01, row_mask=None):
    """Server-side objective F_s (Eq. 5): centralized training on activations
    ξ ~ A.  `acts` arrive detached (lax.stop_gradient at call site mirrors
    the no-gradient-to-device property).  `frontend` carries patch/frame
    embeddings for server-side cross-attention layers (VLM).  `row_mask`
    (B,) drops rows that hold no emission from the loss and its gradient."""
    acts = jax.lax.stop_gradient(acts)
    positions = jnp.arange(acts.shape[1])[None, :]
    h, moe_aux = _run_stack(srv_params["blocks"], cfg, acts,
                            positions=positions, frontend=frontend,
                            use_kernel=use_kernel, parallelism=parallelism,
                            remat=remat)
    h = rmsnorm_apply(srv_params["final_norm"], h)
    if "lm_head" in srv_params:
        head = {"lm_head": srv_params["lm_head"]}
    else:
        head = {"embed": srv_params["embed_out"]}
    loss = chunked_ce_loss(head, cfg, h, labels, _row_mask(row_mask, labels))
    return loss + aux_weight * moe_aux


def server_encdec_loss(srv_params: Params, cfg: ArchConfig, acts, tokens,
                       labels, *, use_kernel=False, parallelism=None,
                       remat=True, aux_weight=0.01, row_mask=None):
    """Server-side objective for enc-dec archs (whisper): finish the encoder
    on the device activations, then run the full decoder with cross-attn to
    the final encoder states, next-token CE on the local transcript."""
    acts = jax.lax.stop_gradient(acts)
    positions = jnp.arange(acts.shape[1])[None, :]
    enc_h, aux_e = _run_stack(srv_params["blocks"], cfg, acts,
                              positions=positions, use_kernel=use_kernel,
                              parallelism=parallelism, remat=remat)
    enc_h = rmsnorm_apply(srv_params["final_norm"], enc_h)
    dec_cfg = _decoder_cfg(cfg)
    h = srv_params["embed_out"][tokens] if "embed_out" in srv_params \
        else srv_params["lm_head"].T[tokens]
    dpos = jnp.arange(h.shape[1])[None, :]
    h, aux_d = _run_stack(srv_params["dec_blocks"], dec_cfg, h,
                          positions=dpos, frontend=enc_h,
                          use_kernel=use_kernel, parallelism=parallelism,
                          remat=remat)
    h = rmsnorm_apply(srv_params["dec_norm"], h)
    head = {"embed": srv_params["embed_out"]} if "embed_out" in srv_params \
        else {"lm_head": srv_params["lm_head"]}
    loss = chunked_ce_loss(head, cfg, h, labels, _row_mask(row_mask, labels))
    return loss + aux_weight * (aux_e + aux_d)
