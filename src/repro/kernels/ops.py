"""Public jit'd wrappers around the Pallas kernels.

On a TPU the kernels compile to Mosaic (``tests/test_tpu_compile.py``
compiles them for a described v5e; ``chip_smoke.py`` runs them on one).
On the CPU backend they run with interpret=True: the kernel body executes
per grid step, which checks the program's logic but not Mosaic's tiling
rules.  Interpret mode is decided per-call (``interpret=``), scoped
(``kernel_mode``), or globally (``set_kernel_mode``); it is resolved
OUTSIDE the jit boundary and passed as a static argument, so overrides
actually retrace instead of being swallowed by the jit cache.

Both ops are differentiable: ``jax.custom_vjp`` routes their backward
passes through the fused Pallas backward kernels (FlashAttention-style
recompute from (q, k, v, o, lse); reverse chunk scan for SSD), so
``use_kernel=True`` survives ``jax.value_and_grad`` in the hybrid train
step with no Python-level branching.  The (o, lse) / chunk-state residuals
are ``checkpoint_name``d "kernel_out" so the selective-remat policy
(transformer.py) can save them instead of recomputing the forward kernel —
never anything (S × S)-shaped.
"""
from __future__ import annotations

from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .flash_attention import (flash_attention_bwd_bhsd,
                              flash_attention_fwd_bhsd)
from .ssd import ssd_bwd_chunked_pallas, ssd_fwd_chunked_pallas

_FORCE_INTERPRET: bool | None = None


def set_kernel_mode(interpret: bool | None):
    """None = auto (interpret on CPU); True/False forces."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = interpret


@contextmanager
def kernel_mode(interpret: bool | None):
    """Scoped ``set_kernel_mode``: restores the previous mode on exit, so
    tests/benchmarks can't leak the global override across modules."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = interpret
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


def _interpret() -> bool:
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa(q, k, v, causal, window, logit_cap, block_q, block_k, interpret):
    out, _ = flash_attention_fwd_bhsd(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out


def _fa_fwd(q, k, v, causal, window, logit_cap, block_q, block_k, interpret):
    out, lse = flash_attention_fwd_bhsd(
        q, k, v, causal=causal, window=window, logit_cap=logit_cap,
        block_q=block_q, block_k=block_k, interpret=interpret)
    out = checkpoint_name(out, "kernel_out")
    lse = checkpoint_name(lse, "kernel_out")
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, logit_cap, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd_bhsd(
        q, k, v, out, lse, do, causal=causal, window=window,
        logit_cap=logit_cap, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_fa.defvjp(_fa_fwd, _fa_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "logit_cap",
                                   "block_q", "block_k", "interpret"))
def _flash_attention_jit(q, k, v, *, causal, window, logit_cap, block_q,
                         block_k, interpret):
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _fa(qt, kt, vt, causal, window, logit_cap, block_q, block_k,
              interpret)
    return jnp.swapaxes(out, 1, 2)


def flash_attention(q, k, v, *, causal=True, window=None, logit_cap=None,
                    block_q=128, block_k=128, interpret=None):
    """q: (B, S, H, hd); k, v: (B, Skv, Hkv, hd) -> (B, S, H, hd).
    Differentiable (custom_vjp through the Pallas backward kernels)."""
    if interpret is None:
        interpret = _interpret()
    return _flash_attention_jit(q, k, v, causal=causal, window=window,
                                logit_cap=logit_cap, block_q=block_q,
                                block_k=block_k, interpret=bool(interpret))


# ---------------------------------------------------------------------------
# SSD (Mamba2 sequence mixer)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, Bm, Cm, chunk, interpret):
    y, _ = ssd_fwd_chunked_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                                  interpret=interpret)
    return y


def _ssd_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    y, states = ssd_fwd_chunked_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                                       interpret=interpret)
    states = checkpoint_name(states, "kernel_out")
    return y, (x, dt, A, Bm, Cm, states)


def _ssd_bwd(chunk, interpret, res, dy):
    x, dt, A, Bm, Cm, states = res
    dx, ddt, dA, dBm, dCm = ssd_bwd_chunked_pallas(
        x, dt, A, Bm, Cm, states, dy.astype(jnp.float32), chunk=chunk,
        interpret=interpret)
    return (dx.astype(x.dtype), ddt.astype(dt.dtype), dA.astype(A.dtype),
            dBm.astype(Bm.dtype), dCm.astype(Cm.dtype))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, dt, A, Bm, Cm, *, chunk, interpret):
    """(B, T, ·) model layout -> the kernels' head-major (B, ·, T) layout,
    T padded to a chunk multiple, and back."""
    T = x.shape[1]
    pad = (-T) % chunk

    def head_major(t):
        t = jnp.swapaxes(t, 1, 2)
        return jnp.pad(t, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 3))

    y = _ssd(head_major(x), head_major(dt), A, head_major(Bm),
             head_major(Cm), chunk, interpret)
    return jnp.swapaxes(y[:, :, :T], 1, 2)


def ssd(x, dt, A, Bm, Cm, *, chunk=128, interpret=None):
    """Chunked SSD sequence mixer.  x: (B, T, H, P); dt: (B, T, H);
    A: (H,); Bm, Cm: (B, T, G, N) -> y (B, T, H, P).  Differentiable
    (custom_vjp reverse chunk scan).  ``chunk`` is clamped to T, then T is
    padded to a chunk multiple (zero dt ⇒ identity decay, zero input ⇒ no
    state change)."""
    T = x.shape[1]
    chunk = min(chunk, T)
    assert chunk >= 1, f"empty sequence: T={T}"
    # _ssd_jit pads T up to a chunk multiple; the kernel wrappers assert
    # the padded T % chunk == 0 invariant they actually consume.
    if interpret is None:
        interpret = _interpret()
    return _ssd_jit(x, dt, A, Bm, Cm, chunk=chunk, interpret=bool(interpret))
