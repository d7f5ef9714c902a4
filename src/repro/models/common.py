"""Common model building blocks: norms, rotary embeddings, initializers.

Pure JAX (no flax). Parameters are plain pytrees (nested dicts of arrays).
Every block follows the convention::

    params = block_init(rng, cfg)          # build params
    out    = block_apply(params, x, ...)   # pure function

Weights that repeat across layers are *stacked* on a leading axis so the
forward pass can ``jax.lax.scan`` over them — this keeps compiled HLO size
independent of depth.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree of jnp arrays


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(rng, in_dim: int, out_dim: int, dtype=jnp.float32, scale: float | None = None):
    """Variance-scaling (fan-in) init, the standard for transformer dense layers."""
    std = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return jax.random.normal(rng, (in_dim, out_dim), dtype) * jnp.asarray(std, dtype)


def embed_init(rng, vocab: int, dim: int, dtype=jnp.float32):
    return jax.random.normal(rng, (vocab, dim), dtype) * jnp.asarray(0.02, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm_apply(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(orig_dtype)


def layernorm_init(dim: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm_apply(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE)
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    """Inverse frequencies for RoPE; shape (head_dim // 2,), float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0) -> jnp.ndarray:
    """Rotate pairs of channels. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)                     # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * inv_freq  # (..., seq, hd/2)
    angles = angles[..., None, :]                                    # (..., seq, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activation functions
# ---------------------------------------------------------------------------

def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x)


def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def softcap(logits: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(logits / cap)


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------

def tree_stack(trees):
    """Stack a list of identically-structured pytrees on a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_scale(tree, s):
    return jax.tree.map(lambda x: x * s, tree)


def tree_lerp(a, b, alpha):
    """alpha * b + (1 - alpha) * a, elementwise over pytrees (FedAsync update)."""
    return jax.tree.map(lambda x, y: (1.0 - alpha) * x + alpha * y, a, b)


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_param_count(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def split_rngs(rng, n: int):
    return list(jax.random.split(rng, n))
