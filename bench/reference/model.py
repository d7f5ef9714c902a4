"""Layers of the two configurations, and their initial weights.

``Arch`` holds the sizes, read from a configuration file.  The weights
follow the program's initialiser key for key (the same ``jax.random``
draws in the same order), so that the reference starts where the run
starts; the layer equations follow the published models:

* Llama-style block (SmolLM): RMSNorm, rotary embeddings on halves of
  each head, grouped-query causal attention, SwiGLU, residuals; tied
  input and output embeddings.
* Mamba2 block: in_proj to (z, x, B, C, dt), depthwise causal conv with
  SiLU, softplus step, the SSD recurrence in its quadratic (masked
  decay) form, the D skip, RMSNorm of y * SiLU(z), out_proj; no MLP.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Arch:
    family: str
    n_layers: int
    d_model: int
    vocab: int
    eps: float
    aux_dim: int
    dev_layers: int
    # llama
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    rope_theta: float = 10000.0
    # mamba2
    expand: int = 0
    ssm_head_dim: int = 0
    d_state: int = 0
    ssm_groups: int = 0
    d_conv: int = 0

    @staticmethod
    def from_config(c: dict) -> "Arch":
        if c["family"] == "llama":
            return Arch("llama", c["num_hidden_layers"], c["hidden_size"],
                        c["vocab_size"], c["rms_norm_eps"], c["aux_dim"],
                        c["device_layers"], n_heads=c["num_attention_heads"],
                        n_kv_heads=c["num_key_value_heads"],
                        head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                        rope_theta=c["rope_theta"])
        if c["family"] == "mamba2":
            return Arch("mamba2", c["n_layer"], c["d_model"], c["vocab_size"],
                        c["norm_epsilon"], c["aux_dim"], c["device_layers"],
                        expand=c["expand"], ssm_head_dim=c["headdim"],
                        d_state=c["d_state"], ssm_groups=c["ngroups"],
                        d_conv=c["d_conv"])
        raise ValueError(f"no reference for family {c['family']!r}")

    # mamba2 sizes
    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.d_state


# ---------------------------------------------------------------------------
# Initial weights (the program's recipe)
# ---------------------------------------------------------------------------

def _dense(key, n_in, n_out, std=None):
    std = std if std is not None else 1.0 / math.sqrt(n_in)
    return jax.random.normal(key, (n_in, n_out), jnp.float32) * \
        jnp.asarray(std, jnp.float32)


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


def _block_init(key, a: Arch) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"ln1": _ones(a.d_model)}
    if a.family == "llama":
        ks = jax.random.split(k1, 4)
        H, Hkv, hd, D = a.n_heads, a.n_kv_heads, a.head_dim, a.d_model
        p["mixer"] = {"wq": _dense(ks[0], D, H * hd),
                      "wk": _dense(ks[1], D, Hkv * hd),
                      "wv": _dense(ks[2], D, Hkv * hd),
                      "wo": _dense(ks[3], H * hd, D, 1.0 / (H * hd) ** 0.5)}
        f1, f2, f3 = jax.random.split(k2, 3)
        p["ln2"] = _ones(D)
        p["ffn"] = {"w_gate": _dense(f1, D, a.d_ff),
                    "w_up": _dense(f2, D, a.d_ff),
                    "w_down": _dense(f3, a.d_ff, D)}
    else:
        ks = jax.random.split(k1, 5)
        Hs, D = a.ssm_heads, a.d_model
        d_in = 2 * a.d_inner + 2 * a.ssm_groups * a.d_state + Hs
        u = jax.random.uniform(ks[3], (Hs,), jnp.float32)
        dt_min, dt_max = 0.001, 0.1
        dt = jnp.exp(u * (jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min))
        p["mixer"] = {
            "in_proj": _dense(ks[0], D, d_in),
            "conv_w": jax.random.normal(ks[1], (a.d_conv, a.conv_dim),
                                        jnp.float32) * 0.2,
            "conv_b": jnp.zeros((a.conv_dim,), jnp.float32),
            "A_log": jnp.log(jnp.arange(1, Hs + 1, dtype=jnp.float32)),
            "D": jnp.ones((Hs,), jnp.float32),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
            "norm": _ones(a.d_inner),
            "out_proj": _dense(ks[4], a.d_inner, D),
        }
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_state(rng, a: Arch, G: int):
    """(dev, aux, srv) from ``jax.random.PRNGKey(seed)``, as the program
    lays them out: dev/aux stacked over the G groups, layer weights
    stacked over layers."""
    ke, kb, _, _ = jax.random.split(rng, 4)
    embed = jax.random.normal(ke, (a.vocab, a.d_model), jnp.float32) * \
        jnp.asarray(0.02, jnp.float32)
    keys = jax.random.split(jax.random.fold_in(kb, 0), a.n_layers)
    layers = _stack([_block_init(k, a) for k in keys])
    L = a.dev_layers
    dev = {"blocks": [jax.tree.map(lambda x: x[:L], layers)], "embed": embed}
    srv = {"blocks": [jax.tree.map(lambda x: x[L:], layers)],
           "final_norm": _ones(a.d_model), "embed_out": embed}
    k1, k2, k3 = jax.random.split(jax.random.fold_in(rng, 1), 3)
    aux = {"block": _block_init(k1, a), "norm": _ones(a.d_model),
           "head_in": _dense(k2, a.d_model, a.aux_dim),
           "head_out": _dense(k3, a.aux_dim, a.vocab)}
    per_group = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), t)
    return per_group(dev), per_group(aux), srv


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def rmsnorm(p, x, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta):
    """x: (B, S, H, hd); rotates (first half, second half) pairs."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2].astype(jnp.float32), \
        x[..., hd // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def attention(p, x, a: Arch):
    B, S, _ = x.shape
    H, Hkv, hd = a.n_heads, a.n_kv_heads, a.head_dim
    q = rope((x @ p["wq"]).reshape(B, S, H, hd), a.rope_theta)
    k = rope((x @ p["wk"]).reshape(B, S, Hkv, hd), a.rope_theta)
    v = (x @ p["wv"]).reshape(B, S, Hkv, hd)
    # query head h reads key/value head h // (H / Hkv)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return o.astype(x.dtype).reshape(B, S, H * hd) @ p["wo"]


def swiglu(p, x):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def segsum(x):
    """x: (..., T) -> (..., T, T) with [t, s] = sum of x over (s, t] for
    s <= t, -inf above the diagonal; summed within the masked matrix so
    no difference of long cumulative sums is taken."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., :, None], x.shape + (T,))
    below = jnp.tril(jnp.ones((T, T), bool), -1)
    cs = jnp.cumsum(jnp.where(below, xx, 0.0), axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), cs, -jnp.inf)


def mamba2(p, x, a: Arch):
    B, T, _ = x.shape
    Di, Hs, P, N, G = (a.d_inner, a.ssm_heads, a.ssm_head_dim, a.d_state,
                       a.ssm_groups)
    zxbcdt = x @ p["in_proj"]
    z = zxbcdt[..., :Di]
    xbc = zxbcdt[..., Di:Di + a.conv_dim]
    dt = zxbcdt[..., Di + a.conv_dim:]
    K = a.d_conv
    xp = jnp.concatenate([jnp.zeros((B, K - 1, a.conv_dim), x.dtype), xbc],
                         axis=1)
    conv = sum(xp[:, k:k + T] * p["conv_w"][k] for k in range(K))
    xbc = silu(conv + p["conv_b"])
    xs = xbc[..., :Di].reshape(B, T, Hs, P).astype(jnp.float32)
    Bm = xbc[..., Di:Di + G * N].reshape(B, T, G, N).astype(jnp.float32)
    Cm = xbc[..., Di + G * N:].reshape(B, T, G, N).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,T,Hs)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(segsum(jnp.moveaxis(dt * A, -1, 1)))          # (B,Hs,T,T)
    cb = jnp.einsum("btgn,bsgn->bgts", Cm, Bm)
    cb = jnp.repeat(cb, Hs // G, axis=1)                          # (B,Hs,T,T)
    y = jnp.einsum("bhts,bsh,bshp->bthp", cb * decay, dt, xs)
    y = y + p["D"][None, None, :, None] * xs
    y = y.reshape(B, T, Di).astype(x.dtype)
    y = rmsnorm(p["norm"], y * silu(z), a.eps)
    return y @ p["out_proj"]


def block(p, h, a: Arch):
    """One residual block (the configuration's only kind)."""
    if a.family == "llama":
        h = h + attention(p["mixer"], rmsnorm(p["ln1"], h, a.eps), a)
        return h + swiglu(p["ffn"], rmsnorm(p["ln2"], h, a.eps))
    return h + mamba2(p["mixer"], rmsnorm(p["ln1"], h, a.eps), a)


def ce_sum(logits, labels):
    """Summed next-token cross-entropy of (rows, S, V) logits."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)
