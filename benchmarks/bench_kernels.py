"""Pallas kernel micro-benchmarks: fused flash-attention / SSD vs the
pure-JAX references, forward and forward+backward, at a few training-shaped
sizes.  Emits the usual CSV rows AND writes ``BENCH_kernels.json`` at the
repo root so the kernel-path perf trajectory is tracked across PRs.

On CPU the kernels run in interpret mode (the Pallas grid executed by a
Python interpreter), so absolute numbers measure program *logic*, not TPU
performance — the JSON records backend + mode so trajectories only compare
like with like.  On a TPU backend the same harness times the Mosaic
kernels.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.models.attention import sdpa_blockwise

from . import common
from .common import Row, timed

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_kernels.json")

# (B, S, H, Hkv, hd) — GQA training shapes, small enough for interpret mode
ATTN_SHAPES = [(1, 256, 8, 2, 64), (2, 512, 8, 2, 64)]
# (B, T, H, P, G, N, chunk)
SSD_SHAPES = [(1, 256, 8, 64, 1, 32, 64), (2, 512, 8, 64, 1, 32, 128)]

REPEAT = 3


def _block(x):
    jax.block_until_ready(x)
    return x


def _time_pair(fwd_fn, args):
    """(fwd_us, fwd+bwd_us) for a scalar-loss wrapper of fwd_fn, both
    jit-compiled and warmed before timing."""
    f = jax.jit(lambda *a: fwd_fn(*a))
    g = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fwd_fn(*a)) ** 2,
                                   argnums=tuple(range(len(args)))))
    _block(f(*args))                       # compile
    _block(g(*args))
    _, fwd_us = timed(lambda: _block(f(*args)), repeat=REPEAT)
    _, bwd_us = timed(lambda: _block(g(*args)), repeat=REPEAT)
    return fwd_us, bwd_us


def _bench_attention(record):
    rows = []
    for B, S, H, Hkv, hd in ATTN_SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, Hkv, hd))
        v = jax.random.normal(ks[2], (B, S, Hkv, hd))
        name = f"attn_b{B}_s{S}_h{H}kv{Hkv}_d{hd}"
        kf, kb = _time_pair(
            lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
            (q, k, v))
        rf, rb = _time_pair(
            lambda q, k, v: sdpa_blockwise(q, k, v, causal=True, window=None,
                                           logit_cap=None, chunk_q=128),
            (q, k, v))
        record[name] = {"kernel_fwd_us": kf, "kernel_fwd_bwd_us": kb,
                        "ref_fwd_us": rf, "ref_fwd_bwd_us": rb}
        rows.append(Row(f"kernels/{name}/fwd", kf, f"ref_us={rf:.1f}"))
        rows.append(Row(f"kernels/{name}/fwd_bwd", kb, f"ref_us={rb:.1f}"))
    return rows


def _bench_ssd(record):
    rows = []
    for B, T, H, P, G, N, chunk in SSD_SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        x = jax.random.normal(ks[0], (B, T, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0)
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
        Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.5
        Cm = jax.random.normal(jax.random.fold_in(ks[3], 1),
                               (B, T, G, N)) * 0.5
        name = f"ssd_b{B}_t{T}_h{H}p{P}_n{N}_q{chunk}"
        kf, kb = _time_pair(
            lambda *a: ops.ssd(*a, chunk=chunk), (x, dt, A, Bm, Cm))
        rf, rb = _time_pair(
            lambda *a: ref.ssd_reference(*a)[0], (x, dt, A, Bm, Cm))
        record[name] = {"kernel_fwd_us": kf, "kernel_fwd_bwd_us": kb,
                        "ref_fwd_us": rf, "ref_fwd_bwd_us": rb}
        rows.append(Row(f"kernels/{name}/fwd", kf, f"ref_us={rf:.1f}"))
        rows.append(Row(f"kernels/{name}/fwd_bwd", kb, f"ref_us={rb:.1f}"))
    return rows


def main() -> list[Row]:
    record: dict = {"backend": jax.default_backend(),
                    "interpret": ops._interpret(), "repeat": REPEAT}
    rows = _bench_attention(record) + _bench_ssd(record)
    common.write_record(OUT_PATH, record)
    rows.append(Row("kernels/json", 0.0,
                    f"wrote={os.path.basename(OUT_PATH)}"))
    return rows


if __name__ == "__main__":
    for r in main():
        print(r.csv())
