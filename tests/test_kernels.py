"""Pallas kernel correctness: sweep shapes/dtypes, assert_allclose against
the pure-jnp oracles (ref.py).  interpret=True executes the exact TPU
program logic on CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_SHAPES = [
    # (B, S, Skv, H, Hkv, hd)
    (1, 128, 128, 4, 4, 64),      # MHA
    (2, 256, 256, 8, 2, 32),      # GQA 4:1
    (1, 128, 128, 9, 3, 64),      # odd head counts (smollm)
    (1, 384, 384, 4, 1, 64),      # MQA
]


def _qkv(shape, dtype, seed=0):
    B, S, Skv, H, Hkv, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(shape, dtype):
    q, k, v = _qkv(shape, dtype)
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_reference(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    q, k, v = _qkv((1, 256, 256, 4, 4, 64), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_logit_softcap():
    q, k, v = _qkv((1, 128, 128, 4, 2, 64), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, logit_cap=30.0)
    want = ref.flash_attention_reference(q, k, v, causal=True, logit_cap=30.0)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal():
    q, k, v = _qkv((1, 128, 128, 4, 4, 32), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False)
    want = ref.flash_attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@given(st.integers(1, 2), st.sampled_from([64, 128, 192]),
       st.sampled_from([(4, 4), (4, 2), (6, 3)]), st.sampled_from([32, 64]))
@settings(max_examples=8, deadline=None)
def test_flash_attention_property(b, s, heads, hd):
    H, Hkv = heads
    q, k, v = _qkv((b, s, s, H, Hkv, hd), jnp.float32, seed=s)
    got = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_jnp_chunked_path_matches_reference():
    """The jnp path (sdpa_blockwise) is numerically the oracle too."""
    from repro.models.attention import sdpa_blockwise
    q, k, v = _qkv((2, 200, 200, 8, 2, 64), jnp.float32)
    got = sdpa_blockwise(q, k, v, causal=True, window=None, logit_cap=None,
                         chunk_q=64)
    want = ref.flash_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD (Mamba2)
# ---------------------------------------------------------------------------

SSD_SHAPES = [
    # (B, T, H, P, G, N, chunk)
    (1, 128, 4, 32, 1, 16, 32),
    (2, 64, 8, 16, 2, 8, 16),
    (1, 96, 4, 64, 1, 32, 32),    # T % chunk == 0
]


def _ssd_inputs(shape, seed=0):
    B, T, H, P, G, N, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(ks[3], 1), (B, T, G, N)) * 0.5
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_kernel_vs_sequential_reference(shape):
    x, dt, A, Bm, Cm = _ssd_inputs(shape)
    got = ops.ssd(x, dt, A, Bm, Cm, chunk=shape[-1])
    want, _ = ref.ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-4)


def test_ssd_chunked_jnp_matches_reference():
    from repro.models.mamba import ssd_chunked
    x, dt, A, Bm, Cm = _ssd_inputs((2, 64, 4, 16, 2, 8, 16))
    got_y, got_h = ssd_chunked(x, dt, A, Bm, Cm, 16)
    want_y, want_h = ref.ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(got_y, want_y, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(got_h, want_h, atol=5e-4, rtol=5e-4)


def test_ssd_chunk_invariance():
    """Same result regardless of chunk size (chunking is exact algebra)."""
    from repro.models.mamba import ssd_chunked
    x, dt, A, Bm, Cm = _ssd_inputs((1, 96, 4, 16, 1, 8, 0))
    y1, h1 = ssd_chunked(x, dt, A, Bm, Cm, 8)
    y2, h2 = ssd_chunked(x, dt, A, Bm, Cm, 48)
    np.testing.assert_allclose(y1, y2, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(h1, h2, atol=5e-4, rtol=5e-4)


def test_ssd_padding_path():
    """ops.ssd pads T to a chunk multiple; result must match unpadded ref."""
    x, dt, A, Bm, Cm = _ssd_inputs((1, 50, 4, 16, 1, 8, 0))
    got = ops.ssd(x, dt, A, Bm, Cm, chunk=16)
    want, _ = ref.ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=5e-4)


@given(st.sampled_from([32, 64]), st.sampled_from([2, 4]),
       st.sampled_from([8, 16]))
@settings(max_examples=6, deadline=None)
def test_ssd_property(t, h, n):
    x, dt, A, Bm, Cm = _ssd_inputs((1, t, h, 16, 1, n, 0), seed=t + h)
    got = ops.ssd(x, dt, A, Bm, Cm, chunk=16)
    want, _ = ref.ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-3)
