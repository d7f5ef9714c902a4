"""Differentiable Pallas kernels: gradient parity against the pure-jnp
oracles (interpret mode), LSE residual correctness, kernel_mode scoping,
chunk clamping, and the end-to-end kernel-mode hybrid train step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)

GTOL = 1e-4


def _qkv(shape, dtype=jnp.float32, seed=0):
    B, S, Skv, H, Hkv, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), dtype)
    return q, k, v


def _attn_grads(fn, q, k, v, **kw):
    # non-linear readout so every output element contributes a distinct
    # cotangent (catches transposition/accumulation mistakes a plain sum
    # would mask)
    loss = lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, **kw)))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


ATTN_GRAD_CASES = [
    # (shape, kwargs)
    ((1, 128, 128, 4, 4, 32), dict(causal=True)),              # MHA causal
    ((2, 128, 128, 8, 2, 32), dict(causal=True)),              # GQA 4:1
    ((1, 192, 192, 4, 4, 32), dict(causal=True, window=32)),   # sliding win
    ((1, 128, 128, 4, 2, 32), dict(causal=True, logit_cap=20.0)),  # softcap
    ((1, 64, 64, 4, 1, 32), dict(causal=False)),               # MQA, full
    ((1, 100, 100, 4, 2, 32), dict(causal=True)),              # ragged S
    ((1, 100, 72, 4, 2, 32), dict(causal=False)),              # ragged Skv
    ((1, 160, 160, 4, 2, 32),
     dict(causal=True, window=48, logit_cap=15.0)),            # all stacked
]


@pytest.mark.parametrize("shape,kw", ATTN_GRAD_CASES)
def test_flash_attention_grad_parity(shape, kw):
    q, k, v = _qkv(shape)
    with ops.kernel_mode(True):
        got = _attn_grads(ops.flash_attention, q, k, v, **kw)
    want = _attn_grads(ref.flash_attention_reference, q, k, v, **kw)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=GTOL, rtol=GTOL,
                                   err_msg=f"d{name} {shape} {kw}")


def test_flash_attention_grad_matches_sdpa_chunked():
    """The training path without kernels (sdpa_blockwise, which replaced
    sdpa_chunked) and the kernel agree on grads."""
    from repro.models.attention import sdpa_blockwise
    q, k, v = _qkv((2, 96, 96, 8, 2, 32))
    kw = dict(causal=True, window=None, logit_cap=None)
    with ops.kernel_mode(True):
        got = _attn_grads(ops.flash_attention, q, k, v,
                          causal=True)
    want = _attn_grads(sdpa_blockwise, q, k, v, chunk_q=32, **kw)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=GTOL, rtol=GTOL, err_msg=f"d{name}")


def test_flash_attention_lse_matches_reference():
    from repro.kernels.flash_attention import flash_attention_fwd_bhsd
    q, k, v = _qkv((2, 96, 96, 4, 2, 32))
    out, lse = flash_attention_fwd_bhsd(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
        causal=True, interpret=True)
    want_out, want_lse = ref.flash_attention_reference(q, k, v, causal=True,
                                                       return_lse=True)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(out, 1, 2)),
                               np.asarray(want_out), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)


@given(st.sampled_from([64, 96, 128]), st.sampled_from([(4, 4), (4, 2)]),
       st.sampled_from([None, 32]), st.sampled_from([None, 25.0]))
@settings(max_examples=4, deadline=None)
def test_flash_attention_grad_property(s, heads, window, cap):
    H, Hkv = heads
    q, k, v = _qkv((1, s, s, H, Hkv, 32), seed=s + H)
    kw = dict(causal=True, window=window, logit_cap=cap)
    with ops.kernel_mode(True):
        got = _attn_grads(ops.flash_attention, q, k, v, **kw)
    want = _attn_grads(ref.flash_attention_reference, q, k, v, **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=GTOL, rtol=GTOL)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

def _ssd_inputs(shape, seed=0):
    B, T, H, P, G, N = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.5
    Cm = jax.random.normal(jax.random.fold_in(ks[3], 1), (B, T, G, N)) * 0.5
    return x, dt, A, Bm, Cm


def _ssd_grads(fn, args):
    loss = lambda *a: jnp.sum(jnp.sin(fn(*a)))
    return jax.grad(loss, argnums=tuple(range(5)))(*args)


SSD_GRAD_CASES = [
    # ((B, T, H, P, G, N), chunk)
    ((1, 64, 4, 16, 1, 8), 16),
    ((2, 64, 8, 16, 2, 8), 32),     # grouped B/C (rep=4)
    ((1, 50, 4, 16, 1, 8), 16),     # ragged: T % chunk != 0 (padding bwd)
    ((1, 12, 4, 16, 1, 8), 32),     # T < chunk (clamp + single chunk)
]


@pytest.mark.parametrize("shape,chunk", SSD_GRAD_CASES)
def test_ssd_grad_parity(shape, chunk):
    args = _ssd_inputs(shape)
    with ops.kernel_mode(True):
        got = _ssd_grads(lambda *a: ops.ssd(*a, chunk=chunk), args)
    want = _ssd_grads(lambda *a: ref.ssd_reference(*a)[0], args)
    for g, w, name in zip(got, want, ["x", "dt", "A", "B", "C"]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=GTOL, rtol=GTOL,
                                   err_msg=f"d{name} {shape} chunk={chunk}")


@given(st.sampled_from([24, 48, 64]), st.sampled_from([2, 4]),
       st.sampled_from([8, 16]))
@settings(max_examples=4, deadline=None)
def test_ssd_grad_property(t, h, n):
    args = _ssd_inputs((1, t, h, 16, 1, n), seed=t + h)
    with ops.kernel_mode(True):
        got = _ssd_grads(lambda *a: ops.ssd(*a, chunk=16), args)
    want = _ssd_grads(lambda *a: ref.ssd_reference(*a)[0], args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# ops plumbing: chunk clamp + kernel_mode scoping
# ---------------------------------------------------------------------------

def test_ssd_chunk_clamped_and_padded():
    """chunk > T clamps once; T % chunk != 0 pads — both match the oracle
    (regression for the dead clamp expression that never re-padded)."""
    for T, chunk in ((12, 128), (50, 16), (48, 48)):
        args = _ssd_inputs((1, T, 4, 16, 1, 8), seed=T)
        got = ops.ssd(*args, chunk=chunk)
        want, _ = ref.ssd_reference(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-4, rtol=5e-4, err_msg=str((T, chunk)))


def test_kernel_mode_scopes_and_restores():
    import repro.kernels.ops as kops
    kops.set_kernel_mode(None)
    assert kops._FORCE_INTERPRET is None
    with kops.kernel_mode(True):
        assert kops._FORCE_INTERPRET is True
        with kops.kernel_mode(False):
            assert kops._FORCE_INTERPRET is False
        assert kops._FORCE_INTERPRET is True
    assert kops._FORCE_INTERPRET is None
    # exception-safe restore
    with pytest.raises(RuntimeError):
        with kops.kernel_mode(True):
            raise RuntimeError("boom")
    assert kops._FORCE_INTERPRET is None


# ---------------------------------------------------------------------------
# end-to-end: kernel-mode training
# ---------------------------------------------------------------------------

def test_selective_remat_composes_with_kernels():
    """remat="selective" (saves tp_out + kernel_out) must not change the
    kernel-path gradients."""
    from repro.configs import registry
    from repro.models import transformer as tfm
    cfg = registry.smoke_config("mamba2-780m")
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    lab = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, cfg.vocab)
    with ops.kernel_mode(True):
        g_full = jax.grad(lambda x: tfm.lm_loss(
            x, cfg, tok, lab, use_kernel=True, remat=True)[0])(p)
        g_sel = jax.grad(lambda x: tfm.lm_loss(
            x, cfg, tok, lab, use_kernel=True, remat="selective")[0])(p)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_sel)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6)


@pytest.mark.parametrize("arch", ["smollm-135m", "mamba2-780m"])
def test_train_step_use_kernel_full_round(arch):
    """Acceptance: make_train_step(use_kernel=True) traces, lowers, and runs
    a full round — device half + server half under value_and_grad + the
    end-of-round aggregation — through the fused kernels."""
    from repro.configs import registry
    from repro.core import fedopt_step as F
    from repro.launch.mesh import make_debug_mesh
    a = registry.smoke_config(arch)
    cfg = F.FedStepConfig(arch=a, l_split=1, n_groups=2, seq_len=16,
                          per_group_batch=4, H=2, use_kernel=True)
    mesh = make_debug_mesh(1, 1)
    jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh)
    state = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0), cfg),
                    out_shardings=s_spec)()
    batch = F.concrete_train_batch(jax.random.PRNGKey(1), cfg)
    state, metrics = jitted(state, batch)
    assert np.isfinite(float(metrics["d_loss"]))
    assert np.isfinite(float(metrics["s_loss"]))
    assert int(state["step"]) == 1
    # aggregation ran: groups identical after uniform-weight round
    for leaf in jax.tree.leaves(state["dev"]):
        np.testing.assert_allclose(np.asarray(leaf[0]), np.asarray(leaf[1]),
                                   atol=1e-6)


@pytest.mark.slow
def test_train_step_kernel_matches_fallback():
    """One kernel-mode round ≈ one fallback round (same data, same math up
    to reduction order): losses agree to f32 tolerance."""
    from repro.configs import registry
    from repro.core import fedopt_step as F
    from repro.launch.mesh import make_debug_mesh
    a = registry.smoke_config("smollm-135m")
    losses = {}
    for uk in (False, True):
        cfg = F.FedStepConfig(arch=a, l_split=1, n_groups=2, seq_len=16,
                              per_group_batch=4, H=2, use_kernel=uk)
        mesh = make_debug_mesh(1, 1)
        jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh)
        state = jax.jit(lambda c=cfg: F.init_train_state(
            jax.random.PRNGKey(0), c), out_shardings=s_spec)()
        batch = F.concrete_train_batch(jax.random.PRNGKey(1), cfg)
        _, m = jitted(state, batch)
        losses[uk] = (float(m["d_loss"]), float(m["s_loss"]))
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-4)
