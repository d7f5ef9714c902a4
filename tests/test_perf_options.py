"""Step-option correctness: every ``remat`` mode and ``agg_compress`` must
compute the same math (or a documented, bounded variation) as the
default."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.launch.mesh import make_debug_mesh
from repro.models import transformer as tfm


def test_selective_remat_gradients_exact():
    """save_only_these_names("tp_out") changes scheduling, not math."""
    cfg = registry.smoke_config("qwen3-32b")
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    lab = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, cfg.vocab)
    g_full = jax.grad(lambda x: tfm.lm_loss(x, cfg, tok, lab, remat=True)[0])(p)
    g_sel = jax.grad(lambda x: tfm.lm_loss(x, cfg, tok, lab,
                                           remat="selective")[0])(p)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_sel)):
        np.testing.assert_allclose(a, b, atol=5e-6)


def test_selective_remat_moe_and_mamba():
    for name in ("jamba-1.5-large-398b", "mamba2-780m"):
        cfg = registry.smoke_config(name)
        p = tfm.init_params(jax.random.PRNGKey(0), cfg)
        tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
        lab = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab)
        l1, _ = tfm.lm_loss(p, cfg, tok, lab, remat=True)
        l2, _ = tfm.lm_loss(p, cfg, tok, lab, remat="selective")
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def _run_rounds(cfg, n=4, seed=3):
    mesh = make_debug_mesh(1, 1)
    step, _, s_spec, _ = F.jit_train_step(cfg, mesh)
    state = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0), cfg),
                    out_shardings=s_spec)()
    losses = []
    for r in range(n):
        batch = F.concrete_train_batch(jax.random.PRNGKey(seed), cfg)
        state, m = step(state, batch)
        losses.append(float(m["s_loss"]))
    return losses


def test_selective_remat_step_matches_full():
    arch = registry.smoke_config("smollm-135m")
    kw = dict(arch=arch, l_split=1, n_groups=2, seq_len=16,
              per_group_batch=4, H=2)
    l_full = _run_rounds(F.FedStepConfig(**kw, remat=True))
    l_sel = _run_rounds(F.FedStepConfig(**kw, remat="selective"))
    np.testing.assert_allclose(l_full, l_sel, rtol=1e-5)


def test_agg_compress_close_to_exact():
    """int8 aggregation payload: the aggregated model differs from exact
    by < 1% of parameter scale (per-tensor quantization error)."""
    arch = registry.smoke_config("smollm-135m")
    kw = dict(arch=arch, l_split=1, n_groups=2, seq_len=16,
              per_group_batch=2, H=2)
    mesh = make_debug_mesh(1, 1)
    outs = {}
    for comp in (False, True):
        cfg = F.FedStepConfig(**kw, agg_compress=comp)
        step, _, s_spec, _ = F.jit_train_step(cfg, mesh)
        state = jax.jit(lambda c=cfg: F.init_train_state(
            jax.random.PRNGKey(0), c), out_shardings=s_spec)()
        batch = F.concrete_train_batch(jax.random.PRNGKey(1), cfg)
        state, _ = step(state, batch)
        outs[comp] = state["dev"]
    for a, b in zip(jax.tree.leaves(outs[False]), jax.tree.leaves(outs[True])):
        scale = float(jnp.abs(a).max()) + 1e-9
        assert float(jnp.abs(a - b).max()) / scale < 0.02
