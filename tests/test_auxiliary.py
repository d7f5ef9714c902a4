"""Auxiliary network (paper §3.2.2 + §6.5.1 ablation mechanics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer as tfm


def test_default_aux_structure():
    """Default: one block of the same type as the last device layer +
    factorized dense classifier."""
    cfg = registry.smoke_config("smollm-135m")
    aux = tfm.make_aux_params(jax.random.PRNGKey(0), cfg)
    assert set(aux) == {"block", "norm", "head_in", "head_out"}
    assert aux["head_in"].shape == (cfg.d_model, cfg.aux_dim)
    assert aux["head_out"].shape == (cfg.aux_dim, cfg.vocab)


def test_regression_aux_for_continuous_inputs():
    cfg = registry.smoke_config("whisper-tiny")
    aux = tfm.make_aux_params(jax.random.PRNGKey(0), cfg, regression=True)
    assert "head_reg" in aux and "head_out" not in aux
    B, S = 2, 12
    acts = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    frames = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.d_model))
    loss = tfm.aux_head_loss(aux, cfg, acts, frames)
    assert loss.shape == () and bool(jnp.isfinite(loss)) and float(loss) > 0


def test_gradient_free_offloading():
    """The defining property: server-side training produces NO gradient
    w.r.t. device parameters (stop_gradient at the activation hand-off)."""
    cfg = registry.smoke_config("smollm-135m")
    rng = jax.random.PRNGKey(0)
    full = tfm.init_params(rng, cfg)
    dev, srv = tfm.split_params(full, cfg, 1)
    tok = jax.random.randint(rng, (2, 12), 0, cfg.vocab)
    lab = jax.random.randint(rng, (2, 12), 0, cfg.vocab)

    def srv_loss_via_dev(d):
        acts, _ = tfm.device_forward(d, cfg, tok)
        return tfm.server_forward_loss(srv, cfg, acts, lab)

    g = jax.grad(srv_loss_via_dev)(dev)
    norms = [float(jnp.abs(x).max()) for x in jax.tree.leaves(g)]
    assert max(norms) == 0.0, "gradient leaked from server to device"


def test_aux_loss_trains_device_block():
    """A few aux-loss SGD steps reduce the local loss (Alg. 1)."""
    cfg = registry.smoke_config("smollm-135m")
    rng = jax.random.PRNGKey(0)
    full = tfm.init_params(rng, cfg)
    dev, _ = tfm.split_params(full, cfg, 1)
    aux = tfm.make_aux_params(rng, cfg)
    tok = jax.random.randint(rng, (4, 16), 0, cfg.vocab)
    lab = jax.random.randint(rng, (4, 16), 0, cfg.vocab)

    @jax.jit
    def step(dev, aux):
        (loss, _), (gd, ga) = jax.value_and_grad(
            lambda d, a: tfm.device_train_loss(d, a, cfg, tok, lab),
            argnums=(0, 1), has_aux=True)(dev, aux)
        dev = jax.tree.map(lambda p, g: p - 0.1 * g, dev, gd)
        aux = jax.tree.map(lambda p, g: p - 0.1 * g, aux, ga)
        return dev, aux, loss

    losses = []
    for _ in range(12):
        dev, aux, loss = step(dev, aux)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.05, losses


ARCHS = sorted(registry.ARCHS)


@pytest.mark.parametrize("name,l_split", [
    (name, l) for name in ARCHS
    for l in range(1, registry.smoke_config(name).n_periods)])
def test_split_merge_roundtrip(name, l_split):
    cfg = registry.smoke_config(name)
    full = tfm.init_params(jax.random.PRNGKey(0), cfg)
    dev, srv = tfm.split_params(full, cfg, l_split)
    merged = tfm.merge_params(dev, srv, cfg)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 full, merged)


@pytest.mark.parametrize("name", ARCHS)
def test_split_equivalence_full_forward(name):
    """device_forward + the server half == full forward (same math, split
    after the first period).  Enc-dec (whisper): the device half is the
    encoder prefix on the frontend frames and the server finishes it and
    runs the decoder; VLM: both halves see the frontend."""
    cfg = registry.smoke_config(name)
    rng = jax.random.PRNGKey(0)
    full = tfm.init_params(rng, cfg)
    tok = jax.random.randint(rng, (2, 8), 0, cfg.vocab)
    lab = jax.random.randint(rng, (2, 8), 0, cfg.vocab)
    fe = (jax.random.normal(rng, (2, cfg.frontend_len, cfg.d_model))
          if cfg.frontend_len else None)
    want, _ = tfm.lm_loss(full, cfg, tok, lab, frontend=fe, aux_weight=0.0)
    dev, srv = tfm.split_params(full, cfg, 1)
    if cfg.n_decoder_layers:
        acts, _ = tfm.device_forward(dev, cfg, fe)
        got = tfm.server_encdec_loss(srv, cfg, acts, tok, lab,
                                     aux_weight=0.0)
    else:
        acts, _ = tfm.device_forward(dev, cfg, tok, frontend=fe)
        got = tfm.server_forward_loss(srv, cfg, acts, lab, frontend=fe,
                                      aux_weight=0.0)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
