"""Per-architecture smoke tests: reduced config of the same family, one
forward + one hybrid train step on CPU; output shapes + no NaNs.
(The FULL configs are checked here by shape only — no allocation.)"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.launch.mesh import make_debug_mesh
from repro.models import transformer as tfm

ARCHS = sorted(registry.ARCHS)


@pytest.mark.parametrize("name", ARCHS)
def test_forward_and_loss(name, rng):
    cfg = registry.smoke_config(name)
    params = tfm.init_params(rng, cfg)
    B, S = 2, 24
    tok = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    lab = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    fe = (jax.random.normal(rng, (B, cfg.frontend_len, cfg.d_model))
          if cfg.frontend_len else None)
    loss, (ce, aux) = tfm.lm_loss(params, cfg, tok, lab, frontend=fe)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{name}: non-finite loss"
    if not cfg.n_decoder_layers:
        h, _ = tfm.forward(params, cfg, tok, frontend=fe)
        assert h.shape == (B, S, cfg.d_model)
        assert bool(jnp.isfinite(h).all()), f"{name}: NaNs in hidden states"


@pytest.mark.parametrize("name", ARCHS)
def test_hybrid_train_step(name, rng):
    """One FedOptima round (H micro-iterations + aggregation) per arch."""
    arch = registry.smoke_config(name)
    mesh = make_debug_mesh(1, 1)
    cfg = F.FedStepConfig(arch=arch, l_split=1, n_groups=2, seq_len=16,
                          per_group_batch=2, H=2, param_dtype=jnp.float32)
    jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh)
    state = jax.jit(lambda: F.init_train_state(rng, cfg),
                    out_shardings=s_spec)()
    batch = F.concrete_train_batch(rng, cfg)
    state, metrics = jitted(state, batch)
    assert bool(jnp.isfinite(metrics["d_loss"]))
    assert bool(jnp.isfinite(metrics["s_loss"]))
    assert int(state["step"]) == 1 and int(state["version"]) == 1
    # a second round continues from donated state
    state, metrics = jitted(state, F.concrete_train_batch(
        jax.random.fold_in(rng, 1), cfg))
    assert bool(jnp.isfinite(metrics["s_loss"]))


@pytest.mark.parametrize("name", ARCHS)
def test_decode_step(name, rng):
    cfg = registry.smoke_config(name)
    params = tfm.init_params(rng, cfg)
    B = 2
    caches = tfm.init_serve_state(cfg, B, max_len=32)
    tok = jax.random.randint(rng, (B, 1), 0, cfg.vocab)
    logits, caches = tfm.serve_decode_step(params, cfg, caches, tok,
                                           jnp.int32(0))
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("name", ["smollm-135m", "gemma2-27b", "mamba2-780m",
                                  "jamba-1.5-large-398b", "whisper-tiny",
                                  "llama-3.2-vision-90b"])
def test_prefill_decode_consistency(name, rng):
    """Decode after prefill == prefill of the longer sequence."""
    cfg = registry.smoke_config(name)
    if cfg.n_experts:  # exact match needs dropless capacity
        cfg = cfg.scaled(moe_capacity_factor=float(cfg.n_experts) / cfg.top_k)
    params = tfm.init_params(rng, cfg)
    B, S = 2, 12
    tok = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab)
    fe = (jax.random.normal(rng, (B, cfg.frontend_len, cfg.d_model))
          if cfg.frontend_len else None)
    _, caches = tfm.prefill(params, cfg, tok[:, :S], max_len=32, frontend=fe)
    got, _ = tfm.serve_decode_step(params, cfg, caches, tok[:, S:S + 1],
                                   jnp.int32(S))
    want, _ = tfm.prefill(params, cfg, tok, max_len=32, frontend=fe)
    assert jnp.allclose(got, want, atol=2e-4), \
        f"{name}: decode diverges from prefill"


def test_full_configs_match_assignment():
    """The exact assigned hyper-parameters (spot checks per arch)."""
    a = registry.get("command-r-plus-104b")
    assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads, a.d_ff,
            a.vocab) == (64, 12288, 96, 8, 33792, 256000)
    a = registry.get("qwen3-32b")
    assert a.qk_norm and (a.n_layers, a.d_model, a.vocab) == (64, 5120, 151936)
    a = registry.get("smollm-135m")
    assert (a.n_layers, a.d_model, a.n_heads, a.n_kv_heads) == (30, 576, 9, 3)
    a = registry.get("gemma2-27b")
    assert a.attn_softcap == 50.0 and a.window == 4096 and a.n_layers == 46
    a = registry.get("llama-3.2-vision-90b")
    assert a.n_layers == 100 and any(m == "cross" for m, _ in a.pattern)
    a = registry.get("mamba2-780m")
    assert a.ssm_state == 128 and a.d_ff == 0 and a.n_layers == 48
    a = registry.get("whisper-tiny")
    assert a.n_decoder_layers == 4 and a.d_model == 384
    a = registry.get("jamba-1.5-large-398b")
    assert a.n_experts == 16 and a.top_k == 2 and len(a.pattern) == 8
    assert sum(m == "attn" for m, _ in a.pattern) == 1          # 1:7
    a = registry.get("qwen3-moe-235b-a22b")
    assert a.n_experts == 128 and a.top_k == 8 and a.n_layers == 94
    a = registry.get("llama4-maverick-400b-a17b")
    assert a.n_experts == 128 and a.top_k == 1 and a.vocab == 202048


def test_param_counts_plausible():
    """The parameter tree of each full config lands near its advertised
    size (shapes only: ``eval_shape``, no allocation)."""
    expect = {"command-r-plus-104b": 104e9, "qwen3-32b": 32e9,
              "smollm-135m": 135e6, "gemma2-27b": 27e9,
              "mamba2-780m": 780e6, "qwen3-moe-235b-a22b": 235e9,
              "llama4-maverick-400b-a17b": 400e9,
              "jamba-1.5-large-398b": 398e9}
    for name, n in expect.items():
        params = jax.eval_shape(lambda a=registry.get(name): tfm.init_params(
            jax.random.PRNGKey(0), a, jnp.bfloat16))
        total = sum(x.size for x in jax.tree.leaves(params))
        assert 0.5 * n < total < 1.6 * n, (name, total)
