"""The XLA attention path (``sdpa_blockwise``): exact against
``sdpa_reference`` in value and gradients, forms only the visible tiles,
runs its forward once under the selective-remat policy, and reports the
tiles it computes when traced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import transformer as tfm
from repro.models.attention import (block_size, sdpa_blockwise,
                                    sdpa_reference, tile_counts)
from repro.obs.trace import Tracer, traced

TOL = 2e-5

CASES = [
    # (B, S, Skv, H, Hkv, hd), kwargs, chunk_q, vmapped over a group axis
    ((2, 96, 96, 6, 2, 16), dict(causal=True), 32, False),
    ((2, 96, 96, 6, 2, 16), dict(causal=False), 32, False),
    ((2, 96, 96, 6, 2, 16), dict(causal=True, window=20), 32, False),
    ((2, 96, 96, 6, 2, 16), dict(causal=True, logit_cap=5.0), 32, False),
    ((1, 96, 96, 9, 3, 16), dict(causal=True), 32, False),        # SmolLM GQA
    ((2, 40, 24, 6, 2, 16), dict(causal=False), 16, False),       # cross
    ((2, 100, 100, 6, 3, 16), dict(causal=True), 32, False),      # ragged S
    ((2, 40, 24, 6, 2, 16), dict(causal=False, window=7), 16, False),  # rows see no key
    ((2, 80, 80, 6, 2, 16),
     dict(causal=True, window=24, logit_cap=8.0), 16, False),     # all stacked
    ((2, 64, 64, 6, 2, 16), dict(causal=True), 16, True),         # device half
]


def _inputs(shape, groups=None, seed=0):
    B, S, Skv, H, Hkv, hd = shape
    lead = () if groups is None else (groups,)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], lead + (B, S, H, hd)),
            jax.random.normal(ks[1], lead + (B, Skv, Hkv, hd)),
            jax.random.normal(ks[2], lead + (B, Skv, Hkv, hd)),
            jax.random.normal(ks[3], lead + (B, S, H, hd)))


@pytest.mark.parametrize("shape,kw,chunk_q,vmapped", CASES)
def test_blockwise_matches_reference(shape, kw, chunk_q, vmapped):
    kw = dict(dict(window=None, logit_cap=None), **kw)
    q, k, v, w = _inputs(shape, groups=3 if vmapped else None)

    def loss(fn):
        f = jax.vmap(fn) if vmapped else fn
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)) * w)

    new = lambda q, k, v: sdpa_blockwise(q, k, v, chunk_q=chunk_q, **kw)
    old = lambda q, k, v: sdpa_reference(q, k, v, **kw)
    vg = lambda fn: jax.jit(jax.value_and_grad(loss(fn), argnums=(0, 1, 2)))
    fwd = lambda fn: jax.jit(jax.vmap(fn) if vmapped else fn)
    with jax.default_matmul_precision("highest"):
        got, want = vg(new)(q, k, v), vg(old)(q, k, v)
        out, ref = fwd(new)(q, k, v), fwd(old)(q, k, v)
    np.testing.assert_allclose(out, ref, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(got[0], want[0], rtol=TOL)
    for g, r, name in zip(got[1], want[1], "qkv"):
        np.testing.assert_allclose(g, r, atol=TOL, rtol=TOL,
                                   err_msg=f"d{name}")


def test_block_size_skips_the_causal_triangle():
    """smollm-135m at S = 2048: causal, under chunk_q 1024, the queries are
    cut into blocks of 256 rows, which compute 36 of the square's 64
    tiles; a non-causal call keeps chunk_q's bound and every tile."""
    cq = block_size(2048, causal=True, window=None, chunk_q=1024)
    assert cq == 256
    assert tile_counts(2048, 2048, cq, True, None) == (36, 64)
    assert tile_counts(2048, 2048, 512, True, None) == (10, 16)
    assert block_size(2048, causal=False, window=None, chunk_q=1024) == 1024
    assert tile_counts(2048, 2048, 1024, False, None) == (4, 4)
    assert block_size(64, causal=True, window=None, chunk_q=1024) == 64
    # a window trims the start of the band too
    assert tile_counts(2048, 2048, 512, True, 512) == (7, 16)


def _score_dots(jaxpr, rows, widths, out):
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            shape = e.outvars[0].aval.shape
            if len(shape) == 4 and shape[2] == rows and shape[3] in widths:
                out[shape[3]] = out.get(shape[3], 0) + 1
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _score_dots(inner, rows, widths, out)
    return out


def test_selective_remat_forms_each_visible_tile_once_per_pass():
    """One ``_run_stack`` period under ``remat="selective"``: every visible
    block forms its (rows × keys) scores three times in all, in the
    forward, in the backward's recompute of S and in dP = dO vᵀ.  The
    forward is not re-run (o and lse are saved as ``kernel_out``), and
    masked tiles are never formed."""
    cfg = registry.smoke_config("smollm-135m").scaled(attn_chunk=32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 96
    h = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    pos = jnp.arange(S)[None]

    def f(blocks, h):
        y, _ = tfm._run_stack(blocks, cfg, h, positions=pos,
                              remat="selective")
        return jnp.sum(y ** 2)

    cq = block_size(S, causal=True, window=None, chunk_q=32)
    widths = {cq * (i + 1) for i in range(S // cq)}
    jx = jax.make_jaxpr(jax.value_and_grad(f))(params["blocks"], h)
    counts = _score_dots(jx.jaxpr, cq, widths | {S + 1}, {})
    assert counts == {w: 3 for w in widths}
    assert tile_counts(S, S, cq, True, None) == (6, 9)


def test_engagement_instant_once_per_call_shape():
    """Traced, the path emits one ``host/compile`` instant per attention
    call shape at trace time, however often JAX traces it (value, grad,
    vmap, jit retrace), carrying the tiles computed of the full square."""
    q, k, v, _ = _inputs((2, 96, 96, 6, 2, 16))
    fn = lambda q, k, v: jnp.sum(sdpa_blockwise(
        q, k, v, causal=True, window=None, logit_cap=None, chunk_q=32))
    with traced(Tracer()) as tr:
        jax.jit(jax.value_and_grad(fn))(q, k, v)
        jax.jit(fn)(q, k, v)
        jax.vmap(fn)(q[None], k[None], v[None])
        fn(q[:, :64], k[:, :64], v[:, :64])
    marks = [i for i in tr.instants if i[1] == "attention_tiles"]
    assert [m[0] for m in marks] == ["host/compile"] * 2
    args = sorted((m[3] for m in marks), key=lambda a: a["shape"][1])
    assert args[0]["shape"] == [2, 64, 64, 6, 16]
    assert (args[0]["tiles"], args[0]["of"], args[0]["cq"]) == (3, 4, 32)
    assert (args[1]["tiles"], args[1]["of"], args[1]["cq"]) == (6, 9, 32)
