"""Mamba-2's chunked SSD scan at the published chunk (256): values and
gradients against the sequential recurrence (one B/C group, several, one
per head, and a ragged length through the mixer's padding), C·Bᵀ formed
once per group, a whole pod round with finite updates, and the
``ssd_chunks`` trace-time instant."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.kernels.ref import ssd_reference
from repro.launch.mesh import make_debug_mesh
from repro.models import mamba
from repro.obs.trace import Tracer, traced


def _published_init_inputs(b=2, T=512, H=4, P=8, G=1, N=16):
    """dt log-spaced over the published init range [0.001, 0.1] per head
    (each step jittered by a factor in [e^-0.5, e^0.5]) and A over
    [-16, -1]: the fastest head's log decay sums to ~-400 over a 256-step
    chunk, far past float32's exp range above the diagonal."""
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jax.random.normal(k[0], (b, T, H, P))
    dt = jnp.geomspace(0.001, 0.1, H) * jnp.exp(
        jax.random.uniform(k[1], (b, T, H), minval=-0.5, maxval=0.5))
    A = -jnp.linspace(1.0, 16.0, H)
    B = jax.random.normal(k[2], (b, T, G, N))
    C = jax.random.normal(k[3], (b, T, G, N))
    w = jax.random.normal(k[4], (b, T, H, P))     # the output's cotangent
    return (x, dt, A, B, C), w


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_ssd_chunked_matches_recurrence_at_chunk_256():
    """Forward output and the gradients w.r.t. x, dt, A, B and C are
    finite and agree with ``ssd_reference`` (the per-step recurrence).

    Tolerances are of the largest error over the largest magnitude, both
    in float32 on the CPU.  1e-4 for y and the x, dt, B, C gradients:
    the chunked form sums the same terms in another order (measured
    ~5e-6; either path is within 4e-6 of a float64 recurrence).  1e-3
    for A: dL/dA sums b·T·H·P terms of both signs to a result ~200 times
    smaller than the dt gradients it is made of, so the chunked form's
    reordering shows (measured 2.0e-4 against float64; the recurrence
    6e-7).  A gradient through the masked ``exp`` is NaN or inf, which
    fails every check."""
    args, w = _published_init_inputs()
    chunked = lambda *a: mamba.ssd_chunked(*a, chunk=256)
    y, _ = jax.jit(chunked)(*args)
    y_ref, _ = jax.jit(ssd_reference)(*args)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert _rel(y, y_ref) < 1e-4

    def grads(f):
        return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a)[0] * w),
                                argnums=(0, 1, 2, 3, 4)))(*args)

    tol = {"x": 1e-4, "dt": 1e-4, "A": 1e-3, "B": 1e-4, "C": 1e-4}
    for name, g, g_ref in zip(tol, grads(chunked), grads(ssd_reference)):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, g_ref) < tol[name], (name, _rel(g, g_ref))


def _ragged_mixer_grads(monkeypatch, T=400):
    """The mixer at T = 400 (padded to 512, chunk 256), 2 groups of 4
    heads: its output and the gradients that reach the scan's inputs x,
    B, C and dt (their ``in_proj`` columns) and A (``A_log``), with the
    chunked scan and with the recurrence in its place."""
    cfg = mamba.MambaConfig(d_model=32, d_state=16, head_dim=8, n_groups=2)
    p = mamba.mamba_init(jax.random.PRNGKey(0), cfg)
    k = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k[0], (2, T, cfg.d_model))
    w = jax.random.normal(k[1], (2, T, cfg.d_model))
    Di, GN = cfg.d_inner, cfg.n_groups * cfg.d_state
    cols = {"x": (Di, 2 * Di), "B": (2 * Di, 2 * Di + GN),
            "C": (2 * Di + GN, 2 * Di + 2 * GN), "dt": (2 * Di + 2 * GN, None)}

    def run():
        f = lambda p: jnp.sum(mamba.mamba_apply(p, cfg, x) * w)
        g = jax.jit(jax.grad(f))(p)
        out = {n: g["in_proj"][:, a:b] for n, (a, b) in cols.items()}
        out["A"] = g["A_log"]
        return jax.jit(lambda p: mamba.mamba_apply(p, cfg, x))(p), out

    y, g = run()
    monkeypatch.setattr(mamba, "ssd_chunked",
                        lambda x, dt, A, B, C, Q: ssd_reference(x, dt, A, B, C))
    y_ref, g_ref = run()
    return (y, g), (y_ref, g_ref)


@pytest.mark.parametrize("case", ["g1_rep4", "g2_rep4", "g_eq_h",
                                  "ragged_padded"])
def test_ssd_jnp_path_matches_recurrence(case, monkeypatch):
    """The ``jnp`` path (``ssd_chunked``, chunk 256) against
    ``ssd_reference``: the value and the x, dt, A, B and C gradients,
    for one B/C group of 4 heads, 2 groups of 4, one group per head, and
    a ragged length through ``mamba_apply``'s padding.  Tolerances as in
    ``test_ssd_chunked_matches_recurrence_at_chunk_256``."""
    tol = {"x": 1e-4, "dt": 1e-4, "A": 1e-3, "B": 1e-4, "C": 1e-4}
    if case == "ragged_padded":
        (y, g), (y_ref, g_ref) = _ragged_mixer_grads(monkeypatch)
        g, g_ref = [g[n] for n in tol], [g_ref[n] for n in tol]
    else:
        G, H = {"g1_rep4": (1, 4), "g2_rep4": (2, 8), "g_eq_h": (4, 4)}[case]
        args, w = _published_init_inputs(H=H, G=G)
        chunked = lambda *a: mamba.ssd_chunked(*a, chunk=256)
        y, y_ref = jax.jit(chunked)(*args)[0], jax.jit(ssd_reference)(*args)[0]

        def grads(f):
            return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a)[0] * w),
                                    argnums=(0, 1, 2, 3, 4)))(*args)
        g, g_ref = grads(chunked), grads(ssd_reference)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert _rel(y, y_ref) < 1e-4
    for name, a, b in zip(tol, g, g_ref):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b) < tol[name], (name, _rel(a, b))


def _dot_generals(jaxpr):
    """Every ``dot_general`` equation of a jaxpr and its sub-jaxprs
    (scan and remat bodies, jit calls)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _dot_generals(sub)


def test_ssd_forms_cb_once_per_group():
    """One B/C group of 8 heads: in the value and gradient of
    ``ssd_chunked`` no product that contracts the state dim N has a head
    dim in its output, so C·Bᵀ is a (Q, Q) tile per group and the
    products with the state take the group's heads folded together
    (``test_ssd_chunks_instant_once_per_call_shape`` checks the instant's
    one group and one C·Bᵀ per chunk).  Sizes are distinct so a dim
    names itself: b 2, T 64, H 8, P 4, N 16, chunk 32."""
    H, N, Q = 8, 16, 32
    args, w = _published_init_inputs(b=2, T=64, H=H, P=4, G=1, N=N)
    f = lambda *a: jnp.sum(mamba.ssd_chunked(*a, chunk=Q)[0] * w)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4)))(
        *args).jaxpr
    over_n = []
    for eqn in _dot_generals(jaxpr):
        (lc, _), _ = eqn.params["dimension_numbers"]
        if N in [eqn.invars[0].aval.shape[i] for i in lc]:
            over_n.append(tuple(eqn.outvars[0].aval.shape))
    assert (2, Q, Q) in over_n, over_n           # C·Bᵀ, per (sequence, group)
    assert all(H not in shape for shape in over_n), over_n


def test_mamba_pod_round_at_chunk_256_has_finite_updates():
    """Two rounds of the pod step (device half, ring, server half,
    aggregation) for a mamba2 model at smoke widths, ``ssm_chunk`` 256
    and seq 256: losses are finite, and so is every leaf of each round's
    change, which under plain SGD is the learning rate times the
    round's gradients."""
    arch = registry.smoke_config("mamba2-780m").scaled(ssm_chunk=256)
    cfg = F.FedStepConfig(arch=arch, l_split=1, n_groups=2, seq_len=256,
                          per_group_batch=2, H=2)
    jitted, _, s_spec, _ = F.jit_train_step(cfg, make_debug_mesh(1, 1),
                                            donate=False)
    state = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0), cfg),
                    out_shardings=s_spec)()
    batch = F.concrete_train_batch(jax.random.PRNGKey(1), cfg)
    for r in range(2):
        new, metrics = jitted(state, batch)
        assert np.isfinite(float(metrics["d_loss"])), r
        assert np.isfinite(float(metrics["s_loss"])), r
        for key in ("dev", "aux", "srv"):
            change = jax.tree.map(lambda a, b: a - b, new[key], state[key])
            leaves = jax.tree_util.tree_leaves_with_path(change)
            bad = [jax.tree_util.keystr(p) for p, v in leaves
                   if not bool(jnp.all(jnp.isfinite(v)))]
            assert bad == [], (r, key, bad)
        state = new


def _block():
    cfg = mamba.MambaConfig(d_model=32, d_state=8, head_dim=16, chunk=16)
    return cfg, mamba.mamba_init(jax.random.PRNGKey(0), cfg)


def test_ssd_chunks_instant_once_per_call_shape():
    """Traced, the mixer emits one ``host/compile`` instant per call shape
    at trace time (however often JAX traces it: value, grad, vmap), with
    the scan's shape, chunk, chunk count, padding, path, B/C groups and
    C·Bᵀ products per chunk."""
    cfg, p = _block()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model))
    fn = lambda p, x: jnp.sum(mamba.mamba_apply(p, cfg, x))
    with traced(Tracer()) as tr:
        jax.jit(jax.value_and_grad(fn))(p, x)
        jax.jit(fn)(p, x)
        jax.vmap(fn, in_axes=(None, 0))(p, x[None])
        fn(p, x[:, :32])
    marks = [i for i in tr.instants if i[1] == "ssd_chunks"]
    assert [m[0] for m in marks] == ["host/compile"] * 2
    args = sorted((m[3] for m in marks), key=lambda a: a["shape"][1])
    H, P, N = cfg.n_heads, cfg.head_dim, cfg.d_state
    assert args[0] == {"shape": [2, 32, H, P, N], "chunk": 16,
                       "n_chunks": 2, "pad": 0, "path": "jnp",
                       "groups": 1, "cb_per_chunk": 1}
    assert args[1] == {"shape": [2, 40, H, P, N], "chunk": 16,
                       "n_chunks": 3, "pad": 8, "path": "jnp",
                       "groups": 1, "cb_per_chunk": 1}


def test_ssd_chunks_not_emitted_when_tracing_is_off(monkeypatch):
    cfg, p = _block()
    calls = []
    monkeypatch.setattr(mamba._obs, "emit_instant_once",
                        lambda *a, **k: calls.append(a))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model))
    jax.jit(lambda p, x: mamba.mamba_apply(p, cfg, x))(p, x)
    assert calls == []


@pytest.mark.parametrize("use_kernel", [False, True])
def test_ssd_scan_carries_its_scope(use_kernel):
    """Both branches of the scan, the jnp path with its padding and the
    kernel call, lower under the ``ssd`` scope."""
    cfg, p = _block()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model))
    text = jax.jit(lambda p, x: mamba.mamba_apply(
        p, cfg, x, use_kernel=use_kernel)).lower(p, x).as_text(
            debug_info=True)
    assert '"ssd/' in text or "/ssd/" in text
