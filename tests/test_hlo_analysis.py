"""HLO cost parser: trip-count scaling, collective accounting, roofline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (Cost, analyze_compiled, analyze_text, roofline,
                            count_params, model_flops)
from repro.analysis.hlo import HloModule, _shape_dims, _type_bytes


def test_type_bytes():
    assert _type_bytes("f32[4,8]{1,0}") == 128
    assert _type_bytes("bf16[10]") == 20
    assert _type_bytes("(f32[2,2], s32[3])") == 28
    assert _type_bytes("pred[7]") == 7
    assert _shape_dims("f32[4,8]{1,0}") == [4, 8]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dot_flops_exact():
    c = _compile(lambda a, b: a @ b,
                 jax.ShapeDtypeStruct((64, 32), jnp.float32),
                 jax.ShapeDtypeStruct((32, 16), jnp.float32))
    cost = analyze_compiled(c)
    assert cost.flops == 2 * 64 * 32 * 16


def test_while_trip_count_scaling():
    """A scan of N matmuls must count N×, not 1× (XLA counts 1×)."""
    n, d = 9, 32

    def fn(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        y, _ = jax.lax.scan(body, x, None, length=n)
        return y.sum()

    c = _compile(fn, jax.ShapeDtypeStruct((8, d), jnp.float32),
                 jax.ShapeDtypeStruct((d, d), jnp.float32))
    cost = analyze_compiled(c)
    expect = n * 2 * 8 * d * d
    assert abs(cost.flops - expect) / expect < 0.01
    xla = c.cost_analysis()
    assert xla["flops"] < cost.flops / 2  # XLA undercounts (body once)


def test_nested_scan_scaling():
    def fn(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, ()
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, ()
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y.sum()

    c = _compile(fn, jax.ShapeDtypeStruct((4, 16), jnp.float32),
                 jax.ShapeDtypeStruct((16, 16), jnp.float32))
    cost = analyze_compiled(c)
    expect = 5 * 3 * 2 * 4 * 16 * 16
    assert abs(cost.flops - expect) / expect < 0.01


def test_dynamic_update_slice_counts_slice_not_buffer():
    """In-place accumulation traffic = slice, not the whole buffer."""
    def fn(buf, x):
        def body(b, i):
            return jax.lax.dynamic_update_slice_in_dim(b, x, i, 0), ()
        out, _ = jax.lax.scan(body, buf, jnp.arange(100))
        return out

    c = _compile(fn, jax.ShapeDtypeStruct((1024, 256), jnp.float32),
                 jax.ShapeDtypeStruct((1, 256), jnp.float32))
    cost = analyze_compiled(c)
    full_buffer_per_iter = 100 * 1024 * 256 * 4
    assert cost.bytes < full_buffer_per_iter  # would be 100x buffer if naive


def test_cost_add_and_scale():
    a = Cost(flops=2.0, bytes=4.0)
    a.collective_bytes["all-reduce"] += 8.0
    b = a.scaled(3)
    assert b.flops == 6.0 and b.collective_bytes["all-reduce"] == 24.0
    a += b
    assert a.flops == 8.0 and a.total_collective_bytes == 32.0


def test_exclude_fn_zeroes_matching_buffers():
    def fn(q, k):
        s = q @ k.T                    # (128, 128) score-like
        return jax.nn.softmax(s, axis=-1).sum()

    c = _compile(fn, jax.ShapeDtypeStruct((128, 64), jnp.float32),
                 jax.ShapeDtypeStruct((128, 64), jnp.float32))
    base = analyze_compiled(c)
    excl = analyze_compiled(c, exclude_fn=lambda d: tuple(d) == (128, 128))
    assert excl.bytes < base.bytes
    assert excl.flops == base.flops    # flops unchanged


def test_roofline_terms_and_dominance():
    cost = Cost(flops=197e12, bytes=819e9 / 2)
    cost.collective_bytes["all-reduce"] = 50e9 / 8
    t = roofline(cost, model_flops_total=197e12 / 2, n_chips=1)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 0.5) < 1e-9
    assert abs(t.collective_s - 0.25) < 1e-9   # 2x ring factor
    assert t.dominant == "compute"
    assert abs(t.mfu - 0.5) < 1e-9


def test_model_flops_moe_counts_active_only():
    from repro.configs import registry
    dense_like = registry.get("qwen3-moe-235b-a22b")
    total, active = count_params(dense_like)
    assert active < 0.2 * total        # 235B total vs ~22B active
    mf_train = model_flops(dense_like, 1000, kind="train")
    mf_inf = model_flops(dense_like, 1000, kind="infer")
    assert abs(mf_train / mf_inf - 3.0) < 1e-6


def test_parser_handles_real_sharded_module():
    """End-to-end on an SPMD module would need >1 device; on 1 device the
    parser must still walk the entry and find the dots."""
    def fn(x, w1, w2):
        h = jax.nn.relu(x @ w1)
        return (h @ w2).sum()

    c = _compile(fn, jax.ShapeDtypeStruct((32, 64), jnp.float32),
                 jax.ShapeDtypeStruct((64, 128), jnp.float32),
                 jax.ShapeDtypeStruct((128, 8), jnp.float32))
    cost = analyze_compiled(c)
    expect = 2 * 32 * 64 * 128 + 2 * 32 * 128 * 8
    assert abs(cost.flops - expect) / expect < 0.01
