"""Compiles for a described TPU v5e, with no chip attached: nothing runs.

The TPU's compiler refuses what interpret mode never checks: block shapes
that are not (8, 128)-tiled, too much VMEM, a program larger than the
chip's HBM.  These tests compile the Pallas kernels, forward and backward,
at the widths the models call them (smollm-135m attention, mamba2-780m
SSD), and one whole pod round of smollm-135m at full width on one chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.  Everything built from the topology is built in
fixtures or tests too.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.kernels import ops

HBM_BYTES = 16e9            # one v5e chip
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host; the persistent compilation cache is off
    while this module compiles (its entries could not be read back
    without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _n_kernels(compiled) -> int:
    return compiled.as_text().count(KERNEL)


DTYPES = [pytest.param(jnp.float32, id="f32"),
          pytest.param(jnp.bfloat16, id="bf16")]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, dtype, grad):
    """smollm-135m widths: B=2, S=2048, H=9, Hkv=3, hd=64.  The backward
    is the dq kernel plus the dk/dv kernel."""
    q = jax.ShapeDtypeStruct((2, 2048, 9, 64), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 2048, 3, 64), dtype, sharding=one_chip)

    def attn(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, interpret=False)

    if grad:
        fn = jax.grad(lambda *a: attn(*a).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    else:
        fn = attn
    assert _n_kernels(_compile(fn, q, kv, kv)) == (3 if grad else 1)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_ssd_compiles(one_chip, dtype, grad):
    """mamba2-780m widths: H=48 heads of P=64, state N=128, one B/C
    group, chunk 256 (``ssm_chunk``), T=2048."""
    arch = registry.get("mamba2-780m")
    mcfg = arch.mamba_cfg()
    B, T, H, P, N = 2, 2048, mcfg.n_heads, mcfg.head_dim, mcfg.d_state
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (sds((B, T, H, P), dtype), sds((B, T, H), jnp.float32),
            sds((H,), jnp.float32), sds((B, T, mcfg.n_groups, N), dtype),
            sds((B, T, mcfg.n_groups, N), dtype))

    def ssd(*a):
        return ops.ssd(*a, chunk=mcfg.chunk, interpret=False)

    if grad:
        fn = jax.grad(lambda *a: ssd(*a).astype(jnp.float32).sum(),
                      argnums=tuple(range(5)))
    else:
        fn = ssd
    assert _n_kernels(_compile(fn, *args)) == (2 if grad else 1)


def test_smollm_pod_round_fits_one_chip(topo):
    """One full-width smollm-135m round as ``chip_smoke.py`` runs it:
    G=4 groups, 8 sequences of 2048 per group, H=4, split after 3
    periods.  State and batch are shapes with the step's own shardings on
    a one-chip mesh."""
    arch = registry.get("smollm-135m")
    cfg = F.FedStepConfig(arch=arch, l_split=F.default_l_split(arch),
                          n_groups=4, seq_len=2048, per_group_batch=8, H=4)
    assert cfg.l_split == 3
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    jitted, state, s_spec, b_spec = F.jit_train_step(cfg, mesh)
    sds = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    state = jax.tree.map(sds, state, s_spec)
    batch = {k: sds(v, b_spec[k])
             for k, v in F.train_input_specs(cfg).items()}
    mem = jitted.lower(state, batch).compile().memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    # f32 params: ~0.5 GB server half, ~1.1 GB for four device halves with
    # their aux heads; the rest is the round's activations
    assert mem.argument_size_in_bytes > 1.5e9
    assert peak < HBM_BYTES, f"{peak / 1e9:.2f} GB does not fit one v5e"
