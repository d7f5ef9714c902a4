"""Sharding rules: PartitionSpecs are always valid for their leaves.
Spec assignment only reads mesh.shape, so a stand-in mesh suffices: the
(16, 16) layouts below need no devices."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.models import transformer as tfm
from repro.parallel.sharding import Parallelism, param_specs


class FakeMesh:
    def __init__(self, data=16, model=16, pod=None):
        self.shape = {"data": data, "model": model}
        self.axis_names = ("data", "model")
        if pod:
            self.shape = {"pod": pod, **self.shape}
            self.axis_names = ("pod",) + self.axis_names


def _axis_size(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _check_specs(tree, specs, mesh):
    flat_p = jax.tree.leaves(tree)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        for dim, axis in zip(leaf.shape, tuple(spec) + (None,) * 8):
            size = _axis_size(mesh, axis)
            assert dim % size == 0, \
                f"shape {leaf.shape} not divisible by {spec}"


@pytest.mark.parametrize("name", sorted(registry.ARCHS))
def test_param_specs_divisible_smoke(name, rng):
    mesh = FakeMesh(2, 2)
    par = Parallelism(mesh=mesh, dp_axes=("data",))
    cfg = registry.smoke_config(name)
    params = jax.eval_shape(lambda: tfm.init_params(rng, cfg))
    _check_specs(params, param_specs(params, par), mesh)


@pytest.mark.parametrize("name", sorted(registry.ARCHS))
def test_param_specs_divisible_full_production(name):
    """FULL configs on the (16,16) production layout (eval_shape only)."""
    mesh = FakeMesh(16, 16)
    par = Parallelism(mesh=mesh, dp_axes=("data",))
    cfg = registry.get(name)
    params = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    _check_specs(params, param_specs(params, par), mesh)


@pytest.mark.parametrize("name", ["smollm-135m", "qwen3-moe-235b-a22b",
                                  "whisper-tiny", "mamba2-780m"])
@pytest.mark.parametrize("pod", [None, 2])
def test_train_state_specs_divisible(name, pod):
    mesh = FakeMesh(4, 2, pod=pod)
    dp = ("pod", "data") if pod else ("data",)
    par = Parallelism(mesh=mesh, dp_axes=dp)
    arch = registry.smoke_config(name)
    G = 8 if pod else 4
    cfg = F.FedStepConfig(arch=arch, l_split=1, n_groups=G, seq_len=16,
                          per_group_batch=2, H=2)
    state = F.abstract_train_state(cfg)
    _check_specs(state, F.state_specs(state, par), mesh)


def test_full_train_state_specs_production_mesh():
    """A full arch's whole train state on the (16,16) layout."""
    mesh = FakeMesh(16, 16)
    par = Parallelism(mesh=mesh, dp_axes=("data",))
    arch = registry.get("qwen3-32b")
    cfg = F.FedStepConfig(arch=arch, l_split=F.default_l_split(arch),
                          n_groups=16, seq_len=4096, per_group_batch=16,
                          H=8, param_dtype=jnp.bfloat16)
    state = F.abstract_train_state(cfg)
    _check_specs(state, F.state_specs(state, par), mesh)


def test_tp_actually_assigned_to_big_leaves():
    """The rules must not silently replicate everything."""
    mesh = FakeMesh(2, 2)
    par = Parallelism(mesh=mesh, dp_axes=("data",))
    cfg = registry.smoke_config("qwen3-32b")
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    specs = param_specs(params, par)
    flat = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    n_tp = sum(1 for s in flat if any(a == "model" for a in s))
    n_dp = sum(1 for s in flat
               if any(a == ("data",) or a == "data" for a in s))
    assert n_tp >= 5, "attention/MLP projections must be TP-sharded"
    assert n_dp >= 3, "FSDP must shard some weight dims over data"


def test_validate_drops_nondivisible_axes():
    from repro.parallel.sharding import _validate
    mesh = FakeMesh(16, 16)
    par = Parallelism(mesh=mesh, dp_axes=("data",))
    out = _validate(P("model", None), (9, 4), par)     # 9 % 16 != 0
    assert out == P(None, None)
    out = _validate(P("model", "data"), (32, 64), par)
    assert out == P("model", "data")
