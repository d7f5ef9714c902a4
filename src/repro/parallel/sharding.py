"""Sharding rules: map model parameter/activation pytrees to PartitionSpecs.

Scheme (GSPMD annotations; MoE experts additionally use explicit shard_map
expert-parallelism — see models/mlp.moe_apply_grouped):

  * ``data`` axes (pod × data): batch dim of activations; FSDP dim of
    parameters (ZeRO-3 style: the largest non-TP dim of each weight).
  * ``model`` axis: tensor-parallel dim — attention heads (qkv out dim,
    o_proj in dim), MLP hidden (d_ff), MoE expert axis, vocab dim of
    embedding/lm_head, mamba inner channels.

Optimizer state inherits parameter specs (mu/nu shard identically), which
is exactly ZeRO: optimizer memory scales 1/(dp·tp).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import PartitionSpec as P

def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: the MoE psum
    pattern trips the checker."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclass(frozen=True)
class Parallelism:
    """Runtime parallelism descriptor threaded through model code."""
    mesh: object                      # jax.sharding.Mesh
    dp_axes: tuple = ("data",)        # axes carrying the batch (may incl. "pod")
    tp_axis: str = "model"
    ep: bool = True                   # expert-parallel MoE via shard_map
    fsdp: bool = True                 # shard params over dp axes too
    # --- activation sharding constraints (Megatron TP/SP layout) ---
    # act_batch: mesh axes carrying the activation batch dim (None when the
    #   dp axes are already consumed, e.g. under vmap over FL device groups).
    # seq_shard: shard the residual-stream sequence dim over ``model``
    #   between blocks (SP) — saved scan carries shard too.
    # With ``constraints`` on, per-head / ffn-hidden intermediates are also
    # constrained over ``model`` so weight gradients stay TP-sharded in
    # backward.
    act_batch: tuple | None = None
    seq_shard: bool = False
    constraints: bool = False         # master switch

    @property
    def dp_size(self) -> int:
        out = 1
        for a in self.dp_axes:
            out *= self.mesh.shape[a]
        return out

    def _constrain(self, x, spec: P):
        spec = _validate(spec, x.shape, self)
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    def resid(self, h):
        """(B, S, D) residual stream between blocks."""
        if not self.constraints:
            return h
        return self._constrain(
            h, P(self.act_batch, self.tp_axis if self.seq_shard else None,
                 None))

    def ffn_hidden(self, h):
        """(B, S, F) MLP hidden — keeps dW_ffn TP-sharded in backward."""
        if not self.constraints:
            return h
        return self._constrain(h, P(self.act_batch, None, self.tp_axis))

    def heads(self, x):
        """(B, S, H, hd) per-head tensors — keeps dW_qkvo TP-sharded."""
        if not self.constraints:
            return x
        return self._constrain(x, P(self.act_batch, None, self.tp_axis, None))


# ---------------------------------------------------------------------------
# Parameter partition specs by leaf path
# ---------------------------------------------------------------------------

def _param_spec(path: str, shape, par: Parallelism) -> P:
    """Assign a PartitionSpec from the leaf's path and rank."""
    tp = par.tp_axis
    dp = tuple(par.dp_axes) if par.fsdp else None
    rank = len(shape)

    def fsdp_or_none(axis_idx, spec_list):
        """Put dp on axis_idx if divisible and fsdp on."""
        if dp is not None:
            spec_list[axis_idx] = dp
        return P(*spec_list)

    # --- embeddings / heads: shard vocab over tp, d_model over dp ---
    if "embed" in path or "lm_head" in path or "head_out" in path:
        if rank == 2:
            v_axis = 0 if shape[0] >= shape[1] else 1
            spec = [None, None]
            spec[v_axis] = tp
            return fsdp_or_none(1 - v_axis, spec)
        return P()
    # --- MoE experts (we_*): E over tp, FSDP over the input dim ---
    if "we_gate" in path or "we_up" in path or "we_down" in path:
        if rank == 4:   # stacked (n_periods, E, din, dout)
            return P(None, tp, dp, None) if dp else P(None, tp, None, None)
        # (E, din, dout)
        return P(tp, dp, None) if dp else P(tp, None, None)
    # --- dense MLP: tp on the hidden (d_ff) dim ---
    if "w_gate" in path or "w_up" in path or "w_down" in path:
        hidden_axis = rank - 1 if "w_down" not in path else rank - 2
        spec = [None] * rank
        spec[hidden_axis] = tp
        other = rank - 2 if hidden_axis == rank - 1 else rank - 1
        return fsdp_or_none(other, spec)
    if "router" in path:
        return P()
    # --- attention projections ---
    if "wq" in path or "wk" in path or "wv" in path:
        spec = [None] * rank
        spec[rank - 1] = tp            # heads dim
        return fsdp_or_none(rank - 2, spec)
    if "wo" in path:
        spec = [None] * rank
        spec[rank - 2] = tp            # heads dim (input)
        return fsdp_or_none(rank - 1, spec)
    # --- mamba ---
    if "in_proj" in path or "out_proj" in path:
        spec = [None] * rank
        inner_axis = rank - 1 if "in_proj" in path else rank - 2
        spec[inner_axis] = tp
        return fsdp_or_none(rank - 1 if inner_axis != rank - 1 else rank - 2, spec)
    if "conv_w" in path or "conv_b" in path or "A_log" in path or "D" in path \
            or "dt_bias" in path:
        return P(*([None] * rank))
    # --- norms, scalars, aux heads ---
    return P(*([None] * rank))


def param_specs(params, par: Parallelism):
    """Pytree of PartitionSpecs matching ``params``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        # divisibility guard: drop axes that don't divide
        spec = _param_spec(key, leaf.shape, par)
        spec = _validate(spec, leaf.shape, par)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _validate(spec: P, shape, par: Parallelism) -> P:
    """Remove spec entries that don't divide the dimension."""
    out = []
    for i, axis in enumerate(spec):
        if axis is None or i >= len(shape):
            out.append(None)
            continue
        if shape[i] % _axis_size(par.mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)
