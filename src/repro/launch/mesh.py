"""Production mesh factories.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before *any* jax
import, and everything else must see the real single CPU device.

Mesh layout (TPU v5e pods):
  single-pod : (data=16, model=16)             = 256 chips
  multi-pod  : (pod=2, data=16, model=16)      = 512 chips

FedOptima mapping: one FL "device group" per (pod, data) index — 16 groups
single-pod, 32 groups multi-pod — each group owning a 16-chip ``model``
(TP) slice; the server-side block is trained centrally across the whole
mesh (DP over pod×data, TP over model).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``.  The train step is
    written as GSPMD annotations (``parallel/sharding.py``); JAX's default
    ``Explicit`` axes would type-check shardings through the vmap over FL
    groups and refuse it."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *, pod: int = 0):
    """Small mesh over the first devices: ``(data, model)``, or
    ``(pod, data, model)`` with ``pod``.  On the CPU more than one device
    needs ``--xla_force_host_platform_device_count``."""
    if pod:
        return _auto_mesh((pod, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))


def dp_axes_of(mesh) -> tuple:
    """The data-parallel axes of a mesh: everything except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_groups_of(mesh) -> int:
    """Number of FL device groups hosted on the mesh (= dp size)."""
    out = 1
    for a in dp_axes_of(mesh):
        out *= mesh.shape[a]
    return out
