"""Mesh factories for the pod path.

A mesh is ``(data, model)``, or ``(pod, data, model)``: one FL "device
group" per (pod, data) index, each owning a ``model`` (TP) slice; the
server-side block is trained centrally across the whole mesh (DP over
pod×data, TP over model).  The factories are functions, so importing
this module never touches jax device state.
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
