"""Named scopes of the round program and the table that places the
compiled round's instructions in them.

``core.fedopt_step.make_train_step`` wraps each part of the round in a
``jax.named_scope`` from :data:`SCOPES`, and ``models.mamba.mamba_apply``
wraps its SSD scan in ``ssd`` inside either half.  Scopes are metadata
only: the optimized HLO keeps them in each instruction's ``op_name``
path, and the profiler's device events carry the same instruction names,
so :func:`op_scopes` on the executable's text is what joins a device
trace to the round's parts.
"""
from __future__ import annotations

import re

__all__ = ["SCOPES", "op_scopes", "scope_of"]

#: The round's parts: the vmapped device half with its aux head, the
#: server half (its loss, gradient and update), the ω ring's
#: read/merge/write, the staleness-weighted aggregation, and the SSD
#: chunked scan of Mamba-2 layers (innermost, so its ops leave the half
#: they run in).
SCOPES = ("device_half", "server_half", "ring", "aggregate", "ssd")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*$", re.M)
_OP_NAME = re.compile(r'\bmetadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_PATH_SPLIT = re.compile(r"[/()]")


def scope_of(op_name: str) -> str | None:
    """The innermost of :data:`SCOPES` in an ``op_name`` path.  Path
    parts may be wrapped by transformations (``transpose(jvp(ring))``)."""
    hit = None
    for part in _PATH_SPLIT.split(op_name):
        if part in SCOPES:
            hit = part
    return hit


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: scope}`` for every instruction of an optimized
    HLO module's text (``jax.stages.Compiled.as_text()``); the scope is
    None for one with no ``op_name``, or with none of :data:`SCOPES` in
    it.  Naming every instruction lets a reader tell an unscoped op from
    one of another executable."""
    out = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        op_name = _OP_NAME.search(m.group(0))
        out[m.group(1)] = scope_of(op_name.group(1)) if op_name else None
    return out
