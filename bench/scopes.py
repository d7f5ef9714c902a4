"""Device time per named scope of the round program.

When traced, the program emits one ``host/compile`` span named
``op_table`` whose ``op_scope`` argument maps every instruction name
of the compiled round to its scope (``repro.obs.scopes``: device half,
server half, ring, aggregation), or to None where it has none.  The
device trace's events carry the same
instruction names, so each leaf op (containers left out, as
``DeviceTrace.leaf_ops`` does) is placed by its short name, and an
event whose name the table lacks shows a table of another executable.
A program without the table (one that predates the scopes) gives None.
"""
from __future__ import annotations

import sys

from bench import xtrace

#: The key of the busy time no scope's ops cover.
UNSCOPED = "unscoped"

#: The largest share of the window's leaf-op seconds that may come from
#: instructions the table does not name: more means the table describes
#: another executable than the one traced, and nothing is read.
MAX_UNNAMED_SHARE = 0.01


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def op_table(spans):
    """The ``op_scope`` table of the last ``op_table`` span, or None."""
    table = None
    for lane, _, _, args in spans:
        if lane == "host/compile" and "op_scope" in args:
            table = args["op_scope"]
    return table


def scope_ms(ctx):
    """``{scope: device ms per round}`` in the traced window, plus
    :data:`UNSCOPED`: for each scope the union of its ops' intervals in
    [lo, hi], averaged over the chips, per round; the unscoped time is
    the busy time during which no scoped op runs.  None without a table
    or a round, or when over :data:`MAX_UNNAMED_SHARE` of the leaf-op
    seconds come from instructions the table does not name."""
    table = op_table(ctx.spans)
    if table is None or ctx.rounds < 1 or not ctx.dtrace.ops:
        return None
    seconds = {s: 0.0 for s in table.values() if s is not None}
    seconds[UNSCOPED] = 0.0
    leaf_s = unnamed_s = 0.0
    for chip in ctx.dtrace.ops:
        by_scope: dict = {}
        every = []
        for name, a, b in ctx.dtrace.leaf_ops(chip):
            iv = (max(a, ctx.lo), min(b, ctx.hi))
            if iv[1] <= iv[0]:
                continue
            every.append(iv)
            leaf_s += iv[1] - iv[0]
            if name not in table:
                unnamed_s += iv[1] - iv[0]
            elif table[name] is not None:
                by_scope.setdefault(table[name], []).append(iv)
        for scope, ivs in by_scope.items():
            seconds[scope] += xtrace.total(ivs)
        # the scoped ops are among every op: what they leave is bare
        seconds[UNSCOPED] += xtrace.total(every) - xtrace.total(
            [iv for ivs in by_scope.values() for iv in ivs])
    if unnamed_s > MAX_UNNAMED_SHARE * leaf_s:
        _log(f"op_table: {unnamed_s:.6f} of {leaf_s:.6f} leaf-op seconds "
             "come from instructions it does not name; no scope is read")
        return None
    per = 1e3 / (len(ctx.dtrace.ops) * ctx.rounds)
    return {s: v * per for s, v in seconds.items()}


def read_scope(ctx, scope: str):
    """One scope's device ms per round, or None."""
    ms = scope_ms(ctx)
    return None if ms is None else ms.get(scope)
