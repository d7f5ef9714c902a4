"""Round program, aggregation: device ms per round of the ops in the
``aggregate`` scope (the staleness-weighted average over the groups and
its masked broadcast), from the device trace and the program's
``op_table`` span (``bench/scopes.py``)."""
from bench.scopes import read_scope


def read(ctx):
    return read_scope(ctx, "aggregate")
