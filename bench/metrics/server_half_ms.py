"""Round program, server half: device ms per round of the ops in the
``server_half`` scope (the server layers and LM head on the ring's
batch: forward, backward, gradient accumulation and update), from the
device trace and the program's ``op_table`` span (``bench/scopes.py``)."""
from bench.scopes import read_scope


def read(ctx):
    return read_scope(ctx, "server_half")
