"""Round program, device half: device ms per round of the ops in the
``device_half`` scope (the G device halves and aux heads under vmap:
forward, backward and SGD), from the device trace and the program's
``op_table`` span (``bench/scopes.py``)."""
from bench.scopes import read_scope


def read(ctx):
    return read_scope(ctx, "device_half")
