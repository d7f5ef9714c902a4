"""Round program, activation ring: device ms per round of the ops in the
``ring`` scope (the activation batch's assembly and the ω ring's read,
merge and write), from the device trace and the program's ``op_table``
span (``bench/scopes.py``)."""
from bench.scopes import read_scope


def read(ctx):
    return read_scope(ctx, "ring")
