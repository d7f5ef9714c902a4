"""Round program, outside the named scopes: device ms per round during
which the device is busy and no op of the four scopes runs (the scan's
own slicing and stacking, and ops the compiler leaves without a scope),
from the device trace and the program's ``op_table`` span
(``bench/scopes.py``)."""
from bench.scopes import UNSCOPED, read_scope


def read(ctx):
    return read_scope(ctx, UNSCOPED)
