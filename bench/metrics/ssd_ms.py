"""Round program, SSD chunked scan: device ms per round of the ops in
the ``ssd`` scope (Mamba-2's chunked scan with its padding, forward and
backward, in the device half and the server half alike; the innermost
scope wins, so these ops are not in ``device_half_ms`` or
``server_half_ms``), from the device trace and the program's ``op_table``
span (``bench/scopes.py``).  None for a program without the scope."""
from bench.scopes import read_scope


def read(ctx):
    return read_scope(ctx, "ssd")
