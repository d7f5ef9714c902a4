"""Analysis layer: the protocol sanitizer and the lint CLI.

Re-exports are lazy: the ``sanitize`` instrumentation hooks live on hot
control-plane paths and the ``lint`` CLI must start fast — importing
this package must stay cheap (stdlib only) so ``from repro.analysis
import sanitize`` inside ``repro.core`` neither costs a jax import nor
creates a cycle.
"""
from __future__ import annotations

__all__ = ["sanitize", "lint"]


def __getattr__(name: str):
    import importlib
    if name in __all__:
        return importlib.import_module(f"repro.analysis.{name}")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
