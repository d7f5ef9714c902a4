"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see the
real single CPU device; a test that needs more starts a subprocess that
sets its own device count."""
import os

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _protocol_sanitizer(request):
    """REPRO_SANITIZE=1 runs the whole suite under the protocol sanitizer
    (the CI lane does): any control-plane invariant violation fails the
    offending test at the event that broke it.  Tests that deliberately
    violate invariants (the mutation tests) opt out with
    ``@pytest.mark.no_sanitize``."""
    if os.environ.get("REPRO_SANITIZE") != "1" or \
            request.node.get_closest_marker("no_sanitize") or \
            request.node.module.__name__ == "test_sanitize":
        yield
        return
    from repro.analysis.sanitize import sanitized
    with sanitized():
        yield


@pytest.fixture(scope="session")
def rng():
    import jax
    return jax.random.PRNGKey(0)


@pytest.fixture()
def np_rng():
    return np.random.default_rng(0)
