"""``ssd_ms``: the SSD scan's device time, on the synthetic trace of
``test_scopes`` with one scope renamed, and nothing where the program
has no ``ssd`` scope (smollm, or a program that predates it)."""
import pytest

from bench import scopes
from bench.harness import _load_reader
from bench.tests.test_scopes import TABLE, _ctx, _with_table


def _ssd_table():
    """fusion.5 (server half, 2.5-3.5 s on chip 0) is the scan's op."""
    return {**TABLE, "fusion.5": "ssd"}


def test_ssd_ms_reads_the_ssd_scope():
    ctx = _ctx(_with_table(_ssd_table()))
    ms = scopes.scope_ms(ctx)
    assert _load_reader("ssd_ms")(ctx) == ms["ssd"]
    assert ms["ssd"] == pytest.approx(1e3 * 1.0 / 2 / 2)
    # its time leaves the half it ran in
    assert ms["server_half"] == pytest.approx(1e3 * (5.0 + 4) / 2 / 2)


def test_ssd_ms_reads_none_without_the_scope():
    assert _load_reader("ssd_ms")(_ctx(_with_table())) is None
    spans = [("host/compile", 0.1, 0.2, {"round": 0})]
    assert _load_reader("ssd_ms")(_ctx(spans)) is None
