"""Per-scope device time: the readers on a synthetic device trace and
the program's ``op_table`` span."""
import pytest

from bench import scopes, xtrace
from bench.harness import TraceContext, _load_reader

TABLE = {"fusion.1": "device_half", "fusion.2": "server_half",
         "dynamic-update-slice.3": "ring", "all-reduce.4": "aggregate",
         "fusion.5": "server_half", "copy-done.7": None, "while.9": None}
READERS = {"device_half_ms": "device_half", "server_half_ms": "server_half",
           "ring_ms": "ring", "aggregate_ms": "aggregate",
           "unscoped_ms": "unscoped"}


def _trace():
    """Two rounds on two chips in [0, 10]: a ``while`` over each round,
    scoped ops, one op outside every scope, one op half out of the
    window."""
    chip0 = [("while.9", 0.0, 10.0),
             ("fusion.1", 0.0, 1.0), ("fusion.2", 1.0, 3.0),
             ("fusion.5", 2.5, 3.5),            # overlaps fusion.2
             ("dynamic-update-slice.3", 3.5, 4.0),
             ("copy-done.7", 4.0, 4.5),         # no scope
             ("all-reduce.4", 4.5, 5.0),
             ("fusion.1", 5.0, 6.0), ("fusion.2", 6.0, 9.0),
             ("fusion.1", 9.5, 11.0)]           # half past the window
    chip1 = [("while.9", 0.0, 10.0), ("fusion.1", 0.0, 2.0),
             ("fusion.2", 2.0, 6.0), ("copy-done.7", 6.0, 7.0)]
    return xtrace.DeviceTrace({"0": chip0, "1": chip1})


def _ctx(spans, rounds=2):
    return TraceContext(cell=None, dtrace=_trace(), spans=spans, lo=0.0,
                        hi=10.0, rounds=rounds, round_ids=range(rounds),
                        tokens_per_s=0.0, kind="test")


def _with_table(table=TABLE):
    return [("host/plan", 0.0, 0.1, {"round": 0}),
            ("host/compile", 0.1, 0.2, {"round": 0, "op_scope": table})]


def test_per_scope_unions_per_round():
    ms = scopes.scope_ms(_ctx(_with_table()))
    # chip 0: 1 + 1 + 0.5 (fusion.1); chip 1: 2; mean 2.25 s over 2 rounds
    assert ms["device_half"] == pytest.approx(1e3 * (2.5 + 2) / 2 / 2)
    # chip 0: union of (1, 3.5) and (6, 9) = 5.5; chip 1: 4
    assert ms["server_half"] == pytest.approx(1e3 * (5.5 + 4) / 2 / 2)
    assert ms["ring"] == pytest.approx(1e3 * 0.5 / 2 / 2)
    assert ms["aggregate"] == pytest.approx(1e3 * 0.5 / 2 / 2)
    assert ms["unscoped"] == pytest.approx(1e3 * (0.5 + 1.0) / 2 / 2)


def test_scopes_and_unscoped_sum_to_busy_time():
    ctx = _ctx(_with_table())
    ms = scopes.scope_ms(ctx)
    assert sum(ms.values()) == pytest.approx(1e3 * ctx.busy_s / ctx.rounds)


@pytest.mark.parametrize("reader, scope", list(READERS.items()))
def test_readers_read_their_scope(reader, scope):
    ctx = _ctx(_with_table())
    assert _load_reader(reader)(ctx) == scopes.scope_ms(ctx)[scope]


@pytest.mark.parametrize("reader", list(READERS))
def test_readers_give_none_without_a_table(reader):
    """A program that publishes no table (one that predates the
    scopes) reads as nothing, and nothing raises."""
    spans = [("host/plan", 0.0, 0.1, {"round": 0}),
             ("host/compile", 0.1, 0.2, {"round": 0})]
    assert _load_reader(reader)(_ctx(spans)) is None
    assert _load_reader(reader)(_ctx(_with_table(), rounds=0)) is None


def test_a_scope_missing_from_the_table_reads_none():
    table = {k: (None if v == "ring" else v) for k, v in TABLE.items()}
    ctx = _ctx(_with_table(table))
    assert _load_reader("ring_ms")(ctx) is None
    assert _load_reader("device_half_ms")(ctx) is not None


def test_a_table_of_another_executable_reads_none():
    """Leaf-op seconds from instructions the table does not name, over
    1% of the window's, mean the table describes another executable:
    nothing is read, not an inflated ``unscoped_ms``."""
    table = {k: v for k, v in TABLE.items() if k != "copy-done.7"}
    ctx = _ctx(_with_table(table))
    # copy-done.7: 1.5 of the two chips' 17 leaf-op seconds
    for reader in READERS:
        assert _load_reader(reader)(ctx) is None


def test_unnamed_ops_under_the_share_are_read_as_unscoped(monkeypatch):
    table = {k: v for k, v in TABLE.items() if k != "copy-done.7"}
    monkeypatch.setattr(scopes, "MAX_UNNAMED_SHARE", 0.1)
    ms = scopes.scope_ms(_ctx(_with_table(table)))
    assert ms == scopes.scope_ms(_ctx(_with_table()))
