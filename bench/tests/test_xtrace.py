"""The reduction from a profiler trace to intervals and shares."""
import pytest

from bench import xtrace


@pytest.mark.parametrize("intervals, want", [
    ([], []),
    ([(0, 1), (2, 3)], [(0, 1), (2, 3)]),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(1, 3), (0, 2), (2.5, 4)], [(0, 4)]),
    ([(0, 1), (1, 2)], [(0, 2)]),
    ([(0, 5), (1, 2)], [(0, 5)]),
    ([(3, 3), (0, 1)], [(0, 1)]),
])
def test_union(intervals, want):
    assert xtrace.union(intervals) == want


def test_gaps_and_idle_share():
    busy = [(1, 2), (1.5, 3), (5, 6)]
    assert xtrace.gaps(busy, 0, 7) == [(0, 1), (3, 5), (6, 7)]
    assert xtrace.total(xtrace.clip(busy, 0, 7)) == 3
    assert xtrace.gaps(busy, 1.5, 2.5) == []


def test_collective_exposure():
    d = xtrace.DeviceTrace({"0": [("fusion.1", 0.0, 2.0),
                                  ("all-reduce.3", 1.0, 4.0),
                                  ("fusion.2", 3.5, 5.0),
                                  ("all-gather-start", 6.0, 7.0)]})
    # all-reduce bare over (2, 3.5); all-gather bare over (6, 7)
    assert d.exposed_collective("0", 0, 10) == pytest.approx(2.5)
    assert d.exposed_collective("0", 0, 3) == pytest.approx(1.0)
    assert xtrace.is_collective("reduce-scatter.5")
    assert not xtrace.is_collective("fusion.all-reduce")


def test_label_gaps_by_host_span():
    spans = [("host/plan", 0.0, 1.0), ("host/build", 1.0, 1.5),
             ("host/drain", 2.0, 4.0)]
    got = xtrace.label_gaps([(0.2, 1.4), (2.5, 3.0), (5.0, 6.0)], spans)
    assert got == [("host/plan", pytest.approx(1.2)),
                   ("host/drain", pytest.approx(0.5)),
                   ("no host span", pytest.approx(1.0))]


@pytest.mark.parametrize("name, want", [
    ("while.7", True), ("while.7.clone", True), ("conditional", True),
    ("call.2", True), ("fusion.3", False), ("while_fusion.1", False),
    ("all-reduce.1", False)])
def test_is_container(name, want):
    assert xtrace.is_container(name) is want


def test_a_while_does_not_cover_the_idle_time_inside_it():
    """A round is one ``while`` over its micro-iterations: its event
    spans the whole loop, the stalls between the ops inside too.  Busy
    time, idle gaps and collective exposure are read from the ops it
    contains."""
    d = xtrace.DeviceTrace({"0": [("while.1", 0.0, 10.0),
                                  ("fusion.1", 0.0, 3.0),
                                  ("all-reduce.2", 3.0, 4.0),
                                  ("fusion.2", 6.0, 9.5)]})
    assert d.busy("0", 0, 10) == pytest.approx(7.5)
    assert xtrace.gaps(d.intervals("0"), 0, 10) == [(4.0, 6.0), (9.5, 10)]
    assert d.exposed_collective("0", 0, 10) == pytest.approx(1.0)
    assert d.op_seconds(0, 10) == {"fusion": pytest.approx(6.5),
                                   "all-reduce": pytest.approx(1.0)}
