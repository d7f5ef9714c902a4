"""The trace reader on small traces recorded on one TPU v5e lite: three
steps of a jitted matmul, each inside a ``bench/step`` annotation that
carries the host clock, after the clock marker; and a jitted loop
(``record_trace.py``)."""
from pathlib import Path

import pytest

from bench import xtrace

TRACE = Path(__file__).resolve().parent / "data" / "trace_small_v5e.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    d = xtrace.load_device_trace(str(TRACE))
    steps = []
    for plane in ProfileData.from_file(str(TRACE)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "bench/step":
                    steps.append((ev.start_ns, ev.duration_ns,
                                  dict(ev.stats)["t"]))
    return d, steps


def test_device_ops_read_with_short_names(recorded):
    d, _ = recorded
    assert list(d.ops) == ["0"]
    names = [n for n, _, _ in d.ops["0"]]
    assert names.count("fusion") == 3 and "copy-start" in names


def test_host_annotations_land_on_their_clock(recorded):
    """The marker maps each annotation's trace time onto the host clock
    value it was stamped with, to within 20 microseconds."""
    d, steps = recorded
    assert len(steps) == 3
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(TRACE)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == xtrace.MARKER:
                    offset = dict(ev.stats)["t"] - ev.start_ns * 1e-9
    for start_ns, _, t in steps:
        assert abs(start_ns * 1e-9 + offset - t) < 20e-6


def test_each_step_runs_near_its_annotation(recorded):
    """Device and host timelines agree to about a millisecond: every
    step's fusion starts within 2 ms of the host annotation around its
    dispatch."""
    d, steps = recorded
    fusions = sorted(a for n, a, _ in d.ops["0"] if n == "fusion")
    for (_, _, t), a in zip(steps, fusions):
        assert abs(a - t) < 2e-3


def test_busy_and_idle_of_the_recording(recorded):
    d, steps = recorded
    lo, hi = steps[0][2], steps[-1][2] + steps[-1][1] * 1e-9
    busy = d.busy("0", lo - 2e-3, hi)
    assert 0 < busy < hi - lo + 2e-3
    gaps = xtrace.gaps([(a, b) for _, a, b in d.ops["0"]], lo - 2e-3, hi)
    assert xtrace.total(gaps) + busy == pytest.approx(hi - lo + 2e-3)
    assert d.exposed_collective("0", lo - 2e-3, hi) == 0.0
    assert d.op_seconds(lo - 2e-3, hi)["fusion"] == pytest.approx(
        sum(b - a for n, a, b in d.ops["0"] if n == "fusion"))


LOOP = Path(__file__).resolve().parent / "data" / "trace_loop_v5e.xplane.pb"


@pytest.fixture(scope="module")
def loop():
    """``record_trace.py``'s loop: one ``while`` of 4 steps, each a matmul
    and a 5 ms host callback, on one TPU v5e lite."""
    d = xtrace.load_device_trace(str(LOOP))
    (lo, hi), = [(a, b) for n, a, b in d.ops["0"] if n == "while"]
    return d, lo, hi


def test_the_loop_is_read_from_the_ops_inside_its_while(loop):
    d, lo, hi = loop
    inside = [n for n, a, b in d.leaf_ops("0") if lo <= a and b <= hi]
    assert sum(xtrace.stem(n) == "fusion" for n in inside) == 4
    assert "while" not in {n for n, _, _ in d.leaf_ops("0")}
    # the container alone would cover the whole loop; its ops leave the
    # gaps between them bare
    whole = xtrace.total(xtrace.clip([(a, b) for _, a, b in d.ops["0"]],
                                     lo, hi))
    assert whole == pytest.approx(hi - lo)
    gaps = xtrace.gaps(d.intervals("0"), lo, hi)
    assert len(gaps) >= 8
    assert d.busy("0", lo, hi) + xtrace.total(gaps) == pytest.approx(hi - lo)


def test_a_host_callback_inside_the_loop_reads_as_device_time(loop):
    """The device waits for the callback's answer inside an op of its
    own (``io_callback``), so a host stall in the step shows in the
    breakdown's ops, not as idle time."""
    d, lo, hi = loop
    ops = d.op_seconds(lo, hi)
    assert ops["io_callback"] >= 4 * 0.005
    assert d.busy("0", lo, hi) > 0.99 * (hi - lo)
