"""The trace -> metrics reduction: interval arithmetic on hand-made
events, and the reading of a small recorded `.xplane.pb`."""

from pathlib import Path

import pytest

from lib import intervals, trace_reduce

DATA = Path(__file__).parent / "data"


def test_union_gaps_and_attribution():
    starts, ends = [0, 5, 20, 22], [10, 8, 30, 25]
    assert intervals.merge(starts, ends) == [(0.0, 10.0), (20.0, 30.0)]
    assert intervals.gaps([(0.0, 10.0), (20.0, 30.0)], 0.0, 40.0) == [
        (10.0, 20.0), (30.0, 40.0)]
    names = ["outer", "inner", "elsewhere"]
    assert intervals.attribute((10.0, 20.0), names, [0, 9, 50],
                               [100, 21, 60]) == "host__inner"
    assert intervals.attribute((10.0, 20.0), names[:1], [30], [40]) == (
        "host__unattributed")
    assert intervals.safe_name("%fusion.4 = f32[8,16]{1,0}") == (
        "fusion.4_f32_8_16_1_0")


def test_reduce_busy_window_ops_and_crop():
    s = 1_000_000_000
    device = {"name": "/device:TPU:0", "ops": (
        ["scan", "scan", "merge"],
        [0 * s, 4 * s, 5 * s], [2 * s, 6 * s, 6 * s])}
    host = (["wait", "observe"], [2 * s, 6 * s], [4 * s, 8 * s])
    whole = trace_reduce.reduce([device], host)
    assert whole["window_s"] == pytest.approx(8.0)
    assert whole["busy_s"] == pytest.approx(4.0)       # 0-2 and 4-6
    assert whole["ops"][0][:2] == ["scan", 2]
    assert whole["ops"][0][2] == pytest.approx(4.0)
    assert whole["idle_gaps"][0] == ["host__wait", pytest.approx(2.0)]
    cut = trace_reduce.reduce([device], host, marks=[1 * s, 5 * s])
    assert cut["window_s"] == pytest.approx(4.0)
    assert cut["busy_s"] == pytest.approx(2.0)         # 1-2 and 4-5
    with pytest.raises(SystemExit):
        trace_reduce.reduce([device], host, marks=[1 * s])


def test_reads_a_recorded_xplane():
    """A trace recorded on the host (three jitted matmuls): no device
    plane there, but the file's planes, lines and events are read and the
    python tracer's spans come out as host events."""
    devices, host, marks = trace_reduce.read_planes(
        DATA / "small.xplane.pb", crop_event=r"profiler\.py:\d+ trace$")
    assert devices == []
    assert len(host[0]) == len(host[1]) == len(host[2]) > 0
    assert all(e >= s for s, e in zip(host[1], host[2]))
    assert len(marks) >= 1
    reduced = trace_reduce.reduce(devices, host)
    assert reduced["busy_s"] == 0.0 and reduced["window_s"] > 0
