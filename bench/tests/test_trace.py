"""bench/trace.py on a small trace recorded on one TPU v5e.

    python3 -m pytest bench/tests/test_trace.py

``data/small.xplane.pb``: inside a ``window`` span, three rounds of a
``step`` span around one jitted program and an ``admit`` span around
another, with host sleeps between.  The expected numbers below were read
off the trace's events by hand (device timeline, nanoseconds): the
window runs from 40,791,819 for 22,831,913; the device's operations that
start in it cover 44,751; the first program's first run starts before
the window and is not counted.
"""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
NS = 1e-9


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(DATA))


def test_window_and_busy_union(summary):
    assert summary.devices == [0]
    assert summary.window_s == pytest.approx(22_831_913 * NS)
    # 4,851 + (13 + 3,163 + 11,856) + 4,895 + (13 + 3,280 + 11,856) + 4,824
    assert summary.busy_s[0] == pytest.approx(44_751 * NS)
    idle = 1 - summary.busy_mean_s / summary.window_s
    assert idle == pytest.approx(1 - 44_751 / 22_831_913)


def test_program_time(summary):
    # both lambdas are jit__lambda; five of the six runs start in the window
    s, n = summary.program_time(["jit__lambda"])
    assert n == 5
    assert s == pytest.approx((4_855 + 15_039 + 4_898 + 15_155 + 4_827) * NS)


def test_operation_time_by_name(summary):
    assert summary.op_time([r"^fusion$"]) == (pytest.approx(23_712 * NS), 2)
    assert summary.op_time([r"add_reduce_fusion"]) == (
        pytest.approx((4_851 + 4_895 + 4_824) * NS), 3)
    assert summary.op_time([r"^copy-done$"])[1] == 2


def test_idle_gaps_labelled_by_host_span(summary):
    top = [(round(s / NS), label) for s, label, _ in summary.gaps[:6]]
    assert top == [(5_520_334, "admit"), (4_417_315, "admit"),
                   (4_334_554, "admit"), (3_305_639, "step"),
                   (3_171_426, "step"), (2_037_888, "step")]


def test_breakdown_has_at_most_ten_of_each(summary):
    b = trace.breakdown(summary)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "fusion"
    assert b["idle_gaps"][0] == ["admit (device 0)", 5_520_334 * NS]
