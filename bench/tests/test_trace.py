"""The trace reduction on a small trace recorded on one TPU v5e chip by
``make_trace_fixture.py``: five ``step`` spans, each one jitted program
waited on, between ``wait_arrival`` sleeps of 20 ms."""
from __future__ import annotations

import pytest

from conftest import BENCH
from harness import trace

FIXTURE = BENCH / "tests" / "data" / "steps.xplane.pb"
# Read from the fixture once, by hand, when it was recorded (see
# make_trace_fixture.py's output in PERF.md): the numbers the reduction
# must keep giving.
EXPECT = {"window_s": 0.109084234, "busy_s": 0.000904304,
          "step_device_s": 0.000904304, "step_host_s": 0.004166736}


@pytest.fixture(scope="module")
def reduced():
    tr = trace.load(str(FIXTURE))
    return tr, trace.reduce(tr)


def test_union_and_cover():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert merged == [(0, 3), (5, 9), (10, 11)]
    assert trace.covered(merged, 2, 6) == 2
    assert trace.covered(merged, -5, 20) == 8


def test_fixture_reduces_to_its_numbers(reduced):
    tr, red = reduced
    assert len(red.steps) == 5
    assert len(tr.spans("wait_arrival")) == 5
    got = {"window_s": red.window_s, "busy_s": red.mean_busy_s(),
           "step_device_s": red.step_device_s(),
           "step_host_s": red.step_host_s()}
    for key, want in EXPECT.items():
        assert got[key] == pytest.approx(want, rel=1e-9), key


def test_fixture_busy_lies_inside_the_steps(reduced):
    _, red = reduced
    # the program runs only inside the step spans, and the sleeps leave the
    # device idle for at least 5 x 20 ms
    assert red.step_device_s() == pytest.approx(red.mean_busy_s(), rel=1e-6)
    assert red.window_s - red.mean_busy_s() >= 0.1
    assert 0 < red.step_host_s()


def test_fixture_breakdown(reduced):
    tr, red = reduced
    b = trace.breakdown(tr, red)
    assert 0 < len(b["device_ops"]) <= 10
    assert len(b["idle_gaps"]) <= 10
    assert {name for name, _ in b["device_ops"]} == {
        "fusion", "convolution_tanh_fusion", "copy-start", "copy-done"}
    assert sum(s for _, s in b["device_ops"]) <= red.mean_busy_s() * 1.000001
    labels = {name for name, _ in b["idle_gaps"][:5]}
    assert "wait_arrival" in labels
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)


def test_a_trace_that_lost_its_device_events_is_refused(reduced):
    """The profiler drops device events once its buffer is full, while the
    host's spans go on: the reduction refuses such a trace rather than
    read the missing operations as idle time."""
    tr, _ = reduced
    third = sorted(a for a, _ in tr.spans("step"))[2]
    cut = trace.Trace(ops={d: [e for e in evs if e[2] < third]
                           for d, evs in tr.ops.items()}, host=tr.host)
    with pytest.raises(ValueError, match="dropped"):
        trace.reduce(cut)
