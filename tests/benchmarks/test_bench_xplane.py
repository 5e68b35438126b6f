"""The trace reduction on a small trace recorded on a TPU v5e (three runs
of one jitted program, each under a host annotation ``harness:span_<i>``)."""
import os

import pytest

from benchmarks import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny_trace.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.load(TRACE, span_prefix="harness:")


def test_interval_arithmetic():
    assert xplane.merge([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.union_seconds([(0, 2e9), (1e9, 3e9), (5e9, 6e9)]) == 4.0
    assert xplane.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert xplane.program_name("jit__block_solve(123)") == "jit__block_solve"
    assert xplane.op_name("%fusion.3 = f32[8]{0} fusion(...)") == "fusion.3"


def test_device_plane_programs_and_ops(trace):
    (dev,) = trace.devices
    assert dev.device == 0
    assert [m[0] for m in dev.modules] == ["jit_step"] * 3
    assert len(dev.ops) == 9
    per = trace.program_seconds()
    # three runs of 3.7-3.8 us each
    assert 10e-6 < per["jit_step"] < 13e-6
    top = trace.op_seconds()
    assert top[0][0] == "jit_step/fusion" and top[0][1] > 0.9 * per["jit_step"]


def test_busy_is_the_union_of_op_intervals(trace):
    busy = trace.busy_seconds()
    assert 0 < busy <= trace.program_seconds()["jit_step"]
    first = trace.devices[0].modules[0]
    assert trace.busy_seconds((first[1], first[2])) == pytest.approx(
        busy / 3, rel=0.1)


def test_host_spans_share_the_clock_and_label_the_gaps(trace):
    assert [s[0] for s in trace.spans] == ["span_0", "span_1", "span_2"]
    window = (trace.devices[0].modules[0][1], trace.spans[-1][2])
    gaps = dict(trace.idle_gaps(window))
    # the sleeps between the spans are the longest gaps, and unlabelled
    assert max(gaps, key=gaps.get) == "unspanned"
    assert set(gaps) == {"unspanned", "span_0", "span_1", "span_2"}
    idle = sum(gaps.values())
    assert idle + trace.busy_seconds(window) == pytest.approx(
        (window[1] - window[0]) / 1e9, rel=1e-6)
