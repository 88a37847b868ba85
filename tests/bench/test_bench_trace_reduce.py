"""The trace reduction, on a short trace recorded on the CPU
(`record_cpu_trace.py`) and on hand-made intervals."""
from pathlib import Path

import pytest

import bench_fixture  # noqa: F401
import trace_reduce as T

TRACE = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return T.load(str(TRACE), host_ops=True)


def test_trace_without_a_device_plane_is_refused():
    with pytest.raises(ValueError, match="no /device:TPU"):
        T.load(str(TRACE))


def test_spans_and_window(trace):
    lo, hi = trace.window()
    assert [s[2] for s in trace.spans].count("engine.step") == 3
    assert [s[2] for s in trace.spans].count("wait_arrival") == 3
    assert 0.015 < (hi - lo) * 1e-9 < 1.0
    assert all(lo <= s <= e <= hi for s, e, n in trace.spans if n != "window")


def test_busy_and_idle(trace):
    lo, hi = trace.window()
    per_step = T.span_busy_ns(trace, "engine.step")
    steps = trace.spans_named("engine.step")
    assert len(per_step) == 3
    for b, (s, e, _) in zip(per_step, steps):
        assert 0 < b <= e - s
    busy = T.busy_ns(trace, lo, hi)
    assert busy == pytest.approx(sum(per_step), rel=1e-9)  # all work in steps
    assert 0 < busy < hi - lo
    assert T.program_ns(trace, "jit__lambda", lo, hi) >= busy * 0.99


def test_breakdown_names_ops_and_idle_by_host_span(trace):
    lo, hi = trace.window()
    bd = T.breakdown(trace, lo, hi)
    names = [n for n, _ in bd["device_ops"]]
    assert "dot_general" in names
    idle = dict(bd["idle_gaps"])
    assert idle["wait_arrival"] > 0.01          # the three 5 ms sleeps
    assert sum(idle.values()) == pytest.approx(
        (hi - lo - T.busy_ns(trace, lo, hi)) * 1e-9)


def test_union_and_collectives_on_hand_made_intervals():
    ops = [(0, 10, "fusion.1"), (5, 15, "all-reduce.2"), (20, 30, "dot.3"),
           (40, 50, "all-gather-start.4")]
    tr = T.Trace(ops=[ops, [(0, 30, "fusion.9")]], modules=[[], []],
                 spans=[(0, 60, "window"), (0, 16, "engine.step")])
    assert T.union(ops, 0, 100) == 35
    assert T.union(ops, 7, 25) == 13
    assert T.busy_ns(tr, 0, 60) == (35 + 30) / 2
    assert T.span_busy_ns(tr, "engine.step") == [(15 + 16) / 2]
    assert T.collective_ns(tr, 0, 60) == (10 + 10 + 0) / 2
    bd = T.breakdown(tr, 0, 60)
    assert dict(bd["device_ops"]) == {"fusion": 10e-9, "all-reduce": 10e-9,
                                      "dot": 10e-9, "all-gather-start": 10e-9}
    assert bd["idle_gaps"] == [["other", pytest.approx(25e-9)]]
