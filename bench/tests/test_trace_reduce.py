"""The reduction from a profiler trace to busy and idle time, device time
by operation and program, and idle gaps labelled by host spans."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace

FIXTURE = Path(__file__).parent / "fixtures" / "small.xplane.pb"
MS = 1e6  # ns


def synthetic() -> Trace:
    """A 100 ms window: a while loop over [10, 40] whose body ran two
    fusions, a kernel at [60, 70]; idle [0, 10], [40, 60], [70, 100]."""
    ops = [Event("%while.1 = (s32[]) while(%t)", 10 * MS, 40 * MS),
           Event("%fusion.1 = f32[8]{0} fusion(%a)", 10 * MS, 25 * MS),
           Event("%fusion.2 = f32[8]{0} fusion(%b)", 25 * MS, 40 * MS),
           Event('%k.7 = s32[8]{0} custom-call(%c), custom_call_target='
                 '"tpu_custom_call"', 60 * MS, 70 * MS)]
    modules = [Event("jit_step(1)", 10 * MS, 40 * MS),
               Event("jit_step(1)", 60 * MS, 70 * MS),
               Event("jit_late(2)", 95 * MS, 120 * MS)]
    host = [Event("bench.window", 0, 100 * MS),
            Event("bench.ask", 5 * MS, 45 * MS),
            Event("PjitFunction(step)", 4 * MS, 8 * MS),
            Event("bench.sleep", 41 * MS, 59 * MS),
            Event("bench.ask", 55 * MS, 75 * MS)]
    return Trace(ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": modules},
                 host=host)


def test_busy_and_idle_share():
    red = tr.reduce(synthetic())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.040)       # [10,40] + [60,70]
    assert red.idle_share == pytest.approx(0.6)


def test_op_time_by_name_and_program_runs():
    red = tr.reduce(synthetic())
    # the while is a container of the two fusions: not counted again
    assert red.op_s == pytest.approx({"fusion.1 = f32[8]{0}": 0.015,
                                      "fusion.2 = f32[8]{0}": 0.015,
                                      "k.7 = s32[8]{0}": 0.010})
    assert [tr.is_custom_kernel(e) for e in red.ops] == [False, False, True]
    # only executions wholly inside the window count
    assert red.module_runs == {"jit_step": pytest.approx([0.030, 0.010])}


def test_idle_gaps_labelled_by_innermost_host_span():
    red = tr.reduce(synthetic())
    gaps = [(label, round(s * 1e3, 6)) for label, s in red.idle_gaps]
    assert gaps == [("bench.ask / PjitFunction(step)", 10.0),
                    ("bench.sleep / -", 20.0), ("- / -", 30.0)]
    assert red.top_gaps(2) == [["- / -", pytest.approx(0.03)],
                               ["bench.sleep / -", pytest.approx(0.02)]]


def test_span_time_no_device_op_covers():
    red = tr.reduce(synthetic())
    # ask 1 [5,45]: 10 of 40 ms uncovered; ask 2 [55,75]: 10 of 20 ms
    assert tr.uncovered_per_span(red, "bench.ask") == pytest.approx(
        [0.010, 0.010])


def test_base_names():
    assert tr.base_name("fusion.123") == "fusion"
    assert tr.base_name("jit_batched_step(42)") == "jit_batched_step"


def test_recorded_trace():
    """A trace recorded on a TPU (``record_fixture.py``): three asks of one
    program, a 20 ms sleep with the device idle, one ask of another."""
    trace = tr.load(str(FIXTURE))
    red = tr.reduce(trace)
    assert trace.ops, "no device operations in the fixture"
    assert 0 < red.busy_s < red.window_s
    assert red.idle_share == pytest.approx(1 - red.busy_s / red.window_s)
    # op time by name adds up to the device time of the ops themselves
    lo, hi = tr.window_of(trace)
    total = sum(min(e.end, hi) - max(e.start, lo)
                for evs in trace.ops.values() for e in evs
                if e.end > lo and e.start < hi) / 1e9
    assert sum(red.op_s.values()) == pytest.approx(total)
    assert red.busy_s <= total + 1e-12
    # the sleep is an idle gap of at least 20 ms, put on bench.sleep
    sleep = sum(s for label, s in red.idle_gaps
                if label.startswith("bench.sleep"))
    assert sleep >= 0.019
    assert len(red.spans["bench.ask"]) == 4
    assert sum(len(v) for v in red.module_runs.values()) >= 4
