"""The trace reduction: busy time as the union of device intervals, the
idle share, program seconds by name and the idle gaps' host labels."""
import pytest

import devtrace
from devtrace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _events():
    ms = 1e6
    return [
        Event(HOST, "main", "bench.window", 0.0, 100 * ms),
        # two programs, the second overlapping the first's tail
        Event(DEV, "XLA Modules", "jit_matmul(12)", 10 * ms, 20 * ms),
        Event(DEV, "XLA Modules", "jit_run(3)", 50 * ms, 10 * ms),
        Event(DEV, "XLA Ops", "dot.1", 10 * ms, 15 * ms),
        Event(DEV, "XLA Ops", "fusion.2", 20 * ms, 10 * ms),
        Event(DEV, "XLA Ops", "fusion.3", 50 * ms, 10 * ms),
        # an op that starts before the window: only its inside counts
        Event(DEV, "XLA Ops", "copy.4", -5 * ms, 6 * ms),
        Event(HOST, "python", "PjitFunction(matmul)", 5 * ms, 4 * ms),
        Event(HOST, "python", "bench.wait", 30 * ms, 70 * ms),
        Event(HOST, "python", "PjitFunction(_open_at_zero)", 32 * ms,
              16 * ms),
    ]


def test_busy_idle_and_programs():
    s = devtrace.summarize(_events(), (0.0, 100e6))
    assert s.window_s == pytest.approx(0.1)
    # union: [0,1] + [10,30] + [50,60] ms
    assert s.busy_s == pytest.approx(0.031)
    assert s.idle_share == pytest.approx(0.69)
    assert s.program_s == pytest.approx({"jit_matmul": 0.02,
                                         "jit_run": 0.01})
    assert s.seconds_of(["jit_matmul", "jit_ss_matmul"]) == \
        pytest.approx(0.02)


def test_idle_gaps_are_longest_first_with_what_the_host_did():
    s = devtrace.summarize(_events(), (0.0, 100e6))
    gaps = s.idle_gaps
    assert [round(g, 4) for _, g in gaps] == [0.04, 0.02, 0.009]
    assert gaps[0][0] == "bench.wait"                  # 60..100 ms
    assert gaps[1][0] == "PjitFunction(_open_at_zero)"  # 30..50 ms
    assert gaps[2][0] == "PjitFunction(matmul)"        # 1..10 ms


def test_no_device_events_reads_nothing():
    host_only = [e for e in _events() if e.plane == HOST]
    assert devtrace.summarize(host_only, (0.0, 100e6)) is None


def test_program_name_strips_the_run_id():
    assert devtrace.program_name("jit_matmul(1234)") == "jit_matmul"
    assert devtrace.program_name("fusion.3") == "fusion.3"
