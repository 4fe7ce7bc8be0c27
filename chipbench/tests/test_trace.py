"""The trace reduction, on hand-made events and on a small trace of
three ``hpc_mc.whatif`` calls recorded on a TPU v5e."""

import os

import pytest

from chipbench.trace import Event, Trace, read_events

DEV = "/device:TPU:0"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "whatif3.xplane.pb")


def _op(name, start, dur, line="XLA Ops"):
    return Event(DEV, line, f"%{name} = f32[8]{{0}} custom-call(f32[8] %x)",
                 start, dur, {})


def _span(name, start, dur):
    return Event("/host:CPU", "python3", f"chipbench.{name}", start, dur, {})


EVENTS = [
    _span("window", 0, 100), _span("call", 0, 100), _span("grid", 1, 39),
    _span("backtest", 40, 60),
    _op("_fleet_scan_padded.1", 10, 10), _op("pad.3", 15, 15),
    _op("_fleet_scan_padded.2", 50, 10),
    Event(DEV, "XLA Modules", "jit__backtest_jit(123)", 10, 50, {}),
    _op("outside.1", 200, 10),                   # after the window
]


def test_busy_is_the_union_of_device_intervals():
    tr = Trace(EVENTS)
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s() == pytest.approx(30e-9)      # [10, 30] + [50, 60]


def test_kernel_and_program_time_by_own_name():
    tr = Trace(EVENTS)
    assert tr.op_s(r"_fleet_scan_padded$") == pytest.approx(20e-9)
    assert tr.op_s(r"pad$") == pytest.approx(15e-9)
    assert tr.module_s(r"jit__backtest_jit\(") == pytest.approx(50e-9)
    assert tr.top_ops(1) == [["_fleet_scan_padded", pytest.approx(20e-9)]]


def test_idle_gaps_go_to_the_innermost_open_span():
    gaps = dict(Trace(EVENTS).idle_gaps())
    # idle [0, 10], [30, 50], [60, 100]
    assert gaps == {"backtest": pytest.approx(50e-9),
                    "grid": pytest.approx(19e-9),
                    "call": pytest.approx(1e-9)}


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="chipbench.window"):
        Trace(EVENTS[1:])


def test_recorded_chip_trace():
    tr = Trace(read_events(RECORDED))
    assert tr.devices == [DEV]
    busy = tr.busy_s()
    assert 0 < busy < tr.window_s
    kernel = tr.op_s(r"_fleet_scan_padded$")
    assert 0 < kernel < busy
    assert len([m for m in tr.modules
                if m.name.startswith("jit__backtest_jit(")]) == 3
    gaps = dict(tr.idle_gaps())
    assert set(gaps) <= {"grid", "backtest", "call", "host"}
    assert sum(gaps.values()) == pytest.approx(tr.window_s - busy)
