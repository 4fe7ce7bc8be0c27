"""The program's spans and named scopes in a trace: op names read from
the metadata plane of two traces of three ``hpc_mc.whatif`` calls
recorded on a TPU v5e (one before the program had spans or scopes, one
after), hand-made events, and a short window recorded on the CPU."""

import importlib
import os
import types

import pytest
from conftest import CELL_OVER, CONFIG_OVER, bench

from chipbench import data, run
from chipbench.program_trace import ProgramTrace, breakdown, recorded, \
    scope_segments
from chipbench.trace import Event

DEV = "/device:TPU:0"
DATA = os.path.join(os.path.dirname(__file__), "data")
UNSCOPED = os.path.join(DATA, "whatif3.xplane.pb")
SCOPED = os.path.join(DATA, "whatif3_scoped.xplane.pb")


@pytest.fixture(scope="module")
def unscoped():
    return ProgramTrace.from_file(UNSCOPED)


@pytest.fixture(scope="module")
def scoped():
    return ProgramTrace.from_file(SCOPED)


@pytest.mark.parametrize("op,op_name", [
    ("copy", "jit(_backtest_jit)/gather"),
    ("fusion", "jit(_backtest_jit)/gather"),
    ("pad.0", "jit(_backtest_jit)/jit(_pad)/pad"),
    ("_fleet_scan_padded.1",
     "jit(_backtest_jit)/jit(_fleet_scan_padded)/pallas_call"),
])
def test_op_names_from_the_metadata_plane(unscoped, op, op_name):
    assert (op, op_name) in unscoped.op_names()


def test_every_recorded_op_is_a_leaf_of_a_known_program(unscoped):
    assert len(unscoped.op_names()) == len(unscoped.ops)
    assert sum(unscoped.scopes_s().values()) == pytest.approx(
        sum(unscoped._clip(e)[1] - unscoped._clip(e)[0]
            for e in unscoped.ops) * 1e-9)


def test_segments_under_transformations():
    assert scope_segments(
        "jit(tune_loop)/while/body/closed_call/tune.objective/"
        "transpose(tune.objective)/jvp(jit(_pallas_bwd))/pallas_call") == [
        "tune_loop", "while", "body", "closed_call", "tune.objective",
        "tune.objective", "_pallas_bwd", "pallas_call"]
    assert "tune.update" in scope_segments("transpose(jvp(tune.update))/mul")


def _op(name, start, dur):
    return Event(DEV, "XLA Ops", f"%{name} = f32[8]{{0}} fusion(f32[8] %x)",
                 start, dur, {})


def _span(name, start, dur, **stats):
    return Event("/host:CPU", "python3", name, start, dur, stats)


HLO = {"jit_f(1)": {
    "while.1": ("while", "jit(f)/tune.update/while"),
    "fusion.1": ("fusion", "jit(f)/tune.update/while/body/mul"),
    "fusion.2": ("fusion", "jit(f)/transpose(jvp(tune.update))/add"),
    "fusion.3": ("fusion", "jit(f)/backtest.gather/gather"),
}}
EVENTS = [
    _span("chipbench.window", 0, 200), _span("chipbench.call", 0, 200),
    _span("chipbench.grid", 1, 48), _span("repro.grid.sort", 5, 20),
    _span("repro.grid.upload", 30, 15, bytes=60, arrays=3),
    Event(DEV, "XLA Modules", "jit_f(1)", 50, 100, {}),
    _op("while.1", 60, 50), _op("fusion.1", 65, 10), _op("fusion.2", 80, 20),
    _op("fusion.3", 120, 30),
]


def test_containers_never_count_their_body_twice():
    pt = ProgramTrace(EVENTS, HLO)
    assert pt.scope_s("tune.update") == pytest.approx(30e-9)
    assert pt.scope_s("backtest.gather") == pytest.approx(30e-9)
    assert pt.scopes_s() == {"tune.update": pytest.approx(30e-9),
                             "backtest.gather": pytest.approx(30e-9)}
    assert pt.scope_s("tune.update", op=r"fusion") == pytest.approx(30e-9)
    assert pt.scope_s("tune.update", op=r"mul") == 0.0


def test_idle_time_by_both_span_families():
    pt = ProgramTrace(EVENTS, HLO)
    assert pt.calls == 1
    assert pt.span_s("grid.sort", "grid.upload") == pytest.approx(35e-9)
    # idle [0, 60], [110, 120], [150, 200]
    assert dict(pt.idle_by_span()) == {
        "grid/grid.sort": pytest.approx(20e-9),
        "grid/grid.upload": pytest.approx(15e-9),
        "grid": pytest.approx(13e-9),
        "call": pytest.approx(72e-9)}
    assert pt.idle_in_s("grid.", but=("grid.upload",)) == \
        pytest.approx(20e-9)


def test_span_cost_per_unit_of_its_counters():
    pt = ProgramTrace(EVENTS, HLO)
    assert pt.ns_per_unit("grid.upload") == {"bytes": pytest.approx(0.25),
                                             "arrays": pytest.approx(5.0)}
    assert pt.ns_per_unit("grid.sort") == {}     # a span without counters
    assert breakdown(pt)["ns_per_unit"] == {
        "grid.upload": {"bytes": pytest.approx(0.25),
                        "arrays": pytest.approx(5.0)}}


@pytest.mark.parametrize("span,counters", [
    ("grid.fetch", {"bytes"}), ("grid.sort", {"markets", "hours"}),
    ("grid.thresholds", {"resolutions"}), ("grid.fields", {"rows"}),
    ("grid.upload", {"bytes", "arrays"}),
    ("backtest.launch", {"rows", "hours"})])
def test_recorded_chip_trace_reads_counters(scoped, span, counters):
    rates = breakdown(scoped)["ns_per_unit"][span]
    assert set(rates) == counters
    assert all(v > 0 for v in rates.values())


def test_recorded_chip_trace_reads_spans_and_scopes(scoped):
    assert scoped.calls == 3
    for span in ("grid.fetch", "grid.sort", "grid.thresholds",
                 "grid.fields", "grid.upload", "backtest.launch"):
        assert scoped.span_s(span) > 0, span
    for scope in ("backtest.gather", "backtest.costs", "fleet_scan.layout"):
        assert scoped.scope_s(scope) > 0, scope
    gaps = dict(scoped.idle_by_span())
    assert any(k.startswith("grid/grid.") for k in gaps), gaps
    assert "backtest/backtest.launch" in gaps
    assert sum(gaps.values()) == pytest.approx(
        scoped.window_s - scoped.busy_s())


@pytest.mark.parametrize("metric", [
    "grid.resolve_ms.whatif", "grid.transfer_ms.whatif",
    "backtest.gather_ms", "fleet_scan.layout_ms"])
def test_readers_on_the_recorded_chip_trace(scoped, unscoped, metric):
    reader = run.load_metric(metric)
    assert reader.read(types.SimpleNamespace(program_trace=scoped)) > 0
    # a program without spans or scopes reads nothing, and raises nothing
    assert reader.read(types.SimpleNamespace(program_trace=unscoped)) \
        is None


@pytest.mark.parametrize("metric", [
    "tune.objective_xla_ms", "tune.update_ms", "tune.host_idle_ms"])
def test_tune_readers_read_nothing_without_tune(scoped, metric):
    reader = run.load_metric(metric)
    assert reader.read(types.SimpleNamespace(program_trace=scoped)) is None


def test_recording_the_calls_after_a_window_on_cpu():
    workload = "hpc_mc.sweep"
    _, config, cell = run.load_cell(bench(), workload, CONFIG_OVER[workload],
                                    CELL_OVER[workload])
    module = importlib.import_module(f"chipbench.entries.{cell['entry']}")
    spans = run.Spans()
    entry = module.Entry(config, cell, 2 ** 32 + 11, spans)
    with spans("call"):
        entry.call(data.WINDOW, 0)
    before = {k: list(v) for k, v in spans.seconds.items()}
    ctx = types.SimpleNamespace(entry=entry, attempted=1)
    pt = recorded(ctx)
    assert recorded(ctx) is pt                   # recorded once per run
    assert spans.seconds == before
    assert pt.calls >= 1
    names = {e.name for e in pt.program_spans}
    assert {"repro.grid.fetch", "repro.grid.sort", "repro.grid.thresholds",
            "repro.grid.fields", "repro.grid.upload",
            "repro.backtest.launch"} <= names
    resolve = run.load_metric("grid.resolve_ms.sweep").read(ctx)
    transfer = run.load_metric("grid.transfer_ms.sweep").read(ctx)
    assert 0 < resolve and 0 < transfer
