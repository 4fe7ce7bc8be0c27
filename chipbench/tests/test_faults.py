"""A run whose timed path is broken underneath comes out not correct:
the look for a chip is skipped, the rest of a run is driven at a tiny
size, and each fault a cell's entry has (its module's ``FAULTS``) is
planted in the program call the entry makes."""

import pytest

from chipbench import run
from chipbench.tests.cpu_cells import bench, entry_module, fault_cases, \
    sizes, workloads

CASES = fault_cases()


@pytest.mark.parametrize("workload,kind", CASES,
                         ids=[f"{w}-{k}" for w, k in CASES])
def test_planted_fault_is_not_correct(workload, kind):
    config_over, cell_over = sizes(workload)
    res = run.run_cell(workload, 2 ** 32 + 9, 0.3, False, bench=bench(),
                       config_over=config_over, cell_over=cell_over,
                       require_chip=False,
                       wrap_entry=entry_module(workload).FAULTS[kind],
                       log=lambda *a, **k: None)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", workloads())
def test_sound_run_is_correct(workload):
    config_over, cell_over = sizes(workload)
    res = run.run_cell(workload, 2 ** 32 + 9, 0.3, False, bench=bench(),
                       config_over=config_over, cell_over=cell_over,
                       require_chip=False, log=lambda *a, **k: None)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["platform"] == "cpu"
