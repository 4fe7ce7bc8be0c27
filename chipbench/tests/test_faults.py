"""A run whose timed path is broken underneath comes out not correct:
the look for a chip is skipped, the rest of a run is driven at a tiny
size, and each fault a cell can have is planted in the program call the
entry makes."""

import pytest
from conftest import CELL_OVER, CONFIG_OVER, bench

from chipbench import faults, run

CELL_ENTRY = {"hpc_mc.sweep": "sweep", "hpc_mc.whatif": "sweep",
              "hpc_mc.tune": "tune"}
CASES = [(w, kind) for w, e in CELL_ENTRY.items()
         for kind in faults.BY_ENTRY[e][1]]


@pytest.mark.parametrize("workload,kind", CASES,
                         ids=[f"{w}-{k}" for w, k in CASES])
def test_planted_fault_is_not_correct(workload, kind):
    plant = faults.BY_ENTRY[CELL_ENTRY[workload]][0]
    res = run.run_cell(workload, 2 ** 32 + 9, 0.3, False, bench=bench(),
                       config_over=CONFIG_OVER[workload],
                       cell_over=CELL_OVER[workload], require_chip=False,
                       wrap_entry=plant(kind), log=lambda *a, **k: None)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", sorted(CELL_OVER))
def test_sound_run_is_correct(workload):
    res = run.run_cell(workload, 2 ** 32 + 9, 0.3, False, bench=bench(),
                       config_over=CONFIG_OVER[workload],
                       cell_over=CELL_OVER[workload], require_chip=False,
                       log=lambda *a, **k: None)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["platform"] == "cpu"
