"""The control (the reference in bfloat16, in the program's place) comes
out as not correct where the program comes out correct, in every cell,
at a size a test run holds. The same readings at each cell's own size on
the chip set the limits (``chipbench/readings.py``)."""

import pytest
from conftest import CELL_OVER, CONFIG_OVER, bench

from chipbench.readings import seed_readings


@pytest.mark.parametrize("workload", sorted(CELL_OVER))
def test_control_fails_where_program_passes(workload):
    r = seed_readings(workload, 2 ** 33 + 5, 3,
                      config_over=CONFIG_OVER[workload], bench=bench(),
                      cell_over={**CELL_OVER[workload], "check_calls": 2})
    assert r["program_ok"], r
    assert not r["control_ok"], r
