"""The control (the reference in bfloat16, in the program's place) comes
out as not correct where the program comes out correct, in every cell,
at a size a test run holds. The same readings at each cell's own size on
the chip set the limits (``chipbench/readings.py``)."""

import pytest

from chipbench.readings import seed_readings
from chipbench.tests.cpu_cells import bench, sizes, workloads


@pytest.mark.parametrize("workload", workloads())
def test_control_fails_where_program_passes(workload):
    config_over, cell_over = sizes(workload)
    r = seed_readings(workload, 2 ** 33 + 5, 3, config_over=config_over,
                      bench=bench(), cell_over={**cell_over, "check_calls": 2})
    assert r["program_ok"], r
    assert not r["control_ok"], r
