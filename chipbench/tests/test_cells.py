"""Every cell of ``BENCHMARK.json`` brings what the harness and its tests
use: CPU sizes, the faults of its entry, and the entry's interface
(``chipbench/entries/__init__.py``)."""

import numbers

import pytest

from chipbench import run
from chipbench.tests.cpu_cells import bench, entry_module, sizes, workloads

# what run.py and readings.py call on an entry
METHODS = ("call", "readings", "control", "close", "program_bytes")


def problems(workload: str) -> list[str]:
    try:
        config_over, cell_over = sizes(workload)
    except (FileNotFoundError, KeyError) as e:
        return [f"CPU sizes: {e!r}"]
    module = entry_module(workload)
    found = []
    faults = getattr(module, "FAULTS", None)
    if not isinstance(faults, dict) or not faults:
        found.append(f"entry module {module.__name__} has no FAULTS")
    elif not all(callable(f) for f in faults.values()):
        found.append(f"a fault of {module.__name__} is not callable")
    found += [f"{module.__name__}.Entry has no method {m}"
              for m in METHODS if not callable(getattr(module.Entry, m, None))]
    if not found:
        _, config, cell = run.load_cell(bench(), workload, config_over,
                                        cell_over)
        entry = module.Entry(config, cell, 2 ** 32 + 13, run.Spans())
        work = getattr(entry, "work", None)
        entry.close()
        if not isinstance(work, numbers.Real) or not work > 0:
            found.append(f"{module.__name__}.Entry's work is {work!r}, "
                         f"not a positive number")
    return found


@pytest.mark.parametrize("workload", workloads())
def test_cell_brings_sizes_faults_and_entry(workload):
    found = problems(workload)
    assert not found, f"cell {workload}: " + "; ".join(found)
