"""The cells the harness tests drive, found by name: every workload of
``BENCHMARK.json``, its entry through ``chipbench/cells/<cell>.json``,
and its tiny CPU sizes (the same shapes as the real ones) in
``chipbench/tests/cells/<cell>.json``:
``{"config": {...}, "cell": {...}}``, overriding the keys of the
configuration and of the cell file."""

import importlib
from pathlib import Path

from chipbench.run import HERE, ROOT, load_json

SIZES = Path(__file__).resolve().parent / "cells"


def bench() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workloads() -> list[str]:
    return sorted(w["name"] for w in bench()["workloads"])


def sizes(workload: str) -> tuple[dict, dict]:
    """``(config_over, cell_over)``: the cell's CPU sizes."""
    path = SIZES / f"{workload}.json"
    if not path.exists():
        raise FileNotFoundError(f"cell {workload!r} has no CPU sizes: "
                                f"{path.relative_to(ROOT)} is missing")
    over = load_json(path)
    return over["config"], over["cell"]


def entry_module(workload: str):
    cell = load_json(HERE / "cells" / f"{workload}.json")
    return importlib.import_module(f"chipbench.entries.{cell['entry']}")


def fault_cases() -> list[tuple[str, str]]:
    """(cell, kind) for every fault of every cell's entry."""
    return [(w, kind) for w in workloads()
            for kind in getattr(entry_module(w), "FAULTS", {})]
