"""The benchmark's own tests run on the CPU at tiny sizes, by hand:
``python -m pytest chipbench/tests`` (the repository's tier-1 run
collects only ``tests/``)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny sizes of every cell, the same shapes as the real ones
HOURS = 96
CONFIG_OF = {"hpc_mc.sweep": "paper_hpc_mc", "hpc_mc.whatif": "paper_hpc_mc",
             "hpc_mc.tune": "paper_hpc_mc"}
CELL_OVER = {
    "hpc_mc.sweep": {"years_per_market": 2, "psi_count": 2,
                     "ratings_mw": [1.0], "shares_per_call": 2},
    "hpc_mc.whatif": {"shares_per_call": 2, "check_calls": 4},
    "hpc_mc.tune": {"years_per_market": 1, "psi_count": 2,
                    "ratings_mw": [1.0], "shares_per_call": 2},
}
CONFIG_OVER = {
    "hpc_mc.sweep": {"hours": HOURS},
    "hpc_mc.whatif": {"hours": HOURS},
    "hpc_mc.tune": {"hours": HOURS},
}


def bench() -> dict:
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
