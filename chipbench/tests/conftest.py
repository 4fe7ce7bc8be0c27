"""The benchmark harness's own tests run on the CPU at tiny sizes:
``python -m pytest chipbench/tests``. Each cell's sizes are in
``cells/<cell>.json`` (``cpu_cells.py`` finds them)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
