"""Device milliseconds per `optimize` call of the XLA operations around
the soft-scan kernels in the loss and gradient: the operations under
the named scope ``tune.objective`` less the forward and backward Pallas
kernels, in the calls traced after the measured window
(`chipbench.program_trace.recorded`)."""

import runpy
from pathlib import Path

from chipbench.program_trace import per_call_ms, recorded

# the kernels as `soft_scan_vjp.kernel_ms` matches them, so the two
# metrics split the objective's device time between them
KERNELS = runpy.run_path(
    str(Path(__file__).with_name("soft_scan_vjp.kernel_ms.py")))["KERNELS"]


def read(ctx):
    pt = recorded(ctx)
    scoped = pt.scope_s("tune.objective")
    if not scoped:
        return None
    return per_call_ms(scoped - pt.scope_s("tune.objective", op=KERNELS), pt)
