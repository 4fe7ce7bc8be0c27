"""Share of the fleet scan's roofline the whole `backtest` program
reaches on the device (price gather, padding, scan and cost assembly):
the problem's least time over the program's device time per call."""

from chipbench.peaks import least_seconds
from chipbench.work.fleet_scan import work

PROGRAM = r"jit__backtest_jit\("


def read(ctx):
    t = ctx.trace.module_s(PROGRAM)
    if not t or not ctx.n_done:
        return None
    e = ctx.entry
    least = least_seconds(*work(e.n_rows, e.hours, e.n_markets),
                          ctx.device_kind)
    return 100.0 * least * ctx.n_done / t
