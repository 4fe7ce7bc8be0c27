"""Share of its roofline the fleet scan kernel reaches: the least time
the problem's operations and bytes need at the chip's peaks, over the
kernel's device time per call."""

from chipbench.peaks import least_seconds
from chipbench.work.fleet_scan import work

KERNEL = r"_fleet_scan_padded$"


def read(ctx):
    t = ctx.trace.op_s(KERNEL)
    if not t or not ctx.n_done:
        return None
    e = ctx.entry
    least = least_seconds(*work(e.n_rows, e.hours, e.n_markets),
                          ctx.device_kind)
    return 100.0 * least * ctx.n_done / t
