"""Host milliseconds per call that `build_grid` spends moving data: the
program spans ``repro.grid.fetch`` (the price matrix to the host) and
``repro.grid.upload`` (the grid's arrays to the device), in the calls
traced after the measured window (`chipbench.program_trace.recorded`)."""

from chipbench.program_trace import per_call_ms, recorded


def read(ctx):
    pt = recorded(ctx)
    return per_call_ms(pt.span_s("grid.fetch", "grid.upload"), pt)
