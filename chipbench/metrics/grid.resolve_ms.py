"""Host milliseconds per call that `build_grid` spends resolving
thresholds: the program spans ``repro.grid.sort`` (the price sort) and
``repro.grid.thresholds`` (the N x K resolutions), in the calls traced
after the measured window (`chipbench.program_trace.recorded`)."""

from chipbench.program_trace import per_call_ms, recorded


def read(ctx):
    pt = recorded(ctx)
    return per_call_ms(pt.span_s("grid.sort", "grid.thresholds"), pt)
