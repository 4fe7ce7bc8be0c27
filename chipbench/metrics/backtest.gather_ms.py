"""Device milliseconds per call of the `backtest` program's [B, T] price
gather and per-row price sums: the operations under the named scope
``backtest.gather``, in the calls traced after the measured window
(`chipbench.program_trace.recorded`)."""

from chipbench.program_trace import per_call_ms, recorded


def read(ctx):
    pt = recorded(ctx)
    return per_call_ms(pt.scope_s("backtest.gather"), pt)
