"""Device milliseconds per call of the `backtest` program's per-row
price sums: each market's sum over the hours, gathered into [B] by
market index, the operations under the named scope ``backtest.gather``,
in the calls traced after the measured window
(`chipbench.program_trace.recorded`). On the chip the scan kernel reads
the [N, T] prices by market index, so no [B, T] gather falls under the
scope; an XLA scan off the chip gathers its rows there."""

from chipbench.program_trace import per_call_ms, recorded


def read(ctx):
    pt = recorded(ctx)
    return per_call_ms(pt.scope_s("backtest.gather"), pt)
