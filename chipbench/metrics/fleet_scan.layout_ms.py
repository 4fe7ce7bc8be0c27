"""Device milliseconds per call of the fleet scan's input layout (the
time-major transpose and pad, the parameter rows): the operations under
the named scope ``fleet_scan.layout``, in the calls traced after the
measured window (`chipbench.program_trace.recorded`)."""

from chipbench.program_trace import per_call_ms, recorded


def read(ctx):
    pt = recorded(ctx)
    return per_call_ms(pt.scope_s("fleet_scan.layout"), pt)
