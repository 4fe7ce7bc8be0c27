"""Device milliseconds per `optimize` call of the tuner's update (the
finite-step guard, Adam, the back-off blend): the operations under the
named scope ``tune.update``, in the calls traced after the measured
window (`chipbench.program_trace.recorded`)."""

from chipbench.program_trace import per_call_ms, recorded


def read(ctx):
    pt = recorded(ctx)
    return per_call_ms(pt.scope_s("tune.update"), pt)
