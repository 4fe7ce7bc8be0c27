"""Device-idle milliseconds per `optimize` call while `optimize`'s host
work runs: idle time whose innermost open ``repro.tune.*`` span is not
``repro.tune.loop`` (building the problem, the swept re-evaluation's
fetch, the selection), in the calls traced after the measured window
(`chipbench.program_trace.recorded`)."""

from chipbench.program_trace import per_call_ms, recorded


def read(ctx):
    pt = recorded(ctx)
    return per_call_ms(pt.idle_in_s("tune.", but=("tune.loop",)), pt)
