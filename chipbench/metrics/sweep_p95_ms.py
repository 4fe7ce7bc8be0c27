"""95th percentile of the completed calls' latency, start to results on
the host, over every call of the window. It reads a call of any entry,
not the sweep's alone."""

import numpy as np


def read(ctx):
    if not ctx.latencies:
        return None
    return float(np.percentile(ctx.latencies, 95)) * 1e3
