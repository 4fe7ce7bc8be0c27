"""Host milliseconds of `build_grid` per call (threshold resolution, the
price fetch and the grid's transfers), from the benchmark's span."""

import numpy as np


def read(ctx):
    s = ctx.spans.get("grid")
    return float(np.mean(s)) * 1e3 if s else None
