"""Device milliseconds per `optimize` call of the fused soft-scan pair
(forward and backward Pallas kernels)."""

KERNELS = r".*jit__pallas_(fwd|bwd)_"


def read(ctx):
    t = ctx.trace.op_s(KERNELS)
    return 1e3 * t / ctx.n_done if t and ctx.n_done else None
