"""Device milliseconds per `optimize` call of the hard re-evaluation
programs that run after the tuning loop: the swept baselines'
`_backtest_jit` and `hard_cpc`."""

PROGRAMS = r"jit__backtest_jit\(|jit__hard_cpc_rows\("


def read(ctx):
    t = ctx.trace.module_s(PROGRAMS)
    return 1e3 * t / ctx.n_done if t and ctx.n_done else None
