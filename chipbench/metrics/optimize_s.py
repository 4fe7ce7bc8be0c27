"""Window seconds per completed `optimize` call (the window ends with
its last call)."""


def read(ctx):
    return ctx.window_s / ctx.n_done if ctx.n_done else None
