"""Row-hours of every completed sweep call over the whole window."""


def read(ctx):
    return ctx.work_done / ctx.window_s if ctx.n_done else None
