"""Row-hours of every completed call over the whole window: the entry's
``work`` (rows x hours of its problem per call; a row is a policy on a
market-year and a system, for dispatch a site) times the calls
completed, over the window's seconds. It reads any cell whose entry
gives ``work`` in row-hours, not the sweep's alone."""


def read(ctx):
    return ctx.work_done / ctx.window_s if ctx.n_done else None
