"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    busy = ctx.trace.busy_s()
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx.trace.window_s)
