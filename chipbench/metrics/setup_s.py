"""Set-up seconds: process start to the first timed call (imports,
inputs, compile or cache load, warm-up)."""


def read(ctx):
    return ctx.setup_s
