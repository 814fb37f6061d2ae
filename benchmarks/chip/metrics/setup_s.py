"""Seconds from the process's start to the first counted due time:
loading, compiling or reading the compile cache, weights, warm-up, ramp."""


def read(ctx):
    return ctx.setup_s
