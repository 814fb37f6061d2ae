"""Output tokens produced in the window over the window's seconds."""


def read(ctx):
    return sum(t.n_out for t in ctx.window_ticks()) / ctx.seconds
