"""95th percentile of the gaps between consecutive engine steps that
produced tokens for one request. Open loop: every gap of every request due
in the window. Offline: every gap that ends inside the window."""


def read(ctx):
    gaps = []
    if ctx.cell.open_loop:
        for r in ctx.window_recs():
            ts = [t for t, _ in r.tokens]
            gaps += [b - a for a, b in zip(ts, ts[1:])]
    else:
        for r in ctx.recs:
            ts = [t for t, _ in r.tokens]
            gaps += [b - a for a, b in zip(ts, ts[1:]) if ctx.w0 <= b <= ctx.w1]
    return ctx.nearest_rank(gaps, 95) * 1e3 if gaps else None
