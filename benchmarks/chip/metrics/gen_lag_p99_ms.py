"""99th percentile of how late the load generator submitted a request
after its due time (harness clock), over the window's requests."""


def read(ctx):
    recs = ctx.window_recs()
    if not recs:
        return None
    return ctx.nearest_rank([r.submit - r.due for r in recs], 99) * 1e3
