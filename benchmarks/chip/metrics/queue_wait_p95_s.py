"""95th percentile of the wait from a request's due time to the start of
the engine step that admitted it into a slot (tick to wall-clock map),
over the window's requests; one never admitted counts as infinite."""
import math


def read(ctx):
    recs = ctx.window_recs()
    if not recs:
        return None
    return ctx.nearest_rank([r.admit - r.due if r.admit == r.admit
                             else math.inf for r in recs], 95)
