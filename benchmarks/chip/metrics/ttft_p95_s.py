"""95th percentile of time to first token over every request due in the
window, from its due time on the wall clock to the return of the engine
step that produced its first token. A request with no first token counts
as infinitely late."""
import math


def read(ctx):
    recs = ctx.window_recs()
    if not recs:
        return None
    return ctx.nearest_rank([r.tokens[0][0] - r.due if r.tokens else math.inf
                             for r in recs], 95)
