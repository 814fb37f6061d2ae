"""Device milliseconds per decode tick in the ``cache_update`` scope (the
K/V pool's or the recurrent state's write), from each op's own time."""
from _shared import scope_ms


def read(ctx):
    return scope_ms(ctx, "decode", "cache_update")
