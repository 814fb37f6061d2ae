"""Device milliseconds per decode tick in the ``mixer`` scope (norm, QKV,
rope and attention, or the SSD block), from each op's own time."""
from _shared import scope_ms


def read(ctx):
    return scope_ms(ctx, "decode", "mixer")
