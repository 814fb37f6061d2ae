"""Device milliseconds per decode tick in ops under none of the model
step's scopes, from each op's own time: what XLA builds outside the
layer's own ops, such as the hoisted f32 -> bf16 weight casts."""
from _shared import scope_ms


def read(ctx):
    return scope_ms(ctx, "decode", "unscoped")
