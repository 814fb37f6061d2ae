"""Device milliseconds per decode tick in the ``ffn`` scope (MLP, MoE or
KAN-FFN), from each op's own time."""
from _shared import scope_ms


def read(ctx):
    return scope_ms(ctx, "decode", "ffn")
