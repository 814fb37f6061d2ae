"""Device milliseconds per fused decode tick, from the trace."""
from _shared import program_ms


def read(ctx):
    return program_ms(ctx, "decode")
