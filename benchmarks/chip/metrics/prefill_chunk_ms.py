"""Device milliseconds per chunked-prefill call, from the trace."""
from _shared import program_ms


def read(ctx):
    return program_ms(ctx, "prefill_chunk")
