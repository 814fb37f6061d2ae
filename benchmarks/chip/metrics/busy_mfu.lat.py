"""Model FLOPs of the window over the device's busy seconds in the trace,
times the chip's bf16 peak, in percent, in the open-loop cells (see
``_shared.busy_mfu``)."""
from _shared import busy_mfu


def read(ctx):
    return busy_mfu(ctx)
