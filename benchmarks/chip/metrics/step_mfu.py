"""The whole step's share of the chip's bf16 peak over the window, in
percent, in the offline cell (see ``_shared.step_mfu``)."""
from _shared import step_mfu


def read(ctx):
    return step_mfu(ctx)
