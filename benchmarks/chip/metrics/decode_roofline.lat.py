"""The decode tick's share of its roofline, in percent, in the open-loop
cells (see ``_shared.decode_roofline``)."""
from _shared import decode_roofline


def read(ctx):
    return decode_roofline(ctx)
