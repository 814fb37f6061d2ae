"""Share of the traced window in which no operation ran on the device, in
percent (1 - busy union / window)."""


def read(ctx):
    if not ctx.trace:
        return None
    return 100.0 * ctx.trace["idle_share"]
