"""Active slots per decode tick over the slot count, in percent, from the
engine's counters over the window (EngineStats.occupancy_ticks)."""


def read(ctx):
    ticks = ctx.delta("ticks") - ctx.delta("idle_ticks")
    if ticks <= 0:
        return None
    return 100.0 * ctx.delta("occupancy_ticks") / (ticks * ctx.n_slots)
