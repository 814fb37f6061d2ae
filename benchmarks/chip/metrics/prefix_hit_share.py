"""Prompt pages served from the prefix cache over the prompt pages that
could have matched, in percent, from the engine's counters over the
window. None where no page could match (recurrent stacks)."""


def read(ctx):
    eligible = ctx.delta("prefix_eligible_pages")
    if eligible <= 0:
        return None
    return 100.0 * ctx.delta("prefix_hit_pages") / eligible
