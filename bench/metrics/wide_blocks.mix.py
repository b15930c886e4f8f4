"""Entries into the congested epoch path per whole call whose first blocked
row is wide, its peak demand above the smallest node's capacity
(``placement_stats["wide_blocks"]``).  None where the program does not
count them."""


def read(ctx):
    return ctx.counters.get("wide_blocks_per_call")
