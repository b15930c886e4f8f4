"""In-program waits by wide rows per whole call: rows whose peak demand
exceeds the smallest node's capacity and that waited in the epoch program
(``placement_stats["waits_wide"]``).  None where the program does not
count them."""


def read(ctx):
    return ctx.counters.get("wide_waits_per_call")
