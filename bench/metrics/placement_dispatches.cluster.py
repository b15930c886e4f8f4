"""Placement program dispatches per whole call (``placement_stats``)."""


def read(ctx):
    return ctx.counters["placement_dispatches_per_call"]
