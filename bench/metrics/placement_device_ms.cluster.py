"""Device time of the placement programs per whole call, in ms: the
device's busy time inside the spans around each ``first_fit_window``,
``schedule_epoch`` and ``sweep_schedule`` call."""

SPANS = {"bench.place.window", "bench.place.epoch", "bench.place.sweep"}


def read(ctx):
    if not ctx.trace.devices or not any(ctx.trace.span_count(s) for s in SPANS):
        return None
    return ctx.trace.busy_in(SPANS) * 1e3 / ctx.n_calls
