"""Device time of the retry-ladder pass per whole call, in ms: the device's
busy time inside the benchmark's ``bench.ladder`` spans (``batched_rows``)."""


def read(ctx):
    if not ctx.trace.devices or not ctx.trace.span_count("bench.ladder"):
        return None
    return ctx.trace.busy_in({"bench.ladder"}) * 1e3 / ctx.n_calls
