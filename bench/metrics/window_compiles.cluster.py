"""Programs compiled, or loaded from the compile cache, inside the window:
each one a shape the warm-up did not cover or a retrace."""


def read(ctx):
    return ctx.counters["window_compiles"]
