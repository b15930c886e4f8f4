"""Share of the HBM roofline reached by the Pallas kernel ``range_max_table``: its
operand and result bytes at 819 GB/s, over its device time in the trace."""

from bench.roofline import hbm_share


def read(ctx):
    return hbm_share(ctx, "range_max_table")
