"""Host time preparing the scheduling-epoch dispatches of one call, in ms:
the program's ``sched.epoch.prepare`` spans (timelines folded and padded,
the pending heap sorted, rows padded)."""

from bench.program_record import span_ms


def read(ctx):
    return span_ms("sched.epoch.prepare")
