"""Host time launching the scheduling-epoch program in one call, in ms: the
program's ``sched.epoch.launch`` spans (arguments copied to the device and
the program enqueued, up to the call's return)."""

from bench.program_record import span_ms


def read(ctx):
    return span_ms("sched.epoch.launch")
