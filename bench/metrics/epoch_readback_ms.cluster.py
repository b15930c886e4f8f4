"""Host time reading the scheduling-epoch program's results back in one
call, in ms: the program's ``sched.epoch.readback`` spans (the outputs after
the first converted to numpy and Python scalars; the first read, which waits
for the device, is ``sched.epoch.wait``)."""

from bench.program_record import span_ms


def read(ctx):
    return span_ms("sched.epoch.readback")
