"""Host time of the streaming-window dispatches in one call, in ms: the
program's ``sched.window.prepare``, ``sched.window.launch`` and
``sched.window.readback`` spans (``sched.window.wait``, the first read,
which waits for the device, left out)."""

from bench.program_record import span_ms


def read(ctx):
    return span_ms("sched.window.prepare", "sched.window.launch", "sched.window.readback")
