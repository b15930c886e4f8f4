"""Host time of the windows loop's bookkeeping between dispatches in one
call, in ms: the program's ``sched.place.expire``, ``sched.place.profiles``,
``sched.place.commit`` and ``sched.place.pending`` spans (the node timelines
expired, read out, mirrored; the pending heap rebuilt)."""

from bench.program_record import span_ms


def read(ctx):
    return span_ms("sched.place.expire", "sched.place.profiles", "sched.place.commit", "sched.place.pending")
