"""Host time of the retry-ladder pass in one call, in ms: the program's
``sched.ladder.pack``, ``sched.ladder.unpack``, ``sched.ladder.host_wastage``
and ``sched.ladder.rows`` spans (batches packed, tables sliced per task,
wastage scored in float64, rows flattened per policy)."""

from bench.program_record import span_ms


def read(ctx):
    return span_ms("sched.ladder.pack", "sched.ladder.unpack", "sched.ladder.host_wastage", "sched.ladder.rows")
