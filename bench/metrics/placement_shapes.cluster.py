"""Distinct compiled shapes the placement programs were called with in one
call: the program's ``sched.epoch.shapes`` + ``sched.window.shapes``
counters (each shape a program compiled, or loaded from the cache, the
first time a process meets it)."""

from bench.program_record import counter


def read(ctx):
    return counter("sched.epoch.shapes", "sched.window.shapes")
