"""Spans and counters of the scheduler's own work, kept per call.

These instrument the program:

* ``call(name)`` — the root of one call of an entry point, as a context
  manager or a decorator.  It opens a fresh ``Record``; on exit the record
  is kept as ``last(name)``, the record of the most recent whole call, for
  an operator or a benchmark to read afterwards.
* ``span(name)`` — a context manager around one piece of work.  It opens a
  ``jax.profiler.TraceAnnotation`` of the same name (so a profiler trace
  shows the span on the host clock it aligns the device planes to) and
  adds the span's host duration to the open call's record: per name a
  count, the total ns and the self ns (total minus the time of spans
  opened inside it on the same thread).
* ``distinct(name, key)`` — a counter at the same boundaries: it reads
  the number of distinct keys seen in the call.

A span outside any open call only emits the annotation, and a counter
outside one does nothing.  The
span stack is per thread and record updates take a lock, so spans opened
on worker threads (the ladder pass maps its batches over a thread pool)
count toward the call their thread works for.  Calls do not run
concurrently: a call opened inside another takes over until it closes.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax


class _Thread(threading.local):
    def __init__(self) -> None:
        self.stack: list[span] = []  # open spans of this thread, innermost last


_lock = threading.Lock()
_local = _Thread()
_open: list["Record"] = []  # open calls, innermost last
_last: dict[str, "Record"] = {}


class Record:
    """One call's spans and counters.

    ``spans`` maps a span name to ``[count, total ns, self ns]``;
    ``counts`` maps a counter name to its value."""

    def __init__(self, name: str) -> None:
        self.name = name
        self.spans: dict[str, list[int]] = {}
        self.counts: dict[str, int] = {}
        self._seen: dict[str, set] = {}

    def n(self, name: str) -> int:
        """How many times the span ``name`` closed in the call."""
        return self.spans.get(name, (0, 0, 0))[0]

    def total_s(self, *names: str) -> float:
        """Summed durations of the spans ``names``, in seconds."""
        return sum(self.spans.get(k, (0, 0, 0))[1] for k in names) / 1e9

    def self_s(self, *names: str) -> float:
        """Summed self times of the spans ``names``, in seconds: what they
        cover on their own threads, less the spans opened inside them."""
        return sum(self.spans.get(k, (0, 0, 0))[2] for k in names) / 1e9

    def count(self, name: str) -> int:
        return self.counts.get(name, 0)


_Annotation = jax.profiler.TraceAnnotation


class span:
    """``with span(name):`` — a host span in the open call's record and,
    while a profiler traces, in its trace."""

    __slots__ = ("name", "_ann", "_rec", "_child", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        self._ann = _Annotation(self.name) if _Annotation.is_enabled() else None
        if self._ann is not None:
            self._ann.__enter__()
        self._rec = _open[-1] if _open else None
        if self._rec is not None:
            self._child = 0  # ns of the spans opened inside this one
            _local.stack.append(self)
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        if rec is not None:
            dt = time.perf_counter_ns() - self._t0
            stack = _local.stack
            stack.pop()
            if stack:
                stack[-1]._child += dt
            with _lock:
                s = rec.spans.get(self.name)
                if s is None:
                    s = rec.spans[self.name] = [0, 0, 0]
                s[0] += 1
                s[1] += dt
                s[2] += dt - self._child
        if self._ann is not None:
            self._ann.__exit__(*exc)


def distinct(name: str, key) -> None:
    """Count ``key`` toward the open call's counter ``name`` once per call:
    the counter reads the number of distinct keys seen."""
    if _open:
        rec = _open[-1]
        with _lock:
            seen = rec._seen.setdefault(name, set())
            seen.add(key)
            rec.counts[name] = len(seen)


@contextlib.contextmanager
def call(name: str):
    """The root of one call: a fresh record, spanned as ``name``, kept as
    ``last(name)`` once the call is whole."""
    rec = Record(name)
    _open.append(rec)
    try:
        with span(name):
            yield rec
    finally:
        _open.remove(rec)
    _last[name] = rec


def last(name: str) -> Record | None:
    """The record of the most recent whole call ``name``, or None."""
    return _last.get(name)
