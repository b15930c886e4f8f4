"""What the per-layer metrics read from the program's own record of a call
(``repro.obs``): the spans and counters of the last whole ``sched.call`` of
the window.  A program without that record gives None, and the metric is
left out of the result."""

from __future__ import annotations


def last_call():
    """The program's record of its last whole ``sched.call``, or None."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.last("sched.call")


def span_ms(*names: str) -> float | None:
    """Self time of the spans ``names`` in the last whole call, in ms (self
    time, so a span nested in another of ``names`` counts once); None where
    none of them closed."""
    rec = last_call()
    if rec is None or not any(rec.n(k) for k in names):
        return None
    return rec.self_s(*names) * 1e3


def counter(*names: str) -> int | None:
    """The sum of the counters ``names`` in the last whole call; None where
    none of them was counted."""
    rec = last_call()
    if rec is None or not any(k in rec.counts for k in names):
        return None
    return sum(rec.count(k) for k in names)
