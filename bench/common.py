"""Pieces every cell shares: the compile cache, the compile count, the
benchmark's own spans, and what a per-layer metric reader is given."""

from __future__ import annotations

import functools
import threading

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_hits"


def enable_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at a fixed path, every program
    cached, however small or quick to compile."""
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCount:
    """Counts programs the process had to compile or load from the cache:
    each is a program that was not in memory when it was called."""

    def __init__(self) -> None:
        self.n = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _bump(self) -> None:
        with self._lock:
            self.n += 1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self._bump()

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_LOAD_EVENT:
            self._bump()

    def snapshot(self) -> int:
        return self.n


def span(name: str):
    """A host span in the profiler's trace (free when nothing traces)."""
    return jax.profiler.TraceAnnotation(name)


def wrap(obj, attr: str, name: str) -> None:
    """Put a benchmark span around every call of ``obj.attr`` (once)."""
    fn = getattr(obj, attr)
    if getattr(fn, "_bench_span", None) == name:
        return

    @functools.wraps(fn)
    def spanned(*a, **kw):
        with span(name):
            return fn(*a, **kw)

    spanned._bench_span = name
    setattr(obj, attr, spanned)


class Context:
    """What a per-layer metric reader gets: the reduced trace, the driver's
    counters over the whole window, the number of whole calls the trace
    covers, the table of device peaks, and the device kind."""

    def __init__(self, trace, counters: dict, n_calls: int, peaks: dict, kind: str):
        self.trace = trace
        self.counters = counters
        self.n_calls = n_calls
        self.peaks = peaks
        self.kind = kind

    def peak(self, key: str) -> float:
        """A published peak of this device; an unknown device is an error."""
        if self.kind not in self.peaks["devices"]:
            raise KeyError(f"no peaks for device kind {self.kind!r} in bench/peaks.json")
        return float(self.peaks["devices"][self.kind][key])
