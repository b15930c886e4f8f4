"""Trace-time audit: retrace/recompile counting + jaxpr structure checks.

This is layer 2 of repro.analysis — checks that need a live jax rather
than an AST.  jax is imported lazily so the lint layer (and its CI step)
never pays for it.

``CompileCounter`` listens on the same jax monitoring events
``tests/test_compile_cache.py`` taps:

* ``/jax/core/compile/jaxpr_trace_duration``  — one per retrace,
* ``/jax/core/compile/backend_compile_duration`` — one per backend
  (XLA) compile,
* ``/jax/compilation_cache/cache_hits`` / ``cache_misses`` — persistent
  compile-cache traffic.

Warm re-invocations of the repo's device programs at already-seen bucket
shapes must produce ZERO trace and compile events — that is the
`fine_bucket`/`pad_rows` padding contract the PR 6 speedups rest on, and
what the bench canaries and ``tests/test_retrace.py`` enforce via
``no_recompiles``.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_SUBSTR = "compilation_cache/cache_hit"
CACHE_MISS_SUBSTR = "compilation_cache/cache_miss"


def _monitoring():
    from jax._src import monitoring

    return monitoring


class CompileCounter:
    """Context manager counting retraces / backend compiles / cache traffic.

    >>> with CompileCounter() as cc:
    ...     program(*args)
    >>> assert cc.traces == 0 and cc.compiles == 0

    Listener registration is global and this object unregisters itself on
    exit, so nesting and sequential use are both fine; concurrent use
    from multiple threads counts events from all of them (dispatches from
    `_map_concurrent` worker threads are attributed to whichever counter
    is open — exactly what the bench audit wants).
    """

    def __init__(self) -> None:
        self.traces = 0
        self.compiles = 0
        self.compile_s = 0.0  # summed backend-compile durations
        self.cache_hits = 0
        self.cache_misses = 0
        self._active = False

    def _on_duration(self, event: str, duration: float, **kw: Any) -> None:
        if not self._active:
            return
        if event == TRACE_EVENT:
            self.traces += 1
        elif event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event: str, **kw: Any) -> None:
        if not self._active:
            return
        if CACHE_HIT_SUBSTR in event:
            self.cache_hits += 1
        elif CACHE_MISS_SUBSTR in event:
            self.cache_misses += 1

    def __enter__(self) -> "CompileCounter":
        mon = _monitoring()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        self._active = True
        return self

    def __exit__(self, *exc: object) -> None:
        self._active = False
        mon = _monitoring()
        # The unregister helpers are test-support API; fall back to the
        # _active flag (listener stays registered but inert) if a future
        # jax drops them.
        for name, cb in (
            ("_unregister_event_duration_listener_by_callback", self._on_duration),
            ("_unregister_event_listener_by_callback", self._on_event),
        ):
            fn = getattr(mon, name, None)
            if fn is not None:
                try:
                    fn(cb)
                except ValueError:
                    pass

    def snapshot(self) -> dict[str, int]:
        return {
            "traces": self.traces,
            "compiles": self.compiles,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


class RecompileError(AssertionError):
    """A warm section retraced or recompiled when it must not."""


@contextlib.contextmanager
def no_recompiles(
    what: str = "warm section", *, allow_traces: int = 0, allow_compiles: int = 0
) -> Iterator[CompileCounter]:
    """Assert the wrapped block performs no (or at most the allowed number
    of) retraces/backend compiles.  The repo's padding contract means any
    warm re-invocation at an already-seen bucket shape must pass this.
    """
    with CompileCounter() as cc:
        yield cc
    if cc.traces > allow_traces or cc.compiles > allow_compiles:
        raise RecompileError(
            f"{what}: {cc.traces} retrace(s) and {cc.compiles} backend "
            f"compile(s) in a section that allows {allow_traces}/{allow_compiles} "
            "— a shape fell off the fine_bucket/pad_rows padding contract or a "
            "config context changed between calls"
        )


# ---------------------------------------------------------------------------
# jaxpr structure checks
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CarryReport:
    """One lax.scan (or while_loop) carry slot."""

    primitive: str
    index: int
    shape: tuple
    dtype: str
    weak_type: bool = False


@dataclass(frozen=True)
class ConstReport:
    """One closure-captured constant baked into the jaxpr."""

    shape: tuple
    dtype: str
    nbytes: int


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    import jax.extend.core as jcore

    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            if isinstance(item, jcore.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, jcore.Jaxpr):
                yield item


def _iter_eqns(jaxpr: Any) -> Iterator[Any]:
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from _iter_eqns(sub)


def _make_jaxpr(fn: Callable, *args: Any, **kwargs: Any) -> Any:
    import jax

    return jax.make_jaxpr(fn)(*args, **kwargs)


def scan_carries(fn: Callable, *args: Any, **kwargs: Any) -> list[CarryReport]:
    """Trace ``fn(*args, **kwargs)`` and report every scan/while carry slot
    (recursing through nested jit/scan/cond sub-jaxprs)."""
    closed = _make_jaxpr(fn, *args, **kwargs)
    reports: list[CarryReport] = []
    for eqn in _iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name == "scan":
            nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
            carry_vars = eqn.invars[nc : nc + nk]
        elif name == "while":
            nc = eqn.params.get("cond_nconsts", 0) + eqn.params.get("body_nconsts", 0)
            carry_vars = eqn.invars[nc:]
        else:
            continue
        for i, v in enumerate(carry_vars):
            aval = v.aval
            reports.append(
                CarryReport(
                    primitive=name,
                    index=i,
                    shape=tuple(getattr(aval, "shape", ())),
                    dtype=str(getattr(aval, "dtype", "")),
                    weak_type=bool(getattr(aval, "weak_type", False)),
                )
            )
    return reports


def check_scan_carry_stability(
    fn: Callable,
    *args: Any,
    forbid_dtypes: tuple[str, ...] = (),
    **kwargs: Any,
) -> list[str]:
    """Check every scan/while carry for dtype discipline.

    Tracing itself already guarantees shape/dtype stability *within* one
    scan (jax rejects mismatched carries), so the check here is the
    cross-program one tracing can't do: no carry slot may use a forbidden
    dtype — e.g. ``forbid_dtypes=("float32",)`` under the x64 parity
    ladder, where an f32 carry silently truncates every accumulation
    step.  Returns a list of violation strings (empty = clean).
    """
    problems: list[str] = []
    for rep in scan_carries(fn, *args, **kwargs):
        if rep.dtype in forbid_dtypes:
            problems.append(
                f"{rep.primitive} carry[{rep.index}] has forbidden dtype "
                f"{rep.dtype} (shape {rep.shape})"
            )
    return problems


def closure_constants(
    fn: Callable, *args: Any, min_bytes: int = 1 << 20, **kwargs: Any
) -> list[ConstReport]:
    """Flag giant closure-captured constants baked into the traced program.

    A large array captured by closure (instead of passed as an argument)
    is embedded in every specialization of the executable: it bloats the
    persistent compile cache, defeats donation, and re-uploads per
    compile.  Returns consts of at least ``min_bytes``, largest first.
    """
    import numpy as np

    closed = _make_jaxpr(fn, *args, **kwargs)

    def _consts_of(closed_or_jaxpr: Any) -> Iterator[Any]:
        consts = getattr(closed_or_jaxpr, "consts", None)
        if consts:
            yield from consts

    found: list[ConstReport] = []
    seen: set[int] = set()
    stack = [closed]
    while stack:
        item = stack.pop()
        for const in _consts_of(item):
            if id(const) in seen:
                continue
            seen.add(id(const))
            arr = np.asarray(const)
            if arr.nbytes >= min_bytes:
                found.append(
                    ConstReport(shape=tuple(arr.shape), dtype=str(arr.dtype), nbytes=arr.nbytes)
                )
        jaxpr = getattr(item, "jaxpr", item)
        for eqn in getattr(jaxpr, "eqns", ()):
            import jax.extend.core as jcore

            for v in eqn.params.values():
                vals = v if isinstance(v, (list, tuple)) else (v,)
                for sub in vals:
                    if isinstance(sub, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                        stack.append(sub)
    return sorted(found, key=lambda r: -r.nbytes)
