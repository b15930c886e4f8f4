"""Batched evaluation engine: the paper's whole fig7 grid as device programs.

``simulator.simulate_suite`` walks the (task type x method x training
fraction) grid as a 4-deep Python loop — one ``simulate_task`` call per cell,
each dispatching numpy per execution.  This engine evaluates the same grid as
a handful of device dispatches:

1. The corpus is packed once into bucket-padded ``(L, B, T)`` batches
   (``traces.pack_traces``), bounding padding waste and compiled-shape count.
2. Each bucket runs ``jax_sim.simulate_task_methods`` vmapped over lanes: one
   multi-method ``lax.scan`` per lane scores every method on every execution.
3. Training fractions are pure aggregation: the model-state trajectory does
   not depend on where the train/test split falls (see jax_sim module
   docstring), so each fraction is a host-side slice of the same per-execution
   outcomes — the fraction axis is free.

The same packing serves the cluster scheduler: ``compute_cluster_ladders``
records every queued execution's full retry ladder (attempt -> allocation,
failure index) for all policies in one pass and scores each attempt's
wastage on the host in float64, so
``repro.sim.cluster.run_cluster_batched``'s host loop only does placement
(per-task parity with the sequential scheduler in tests/test_cluster_batch.py).

The sequential simulator stays the cross-check oracle: with
``error_mode="progressive"`` — or ``error_mode="insample"`` and an explicit
``insample_window`` — both engines agree per execution (see
tests/test_batch_engine.py, tests/test_predictor_zoo.py).  Differences to
the oracle elsewhere:

* Insample offsets need an explicit history bound: the engine carries a
  fixed-size observation ring (see jax_sim module docstring), so the
  *unbounded* ``KSegmentsConfig(insample_window=None)`` default is rejected
  here — pick a window (the sequential oracle with the same window is the
  parity twin).
* PPM considers every observed peak as a candidate instead of capping at
  ``TovarPPM.MAX_CANDIDATES`` quantiles (matters only past 256 distinct
  peaks).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.allocation import AttemptLadder, attempt_wastage_np
from repro.core.ksegments import KSegmentsConfig

# The shared probe/packing device programs live in repro.sim.device_timeline
# (one implementation for the admission and placement engines); re-exported
# here because callers historically found them on the batch engine.
from repro.sim.device_timeline import (  # noqa: F401  (re-exports)
    candidate_probe_parts,
    pad_rows,
    schedule_epoch,
)
from repro.sim.jax_sim import MAX_RETRIES, ENGINE_METHODS, simulate_task_ladders, simulate_task_methods
from repro.sim.simulator import SimConfig, TaskResult
from repro.sim.traces import TaskTrace, WorkflowTrace, bucket_size, pack_traces

GRID_METHODS = tuple(m for m in ENGINE_METHODS if m != "witt-lr-max")


def _map_concurrent(fn, items: list):
    """Map ``fn`` over ``items`` on a small thread pool, preserving order.

    Bucket programs compile and execute with the GIL released, so the cold
    path compiles shapes concurrently (the "warm bucket shapes" half of the
    cold-start fix; the other half is the persistent cache above) and the
    warm path overlaps the buckets' device dispatches."""
    if len(items) <= 1:
        return [fn(it) for it in items]
    # one worker per core: XLA's own intra-op pool saturates the cores, and
    # oversubscribing python threads just adds dispatch-lock contention
    with ThreadPoolExecutor(max_workers=min(len(items), os.cpu_count() or 2)) as ex:
        return list(ex.map(fn, items))


@functools.lru_cache(maxsize=None)
def _lane_batched(
    methods: tuple[str, ...],
    k: int,
    interval_s: float,
    factor: float,
    floor_mib: float,
    cap_mib: float,
    error_mode: str = "progressive",
    insample_window: int = 0,
):
    """Compiled (lanes-vmapped) engine for one static configuration."""
    f = functools.partial(
        simulate_task_methods,
        methods=methods,
        k=k,
        interval_s=interval_s,
        factor=factor,
        floor_mib=floor_mib,
        cap_mib=cap_mib,
        error_mode=error_mode,
        insample_window=insample_window,
    )
    return jax.jit(jax.vmap(f, in_axes=(0, 0, 0, 0, None)))


@functools.lru_cache(maxsize=None)
def _ksweep_batched(
    method: str,
    k_max: int,
    interval_s: float,
    factor: float,
    floor_mib: float,
    cap_mib: float,
    error_mode: str = "progressive",
    insample_window: int = 0,
):
    """Compiled engine vmapped over the traced segment count (fig8)."""
    f = functools.partial(
        simulate_task_methods,
        methods=(method,),
        k=k_max,
        interval_s=interval_s,
        factor=factor,
        floor_mib=floor_mib,
        cap_mib=cap_mib,
        error_mode=error_mode,
        insample_window=insample_window,
    )
    return jax.jit(jax.vmap(f, in_axes=(None, None, None, None, 0)))


@functools.lru_cache(maxsize=None)
def _ladder_batched(
    methods: tuple[str, ...],
    k: int,
    interval_s: float,
    factor: float,
    floor_mib: float,
    cap_mib: float,
    max_attempts: int,
    x64: bool,
    error_mode: str = "progressive",
    insample_window: int = 0,
):
    """Compiled (lanes-vmapped) retry-ladder recorder for one static config."""
    f = functools.partial(
        simulate_task_ladders,
        methods=methods,
        k=k,
        interval_s=interval_s,
        factor=factor,
        floor_mib=floor_mib,
        cap_mib=cap_mib,
        max_attempts=max_attempts,
        x64=x64,
        error_mode=error_mode,
        insample_window=insample_window,
    )
    return jax.jit(jax.vmap(f, in_axes=(0, 0, 0, 0, None)))


def _check_methods(methods) -> tuple[str, ...]:
    unknown = [m for m in methods if m not in ENGINE_METHODS]
    if unknown:
        raise ValueError(f"batch engine does not implement {unknown!r}; available: {ENGINE_METHODS}")
    return tuple(methods)


def _engine_error_mode(kcfg: KSegmentsConfig) -> tuple[str, int]:
    """Map a ``KSegmentsConfig`` onto the device engine's static error-mode
    pair ``(error_mode, insample_window)``.

    Progressive normalizes the window to 0 (one canonical jit cache key).
    Insample requires the bound to be explicit: the device engine carries a
    fixed-size observation ring, so the sequential default
    ``insample_window=None`` (unbounded refit history) has no device twin —
    callers pick a window and cross-check against the sequential oracle run
    with the same ``insample_window``.
    """
    if kcfg.error_mode == "progressive":
        return "progressive", 0
    if kcfg.insample_window is None:
        raise ValueError(
            "the batch engine's insample mode needs an explicit history bound: "
            "set KSegmentsConfig(insample_window=W) (the sequential oracle with "
            "the same window is the parity twin), or use error_mode='progressive'"
        )
    return "insample", int(kcfg.insample_window)


def simulate_grid(
    workflows: list[WorkflowTrace],
    methods: tuple[str, ...] = GRID_METHODS,
    train_fracs: tuple[float, ...] = (0.25, 0.5, 0.75),
    cfg: SimConfig | None = None,
    grid_stats: dict | None = None,
) -> list[TaskResult]:
    """Batched twin of ``simulator.simulate_suite``: same grid, same
    ``TaskResult`` rows (ordered workflow -> task -> fraction -> method), but
    every (method x fraction) cell of a task comes from one scan pass.

    ``grid_stats``, when given, receives ``program_calls``: the bucket
    programs this call dispatched, counted as they are called."""
    cfg = cfg or SimConfig()
    methods = _check_methods(methods)
    kcfg = cfg.ksegments
    emode, ewin = _engine_error_mode(kcfg)
    fn = _lane_batched(
        methods, kcfg.k, kcfg.interval_s, kcfg.retry_factor, kcfg.floor_mib, cfg.node_cap_mib, emode, ewin
    )

    per_task: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    tasks = [t for wf in workflows for t in wf.eligible_tasks(cfg.min_executions)]

    calls: list[int] = []  # appended per dispatch (thread-safe under the GIL)

    def _run(batch):
        waste, retries = fn(
            jnp.asarray(batch.x),
            jnp.asarray(batch.y),
            jnp.asarray(batch.lengths),
            jnp.asarray(batch.default_mib, jnp.float32),
            jnp.asarray(kcfg.k, jnp.int32),
        )
        calls.append(1)
        return np.asarray(waste, dtype=np.float64), np.asarray(retries)  # (L, M, B)

    batches = pack_traces(tasks)
    outs = _map_concurrent(_run, batches)
    if grid_stats is not None:
        grid_stats["program_calls"] = len(calls)
    for batch, (waste, retries) in zip(batches, outs):
        for li, trace in enumerate(batch.tasks):
            n = int(batch.n_execs[li])
            per_task[id(trace)] = (waste[li, :, :n], retries[li, :, :n])

    results = []
    for wf in workflows:
        for trace in wf.eligible_tasks(cfg.min_executions):
            w, r = per_task[id(trace)]
            n = trace.n_executions
            for frac in train_fracs:
                n_train = int(n * frac)
                for mi, m in enumerate(methods):
                    results.append(
                        TaskResult(
                            task=trace.name,
                            workflow=trace.workflow,
                            method=m,
                            train_frac=frac,
                            n_train=n_train,
                            n_test=n - n_train,
                            wastage_gib_s=w[mi, n_train:],
                            retries=r[mi, n_train:],
                        )
                    )
    return results


@dataclasses.dataclass
class TaskLadders:
    """All methods' retry ladders for one task type, host-side (float64).

    Arrays are indexed [method, execution, attempt(, segment)]; see
    ``jax_sim.simulate_task_ladders`` for semantics.  ``row`` materializes one
    (method, execution) cell as the ``AttemptLadder`` the cluster scheduler
    consumes.
    """

    methods: tuple[str, ...]
    boundaries: np.ndarray  # (M, B, k)
    values: np.ndarray  # (M, B, A, k)
    failure_index: np.ndarray  # (M, B, A)
    wastage_gib_s: np.ndarray  # (M, B, A)
    n_attempts: np.ndarray  # (M, B)

    def row(self, method: str, execution: int) -> AttemptLadder:
        mi = self.methods.index(method)
        n = int(self.n_attempts[mi, execution])
        if int(self.failure_index[mi, execution, n - 1]) >= 0:
            hint = (
                "raise max_attempts"
                if self.values.shape[2] <= MAX_RETRIES
                else f"the engine caps retries at {MAX_RETRIES}; the task cannot be scheduled"
            )
            raise RuntimeError(
                f"retry ladder of execution {execution} under {method!r} did not "
                f"converge within the recorded {self.values.shape[2]} attempts; {hint}"
            )
        return AttemptLadder(
            boundaries=self.boundaries[mi, execution],
            values=self.values[mi, execution],
            failure_index=self.failure_index[mi, execution],
            wastage_gib_s=self.wastage_gib_s[mi, execution],
            n_attempts=n,
        )


def compute_cluster_ladders(
    tasks: list[TaskTrace],
    methods: tuple[str, ...],
    node_cap_mib: float,
    kcfg: KSegmentsConfig | None = None,
    max_attempts: int = 32,
    x64: bool = False,
) -> dict[tuple[str, str], TaskLadders]:
    """Precompute every execution's retry ladder for every method, batched.

    The cluster scheduler's per-task work — predict, score attempts, observe —
    is exactly the online recurrence ``simulate_task_ladders`` expresses, so
    the whole corpus runs as one bucket-padded vmapped program per shape
    (``pack_traces``).  Returns ``{(workflow, task name): TaskLadders}``; any
    training fraction is a post-hoc row slice, as in ``simulate_grid``.

    k-Segments error offsets follow ``kcfg.error_mode`` — progressive, or
    bounded-history insample with an explicit ``kcfg.insample_window`` (see
    ``_engine_error_mode``); cross-checks must run the sequential oracle with
    the same mode and window.

    ``x64=True`` runs the ladder scan in float64 under ``jax.enable_x64``
    (~1.5x ladder cost): a CPU reference for the rare corpus where a float32
    prediction lands within an ulp of a comparison against the float64 numpy
    oracle (tests/test_cluster_placement.py pins the known boundary seed).
    No TPU path enters it: the device ladder is float32.
    """
    kcfg = kcfg or KSegmentsConfig()
    methods = _check_methods(methods)
    for t in tasks:
        if t.interval_s != kcfg.interval_s:
            raise ValueError(
                f"trace {t.name!r} interval {t.interval_s} != config interval {kcfg.interval_s}; "
                "the ladder program bakes one static monitoring interval"
            )
    emode, ewin = _engine_error_mode(kcfg)
    fn = _ladder_batched(
        methods, kcfg.k, kcfg.interval_s, kcfg.retry_factor, kcfg.floor_mib, node_cap_mib, max_attempts, x64, emode, ewin
    )
    out: dict[tuple[str, str], TaskLadders] = {}
    dt = jnp.float64 if x64 else jnp.float32

    def _run(batch):
        # Inputs are cast to the working dtype on the host (under the x64
        # context ``jnp.asarray`` would silently keep the float64 trace
        # arrays).
        with jax.enable_x64(x64):
            with obs.span("sched.ladder.dispatch"):
                tbl = fn(
                    jnp.asarray(batch.x.astype(dt)),
                    jnp.asarray(batch.y.astype(dt)),
                    jnp.asarray(batch.lengths),
                    jnp.asarray(batch.default_mib, dt),
                    jnp.asarray(kcfg.k, jnp.int32),
                )
            with obs.span("sched.ladder.fetch"):
                return {name: np.asarray(v) for name, v in tbl.items()}

    with obs.span("sched.ladder.pack"):
        batches = pack_traces(tasks)
    tables = _map_concurrent(_run, batches)
    with obs.span("sched.ladder.unpack"):
        for batch, tbl in zip(batches, tables):
            for li, trace in enumerate(batch.tasks):
                n = int(batch.n_execs[li])
                bnd = tbl["boundaries"][li, :, :n].astype(np.float64)
                vals = tbl["values"][li, :, :n].astype(np.float64)
                fi = tbl["failure_index"][li, :, :n]
                natt = tbl["n_attempts"][li, :, :n]
                out[(trace.workflow, trace.name)] = TaskLadders(
                    methods=methods,
                    boundaries=bnd,
                    values=vals,
                    failure_index=fi,
                    wastage_gib_s=_host_wastage(trace, bnd, vals, fi, natt),
                    n_attempts=natt,
                )
    return out


def _host_wastage(trace: TaskTrace, bnd, vals, fi, natt) -> np.ndarray:
    """Every recorded attempt's wastage (M, B, A), scored on the host in
    float64 from the allocation and kill sample the ladder decided —
    ``score_attempt_np``'s accounting (``attempt_wastage_np``).  The ladder
    decides in its working dtype; the paper's metric is summed as the
    sequential oracle sums it, so equal allocations give bit-equal wastage
    whatever that dtype is."""
    M, B, A = fi.shape
    out = np.zeros((M, B, A))
    with obs.span("sched.ladder.host_wastage"):
        for e in range(B):
            y = np.asarray(trace.executions[e].series, dtype=np.float64)
            t = (np.arange(len(y)) + 0.5) * trace.interval_s
            for m in range(M):
                na = int(natt[m, e])
                # StepAllocation.at for every attempt at once: they share boundaries
                seg = np.minimum(np.searchsorted(bnd[m, e], t, side="left"), bnd.shape[-1] - 1)
                a_all = vals[m, e, :na][:, seg]
                for a in range(na):
                    out[m, e, a] = attempt_wastage_np(a_all[a], y, int(fi[m, e, a]), trace.interval_s)
    return out


def simulate_ksweep(
    trace: TaskTrace,
    ks: tuple[int, ...],
    train_frac: float = 0.5,
    cfg: SimConfig | None = None,
    method: str = "ksegments-selective",
) -> dict[int, TaskResult]:
    """Fig. 8: one task's wastage as a function of k, as a single vmap over
    the traced segment count (static shapes sized by max(ks))."""
    cfg = cfg or SimConfig()
    kcfg = cfg.ksegments
    emode, ewin = _engine_error_mode(kcfg)
    fn = _ksweep_batched(method, max(ks), kcfg.interval_s, kcfg.retry_factor, kcfg.floor_mib, cfg.node_cap_mib, emode, ewin)
    x, y, lengths = trace.padded()
    waste, retries = fn(
        jnp.asarray(x),
        jnp.asarray(y),
        jnp.asarray(lengths),
        jnp.asarray(trace.default_mib, jnp.float32),
        jnp.asarray(list(ks), jnp.int32),
    )
    waste = np.asarray(waste, dtype=np.float64)  # (K, 1, B)
    retries = np.asarray(retries)
    n = trace.n_executions
    n_train = int(n * train_frac)
    return {
        kv: TaskResult(
            task=trace.name,
            workflow=trace.workflow,
            method=method,
            train_frac=train_frac,
            n_train=n_train,
            n_test=n - n_train,
            wastage_gib_s=waste[ki, 0, n_train:],
            retries=retries[ki, 0, n_train:],
        )
        for ki, kv in enumerate(ks)
    }
