"""Step-function memory allocations, failure detection and wastage accounting.

Units: memory in **MiB**, time in **seconds**, wastage in **GiB*s**
(1 GiB*s = 1024 MiB*s).  The paper's 100 MB minimum allocation and GB-seconds
wastage metric map onto these directly.

An allocation is the paper's Eq. (1): a monotonically non-decreasing step
function given by ``k`` values ``v`` and ``k`` right-open time boundaries
``r`` (``r_k`` = predicted runtime).  Past ``r_k`` the allocation holds ``v_k``
— the schedule must cover tasks that run longer than predicted (this is why
the runtime model is offset *downward*).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

# The event-timeline primitives (demand profiles, probe expressions, the
# incremental profile) live in repro.core.timeline — the single
# implementation every packer consumes.  Re-exported here because
# allocations and their demand semantics are one API surface to callers.
from repro.core.timeline import (  # noqa: F401  (re-exports)
    IncrementalDemandProfile,
    Timeline,
    demand_exceeds,
    demand_exceeds_many,
    pack_steps,
    plan_profile_events,
    quantize_steps,
    shared_probe_set,
    step_demand_profile,
)

MIB_PER_GIB = 1024.0


@dataclasses.dataclass
class StepAllocation:
    """A k-step allocation schedule.

    Attributes:
      boundaries: (k,) seconds; right edges of each segment, non-decreasing.
      values: (k,) MiB; non-decreasing (enforced by the predictor).
    """

    boundaries: np.ndarray
    values: np.ndarray

    @property
    def k(self) -> int:
        return len(self.values)

    def at(self, t: np.ndarray) -> np.ndarray:
        """Allocation at time(s) ``t`` (vectorized); holds v_k past the end."""
        idx = np.searchsorted(self.boundaries, np.asarray(t), side="left")
        idx = np.minimum(idx, self.k - 1)
        return self.values[idx]

    def segment_of(self, t: float) -> int:
        return int(min(np.searchsorted(self.boundaries, t, side="left"), self.k - 1))

    def with_retry(self, failed_segment: int, strategy: str, factor: float) -> "StepAllocation":
        """Paper Sec. III-D: selective bumps only the failed segment, partial
        bumps the failed segment and every later one."""
        v = self.values.copy()
        if strategy == "selective":
            v[failed_segment] = v[failed_segment] * factor
        elif strategy == "partial":
            v[failed_segment:] = v[failed_segment:] * factor
        else:
            raise ValueError(f"unknown retry strategy: {strategy!r}")
        # Re-impose monotonicity (a selective bump can break it upward only,
        # which is fine; but keep the invariant explicit).
        v = np.maximum.accumulate(v)
        return StepAllocation(self.boundaries.copy(), v)


def static_allocation(value_mib: float, runtime_s: float) -> StepAllocation:
    """A single-value allocation (every baseline is the k=1 special case)."""
    return StepAllocation(np.asarray([runtime_s], dtype=np.float64), np.asarray([value_mib], dtype=np.float64))


# ---------------------------------------------------------------------------
# Execution outcome scoring (reference numpy path; the Pallas ``wastage``
# kernel and the jnp batch path below are the accelerated equivalents).
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AttemptOutcome:
    failed: bool
    failure_index: int  # sample index of the OOM kill (-1 on success)
    wastage_gib_s: float  # GiB*s wasted by this attempt
    alloc_gib_s: float  # total allocation integral of the attempt


def score_attempt_np(series_mib: np.ndarray, interval_s: float, alloc: StepAllocation) -> AttemptOutcome:
    """Score one attempt of one execution against an allocation schedule.

    Failure: first sample where usage exceeds the allocation.  A failed
    attempt wastes its *entire* allocation up to (and including) the kill
    sample — nothing useful was produced.  A successful attempt wastes
    ``alloc(t) - usage(t)`` over its true runtime.
    """
    y = np.asarray(series_mib, dtype=np.float64)
    t = (np.arange(len(y)) + 0.5) * interval_s  # sample midpoints
    a = alloc.at(t)
    over = y > a
    if over.any():
        fi = int(np.argmax(over))
        waste = attempt_wastage_np(a, y, fi, interval_s)
        return AttemptOutcome(True, fi, waste, waste)
    alloc_int = float(np.sum(a) * interval_s)
    return AttemptOutcome(False, -1, attempt_wastage_np(a, y, -1, interval_s), alloc_int / MIB_PER_GIB)


def attempt_wastage_np(a: np.ndarray, y: np.ndarray, failure_index: int, interval_s: float) -> float:
    """Wastage (GiB*s) of one attempt whose outcome is known, in float64:
    the allocation ``a`` (sampled at the sample midpoints) up to and
    including the kill sample ``failure_index``, or ``a - y`` over the whole
    run when it is -1.  ``score_attempt_np``'s accounting, shared with the
    device ladder's host-side scoring (``batch_engine.compute_cluster_ladders``)
    so that equal allocations give bit-equal wastage."""
    if failure_index >= 0:
        return float(np.sum(a[: failure_index + 1]) * interval_s) / MIB_PER_GIB
    return float(np.sum(a - y) * interval_s) / MIB_PER_GIB


@dataclasses.dataclass
class AttemptLadder:
    """The precomputed retry ladder of one execution under one method.

    This is the row format the batched cluster scheduler consumes: the device
    engine (``repro.sim.jax_sim.simulate_task_ladders``) scores every attempt
    of every queued execution up front, and the host-side event loop only
    places these rows against node step profiles.  Attempt ``a``'s allocation
    shares the prediction's boundaries; ``failure_index[a]`` is its OOM-kill
    sample (-1 on the final, successful attempt) and ``wastage_gib_s[a]`` its
    wastage under the same accounting as ``score_attempt_np``.
    """

    boundaries: np.ndarray  # (k,) seconds
    values: np.ndarray  # (A, k) MiB, one row per attempt
    failure_index: np.ndarray  # (A,) int, -1 = success
    wastage_gib_s: np.ndarray  # (A,)
    n_attempts: int  # recorded attempts (retries + 1)

    def alloc(self, attempt: int) -> StepAllocation:
        return StepAllocation(self.boundaries, self.values[attempt])

    def run_time_s(self, attempt: int, duration_s: float, interval_s: float) -> float:
        """Node occupancy of one attempt: full duration on success, up to and
        including the kill sample on failure (as the cluster oracle counts)."""
        fi = int(self.failure_index[attempt])
        return duration_s if fi < 0 else (fi + 1) * interval_s

    @property
    def total_wastage_gib_s(self) -> float:
        return float(self.wastage_gib_s[: self.n_attempts].sum())


def run_with_retries_np(
    series_mib: np.ndarray,
    interval_s: float,
    alloc: StepAllocation,
    strategy: str,
    factor: float,
    node_cap_mib: float,
    max_retries: int = 64,
) -> tuple[float, int, StepAllocation]:
    """Run one execution to success, applying the retry strategy on failure.

    Returns (total wastage GiB*s across all attempts, #retries, final alloc).
    Allocations are capped at the node's memory; a task whose true peak
    exceeds the node cap cannot succeed and raises (the trace generators never
    produce one).
    """
    total = 0.0
    retries = 0
    peak = float(np.max(series_mib))
    if peak > node_cap_mib:
        raise ValueError(f"task peak {peak} MiB exceeds node capacity {node_cap_mib} MiB")
    cur = StepAllocation(alloc.boundaries.copy(), np.minimum(alloc.values, node_cap_mib))
    while True:
        out = score_attempt_np(series_mib, interval_s, cur)
        total += out.wastage_gib_s
        if not out.failed:
            return total, retries, cur
        retries += 1
        if retries > max_retries:
            raise RuntimeError("retry loop did not converge")
        t_fail = (out.failure_index + 0.5) * interval_s
        seg = cur.segment_of(t_fail)
        cur = cur.with_retry(seg, strategy, factor)
        cur = StepAllocation(cur.boundaries, np.minimum(cur.values, node_cap_mib))


# ---------------------------------------------------------------------------
# Vectorized jnp batch scorer (same semantics, padded batches).  Used by the
# benchmark harness and cross-checked against the numpy path in tests; its
# inner reduction is what kernels/wastage implements as a Pallas kernel.
# ---------------------------------------------------------------------------


def attempt_outcomes_batch(
    y: jnp.ndarray,
    lengths: jnp.ndarray,
    interval_s,
    boundaries: jnp.ndarray,
    values: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Score B attempts at once.

    Args:
      y: (B, T) padded series (MiB).
      lengths: (B,) valid counts.
      interval_s: scalar monitoring interval.
      boundaries: (B, k) seconds.
      values: (B, k) MiB.

    Returns:
      wastage_gib_s: (B,) per-attempt wastage (failed attempts waste their
        allocation up to the kill).
      failure_index: (B,) first OOM sample, -1 for success.
    """
    B, T = y.shape
    k = values.shape[-1]
    t = (jnp.arange(T)[None, :] + 0.5) * interval_s  # (1, T)
    # alloc(t): Eq. (1) is right-open (f = v_s for r_{s-1} < t <= r_s); v_k past end.
    seg_idx = jnp.sum(t[:, :, None] > boundaries[:, None, :], axis=-1)  # (B, T)
    seg_idx = jnp.minimum(seg_idx, k - 1)
    a = jnp.take_along_axis(values, seg_idx.reshape(B, -1), axis=-1).reshape(B, T)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    over = (y > a) & valid
    any_fail = jnp.any(over, axis=-1)
    fail_idx = jnp.where(any_fail, jnp.argmax(over, axis=-1), -1)
    pos = jnp.arange(T)[None, :]
    # success: sum (a - y) over valid; failure: sum a over [0, fail_idx].
    succ_w = jnp.sum(jnp.where(valid, a - y, 0.0), axis=-1)
    fail_w = jnp.sum(jnp.where(pos <= fail_idx[:, None], a, 0.0), axis=-1)
    waste = jnp.where(any_fail, fail_w, succ_w) * interval_s / MIB_PER_GIB
    return waste, fail_idx
