"""Retrace-count regression tests: the fine_bucket/pad_rows padding contract.

PR 6's speedups rest on every warm device-program invocation hitting the
in-process jit cache: host wrappers pad data-dependent axes to a bounded
set of bucket shapes, so re-invocations at already-seen buckets must
report ZERO new traces and ZERO backend compiles.  These tests pin that
contract for the three program families — `admission_program` (serving),
`first_fit_window`/`schedule_epoch` (windows placement), and
`sweep_schedule` (the lane-vmapped capacity sweep) — by re-invoking each
with *different values and different row counts inside the same bucket*
under the trace-audit guard.  A shape leak (a new unpadded axis, a
config context forked between calls, a dtype drift) fails here before it
shows up as a 10x bench regression.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.analysis.trace_audit import no_recompiles  # noqa: E402
from repro.core.timeline import NEVER, Timeline, quantize_steps, time_keys  # noqa: E402
from repro.sim.batch_engine import bucket_size, pad_rows  # noqa: E402
from repro.sim.device_timeline import (  # noqa: E402
    admission_epoch,
    admission_program,
    first_fit_window,
    schedule_epoch,
    sweep_schedule,
)

K = 2  # allocation-schedule width used throughout


def _candidates(C: int, seed: int):
    """Synthetic admission candidates in the controller's array layout
    (time keys, demand units)."""
    rng = np.random.default_rng(seed)
    starts = time_keys(np.sort(rng.uniform(0.0, 10.0, C)))
    durs = time_keys(rng.uniform(1.0, 3.0, C))
    ends = starts + durs
    rels = ends + time_keys(rng.uniform(0.5, 1.0, C))
    bnd, val = quantize_steps(
        np.stack([rng.uniform(0.5, 1.5, C), np.full(C, np.inf)], axis=1),
        rng.uniform(10.0, 50.0, (C, K)),
    )
    return starts, ends, rels, bnd, val


def _admission_args(C: int, Cp: int, Pp: int, seed: int):
    """Bucket-padded argument tuple, mirroring _admit_device's packing."""
    starts, ends, rels, bnd, val = _candidates(C, seed)
    valext = np.concatenate([val, val[:, -1:]], axis=1)
    live = bnd < (rels - starts)[:, None]
    sw = np.where(live, starts[:, None] + np.where(live, bnd, 0) + 1, NEVER)
    P = np.unique(np.concatenate([starts, sw[live]]))
    assert len(P) <= Pp and C <= Cp
    i32 = np.int32
    return (
        np.concatenate([P, np.full(Pp - len(P), NEVER)]).astype(i32),
        np.zeros(Pp, i32),
        pad_rows(starts.astype(i32), Cp, NEVER),
        pad_rows(ends.astype(i32), Cp, -1),
        pad_rows(rels.astype(i32), Cp, -1),
        pad_rows(bnd.astype(i32), Cp, NEVER),
        pad_rows(val.astype(i32), Cp, 0),
        pad_rows(valext.astype(i32), Cp, 0),
        pad_rows(sw.astype(i32), Cp, NEVER),
        pad_rows(live, Cp, False),
        pad_rows(np.ones(C, dtype=bool), Cp, False),
    )


def _window_rows(w: int, seed: int):
    """Attempt rows in timeline units: (boundary keys, value units, run keys)."""
    rng = np.random.default_rng(seed)
    bnd, val = quantize_steps(
        np.stack([rng.uniform(1.0, 2.0, w), np.full(w, np.inf)], axis=1),
        rng.uniform(50.0, 200.0, (w, K)),
    )
    return bnd, val, time_keys(rng.uniform(2.0, 4.0, w))


def _k(seconds) -> int:
    return int(time_keys(seconds))


def test_admission_program_warm_zero_retrace():
    Cp, Pp = bucket_size(5), 16
    budget = np.int32(1000)
    np.asarray(admission_program()(*_admission_args(5, Cp, Pp, seed=0), budget))
    # warm: different values AND a different candidate count that pads
    # into the SAME (Cp, Pp) buckets — zero new traces
    for C, seed in ((5, 1), (6, 2), (7, 3)):
        assert bucket_size(C) == Cp
        with no_recompiles(f"admission C={C}"):
            np.asarray(admission_program()(*_admission_args(C, Cp, Pp, seed), budget))


def test_admission_epoch_warm_zero_retrace():
    """The carried-admission program re-dispatches silently at seen
    (S, L, Smax, Cb, Rb, k) buckets: decision batches, queued releases, and
    the advancing clock are all value changes, never shape changes — the
    whole point of a long-lived control plane is that batch #1000 costs the
    same dispatch as batch #2."""
    from repro.serve.admission import ShardedAdmissionController

    rng = np.random.default_rng(0)
    ctl = ShardedAdmissionController(50_000.0, k=4, interval_s=1.0, n_shards=2)
    for _ in range(30):
        plen = int(rng.integers(100, 2000))
        ctl.observe(plen, (plen * 0.08 + 8.0 * np.arange(80)).astype(np.float32))

    def run_batch(step: int, c: int, prev: list) -> list:
        for rid in prev:  # releases match prior admits: a bounded live set
            ctl.release(rid)
        ids = [f"b{step}c{j}" for j in range(c)]
        plens = [int(rng.integers(100, 2000)) for _ in range(c)]
        got = ctl.try_admit_many(ids, plens, float(step))
        return [r for r, p in zip(ids, got) if p is not None]

    # pre-warm: climb the timeline-growth ladder to the steady L bucket
    # (growth is a legitimate shape change — a new compile)
    prev: list = []
    for step in range(8):
        prev = run_batch(step, 8, prev)
    L_warm = ctl._L
    # warm: counts drift inside the same Cb bucket, releases queued and
    # applied, the clock advances — zero new traces, zero backend compiles
    for step in range(8, 12):
        with no_recompiles(f"admission_epoch step={step}"):
            prev = run_batch(step, int(4 + step % 5), prev)
    assert ctl._L == L_warm  # the audited batches sat at the steady bucket
    assert ctl.reseeds == 0


def test_first_fit_window_warm_zero_retrace():
    profiles = [Timeline().arrays() for _ in range(2)]
    bnd, val, run = _window_rows(5, seed=0)
    first_fit_window(0, bnd, val, run, run, profiles, 10_000)
    # same window bucket (32) and probe bucket despite w and values changing
    for w, seed in ((5, 1), (7, 2), (9, 3)):
        bnd, val, run = _window_rows(w, seed)
        with no_recompiles(f"first_fit_window w={w}"):
            first_fit_window(_k(seed), bnd, val, run, run, profiles, 10_000)


def test_schedule_epoch_warm_zero_retrace():
    node_events = [Timeline().events() for _ in range(2)]
    pending = time_keys([3.5, 7.25])
    bnd, val, run = _window_rows(5, seed=0)
    schedule_epoch(0, bnd, val, run, node_events, pending, 10_000)
    for w, seed in ((5, 1), (7, 2)):
        bnd, val, run = _window_rows(w, seed)
        with no_recompiles(f"schedule_epoch w={w}"):
            schedule_epoch(_k(seed), bnd, val, run, node_events, pending, 10_000)


def test_schedule_epoch_congested_budget_warm_zero_retrace():
    """A tight budget drives the in-program wait path (rows blocked until
    pending completions); warm re-dispatch must still be silent."""
    node_events = [Timeline().events() for _ in range(1)]
    pending = time_keys([1.0, 2.0, 3.0])
    bnd, val, run = _window_rows(6, seed=0)
    budget = int(np.sort(val.ravel())[len(val) // 2])  # ~half the rows fit
    schedule_epoch(0, bnd, val, run, node_events, pending, budget)
    bnd, val, run = _window_rows(6, seed=1)
    with no_recompiles("schedule_epoch congested"):
        schedule_epoch(0, bnd, val, run, node_events, pending, budget)


def _lanes(rows_per_lane, seed):
    lane_rows = []
    for i, r in enumerate(rows_per_lane):
        bnd, val, run = _window_rows(r, seed=seed + i)
        lane_rows.append((bnd, val, run, run))
    return lane_rows


def test_sweep_schedule_warm_zero_retrace():
    nodes, budgets = [2, 3], [500, 500]
    sweep_schedule(_lanes([10, 11], seed=0), nodes, budgets)
    # warm: new values, row counts drift within the same _row_bucket
    for rows, seed in (([10, 11], 10), ([11, 12], 20), ([12, 9], 30)):
        with no_recompiles(f"sweep rows={rows}"):
            sweep_schedule(_lanes(rows, seed), nodes, budgets)


def test_sweep_schedule_congested_budget_warm_zero_retrace():
    """Tight budgets drive the sweep's in-program wait path AND its
    chunk-boundary compaction fold (rows span several _SWEEP_W chunks, so
    the carry is repeatedly folded and compacted); warm re-dispatches with
    new values and drifting row counts in the same bucket stay silent."""
    nodes = [1, 2]
    # every row fits alone (max value < budget) but most pairs don't: the
    # single-node lane serializes through the wait path
    budgets = [220, 220]
    _, _, _, _, waited, dead = (None, *sweep_schedule(_lanes([40, 44], seed=0), nodes, budgets))
    assert not dead.any()
    assert waited.sum() >= 10
    for rows, seed in (([40, 44], 7), ([44, 40], 8), ([42, 38], 9)):
        with no_recompiles(f"sweep congested rows={rows}"):
            sweep_schedule(_lanes(rows, seed), nodes, budgets)
