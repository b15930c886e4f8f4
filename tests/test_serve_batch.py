"""Parity of the batched admission engine against the scalar oracle — the
serving analogue of tests/test_cluster_batch.py.

Random arrival/finish streams driven through ``AdmissionController`` (one
probe per candidate, profile rebuilt on change) and
``BatchedAdmissionController`` (incremental profile + device batch program)
must produce identical admit/reject sequences and identical wastage
accounting — on both of the batched controller's dispatch paths (host
small-batch and device), and end to end through the stream simulator.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serve.admission import AdmissionController, BatchedAdmissionController
from repro.serve.stream import StreamConfig, generate_arrivals, run_stream


def _growth_series(plen, steps):
    return (plen * 0.08 + 8.0 * np.arange(steps)).astype(np.float32)


def _trained_pair(budget, rng, n_obs=50, **kw):
    sc = AdmissionController(budget, k=4, interval_s=1.0)
    bc = BatchedAdmissionController(budget, k=4, interval_s=1.0, **kw)
    for _ in range(n_obs):
        plen = int(rng.integers(100, 2000))
        s = _growth_series(plen, int(60 + plen * 0.05 + rng.normal(0, 2)))
        sc.observe(plen, s)
        bc.observe(plen, s)
    return sc, bc


def _check_stream_parity(seed: int, device_min_batch: int) -> None:
    """Random admit/release/observe interleavings: decisions must match
    call by call, and shared state (active set, static reservation) after."""
    rng = np.random.default_rng(seed)
    sc, bc = _trained_pair(12_000.0, rng, device_min_batch=device_min_batch)
    now = 0.0
    for step in range(60):
        op = rng.random()
        if op < 0.55:  # admission batch with per-candidate arrival times
            c = int(rng.integers(1, 9))
            ids = [f"s{step}c{j}" for j in range(c)]
            plens = [int(rng.integers(100, 2000)) for _ in range(c)]
            nows = now + np.sort(rng.uniform(0.0, 0.5, c))
            seq = [sc.try_admit(r, p, float(t)) for r, p, t in zip(ids, plens, nows)]
            bat = bc.try_admit_many(ids, plens, nows)
            assert [p is not None for p in seq] == [p is not None for p in bat], step
            for a, b in zip(seq, bat):
                if a is not None:
                    np.testing.assert_array_equal(a.alloc.boundaries, b.alloc.boundaries)
                    np.testing.assert_array_equal(a.alloc.values, b.alloc.values)
            now = float(nows[-1])
        elif op < 0.85 and sc.active:  # release a finished request
            rid = str(rng.choice(sorted(sc.active)))
            sc.release(rid)
            bc.release(rid)
        else:  # online learning changes later predictions for both
            plen = int(rng.integers(100, 2000))
            s = _growth_series(plen, int(60 + plen * 0.05))
            sc.observe(plen, s)
            bc.observe(plen, s)
        now += float(rng.exponential(1.0))
    assert set(sc.active) == set(bc.active)
    assert np.isclose(sc._static_reserved, bc._static_reserved)


@pytest.mark.parametrize("seed", [0, 1, 2, 5])
@pytest.mark.parametrize("device_min_batch", [1, 4, 1_000_000])
def test_admission_stream_parity(seed, device_min_batch):
    # device_min_batch=1 forces every decision through the device program,
    # 1_000_000 forces the host path, 4 exercises the hybrid dispatch
    _check_stream_parity(seed, device_min_batch)


@settings(deadline=None, max_examples=15)
@given(st.integers(0, 2**31 - 1))
def test_property_admission_stream_parity(seed):
    _check_stream_parity(seed, device_min_batch=4)


def test_empty_model_default_parity():
    """Before any observation both controllers admit against the same flat
    5%-of-budget placeholder, so at most 20 fit."""
    sc = AdmissionController(1000.0, k=4, interval_s=1.0)
    bc = BatchedAdmissionController(1000.0, k=4, interval_s=1.0, device_min_batch=1)
    ids = [f"r{i}" for i in range(25)]
    seq = [sc.try_admit(r, 100, 0.0) is not None for r in ids]
    bat = [p is not None for p in bc.try_admit_many(ids, [100] * 25, 0.0)]
    assert seq == bat
    assert sum(seq) == 20


def test_within_batch_sequencing():
    """A batch whose members individually fit but collectively exceed the
    budget must admit a strict prefix-by-order, not all of them."""
    rng = np.random.default_rng(4)
    sc, bc = _trained_pair(10_000.0, rng, device_min_batch=1)
    ids = [f"q{i}" for i in range(32)]
    plens = [1000] * 32
    seq = [sc.try_admit(r, p, 0.0) is not None for r, p in zip(ids, plens)]
    bat = [p is not None for p in bc.try_admit_many(ids, plens, 0.0)]
    assert seq == bat
    assert 0 < sum(bat) < 32  # the budget binds inside the batch


@pytest.mark.parametrize("engine", ["scalar", "device"])
def test_plan_holds_until_its_end_between_ticks(engine):
    """A plan admitted at 0.9 ms with a 1.0005 s reservation ends at
    1.0014 s, which lies between 1 ms ticks: the plan must still hold
    capacity against a newcomer at 1.0012 s (released at the tick after its
    end, never before), and free it a few ticks later."""
    from repro.core.allocation import StepAllocation

    def fractional(cls):
        class Ctl(cls):
            def _default_alloc(self):
                return StepAllocation(np.asarray([1.0005]), np.asarray([600.0]))

        return Ctl

    if engine == "scalar":
        ctl = fractional(AdmissionController)(1000.0, k=4, interval_s=1.0)
    else:
        ctl = fractional(BatchedAdmissionController)(1000.0, k=4, interval_s=1.0, device_min_batch=1)
    assert ctl.try_admit("a", 100, 0.0009) is not None
    assert ctl.try_admit("b", 100, 1.0012) is None
    assert ctl.try_admit("c", 100, 1.003) is not None


def test_try_admit_many_empty():
    bc = BatchedAdmissionController(1000.0)
    assert bc.try_admit_many([], [], 0.0) == []


@pytest.mark.parametrize("arrival", ["poisson", "bursty"])
def test_run_stream_engine_parity(arrival):
    """End-to-end: the stream simulator produces identical decision
    sequences, counts and wastage on both engines."""
    cfg = StreamConfig(
        n_requests=160,
        n_warmup=32,
        arrival=arrival,
        rate_per_s=30.0 if arrival == "bursty" else 6.0,
        seed=11,
    )
    rs = run_stream(cfg, "scalar")
    rb = run_stream(cfg, "batched")
    assert rs.decisions == rb.decisions
    assert (rs.admitted, rs.rejected, rs.evicted, rs.finished) == (
        rb.admitted,
        rb.rejected,
        rb.evicted,
        rb.finished,
    )
    assert rs.rejected > 0  # the budget binds, so parity is non-trivial
    np.testing.assert_allclose(
        rs.wastage["segmentwise_gib_s"], rb.wastage["segmentwise_gib_s"], rtol=1e-9
    )
    np.testing.assert_allclose(
        rs.wastage["peak_reservation_gib_s"], rb.wastage["peak_reservation_gib_s"], rtol=1e-9
    )
    assert rs.makespan_s == rb.makespan_s


def test_run_stream_eviction_parity():
    """Under-prediction (shrinking training series, growing served series)
    forces the OOM backstop; evictions must agree across engines."""
    cfg = StreamConfig(
        n_requests=120,
        n_warmup=24,
        rate_per_s=8.0,
        hbm_budget_mib=20_000.0,
        growth_mib_per_step=8.0,
        seed=2,
    )
    warm, arrivals = generate_arrivals(cfg)
    # serve series 3x the footprint the model learned from
    for a in arrivals:
        a.series = a.series * 3.0
    rs = run_stream(cfg, "scalar", arrivals=(warm, arrivals))
    rb = run_stream(cfg, "batched", arrivals=(warm, arrivals))
    assert rs.decisions == rb.decisions
    assert rs.evicted == rb.evicted > 0
    assert rs.admitted == rb.admitted and rs.finished == rb.finished
