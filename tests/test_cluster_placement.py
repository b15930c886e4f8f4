"""Batched wait-epoch placement vs the scalar oracle, and the node-profile
boundary semantics under a brute-force oracle.

Three layers:

* **Engine parity given rows** — the placement engine itself
  (``_place_rows_batched``: windowed device program + congested host regime
  + vectorized wait scan) must produce the exact (node, start, end) the
  scalar ``_find_slot`` loop produces for the *same* attempt rows, on any
  corpus.  This is the invariant the device program owns.
* **End-to-end placement parity** — randomized corpora replayed through
  ``run_cluster_batched`` and the sequential ``run_cluster`` oracle must
  produce the exact same (node, start, end) per attempt, across all four
  bench policies and cluster sizes that exercise both regimes.  (End-to-end
  exactness additionally needs the float32 device *predictions* to land on
  the same side of every capacity comparison as the float64 numpy
  predictors — corpora are chosen away from such ulp boundaries, same as
  tests/test_cluster_batch.py; the engine-parity layer above is
  boundary-free because both sides consume identical rows.)
* **Boundary oracle** — ``NodeState.fits`` / ``reserved_at`` /
  ``demand_exceeds_many`` probed against a naive Eq. (1) evaluator at every
  event key and its neighbouring keys (mirroring
  tests/test_demand_oracle.py), including reservations starting *exactly* at
  another's release instant — the case where an off-by-one disagreement
  between the profile's release events and the probe sides would show up.

Each property runs as a seeded loop plus a hypothesis variant (skipped
cleanly by the conftest shim when hypothesis is absent).
"""

import dataclasses
import heapq

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.allocation import demand_exceeds, demand_exceeds_many
from repro.core.ksegments import KSegmentsConfig
from repro.core.timeline import key_seconds, quantize_steps, time_keys
from repro.sim.cluster import (
    NodeState,
    _eligible_queue,
    _find_slot,
    _place_rows_batched,
    _policy_rows,
    oracle_rows,
    place_rows,
    run_cluster,
    run_cluster_batched,
)
from repro.sim.traces import generate_suite, generate_workflow

POLICIES = ("default", "witt-lr", "ppm-improved", "ksegments-selective")
NODE_MIB = 128 * 1024.0


# ---------------------------------------------------------------------------
# Engine parity given rows: device/hybrid placement == scalar _find_slot
# ---------------------------------------------------------------------------


def _k(seconds) -> int:
    """Seconds -> the key of that instant (timeline units)."""
    return int(time_keys(seconds))


def _scalar_place_rows(bnd_rows, val_rows, run_rows, probe_rows, n_nodes):
    """Reference placement of flat attempt rows (timeline units) via the
    oracle's scalar ``_find_slot`` + ``NodeState`` loop (``place_rows``,
    the placement half of ``run_cluster``)."""
    node, start, end = place_rows(bnd_rows, val_rows, run_rows, probe_rows, n_nodes, NODE_MIB)
    return list(zip(node.tolist(), start.tolist(), end.tolist()))


def test_oracle_rows_replace_run_cluster_placement():
    """``run_cluster`` is ``oracle_rows`` placed by ``place_rows``; the
    hand-rolled ``_find_slot`` loop over those rows gives the same records."""
    wfs = [generate_workflow("eager", seed=3, scale=0.1)]
    kw = dict(train_frac=0.5, max_tasks_per_type=10, min_executions=8)
    queue, (bnd, val, run, probe, counts, _waste) = oracle_rows(wfs, "ksegments-selective", NODE_MIB, **kw)
    nodes = [NodeState(NODE_MIB) for _ in range(2)]
    events: list = []
    now = 0
    ref = []
    for r in range(len(run)):
        placed, now = _find_slot(nodes, events, now, bnd[r], val[r], int(probe[r]))
        end = now + int(run[r])
        nodes[placed].add(end, bnd[r], val[r], now)
        heapq.heappush(events, (end, placed))
        ref.append((placed, float(key_seconds(now)), float(key_seconds(end))))
    res = run_cluster(wfs, "ksegments-selective", n_nodes=2, **kw)
    assert len(res.records) == len(queue) and sum(counts) == len(ref) >= len(queue) > 0
    assert [p for rec in res.records for p in rec.placements] == ref


@pytest.mark.parametrize(
    "seed,name,n_nodes,window",
    [
        (11, "sarek", 3, 32),  # the seed whose f32 predictions sit on a capacity ulp
        (3, "eager", 2, 32),
        (5, "eager", 5, 8),  # tiny window: many epoch boundaries
        (41, "sarek", 4, 32),
    ],
)
def test_engine_parity_given_rows(seed, name, n_nodes, window):
    """Same ladder rows in, same (node, start, end) out — regardless of how
    the rows were predicted."""
    from repro.sim.batch_engine import compute_cluster_ladders

    wfs = [generate_workflow(name, seed=seed, scale=0.06)]
    queue, traces = _eligible_queue(wfs, 0.5, 10, 8)
    trunc = [dataclasses.replace(t, executions=t.executions[: nt + 10]) for t, nt in traces]
    ladders = compute_cluster_ladders(trunc, POLICIES, NODE_MIB, KSegmentsConfig(error_mode="progressive"))
    for policy in POLICIES:
        bnd_rows, val_rows, run_rows, probe_rows, _counts, _waste = _policy_rows(ladders, queue, policy)
        ref = _scalar_place_rows(bnd_rows, val_rows, run_rows, probe_rows, n_nodes)
        rn, rs, re = _place_rows_batched(
            bnd_rows, val_rows, run_rows, probe_rows, n_nodes, NODE_MIB, window, None
        )
        got = [(int(rn[r]), int(rs[r]), int(re[r])) for r in range(len(run_rows))]
        assert got == ref, policy


# ---------------------------------------------------------------------------
# Placement parity: batched epoch program vs the sequential oracle
# ---------------------------------------------------------------------------


def _assert_cluster_parity(wfs, policies, **kw):
    cfg = KSegmentsConfig(error_mode="progressive")
    batched = run_cluster_batched(wfs, policies, **kw)
    for policy in policies:
        seq = run_cluster(wfs, policy, ksegments_config=cfg, **kw)
        bat = batched[policy]
        assert seq.tasks_run == bat.tasks_run > 0
        assert seq.retries == bat.retries
        assert seq.makespan_s == bat.makespan_s
        for rs, rb in zip(seq.records, bat.records):
            assert (rs.workflow, rs.task, rs.exec_index) == (rb.workflow, rb.task, rb.exec_index)
            assert rs.attempts == rb.attempts
            # exact placement decisions: same nodes at the same instants
            assert rs.placements == rb.placements
            np.testing.assert_allclose(rs.wastage_gib_s, rb.wastage_gib_s, rtol=1e-3, atol=1e-6)
        np.testing.assert_allclose(seq.wastage_gib_s, bat.wastage_gib_s, rtol=1e-3)
        if policy == "default":
            # no prediction: equal allocations, scored by the same float64 host
            # accounting in both engines
            assert [r.wastage_gib_s for r in seq.records] == [r.wastage_gib_s for r in bat.records]
            assert seq.wastage_gib_s == bat.wastage_gib_s


@pytest.mark.parametrize("seed", [0, 4])
def test_default_policy_wastage_bit_equal(seed):
    """The paper's metric under the float32 device ladder: the default
    policy's wastage equals the float64 sequential oracle's bit for bit, per
    task and in total, on a corpus spanning both workflows (the ladder
    decides in float32; every attempt's wastage is scored on the host in
    float64, as the oracle scores it)."""
    wfs = generate_suite(seed=seed, scale=0.05)
    kw = dict(n_nodes=4, max_tasks_per_type=16, min_executions=8, train_frac=0.5)
    seq = run_cluster(wfs, "default", ksegments_config=KSegmentsConfig(error_mode="progressive"), **kw)
    bat = run_cluster_batched(wfs, ("default", "ksegments-selective"), **kw)["default"]
    assert seq.tasks_run == bat.tasks_run > 0
    assert [r.wastage_gib_s for r in seq.records] == [r.wastage_gib_s for r in bat.records]
    assert seq.wastage_gib_s == bat.wastage_gib_s


@pytest.mark.parametrize(
    "seed,name,n_nodes,scale",
    [
        (3, "eager", 2, 0.12),  # tight cluster: the congested host regime dominates
        (5, "eager", 5, 0.12),  # loose cluster: long streaming windows on device
        (13, "sarek", 3, 0.06),
        (23, "eager", 4, 0.1),
    ],
)
def test_randomized_corpus_placement_parity(seed, name, n_nodes, scale):
    wfs = [generate_workflow(name, seed=seed, scale=scale)]
    _assert_cluster_parity(
        wfs, POLICIES, n_nodes=n_nodes, max_tasks_per_type=12, min_executions=8, train_frac=0.5
    )


def test_placement_parity_across_fracs():
    wfs = [generate_workflow("eager", seed=9, scale=0.12)]
    for frac in (0.25, 0.75):
        _assert_cluster_parity(
            wfs,
            ("default", "ksegments-selective"),
            n_nodes=3,
            max_tasks_per_type=10,
            min_executions=8,
            train_frac=frac,
        )


def test_x64_ladders_exact_parity_on_f32_boundary_seed():
    """The float64 ladder option (a CPU reference) on the corpus that
    historically flipped end-to-end parity (sarek seed 11 at scale 0.06 — a
    prediction lands within a float32 ulp of a comparison; ``ladder_x64``
    closes that ulp-boundary class).  Exact (node, start, end) parity with
    the float64 numpy oracle across all bench policies."""
    wfs = [generate_workflow("sarek", seed=11, scale=0.06)]
    kw = dict(n_nodes=3, max_tasks_per_type=10, min_executions=8, train_frac=0.5)
    cfg = KSegmentsConfig(error_mode="progressive")
    batched = run_cluster_batched(wfs, POLICIES, ladder_x64=True, **kw)
    for policy in POLICIES:
        seq = run_cluster(wfs, policy, ksegments_config=cfg, **kw)
        bat = batched[policy]
        assert seq.retries == bat.retries, policy
        for rs, rb in zip(seq.records, bat.records):
            assert rs.attempts == rb.attempts, policy
            assert rs.placements == rb.placements, policy


@settings(deadline=None, max_examples=5)
@given(st.integers(0, 2**31 - 1), st.integers(2, 5))
def test_property_placement_parity(seed, n_nodes):
    wfs = [generate_workflow("eager", seed=seed, scale=0.05)]
    _assert_cluster_parity(
        wfs,
        ("default", "ksegments-selective"),
        n_nodes=n_nodes,
        max_tasks_per_type=6,
        min_executions=6,
        train_frac=0.5,
    )


# ---------------------------------------------------------------------------
# Brute-force boundary oracle for the node profile
# ---------------------------------------------------------------------------


def _oracle_value(steps, start: int, t: int) -> int:
    """Naive Eq. (1): step s+1 applies from the key just after the switch
    instant ``start + b_s`` (right-open steps)."""
    b, v = steps
    return int(v[sum(1 for bb in b[:-1] if t >= start + bb + 1)])


def _oracle_total(rows, t: int) -> int:
    """Naive reserved total: a reservation holds on [start, end) — its end
    is the release instant, exclusive (unlike a serving plan's Eq. 1 domain,
    which holds through r_e)."""
    return sum(_oracle_value((b, v), s, t) for e, b, v, s in rows if s <= t < e)


def _rand_alloc(rng, exact_ties: bool):
    k = int(rng.integers(1, 5))
    b = np.sort(rng.uniform(0.5, 40.0, k))
    if exact_ties:  # values that can sum exactly to the capacity
        v = np.maximum.accumulate(rng.choice([100.0, 200.0, 250.0, 500.0], k))
    else:
        v = np.maximum.accumulate(rng.uniform(10.0, 500.0, k))
    return quantize_steps(b, v)


def _build_node(rng, exact_ties: bool):
    """A NodeState under add/expire churn; half the reservations start
    exactly at the previous one's release instant."""
    nd = NodeState(capacity_mib=1000.0)
    rows = []
    for _ in range(int(rng.integers(2, 8))):
        b, v = _rand_alloc(rng, exact_ties)
        start = rows[-1][0] if rows and rng.random() < 0.5 else _k(rng.uniform(0.0, 60.0))
        end = start + _k(rng.uniform(2.0, 50.0))
        nd.add(end, b, v, start)
        rows.append((end, b, v, start))
        if rng.random() < 0.3:
            cut = _k(rng.uniform(0.0, 80.0))
            nd.expire(cut)
            rows = [r for r in rows if r[0] > cut]
    return nd, rows


def _probe_grid(rows, rng):
    """Every event key, one key before, one key after, plus random keys."""
    ev = [0]
    for end, b, _, start in rows:
        ev += [start, end]
        ev += list(start + b + 1)
    ev = np.asarray(ev, dtype=np.int64)
    return np.concatenate([ev, ev - 1, ev + 1, time_keys(rng.uniform(0.0, 120.0, 48))])


def _check_node_matches_oracle(seed: int) -> None:
    rng = np.random.default_rng(seed)
    nd, rows = _build_node(rng, exact_ties=seed % 2 == 0)
    grid = _probe_grid(rows, rng)
    for t in grid:
        assert nd.reserved_at(int(t)) == _oracle_total(rows, int(t)), int(t)
    for _ in range(6):
        cand = _rand_alloc(rng, seed % 2 == 0)
        # placement windows that start exactly at a release instant are the
        # regression case: the released row must not count at the start probe
        start = int(rng.choice([r[0] for r in rows])) if rows and rng.random() < 0.6 else _k(rng.uniform(0.0, 70.0))
        dur = _k(rng.uniform(1.0, 45.0))
        end = start + dur
        pts = np.concatenate([[start], start + cand[0] + 1, grid])
        pts = pts[(pts >= start) & (pts < end)]
        peak = max(_oracle_total(rows, int(t)) + _oracle_value(cand, start, int(t)) for t in pts)
        want = peak <= 1000  # fits' budget: the capacity in whole units
        assert nd.fits(*cand, start, dur) == want, (start, dur, peak)
        # the vectorized multi-start probe must agree with the scalar one
        times, cum = nd.profile_arrays()
        starts = np.asarray([start, start + _k(0.5), start + 1])
        many = demand_exceeds_many(times, cum, *cand, starts, dur, 1000)
        for s, got in zip(starts, many):
            scalar = demand_exceeds(times, cum, *cand, int(s), int(s) + dur, 1000)
            assert bool(got) == scalar, (int(s), dur)


@pytest.mark.parametrize("seed", [0, 1, 2, 7, 19, 101])
def test_node_profile_matches_oracle(seed):
    _check_node_matches_oracle(seed)


@settings(deadline=None, max_examples=30)
@given(st.integers(0, 2**31 - 1))
def test_property_node_profile_matches_oracle(seed):
    _check_node_matches_oracle(seed)


def test_reservation_at_anothers_release_boundary_exact():
    """Pinned semantics at the exact-collision instant: at A's release
    instant B (starting right there) is the only live reservation; one key
    earlier A is the only one; and a candidate window starting at the
    collision packs against B alone."""
    nd = NodeState(capacity_mib=1000.0)
    t10, t20 = _k(10.0), _k(20.0)
    nd.add(t10, *quantize_steps([10.0], [700.0]), 0)
    nd.add(t20, *quantize_steps([10.0], [600.0]), t10)
    assert nd.reserved_at(t10 - 1) == 700
    assert nd.reserved_at(t10) == 600  # A released, B live
    # 400 fits alongside B (600 + 400 <= 1000) but not alongside A + B
    cand = quantize_steps([5.0], [400.0])
    assert nd.fits(*cand, t10, _k(5.0))
    assert not nd.fits(*cand, t10 - 1, _k(5.0))


def test_profile_add_many_matches_sequential_adds():
    """One vectorized spliced commit must leave the profile arrays
    identical to one-at-a-time adds — this is what keeps the batched
    scheduler's per-epoch commits (``profs[n].add_many`` in
    ``_place_rows_batched``) on the same profile as the oracle's sequential
    ``NodeState.add`` commits."""
    from repro.core.allocation import IncrementalDemandProfile

    rng = np.random.default_rng(5)
    one, many = IncrementalDemandProfile(), IncrementalDemandProfile()
    k = 3
    bnd, val = quantize_steps(
        np.sort(rng.uniform(0.5, 30.0, (6, k)), axis=1),
        np.maximum.accumulate(rng.uniform(10.0, 400.0, (6, k)), axis=1),
    )
    ends = time_keys(rng.uniform(40.0, 80.0, 6))
    for i in range(6):
        one.add(i, bnd[i], val[i], _k(7.0), int(ends[i]))
    many.add_many(range(6), bnd, val, np.full(6, _k(7.0)), ends)
    t1, c1 = one.arrays()
    t2, c2 = many.arrays()
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(c1, c2)
