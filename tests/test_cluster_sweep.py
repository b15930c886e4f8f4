"""The lane-vmapped whole-run sweep engine vs the per-policy windows engine.

``run_cluster_batched(placement="sweep")`` and ``run_cluster_sweep`` stack
independent simulation lanes — policy x node-count x corpus design points —
along a leading lane axis of ONE vmapped device program
(``device_timeline.sweep_schedule``).  Every lane must reproduce the
per-policy windows engine (itself oracle-exact, tests/test_cluster_*.py)
attempt by attempt: exact (node, start, end), exact wait counts, zero
host-resolved waits — including lanes with *unequal* node counts, which the
program handles by masking nodes past each lane's count.
"""

import numpy as np
import pytest
from hypothesis import assume, given, settings
from hypothesis import strategies as st

from repro.sim.cluster import (
    pareto_frontier,
    run_cluster_batched,
    run_cluster_sweep,
)
from repro.sim.traces import generate_workflow

POLICIES = ("default", "witt-lr", "ppm-improved", "ksegments-selective")

# congested corpus: small nodes, long queue — placement is dominated by
# in-program waits (the regime the sparse-table probes exist for)
CONGESTED = dict(node_mib=24 * 1024.0, max_tasks_per_type=25, min_executions=6, train_frac=0.5)


def _wfs(seed=7, name="eager", scale=0.25):
    return [generate_workflow(name, seed=seed, scale=scale)]


def _assert_equal_results(a, b):
    assert a.tasks_run == b.tasks_run > 0
    assert a.retries == b.retries
    assert a.makespan_s == b.makespan_s
    assert a.wastage_gib_s == b.wastage_gib_s  # bit-equal: shared ladders
    for ra, rb in zip(a.records, b.records):
        assert (ra.workflow, ra.task, ra.exec_index) == (rb.workflow, rb.task, rb.exec_index)
        assert ra.attempts == rb.attempts
        assert ra.placements == rb.placements  # exact (node, start, end)
        assert ra.wastage_gib_s == rb.wastage_gib_s


def test_sweep_matches_windows_congested():
    """One dispatch for all policies on a congested corpus: >= 5 in-program
    waits, zero host-resolved waits, exact per-attempt parity."""
    wfs = _wfs()
    st_s: dict = {}
    st_w: dict = {}
    sweep = run_cluster_batched(
        wfs, POLICIES, n_nodes=2, placement="sweep", placement_stats=st_s, **CONGESTED
    )
    windows = run_cluster_batched(
        wfs, POLICIES, n_nodes=2, placement="windows", placement_stats=st_w, **CONGESTED
    )
    assert st_s["waits_host"] == 0
    assert st_s["waits_program"] >= 5
    assert st_s["waits_program"] == st_w["waits_program"]
    # the whole policy set resolved in one (warm) vmapped dispatch
    assert st_s["program_calls"] == 1
    for p in POLICIES:
        _assert_equal_results(sweep[p], windows[p])


def test_auto_routes_by_cost_model():
    """``placement="auto"`` routes by the measured per-row cost model
    (``cluster._auto_sweep``): many short lanes on a one-node cluster
    amortize into one sweep dispatch; deeper lanes — where the sweep pays
    per attempt row over its carried (nodes x timeline) grid — honestly
    route to the per-policy windows loop."""
    shallow = dict(node_mib=24 * 1024.0, max_tasks_per_type=6, min_executions=6, train_frac=0.5)
    wfs = _wfs(seed=3, scale=0.1)
    st_a: dict = {}
    auto = run_cluster_batched(wfs, POLICIES, n_nodes=1, placement_stats=st_a, **shallow)
    assert st_a["program_calls"] == 1  # sweep: one dispatch, not a window loop
    windows = run_cluster_batched(wfs, POLICIES, n_nodes=1, placement="windows", **shallow)
    for p in POLICIES:
        _assert_equal_results(auto[p], windows[p])
    # ~6x the rows per lane: the row-step cost now exceeds the windows
    # loop's per-dispatch overhead, so auto picks windows (>1 dispatch)
    st_d: dict = {}
    run_cluster_batched(_wfs(seed=3), POLICIES[:2], n_nodes=1, placement_stats=st_d, **CONGESTED)
    assert st_d["program_calls"] > 1
    # a single policy never sweeps: one lane can't amortize the scan, so
    # auto's dispatch pattern matches the forced windows run exactly
    st_1: dict = {}
    st_1w: dict = {}
    run_cluster_batched(wfs, POLICIES[:1], n_nodes=1, placement_stats=st_1, **shallow)
    run_cluster_batched(
        wfs, POLICIES[:1], n_nodes=1, placement="windows", placement_stats=st_1w, **shallow
    )
    assert st_1["program_calls"] == st_1w["program_calls"]


def test_sweep_deep_lane_parity_and_bounded_carry():
    """Congested-depth lanes (>= 512 attempt rows each) forced through the
    sweep: still ONE dispatch, exact per-attempt parity with the windows
    engine, and — the compaction invariant — the carried timeline axis and
    its per-lane high-water stay bounded by live breakpoints instead of
    growing with run length (hw << rows/lane; pre-compaction the carry held
    every splice the run ever made)."""
    from repro.sim import generate_suite

    wfs = generate_suite(seed=0, scale=0.2)
    pol = ("default", "ksegments-selective")
    kw = dict(n_nodes=2, node_mib=24 * 1024.0, max_tasks_per_type=150,
              min_executions=6, train_frac=0.5)
    st_s: dict = {}
    sweep = run_cluster_batched(wfs, pol, placement="sweep", placement_stats=st_s, **kw)
    rows_per_lane = st_s["rows"] // len(pol)
    assert rows_per_lane >= 512
    assert st_s["program_calls"] == 1
    assert st_s["waits_host"] == 0
    assert st_s["waits_program"] >= 100  # genuinely congested: waits dominate
    # bounded carry: the compacted axis and every lane's breakpoint
    # high-water sit well under the lane depth (and far under rows x (k+2),
    # the uncompacted event volume)
    assert st_s["timeline_axis"] < rows_per_lane
    assert max(st_s["carried_hw"]) < rows_per_lane // 2
    windows = run_cluster_batched(wfs, pol, placement="windows", **kw)
    for p in pol:
        _assert_equal_results(sweep[p], windows[p])


@settings(deadline=None, max_examples=3)
@given(st.integers(0, 2**31 - 1), st.sampled_from([25, 60, 150]))
def test_property_sweep_parity_over_densities(seed, mtpt):
    """Queue density (tasks admitted per type) sets lane depth; at every
    density the forced sweep must match the windows engine attempt by
    attempt, in one dispatch."""
    wfs = _wfs(seed=seed, scale=0.25)
    kw = dict(
        n_nodes=2, node_mib=24 * 1024.0, max_tasks_per_type=mtpt,
        min_executions=6, train_frac=0.5,
    )
    # about one corpus in ten has an execution that outgrows a 24 GiB node:
    # no engine can schedule it (the oracle raises), so it is no parity case
    assume(max(float(e.series.max()) for t in wfs[0].tasks for e in t.executions) <= kw["node_mib"])
    st_s: dict = {}
    sweep = run_cluster_batched(
        wfs, POLICIES[:2], placement="sweep", placement_stats=st_s, **kw
    )
    assert st_s["program_calls"] == 1
    assert st_s["waits_host"] == 0
    windows = run_cluster_batched(wfs, POLICIES[:2], placement="windows", **kw)
    for p in POLICIES[:2]:
        _assert_equal_results(sweep[p], windows[p])


def test_lane_heterogeneity_unequal_node_counts():
    """Lanes with different n_nodes in ONE dispatch must each match the
    per-policy engine run at that node count exactly."""
    wfs = _wfs()
    node_counts = (1, 2, 3)
    stats: dict = {}
    kw = dict(CONGESTED, max_tasks_per_type=12)  # 9 lanes: keep the refs cheap
    res = run_cluster_sweep(
        wfs, POLICIES[:3], node_counts=node_counts, placement_stats=stats, **kw
    )
    assert stats["waits_host"] == 0
    assert stats["waits_program"] >= 5
    assert stats["program_calls"] == 1
    for (corpus, policy, nn), r in res.items():
        assert corpus == ""
        ref = run_cluster_batched(
            wfs, (policy,), n_nodes=nn, placement="windows", **kw
        )[policy]
        _assert_equal_results(r, ref)
    # more nodes never lengthen the makespan on the same rows
    for p in POLICIES[:3]:
        spans = [res[("", p, nn)].makespan_s for nn in node_counts]
        assert spans == sorted(spans, reverse=True)


def test_sweep_multi_corpus_keys_and_pareto():
    corpora = {"a": _wfs(seed=3), "b": _wfs(seed=7)}
    res = run_cluster_sweep(
        corpora, POLICIES[:2], node_counts=(1, 2), max_tasks_per_type=8,
        node_mib=24 * 1024.0, min_executions=6, train_frac=0.5,
    )
    assert set(res) == {
        (c, p, n) for c in corpora for p in POLICIES[:2] for n in (1, 2)
    }
    for c in corpora:
        pts = [(r.makespan_s, r.wastage_gib_s) for k, r in sorted(res.items()) if k[0] == c]
        keep = pareto_frontier(pts)
        assert keep.any()
        # frontier members are genuinely non-dominated
        arr = np.asarray(pts)
        for i in np.flatnonzero(keep):
            dom = (arr <= arr[i]).all(axis=1) & (arr < arr[i]).any(axis=1)
            assert not dom.any()


def _lane(r, seed, k=2):
    """Synthetic attempt rows in sweep_schedule's lane layout (timeline
    units: boundary keys, value units, run keys)."""
    from repro.core.timeline import quantize_steps, time_keys

    rng = np.random.default_rng(seed)
    bnd, val = quantize_steps(
        np.stack([rng.uniform(1.0, 2.0, r), np.full(r, np.inf)], axis=1),
        rng.uniform(50.0, 200.0, (r, k)),
    )
    run = time_keys(rng.uniform(2.0, 4.0, r))
    return bnd, val, run, run


def test_sweep_hint_lru_bounded():
    """The timeline-axis hint is a bounded LRU: long sessions sweeping many
    grid shapes must not grow it without bound, eviction is oldest-first,
    and a read refreshes recency."""
    from repro.sim import device_timeline as dt

    saved = dict(dt._SWEEP_L_HINT)
    try:
        dt._SWEEP_L_HINT.clear()
        for i in range(dt._SWEEP_L_HINT_CAP + 10):
            dt._hint_put(("grid", i), 256)
        assert len(dt._SWEEP_L_HINT) == dt._SWEEP_L_HINT_CAP
        assert dt._hint_get(("grid", 0)) == 0  # oldest: evicted
        assert dt._hint_get(("grid", dt._SWEEP_L_HINT_CAP + 9)) == 256
        # a hit refreshes recency: the touched key survives the next eviction
        oldest_alive = ("grid", 10)
        assert dt._hint_get(oldest_alive) == 256
        dt._hint_put(("grid", "fresh"), 512)
        assert dt._hint_get(oldest_alive) == 256
        assert dt._hint_get(("grid", 11)) == 0  # the unrefreshed one went
    finally:
        dt._SWEEP_L_HINT.clear()
        dt._SWEEP_L_HINT.update(saved)


def test_sweep_overflow_doubling_and_dead_lane():
    """The axis-growth ladder end to end: a floor far below the carried
    events re-dispatches with the axis doubled (extra program_calls, same
    placements bit for bit); a cap below the need flags the deep lane dead
    while the shallow lane still schedules."""
    from repro.sim import device_timeline as dt
    from repro.sim.device_timeline import sweep_schedule

    # one node, generous budget: every row starts immediately, so the carry
    # holds ~all future completions at once — deeper than a tiny axis
    lanes = [_lane(60, 0), _lane(6, 1)]
    nodes, budgets = [1, 1], [50_000, 50_000]
    saved = dict(dt._SWEEP_L_HINT)
    try:
        dt._SWEEP_L_HINT.clear()
        st_ref: dict = {}
        ref = sweep_schedule(lanes, nodes, budgets, stats=st_ref)
        assert not ref[4].any()
        dt._SWEEP_L_HINT.clear()
        st_d: dict = {}
        got = sweep_schedule(lanes, nodes, budgets, timeline_floor=16, stats=st_d)
        assert st_d["program_calls"] > st_ref["program_calls"]  # walked the ladder
        assert st_d["timeline_axis"] > 16
        assert not got[4].any()
        np.testing.assert_array_equal(got[0], ref[0])  # node choices
        np.testing.assert_array_equal(got[1], ref[1])  # start times
        # still overflowing at the cap: the deep lane is dead, the shallow
        # lane's placements are intact
        dt._SWEEP_L_HINT.clear()
        capped = sweep_schedule(lanes, nodes, budgets, timeline_floor=16, timeline_cap=16)
        assert bool(capped[4][0]) and not bool(capped[4][1])
        r1 = lanes[1][0].shape[0]
        np.testing.assert_array_equal(capped[0][1, :r1], ref[0][1, :r1])
        np.testing.assert_array_equal(capped[1][1, :r1], ref[1][1, :r1])
    finally:
        dt._SWEEP_L_HINT.clear()
        dt._SWEEP_L_HINT.update(saved)


def test_dead_lane_replays_through_windows_engine(monkeypatch):
    """A lane reported dead by the sweep program (timeline overflow at the
    cap) must transparently replay through the per-policy windows engine
    inside ``run_cluster_batched`` — same results, attempt for attempt."""
    import repro.sim.device_timeline as dt

    orig = dt.sweep_schedule

    def first_lane_dead(lane_rows, lane_nodes, lane_budgets, **kw):
        node, start, pops, waited, dead = orig(lane_rows, lane_nodes, lane_budgets, **kw)
        dead = dead.copy()
        dead[0] = True
        return node, start, pops, waited, dead

    monkeypatch.setattr(dt, "sweep_schedule", first_lane_dead)
    wfs = _wfs()
    st: dict = {}
    res = run_cluster_batched(
        wfs, POLICIES[:2], n_nodes=2, placement="sweep", placement_stats=st, **CONGESTED
    )
    assert st["program_calls"] > 1  # the sweep dispatch plus windows replays
    windows = run_cluster_batched(wfs, POLICIES[:2], n_nodes=2, placement="windows", **CONGESTED)
    for p in POLICIES[:2]:
        _assert_equal_results(res[p], windows[p])


def test_pareto_frontier_basics():
    keep = pareto_frontier([(1.0, 3.0), (2.0, 2.0), (3.0, 1.0), (3.0, 3.0)])
    assert keep.tolist() == [True, True, True, False]
    # exact duplicates both survive (neither strictly dominates)
    keep = pareto_frontier([(1.0, 1.0), (1.0, 1.0)])
    assert keep.tolist() == [True, True]
    with pytest.raises(ValueError):
        pareto_frontier([1.0, 2.0])


@settings(deadline=None, max_examples=4)
@given(st.integers(0, 2**31 - 1), st.integers(1, 3), st.integers(6, 12))
def test_property_sweep_windows_parity(seed, n_nodes, mtpt):
    """Random congested corpora: sweep == windows, attempt by attempt."""
    wfs = [generate_workflow("eager", seed=seed, scale=0.06)]
    kw = dict(
        n_nodes=n_nodes, node_mib=32 * 1024.0, max_tasks_per_type=mtpt,
        min_executions=6, train_frac=0.5,
    )
    sweep = run_cluster_batched(wfs, ("default", "ksegments-selective"), placement="sweep", **kw)
    windows = run_cluster_batched(wfs, ("default", "ksegments-selective"), placement="windows", **kw)
    for p in sweep:
        _assert_equal_results(sweep[p], windows[p])
