"""A pool whose nodes differ in memory: ``node_mib`` as a capacity per node.

Every placement program — the streaming window (both variants), the
scheduling epoch and the whole-run sweep — must hold each node to its own
capacity and place every attempt exactly as the sequential ``run_cluster``
oracle does on the same pool; a uniform list must be bit-equal to the
scalar it repeats; and the wide-row counters (``waits_wide``,
``wide_blocks``) must equal a recount from the placements.
"""

import numpy as np
import pytest

import repro.sim.device_timeline as device_timeline
from repro.core.ksegments import KSegmentsConfig
from repro.core.timeline import Timeline, budget_units, quantize_steps, time_keys
from repro.sim.cluster import (
    _place_rows_batched,
    batched_rows,
    node_capacities,
    place_rows,
    run_cluster,
    run_cluster_batched,
    run_cluster_sweep,
)
from repro.sim.traces import generate_workflow

POLICIES = ("default", "witt-lr", "ppm-improved", "ksegments-selective")
GIB = 1024.0
# three nodes of three sizes, the largest not first: eager's biggest tasks
# (up to ~14 GiB) fit only the 16 GiB node, so wide rows queue behind it
MIXED = [8 * GIB, 16 * GIB, 4 * GIB]
CORPUS = dict(max_tasks_per_type=25, min_executions=6, train_frac=0.5)


def _wfs(seed=7, name="eager", scale=0.25):
    return [generate_workflow(name, seed=seed, scale=scale)]


def _assert_same(a, b):
    assert a.tasks_run == b.tasks_run > 0
    assert (a.retries, a.makespan_s) == (b.retries, b.makespan_s)
    for ra, rb in zip(a.records, b.records):
        assert (ra.workflow, ra.task, ra.exec_index, ra.attempts) == (rb.workflow, rb.task, rb.exec_index, rb.attempts)
        assert ra.placements == rb.placements  # exact (node, start, end)


def _window_variants(monkeypatch) -> list:
    """Record which streaming-window variant each dispatch ran."""
    seen = []
    orig = device_timeline._window_inputs

    def recording(*a, **kw):
        out = orig(*a, **kw)
        seen.append(out[0])
        return out

    monkeypatch.setattr(device_timeline, "_window_inputs", recording)
    return seen


@pytest.mark.parametrize("placement", ["windows", "sweep"])
def test_mixed_pool_matches_oracle(placement, monkeypatch):
    """Each engine places every attempt of all four policies as the oracle
    does on the mixed pool, and the pool is congested: rows wait in the
    program, wide rows among them."""
    wfs = _wfs()
    variants = _window_variants(monkeypatch)
    stats: dict = {}
    got = run_cluster_batched(
        wfs, POLICIES, n_nodes=3, node_mib=MIXED, placement=placement, placement_stats=stats, **CORPUS
    )
    cfg = KSegmentsConfig(error_mode="progressive")
    for p in POLICIES:
        _assert_same(got[p], run_cluster(wfs, p, n_nodes=3, node_mib=MIXED, ksegments_config=cfg, **CORPUS))
    assert stats["waits_host"] == 0
    assert stats["waits_program"] >= 5
    if placement == "windows":
        assert stats["waits_wide"] > 0 and stats["wide_blocks"] > 0
        assert variants
    else:
        assert stats["program_calls"] == 1 and not variants


@pytest.mark.parametrize("per_node,variant", [(0, "shared"), (12, "pernode")])
def test_window_variant_holds_each_node_to_its_capacity(per_node, variant, monkeypatch):
    """Both streaming-window variants against the host's first-fit at a
    fixed clock, each node probed against its own capacity: empty nodes
    take the shared probe set, sixteen busy ones the per-node probes."""
    rng = np.random.default_rng(5)
    N, w = 16, 32
    caps = rng.choice([1000, 1400, 2000, 2800], N)
    now = int(time_keys(2.5))
    host, device = [], []
    for n in range(N):
        tls = (Timeline(), Timeline())
        for j in range(per_node):
            start = int(time_keys(rng.uniform(0.0, 2.5)))
            b, v = quantize_steps([1.0, np.inf], np.sort(rng.uniform(10.0, 90.0, 2)))
            end = start + int(time_keys(rng.uniform(1.0, 6.0)))
            for tl in tls:
                tl.add(j, b, v, start, end)
        for tl in tls:
            tl.expire(now)
        host.append(tls[0])
        device.append(tls[1].arrays())
    cuts = np.cumsum(rng.uniform(0.5, 1.5, (w, 2)), axis=1)
    cuts[:, -1] = np.inf
    bnd, val = quantize_steps(cuts, np.sort(rng.uniform(300.0, 1400.0, (w, 2)), axis=1))
    run = time_keys(rng.uniform(1.0, 3.0, w))
    probe = run + time_keys(rng.uniform(0.0, 1.0, w))
    variants = _window_variants(monkeypatch)
    placed, node = device_timeline.first_fit_window(now, bnd, val, run, probe, device, caps, 32)
    assert variants == [variant]
    want = []
    for i in range(w):
        hit = next(
            (n for n, tl in enumerate(host) if not tl.demand_exceeds(bnd[i], val[i], now, now + int(probe[i]), int(caps[n]))),
            None,
        )
        if hit is None:
            break
        host[hit].add(100 + i, bnd[i], val[i], now, now + int(run[i]))
        want.append(hit)
    assert 0 < len(want) < w  # some rows placed, then one fits nowhere
    assert placed.tolist() == [True] * len(want) + [False] * (w - len(want))
    assert node[: len(want)].tolist() == want
    assert len(set(want)) > 1 and min(caps[want]) < max(caps)


@pytest.mark.parametrize("placement", ["windows", "sweep"])
def test_uniform_list_bit_equal_to_scalar(placement):
    wfs = _wfs()
    runs = []
    for node_mib in (16 * GIB, [16 * GIB] * 2):
        stats: dict = {}
        res = run_cluster_batched(
            wfs, POLICIES, n_nodes=2, node_mib=node_mib, placement=placement, placement_stats=stats, **CORPUS
        )
        runs.append((res, stats))
    (a, sa), (b, sb) = runs
    assert sa == sb
    assert sa["waits_wide"] == sa["wide_blocks"] == 0
    for p in POLICIES:
        _assert_same(a[p], b[p])
        assert a[p].wastage_gib_s == b[p].wastage_gib_s
        assert [r.wastage_gib_s for r in a[p].records] == [r.wastage_gib_s for r in b[p].records]


def test_uniform_list_bit_equal_in_oracle_and_sweep_grid():
    wfs = _wfs()
    for p in ("default", "ksegments-selective"):
        _assert_same(
            run_cluster(wfs, p, n_nodes=2, node_mib=16 * GIB, **CORPUS),
            run_cluster(wfs, p, n_nodes=2, node_mib=[16 * GIB] * 2, **CORPUS),
        )
    a = run_cluster_sweep(wfs, POLICIES[:2], node_counts=(1, 2), node_mib=16 * GIB, **CORPUS)
    b = run_cluster_sweep(wfs, POLICIES[:2], node_counts=(1, 2), node_mib=[16 * GIB] * 2, **CORPUS)
    assert a.keys() == b.keys()
    for key in a:
        _assert_same(a[key], b[key])


def test_sweep_grid_runs_a_prefix_of_the_pool():
    """A lane of n nodes runs the pool's first n; its ladders cap at the
    largest of them, and each lane equals the windows engine on that pool."""
    wfs = _wfs()
    pool = [16 * GIB, 8 * GIB, 24 * GIB]
    grid = run_cluster_sweep(wfs, POLICIES[:2], node_counts=(2, 3), node_mib=pool, **CORPUS)
    for nn in (2, 3):
        one = run_cluster_batched(wfs, POLICIES[:2], n_nodes=nn, node_mib=pool[:nn], placement="windows", **CORPUS)
        for p in POLICIES[:2]:
            _assert_same(grid[("", p, nn)], one[p])


def _rows(node_mib, seed=7):
    _, rows = batched_rows(_wfs(seed=seed), POLICIES, node_mib, ksegments_config=None, **CORPUS)
    return rows


def test_no_node_over_its_own_capacity():
    """No row lands on a node whose capacity is below its peak, and no
    node's reserved demand exceeds its own capacity at any instant."""
    budget = budget_units(node_capacities(MIXED, 3))
    for p, (bnd, val, run, probe, _, _) in _rows(MIXED).items():
        node, start, end = _place_rows_batched(bnd, val, run, probe, 3, MIXED, 32, None)
        want = place_rows(bnd, val, run, probe, 3, MIXED)
        for g, w in zip((node, start, end), want):
            np.testing.assert_array_equal(g, w)
        assert np.all(val.max(axis=1) <= budget[node]), p
        assert np.any(val.max(axis=1) > budget.min()), p  # the pool has wide rows
        for n in range(3):
            tl = Timeline()
            for i in np.flatnonzero(node == n):
                tl.add(int(i), bnd[i], val[i], int(start[i]), int(end[i]))
            _, cum = tl.arrays()
            assert cum.max(initial=0) <= budget[n], (p, n)


@pytest.mark.parametrize("node_mib", [MIXED, 16 * GIB], ids=["mixed", "uniform"])
def test_wide_counters_equal_a_recount(node_mib, monkeypatch):
    """``waits_wide`` is the wide rows whose start is later than the row's
    before it; ``wide_blocks`` the streaming windows whose first unplaced
    row is wide.  Both recounted here from the placements and the window
    dispatches' own outputs; both 0 on a uniform pool."""
    budget = budget_units(node_capacities(node_mib, 3))
    blocked_wide = []
    orig = device_timeline.first_fit_window

    def recording(now, bnd, val, *a, **kw):
        placed, node = orig(now, bnd, val, *a, **kw)
        if not placed.all():
            blocked_wide.append(bool(val[int(np.argmin(placed))].max() > budget.min()))
        return placed, node

    monkeypatch.setattr(device_timeline, "first_fit_window", recording)
    stats: dict = {}
    wfs = _wfs()
    res = run_cluster_batched(
        wfs, POLICIES, n_nodes=3, node_mib=node_mib, placement="windows", placement_stats=stats, **CORPUS
    )
    rows = _rows(node_mib)
    waits_wide = 0
    for p in POLICIES:
        start = np.asarray([s for rec in res[p].records for _, s, _ in rec.placements])
        wide = rows[p][1].max(axis=1) > budget.min()
        waits_wide += int(np.sum(wide[1:] & (start[1:] > start[:-1])))
    assert stats["waits_host"] == 0
    assert stats["waits_wide"] == waits_wide
    assert stats["wide_blocks"] == sum(blocked_wide)
    if np.ndim(node_mib) == 0:
        assert stats["waits_wide"] == stats["wide_blocks"] == 0
        assert stats["waits_program"] > 0  # the uniform pool waits too, never by a wide row
    else:
        assert stats["waits_wide"] > 0 and stats["wide_blocks"] > 0


def test_capacity_count_must_match_the_nodes():
    np.testing.assert_array_equal(node_capacities(8.0, 3), [8.0, 8.0, 8.0])
    np.testing.assert_array_equal(node_capacities([1.0, 2.0], 2), [1.0, 2.0])
    with pytest.raises(ValueError):
        node_capacities([1.0, 2.0], 3)
    with pytest.raises(ValueError):
        run_cluster_batched(_wfs(scale=0.05), POLICIES[:1], n_nodes=2, node_mib=[GIB] * 3)
