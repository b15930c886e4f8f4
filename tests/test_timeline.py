"""The shared event timeline (``repro.core.timeline``): incremental-profile
cache coherence under expire/add_many interleaving, the probe-set dedup
helper, and the Timeline probe methods.

The expire/add_many interleave is the regression for the cache-invalidation
class of bug that previously bit ``AdmissionController._prof``: ``expire``
has a min-release fast path that returns without touching the event arrays,
and every derived cache (the lazy cumulative sum, ``version``-keyed caches
in callers, the min-release bound itself) must stay coherent through any
interleaving of fast-path hits, real expiries and batched adds.
"""

import numpy as np
import pytest

from repro.core.timeline import (
    IncrementalDemandProfile,
    Timeline,
    demand_exceeds,
    demand_exceeds_many,
    quantize_steps,
    shared_probe_set,
    time_keys,
)
from repro.sim.cluster import NodeState


def _k(seconds) -> int:
    """Seconds -> the key of that instant (timeline units)."""
    return int(time_keys(seconds))


def _rand_res(rng, k=3):
    """Random steps in timeline units: (boundary keys, value units)."""
    b = np.sort(rng.uniform(0.5, 30.0, k))
    v = np.maximum.accumulate(rng.uniform(10.0, 400.0, k))
    return quantize_steps(b, v)


def _rebuilt(tl: Timeline, rows) -> Timeline:
    """A from-scratch profile holding the same still-live reservations."""
    fresh = Timeline()
    for owner, (b, v, s, e) in rows.items():
        if owner in tl:
            fresh.add(owner, b, v, s, e)
    return fresh


def test_expire_fast_path_keeps_caches_coherent():
    """Interleave add/add_many with expire calls that alternately hit the
    min-release fast path and actually drop rows; after every step the
    cached cumulative profile must match a from-scratch rebuild and
    ``version`` must change iff the event arrays changed."""
    rng = np.random.default_rng(0)
    tl = Timeline()
    rows: dict = {}
    owner = 0
    clock = 0
    for step in range(40):
        op = rng.random()
        ver = tl.version
        t_before, c_before = (a.copy() for a in tl.arrays())
        if op < 0.45:
            n = int(rng.integers(1, 4))
            bs, vs, ss, es = [], [], [], []
            names = []
            for _ in range(n):
                b, v = _rand_res(rng)
                s = clock + _k(rng.uniform(0.0, 10.0))
                e = s + _k(rng.uniform(5.0, 40.0))
                rows[owner] = (b, v, s, e)
                names.append(owner)
                bs.append(b), vs.append(v), ss.append(s), es.append(e)
                owner += 1
            tl.add_many(names, np.stack(bs), np.stack(vs), ss, es)
            assert tl.version != ver  # arrays changed -> caches must re-key
        elif op < 0.7:
            # a time strictly before every live release: the fast path MUST
            # hit and MUST leave arrays, caches and version untouched
            live = [e for o, (_, _, _, e) in rows.items() if o in tl]
            if live:
                tl.expire(min(live) - 1)
                t, c = tl.arrays()
                np.testing.assert_array_equal(t, t_before)
                np.testing.assert_array_equal(c, c_before)
                assert tl.version == ver
        else:
            clock += _k(rng.uniform(5.0, 25.0))
            dropped = [o for o, (_, _, _, e) in rows.items() if o in tl and e <= clock]
            tl.expire(clock)
            for o in dropped:
                assert o not in tl
            if dropped:
                assert tl.version != ver
        fresh = _rebuilt(tl, rows)
        tf, cf = fresh.arrays()
        t, c = tl.arrays()
        assert len(t) == len(tf)
        np.testing.assert_array_equal(np.sort(t), np.sort(tf))
        # probe a grid: the maintained profile must read identically to the
        # rebuilt one at every instant (value-coherence of the cum cache)
        grid = np.concatenate([tf, [clock, clock + _k(100.0)]]) if len(tf) else np.asarray([clock])
        got = c[np.searchsorted(t, grid, side="right")]
        want = cf[np.searchsorted(tf, grid, side="right")]
        np.testing.assert_array_equal(got, want)


def test_node_state_expire_interleaved_with_add_many():
    """NodeState's profile must survive expire (incl. fast-path hits) and
    vectorized add_many commits without a stale cumulative profile."""
    rng = np.random.default_rng(7)
    nd = NodeState(capacity_mib=5000.0)
    active = []
    clock = 0
    for _ in range(25):
        b, v = _rand_res(rng)
        s = clock + _k(rng.uniform(0.0, 5.0))
        e = s + _k(rng.uniform(3.0, 30.0))
        nd.add(e, b, v, s)
        active.append((e, b, v, s))
        # expire at a time before every active end: fast path territory
        nd.expire(min(a[0] for a in active) - _k(0.5))
        if rng.random() < 0.4:
            clock += _k(rng.uniform(5.0, 20.0))
            nd.expire(clock)
            active = [a for a in active if a[0] > clock]
        # oracle read: rebuilt node with the same still-active rows
        fresh = NodeState(capacity_mib=5000.0)
        for e2, b2, v2, s2 in active:
            fresh.add(e2, b2, v2, s2)
        for t in [clock, clock + _k(1.0), clock + _k(10.0), clock + _k(50.0)]:
            assert nd.reserved_at(t) == fresh.reserved_at(t)


def test_expired_rows_do_not_change_future_probes():
    """Dropping released reservations must not flip any fit decision at
    probes past the expiry clock."""
    tl = Timeline()
    tl.add("a", *quantize_steps([5.0], [400.0]), 0, _k(10.0))
    tl.add("b", *quantize_steps([5.0], [300.0]), 0, _k(30.0))
    cand = quantize_steps([4.0], [500.0])
    before = tl.demand_exceeds(*cand, _k(15.0), _k(25.0), 800)
    tl.expire(_k(12.0))
    assert "a" not in tl and "b" in tl
    assert tl.demand_exceeds(*cand, _k(15.0), _k(25.0), 800) == before


# ---------------------------------------------------------------------------
# shared probe set
# ---------------------------------------------------------------------------


def test_shared_probe_set_dedups_and_sorts():
    a = np.asarray([3, 1, 2])
    b = np.asarray([[2, 5], [1, 3]])  # raveled; overlaps a
    P = shared_probe_set(a, b)
    np.testing.assert_array_equal(P, [1, 2, 3, 5])


def test_shared_probe_set_inverse_maps_back():
    a = np.asarray([4, 4, 1])
    b = np.asarray([1, 9])
    P, inv = shared_probe_set(a, b, return_inverse=True)
    np.testing.assert_array_equal(P, [1, 4, 9])
    cat = np.concatenate([a, b])
    np.testing.assert_array_equal(P[inv.ravel()], cat)


def test_probe_dedup_cannot_change_decisions():
    """Probing a step profile at duplicated instants reads identical values
    — dedup must never flip a demand_exceeds verdict."""
    rng = np.random.default_rng(3)
    tl = Timeline()
    for i in range(6):
        b, v = _rand_res(rng)
        s = _k(rng.uniform(0.0, 20.0))
        tl.add(i, b, v, s, s + _k(rng.uniform(5.0, 30.0)))
    times, cum = tl.arrays()
    # duplicate-heavy probe grid vs its deduped version
    grid = np.concatenate([times, times, np.repeat(times[:4], 3)]) if len(times) else np.zeros(1, int)
    dedup = shared_probe_set(grid)
    got_dup = cum[np.searchsorted(times, grid, side="right")]
    got_ded = cum[np.searchsorted(times, dedup, side="right")]
    assert set(got_dup.tolist()) == set(got_ded.tolist())


# ---------------------------------------------------------------------------
# Timeline probe methods == free functions
# ---------------------------------------------------------------------------


def test_timeline_methods_match_free_functions():
    rng = np.random.default_rng(11)
    tl = Timeline()
    for i in range(5):
        b, v = _rand_res(rng)
        s = _k(rng.uniform(0.0, 15.0))
        tl.add(i, b, v, s, s + _k(rng.uniform(5.0, 25.0)))
    cand = _rand_res(rng)
    times, cum = tl.arrays()
    for s in (_k(0.0), _k(3.0), _k(17.5)):
        for inc in (False, True):
            assert tl.demand_exceeds(*cand, s, s + _k(12.0), 900, inclusive_end=inc) == demand_exceeds(
                times, cum, *cand, s, s + _k(12.0), 900, inclusive_end=inc
            )
    starts = time_keys([0.0, 2.0, 9.0, 21.0])
    np.testing.assert_array_equal(
        tl.demand_exceeds_many(*cand, starts, _k(8.0), 900),
        demand_exceeds_many(times, cum, *cand, starts, _k(8.0), 900),
    )


def test_incremental_demand_profile_alias():
    """The historical name must stay importable and be the same class."""
    assert IncrementalDemandProfile is Timeline


def test_add_many_duplicate_owner_leaves_state_clean():
    tl = Timeline()
    tl.add("x", np.asarray([4000]), np.asarray([100]), 0, 10000)
    with pytest.raises(ValueError):
        tl.add_many(["y", "x"], np.full((2, 1), 4000), np.full((2, 1), 50), [0, 0], [8000, 8000])
    assert "y" not in tl and tl.n_owners == 1


def test_units_round_up_and_forgive_float_noise():
    """Allocations round up to whole units, except that float noise of a few
    ulps above a whole unit (a float32 prediction of a float64 integer)
    stays on that unit; capacities round down; times floor to ticks, +inf
    boundaries become NEVER and the horizon raises."""
    from repro.core.timeline import HORIZON_TICKS, NEVER, TICKS_PER_S, budget_units, demand_units

    noisy = float(np.nextafter(np.nextafter(np.float32(100.0), np.float32(np.inf)), np.float32(np.inf)))
    assert demand_units([100.0, noisy, 100.01, 0.2]).tolist() == [100, 100, 101, 1]
    assert int(budget_units(100.9)) == 100
    assert time_keys([0.0, 1.2345, np.inf]).tolist() == [0, 2468, NEVER]
    for bad in (-1.0, np.nan, HORIZON_TICKS / TICKS_PER_S):
        with pytest.raises(OverflowError):
            time_keys(bad)


def test_span_keys_round_up_and_forgive_float_noise():
    """Run lengths and release instants round up to ticks (a reservation is
    never released before the time it covers); instants and boundaries
    round down.  Both forgive float noise next to a whole tick."""
    from repro.core.timeline import HORIZON_TICKS, NEVER, TICKS_PER_S, span_keys

    assert 3 * 0.1 * TICKS_PER_S > 300  # float noise above a whole tick
    assert span_keys([0.0, 1.2345, 3 * 0.1, 1.0, np.inf]).tolist() == [0, 2470, 600, 2000, NEVER]
    assert 1.003 * TICKS_PER_S < 1003  # float noise below a whole tick
    assert time_keys([1.2345, 3 * 0.1, 1.003]).tolist() == [2468, 600, 2006]
    for bad in (-1.0, np.nan, HORIZON_TICKS / TICKS_PER_S):
        with pytest.raises(OverflowError):
            span_keys(bad)


@pytest.mark.parametrize("now", [0.0, 0.0009, 2.5])
def test_plan_release_key_never_before_its_end(now):
    """An admission plan's final boundary is its end: ``quantize_plan``
    rounds the instant ``now + r_k`` up, so the plan holds through it and
    releases just after, even where ``now`` and ``r_k`` both lose a
    fraction of a tick to the floor.  Interior boundaries still floor."""
    from repro.core.timeline import TICKS_PER_S, quantize_plan

    r = [0.4004, 1.0005]
    b, v, start = quantize_plan(r, [100.0, 200.0], now)
    assert int(start) == 2 * int(np.floor(now * TICKS_PER_S))
    assert b[0] == int(time_keys(r[0]))
    end = int(start) + int(b[-1])
    assert end == 2 * int(np.ceil((now + r[-1]) * TICKS_PER_S))
    assert end >= 2 * (now + r[-1]) * TICKS_PER_S
    # batched quantization of the same plan agrees row for row
    bb, _, ss = quantize_plan(np.tile(r, (2, 1)), np.tile([100.0, 200.0], (2, 1)), np.full(2, now))
    assert (ss + bb[:, -1]).tolist() == [end, end]
