"""Oracle suite for step-demand semantics: the packed profile
(``step_demand_profile``), its incremental twin
(``IncrementalDemandProfile``) and the window probe (``demand_exceeds``) are
checked against a brute-force oracle that evaluates Eq. (1) naively — per
plan, per probe key, no profiles, no cumulative sums.  Everything is in
``core.timeline``'s integer units (time keys, demand units); boundary probes
(at, one key before, and one key after every event key) are always
included.

Each property runs both ways: as a hypothesis ``@given`` test (random seeds,
shrinking — skipped cleanly by the conftest shim when hypothesis is absent)
and as a seeded example loop that keeps coverage in minimal environments.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.allocation import (
    IncrementalDemandProfile,
    demand_exceeds,
    pack_steps,
    quantize_steps,
    step_demand_profile,
)
from repro.core.timeline import time_keys

SEEDS = [0, 1, 2, 7, 19, 101]


def _random_plan(rng) -> tuple[tuple[np.ndarray, np.ndarray], int, int]:
    """((boundary keys, value units), start key, release key) with the
    admission-style release just after r_e."""
    k = int(rng.integers(1, 6))
    steps = quantize_steps(
        np.sort(rng.uniform(0.5, 50.0, k)), np.maximum.accumulate(rng.uniform(10.0, 500.0, k))
    )
    start = int(time_keys(rng.uniform(0.0, 100.0)))
    return steps, start, start + int(steps[0][-1]) + 1


def _oracle_value(steps, start: int, t: int) -> int:
    """Naive Eq. (1): the step to segment s+1 applies from the key just
    after the switch instant ``start + b_s`` (right-open steps)."""
    b, v = steps
    idx = sum(1 for bb in b[:-1] if t >= start + bb + 1)
    return int(v[idx])


def _oracle_total(plans, t: int) -> int:
    """Naive total demand: sum the live plans' values, one at a time."""
    return sum(_oracle_value(steps, s, t) for steps, s, rel in plans if s <= t < rel)


def _event_times(plans) -> np.ndarray:
    ev = []
    for (b, _), start, release in plans:
        ev.append(start)
        ev.extend(start + b + 1)
        ev.append(release)
    return np.asarray(ev, dtype=np.int64)


def _probe_times(plans, rng) -> np.ndarray:
    """Random keys plus every boundary case: each event key, one key before,
    and one key after."""
    ev = _event_times(plans)
    return np.concatenate([time_keys(rng.uniform(0.0, 160.0, 64)), ev, ev - 1, ev + 1])


def _profile_arrays(plans):
    bnd, val = pack_steps([steps for steps, _, _ in plans])
    starts = np.asarray([s for _, s, _ in plans])
    releases = np.asarray([r for _, _, r in plans])
    return step_demand_profile(bnd, val, starts, releases)


def _check_profile_matches_oracle(seed: int) -> None:
    rng = np.random.default_rng(seed)
    plans = [_random_plan(rng) for _ in range(int(rng.integers(1, 9)))]
    times, cum = _profile_arrays(plans)
    for t in _probe_times(plans, rng):
        got = cum[np.searchsorted(times, t, side="right")]
        assert got == _oracle_total(plans, int(t)), t


def _check_incremental_matches_oracle(seed: int) -> None:
    """add/remove/expire churn must leave the incremental profile reading
    exactly like the naive oracle over the surviving plans."""
    rng = np.random.default_rng(seed)
    prof = IncrementalDemandProfile()
    livemap = {}
    for i in range(int(rng.integers(4, 12))):
        steps, start, release = _random_plan(rng)
        prof.add(i, *steps, start, release)
        livemap[i] = (steps, start, release)
    for i in list(livemap):
        if rng.random() < 0.4:
            prof.remove(i)
            del livemap[i]
    plans = list(livemap.values())
    times, cum = prof.arrays()
    for t in _probe_times(plans, rng) if plans else time_keys(np.linspace(0, 100, 16)):
        got = cum[np.searchsorted(times, t, side="right")]
        assert got == _oracle_total(plans, int(t)), t
    # expire at a random instant only drops fully-released plans: readings at
    # keys >= now are unchanged
    if plans:
        now = int(time_keys(rng.uniform(0.0, 200.0)))
        prof.expire(now)
        times2, cum2 = prof.arrays()
        for t in np.concatenate([[now], now + time_keys(rng.uniform(0.0, 100.0, 16))]):
            got = cum2[np.searchsorted(times2, t, side="right")]
            assert got == _oracle_total(plans, int(t))


def _check_demand_exceeds_matches_oracle(seed: int) -> None:
    """The probe's boolean must match the naive window max: the combined
    step function over [start, end] attains its max at some event/boundary
    key, so the oracle evaluates all of them plus their neighbours."""
    rng = np.random.default_rng(seed)
    plans = [_random_plan(rng) for _ in range(int(rng.integers(1, 7)))]
    times, cum = _profile_arrays(plans)
    cand, start, _ = _random_plan(rng)
    end = start + int(cand[0][-1])
    pts = np.concatenate([[start, end], _probe_times(plans, rng), start + cand[0] + 1])
    pts = pts[(pts >= start) & (pts <= end)]
    peak = max(_oracle_total(plans, int(t)) + _oracle_value(cand, start, int(t)) for t in pts)
    for budget, want in [(peak, False), (peak - 1, True)]:
        got = demand_exceeds(times, cum, *cand, start, end, budget, inclusive_end=True)
        assert got == want, (budget, peak, got)


@pytest.mark.parametrize("seed", SEEDS)
def test_profile_matches_oracle(seed):
    _check_profile_matches_oracle(seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_incremental_profile_matches_oracle(seed):
    _check_incremental_matches_oracle(seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_demand_exceeds_matches_oracle(seed):
    _check_demand_exceeds_matches_oracle(seed)


def test_profile_boundary_epsilon_exact():
    """Pinned boundary semantics: AT the switch instant the profile still
    reads the pre-step value; just after it (the odd key), the stepped
    value; through r_e inclusive the plan holds; just after r_e, zero."""
    steps = quantize_steps([10.0, 20.0], [100.0, 500.0])
    start = int(time_keys(5.0))
    end = int(time_keys(25.0))
    plans = [(steps, start, end + 1)]
    times, cum = _profile_arrays(plans)

    def read(t):
        return cum[np.searchsorted(times, t, side="right")]

    sw = int(time_keys(15.0))  # start + first boundary: right-open step
    assert read(sw) == 100
    assert read(sw + 1) == 500
    assert read(end) == 500  # holds through r_e inclusive
    assert read(end + 1) == 0


def test_incremental_remove_is_exact_inverse():
    """After add + remove the arrays are identical to never having added."""
    rng = np.random.default_rng(3)
    prof = IncrementalDemandProfile()
    a1, s1, r1 = _random_plan(rng)
    prof.add("keep", *a1, s1, r1)
    t_before, c_before = (x.copy() for x in prof.arrays())
    a2, s2, r2 = _random_plan(rng)
    prof.add("gone", *a2, s2, r2)
    prof.remove("gone")
    t_after, c_after = prof.arrays()
    np.testing.assert_array_equal(t_before, t_after)
    np.testing.assert_array_equal(c_before, c_after)
    assert "keep" in prof and "gone" not in prof


# -- hypothesis variants (skip cleanly under the conftest shim) -------------


@settings(deadline=None, max_examples=30)
@given(st.integers(0, 2**31 - 1))
def test_property_profile_matches_oracle(seed):
    _check_profile_matches_oracle(seed)


@settings(deadline=None, max_examples=30)
@given(st.integers(0, 2**31 - 1))
def test_property_incremental_matches_oracle(seed):
    _check_incremental_matches_oracle(seed)


@settings(deadline=None, max_examples=30)
@given(st.integers(0, 2**31 - 1))
def test_property_demand_exceeds_matches_oracle(seed):
    _check_demand_exceeds_matches_oracle(seed)
