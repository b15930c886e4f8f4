"""The event timeline: one representation of time-varying step demand.

Every packer in this repo — the cluster scheduler's per-node reservations
(``sim.cluster.NodeState``), the serving admission controller's active plans
(``serve.admission``), and the device programs that batch both
(``sim.device_timeline``) — evaluates the same object: the sum of concurrent
Eq. (1) step reservations as a function of time, probed at the instants where
it can rise.  This module is that object's single implementation.

**Units.**  Time and demand are integers, so every sum is exact in any order
and the device's parallel prefix sums match the host's sequential ones bit
for bit:

* a *tick* is ``1 / TICKS_PER_S`` seconds (1 ms).  A time **key** is twice a
  tick count: the instant ``t`` is key ``2t`` and "just after ``t``" is key
  ``2t + 1``.  Eq. (1) steps are right-open, so a step at boundary ``b`` takes
  effect at key ``2(start + b) + 1``; the odd keys are that explicit tie rank.
  Boundaries, durations and offsets are keys too (``2 x ticks``).
  ``time_keys`` rounds instants and step boundaries down to a tick (a step
  up never starts later than the prediction says); ``span_keys`` rounds run
  lengths and release instants up (a reservation is never released before
  the time it covers).  Both raise past ``HORIZON_TICKS``: keys fit int32
  with ``NEVER`` (int32 max) reserved for "never" — padding, unfired
  switches, +inf boundaries.  At 1 ms ticks the horizon is 12.4 days.
* a demand *unit* is ``MIB_PER_UNIT`` MiB.  ``demand_units`` rounds
  allocations up (never under-reserve by more than float noise: a value
  within ``UNIT_SNAP`` of a unit below counts as that unit, so a prediction
  that is a whole MiB in float64 and a few ulps above it in float32 — on
  another backend — lands on the same unit) and ``budget_units`` rounds
  capacities down (never over-commit); both raise past ``MAX_UNITS``, which
  leaves int32 room for the partial sums inside a tie group.

The objects:

* **events**: sorted keys + demand deltas.  A reservation over
  ``[start, release)`` contributes ``+v_0`` at its start, each step delta at
  ``start + b + 1``, and ``-v_end`` at its release key.
* **cumulative profile**: the running sum of deltas; the demand at key ``p``
  is ``cum[searchsorted(times, p, side="right")]`` — always the value *after*
  every event tied at a key, never a partial mid-tie sum that exists at no
  real time.
* **probes**: ``demand_exceeds`` / ``demand_exceeds_many`` evaluate a
  candidate reservation against the profile at the union of the candidate's
  own step-ups and the profile's events inside the window — the only points
  where the combined step function can rise.  ``shared_probe_set`` builds the
  deduped probe union the batched programs dispatch on.

``Timeline`` maintains the event arrays incrementally under add / add_many /
remove / expire, keyed by owner, so per-decision cost is O(E + k) instead of
a rebuild.
"""

from __future__ import annotations

import numpy as np

TICKS_PER_S = 1000  # one tick is 1 ms
MIB_PER_UNIT = 1.0  # one demand unit is 1 MiB
NEVER = 2**31 - 1  # the key of an instant that never comes (int32 max)
HORIZON_TICKS = 2**30 - 1  # every instant is below this tick: 2t + 1 < NEVER
MAX_UNITS = 2**24  # largest allocation or budget: 2**7 of int32 headroom for sums
UNIT_SNAP = 2.0**-11  # float noise forgiven when rounding up: half a KiB, in units
TICK_SNAP = 2.0**-10  # float noise forgiven when rounding to a tick: ~1 us, in ticks


def _keys(seconds, round_ticks) -> np.ndarray:
    s = np.asarray(seconds, dtype=np.float64)
    fin = np.isfinite(s)
    ticks = round_ticks(np.where(fin, s, 0.0) * TICKS_PER_S)
    if np.any(np.isnan(s) | (s == -np.inf) | (ticks < 0) | (ticks >= HORIZON_TICKS)):
        raise OverflowError(
            f"time outside [0, {HORIZON_TICKS / TICKS_PER_S:.0f} s): the int32 tick horizon"
        )
    return np.where(fin, 2 * ticks.astype(np.int64), NEVER)


def time_keys(seconds) -> np.ndarray:
    """Seconds -> int64 keys, rounded down (``2 x floor(seconds x
    TICKS_PER_S)``, forgiving ``TICK_SNAP`` of float noise, so 1.003 s is
    tick 1003): instants and step boundaries.

    +inf (a padded segment's boundary) maps to ``NEVER``.  Raises
    ``OverflowError`` for a negative or NaN time or one at or past the
    horizon."""
    return _keys(seconds, lambda ticks: np.floor(ticks + TICK_SNAP))


def span_keys(seconds) -> np.ndarray:
    """Seconds -> int64 keys, rounded up (``2 x ceil(seconds x TICKS_PER_S)``,
    forgiving ``TICK_SNAP`` of float noise): run lengths and release
    instants, so a reservation is never released before the time it covers.
    Same ``NEVER`` mapping and horizon checks as ``time_keys``."""
    return _keys(seconds, lambda ticks: np.ceil(ticks - TICK_SNAP))


def key_seconds(keys) -> np.ndarray:
    """Keys of instants -> seconds (the inverse of ``time_keys`` on ticks)."""
    return (np.asarray(keys, dtype=np.int64) // 2) / TICKS_PER_S


def check_horizon(keys) -> None:
    """Raise ``OverflowError`` if any instant key reaches the horizon — the
    guard every scheduler applies to the ends it computes, so an int32 key
    can never wrap silently."""
    keys = np.asarray(keys)
    if keys.size and int(keys.max()) >= 2 * HORIZON_TICKS:
        raise OverflowError(
            f"schedule ran past the int32 time horizon ({HORIZON_TICKS / TICKS_PER_S:.0f} s)"
        )


def _check_units(u: np.ndarray) -> np.ndarray:
    if u.size and (u.min() < 0 or u.max() > MAX_UNITS):
        raise OverflowError(f"demand outside [0, {MAX_UNITS}] units of {MIB_PER_UNIT} MiB")
    return u


def demand_units(mib) -> np.ndarray:
    """MiB -> int64 demand units, rounded up (an allocation is cut by at
    most ``UNIT_SNAP`` units of float noise, never by more)."""
    units = np.ceil(np.asarray(mib, dtype=np.float64) / MIB_PER_UNIT - UNIT_SNAP)
    return _check_units(units.astype(np.int64))


def budget_units(mib) -> np.ndarray:
    """MiB -> int64 demand units, rounded down (a capacity is never grown)."""
    return _check_units(np.floor(np.asarray(mib, dtype=np.float64) / MIB_PER_UNIT).astype(np.int64))


def quantize_steps(boundaries, values) -> tuple[np.ndarray, np.ndarray]:
    """A step allocation (seconds, MiB; any leading shape) in timeline units:
    ``(boundary keys, value units)``."""
    return time_keys(boundaries), demand_units(values)


def quantize_plan(boundaries, values, now) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """An admission plan started at ``now`` seconds (``boundaries``/``values``
    of shape (..., k), ``now`` of the leading shape) in timeline units:
    ``(boundary keys, value units, start keys)``.

    The steps round as ``quantize_steps`` rounds them, except the final
    boundary: it is the plan's end, where nothing steps, and the plan holds
    through it inclusive.  Its instant ``now + r_k`` rounds up
    (``span_keys``), so an admitted plan is never released early."""
    b, v = quantize_steps(boundaries, values)
    starts = time_keys(now)
    b[..., -1] = span_keys(np.asarray(now) + np.asarray(boundaries, dtype=np.float64)[..., -1]) - starts
    return b, v, starts


def step_demand_profile(
    bnd: np.ndarray, val: np.ndarray, starts: np.ndarray, releases: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Total demand of R concurrent step reservations as a cumulative profile.

    Args:
      bnd: (R, kmax) boundary keys, ``NEVER``-padded past each reservation's k.
      val: (R, kmax) value units, hold-last padded.
      starts: (R,) start keys (inclusive).
      releases: (R,) release keys (exclusive: at ``releases[r]`` the
        reservation no longer counts).

    Returns (event keys, cumulative demand): the total at key ``p`` is
    ``cum[np.searchsorted(times, p, side="right")]``.  Eq. (1) steps are
    right-open, so each step-up event sits at ``start + b + 1``.
    """
    bnd = np.asarray(bnd, dtype=np.int64)
    val = np.asarray(val, dtype=np.int64)
    starts = np.asarray(starts, dtype=np.int64)
    releases = np.asarray(releases, dtype=np.int64)
    live = bnd < (releases - starts)[:, None]
    steps = np.concatenate([np.diff(val, axis=1), np.zeros((len(val), 1), np.int64)], axis=1)
    # the released value counts only the switches that fired before release
    vext = np.concatenate([val, val[:, -1:]], axis=1)
    v_end = np.take_along_axis(vext, np.sum(live, axis=1)[:, None], axis=1)[:, 0]
    sw = starts[:, None] + np.where(live, bnd, 0) + 1
    times = np.concatenate([starts, sw[live], releases])
    deltas = np.concatenate([val[:, 0], steps[live], -v_end])
    order = np.argsort(times, kind="stable")
    return times[order], np.concatenate([[0], np.cumsum(deltas[order])])


def _value_at(b: np.ndarray, v: np.ndarray, offsets) -> np.ndarray:
    """The allocation's value at key offsets from its start: ``v[#(b < off)]``
    clipped to the last segment (a step at ``b`` applies from ``b + 1``)."""
    idx = np.minimum(np.searchsorted(b, offsets, side="left"), len(b) - 1)
    return v[idx]


def demand_exceeds(
    times: np.ndarray,
    cum: np.ndarray,
    b: np.ndarray,
    v: np.ndarray,
    start: int,
    end: int,
    budget: int,
    *,
    inclusive_end: bool = False,
) -> bool:
    """Does profile demand + a candidate step reservation exceed ``budget``
    anywhere in [start, end) — or [start, end] with ``inclusive_end``?

    ``(times, cum)`` is a cumulative profile (``step_demand_profile`` /
    ``Timeline.arrays``); the candidate holds the steps ``(b, v)`` (boundary
    keys, value units) from key ``start``.  Demand is probed at the start, at
    the candidate's own step-ups inside the window (``start + b + 1`` for
    ``b < end - start``) and at every profile event in the window — the only
    points where the combined step function can rise.  Shared by
    ``NodeState.fits`` (cluster placement; window right-open at the
    candidate's departure) and ``AdmissionController.try_admit`` (HBM
    packing; a plan holds through its final boundary inclusive), so their
    probe semantics cannot drift apart.
    """
    b = np.asarray(b, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    probes = np.concatenate([[start], start + b[b < end - start] + 1])
    lo = np.searchsorted(times, start, side="right")  # events at start fold into the start probe
    hi = np.searchsorted(times, end, side="right" if inclusive_end else "left")
    t_all = np.concatenate([probes, times[lo:hi]])
    # Every probe reads the cumulative sum AFTER all events tied at its key
    # (searchsorted side="right"), never a partial mid-tie sum.
    prof = cum[np.searchsorted(times, t_all, side="right")]
    return bool(np.any(prof + _value_at(b, v, t_all - start) > budget))


def demand_exceeds_many(
    times: np.ndarray,
    cum: np.ndarray,
    b: np.ndarray,
    v: np.ndarray,
    starts: np.ndarray,
    duration: int,
    budget: int,
) -> np.ndarray:
    """``demand_exceeds`` vectorized over S candidate start keys of ONE
    allocation, with the cluster scheduler's right-open window
    ``[start, start + duration)``.

    Evaluates the exact probe expressions of the scalar function — the start,
    each own switch inside the window and every profile event strictly
    inside it, all read via ``searchsorted(..., "right")`` — so a True/False
    here is identical to S scalar calls (tests/test_cluster_placement.py
    holds it to the scalar probe).

    Returns a (S,) bool array: True where demand would exceed ``budget``.
    """
    b = np.asarray(b, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    starts = np.asarray(starts, dtype=np.int64)
    ends = starts + duration
    own_ok = np.concatenate(
        [np.ones((len(starts), 1), dtype=bool), np.broadcast_to(b < duration, (len(starts), len(b)))],
        axis=1,
    )
    own_p = starts[:, None] + np.concatenate([[0], np.where(b < duration, b, 0) + 1])[None, :]
    prof_own = cum[np.searchsorted(times, own_p, side="right")]
    over = np.any(own_ok & (prof_own + _value_at(b, v, own_p - starts[:, None]) > budget), axis=1)
    # profile events strictly inside each window; only the slice any window
    # can reach participates in the (S, E) probe
    lo = np.searchsorted(times, starts.min(), side="right")
    hi = np.searchsorted(times, ends.max(), side="left")
    if hi > lo:
        ev = times[lo:hi]
        in_win = (ev[None, :] > starts[:, None]) & (ev[None, :] < ends[:, None])
        prof_ev = cum[np.searchsorted(times, ev, side="right")]  # after each tie group
        own_ev = _value_at(b, v, ev[None, :] - starts[:, None])
        over |= np.any(in_win & (prof_ev[None, :] + own_ev > budget), axis=1)
    return over


def plan_profile_events(
    boundaries: np.ndarray, values: np.ndarray, start: int, release: int
) -> tuple[np.ndarray, np.ndarray]:
    """One reservation's demand events, exactly as ``step_demand_profile``
    derives them for a row: ``(keys, deltas)`` sorted by key — the start
    (+v_0), each live switch at ``start + b + 1`` (the step delta), and the
    release (-v_end, where v_end counts only switches that fired before
    ``release``).  The multiset of events produced for a reservation set
    equals ``step_demand_profile``'s, which is what lets ``Timeline``
    maintain the same profile under add/remove instead of rebuilding it."""
    b = np.asarray(boundaries, dtype=np.int64)
    v = np.asarray(values, dtype=np.int64)
    live = b < release - start
    steps = np.append(np.diff(v), 0)  # step at the final boundary is 0 (hold-last)
    idx_end = int(np.sum(live))
    v_end = v[-1] if idx_end >= len(v) else v[idx_end]
    times = np.concatenate([[start], start + b[live] + 1, [release]])
    deltas = np.concatenate([[v[0]], steps[live], [-v_end]])
    return times, deltas


def pack_steps(steps: list[tuple[np.ndarray, np.ndarray]]) -> tuple[np.ndarray, np.ndarray]:
    """Pad R ``(boundary keys, value units)`` step pairs of varying k into the
    (R, kmax) layout ``step_demand_profile`` consumes: ``NEVER``-padded
    boundaries and hold-last values."""
    R = len(steps)
    kmax = max((len(b) for b, _ in steps), default=1)
    bnd = np.full((R, kmax), NEVER, dtype=np.int64)
    val = np.empty((R, kmax), dtype=np.int64)
    for r, (b, v) in enumerate(steps):
        bnd[r, : len(b)] = b
        val[r, : len(v)] = v
        val[r, len(v) :] = v[-1]
    return bnd, val


def shared_probe_set(*parts: np.ndarray, return_inverse: bool = False):
    """The deduped probe union a batched program dispatches on.

    ``parts`` are arrays of probe keys (profile events, candidate starts,
    switch keys ...).  Overlapping candidate boundaries repeat heavily, so
    the sorted-unique union is routinely a power-of-two bucket smaller than
    the raw concatenation — probes only sample step functions, so dropping
    duplicates cannot change any max.  With ``return_inverse`` the
    (concatenated-order) inverse mapping into the unique array is returned
    too, for callers that need to scatter per-probe results back."""
    cat = np.concatenate([np.ravel(np.asarray(p, dtype=np.int64)) for p in parts])
    if return_inverse:
        return np.unique(cat, return_inverse=True)
    return np.unique(cat)


class Timeline:
    """The event timeline maintained incrementally under add / remove /
    expire, keyed by owner.

    A full rebuild re-packs every reservation and re-sorts all events
    (O(R k + E log E) per mutation); this keeps the sorted event arrays live
    and merges one reservation's ~k+2 events in O(E + k) (``np.searchsorted``
    + one splice), recomputing the cumulative sum lazily in one O(E) pass.
    Event values are identical to the rebuilt profile's; only the order of
    key-tied events can differ, which probes never observe (they read the
    cumulative sum after all events tied at a key, and integer sums do not
    depend on order).

    Backing store of the serving admission controllers, the cluster
    simulator's ``NodeState``, and the batched scheduler's per-node state
    (``sim.device_timeline.schedule_epoch`` seeds its scan carry from
    ``events()``), so every consumer reads one source of truth.  ``version``
    increments on every mutation that changes the event arrays — caches
    derived from them must key on it, including across ``expire`` calls that
    hit the min-release fast path and change nothing.
    """

    def __init__(self):
        self._times = np.empty(0, dtype=np.int64)
        self._deltas = np.empty(0, dtype=np.int64)
        self._codes = np.empty(0, dtype=np.int64)
        self._next_code = 0
        self._owners: dict = {}  # owner -> event code
        self._releases: dict = {}  # owner -> release key (for expire())
        self._cum: np.ndarray | None = None
        self._version = 0
        # lower bound on min(self._releases.values()); lets expire() return
        # without scanning the owner dict (the scheduler calls it per epoch).
        # Stale-low is safe: the fast path just isn't taken.
        self._min_release = NEVER

    @property
    def n_events(self) -> int:
        return len(self._times)

    @property
    def n_owners(self) -> int:
        return len(self._owners)

    @property
    def version(self) -> int:
        """Mutation counter: changes iff the event arrays changed."""
        return self._version

    def __contains__(self, owner) -> bool:
        return owner in self._owners

    def add(self, owner, boundaries: np.ndarray, values: np.ndarray, start: int, release: int) -> None:
        """Merge one reservation's events into the profile (O(E + k)) —
        the scalar twin of ``add_many``, skipping its batch plumbing."""
        if owner in self._owners:
            raise ValueError(f"owner(s) already hold a reservation: [{owner!r}]")
        t, d = plan_profile_events(boundaries, values, int(start), int(release))
        code = self._next_code
        self._next_code += 1
        self._owners[owner] = code
        self._releases[owner] = int(release)
        self._min_release = min(self._min_release, int(release))
        self._splice(t, d, np.full(len(t), code, dtype=np.int64))

    def add_many(self, owners, boundaries: np.ndarray, values: np.ndarray, starts, releases) -> None:
        """Merge R reservations in one pass: their events are concatenated
        (each reservation's own events are already key-sorted), sorted once,
        and spliced into the live arrays with a single insert — the batch
        commit path of the admission engine and of the batched cluster
        scheduler's per-epoch placements.

        Event construction is the vectorized twin of ``plan_profile_events``
        — row-major flattening keeps each row's events grouped in commit
        order, so with the stable key sort the spliced arrays are identical
        to R sequential ``add`` calls."""
        owners = list(owners)
        dup = [o for o in owners if o in self._owners]
        if dup or len(set(owners)) != len(owners):
            raise ValueError(f"owner(s) already hold a reservation: {dup or owners!r}")
        R = len(owners)
        if R == 0:
            return
        b = np.asarray(boundaries, dtype=np.int64).reshape(R, -1)
        v = np.asarray(values, dtype=np.int64).reshape(R, -1)
        starts = np.asarray(starts, dtype=np.int64).reshape(R)
        rels = np.asarray(releases, dtype=np.int64).reshape(R)
        codes = np.arange(self._next_code, self._next_code + R, dtype=np.int64)
        self._next_code += R
        for o, c_, rl in zip(owners, codes, rels):
            self._owners[o] = int(c_)
            self._releases[o] = int(rl)
        self._min_release = min(self._min_release, int(rels.min()))
        live = b < (rels - starts)[:, None]
        steps = np.concatenate([np.diff(v, axis=1), np.zeros((R, 1), np.int64)], axis=1)
        vext = np.concatenate([v, v[:, -1:]], axis=1)
        v_end = np.take_along_axis(vext, np.sum(live, axis=1)[:, None], axis=1)[:, 0]
        sw = starts[:, None] + np.where(live, b, 0) + 1
        times = np.concatenate([starts[:, None], sw, rels[:, None]], axis=1)
        deltas = np.concatenate([v[:, :1], steps, -v_end[:, None]], axis=1)
        mask = np.concatenate([np.ones((R, 1), bool), live, np.ones((R, 1), bool)], axis=1)
        m = mask.ravel()
        t = times.ravel()[m]
        d = deltas.ravel()[m]
        c = np.repeat(codes, mask.shape[1])[m]
        order = np.argsort(t, kind="stable")
        self._splice(t[order], d[order], c[order])

    def _splice(self, t: np.ndarray, d: np.ndarray, c: np.ndarray) -> None:
        """Merge key-sorted events into the live arrays — one manual splice
        for all three (np.insert's index normalization costs more than the
        merge itself at this size), ``side="right"`` so key-tied newcomers
        land after existing events."""
        E, n = len(self._times), len(t)
        pos = np.searchsorted(self._times, t, side="right") + np.arange(n)
        old_pos = np.ones(E + n, dtype=bool)
        old_pos[pos] = False
        times = np.empty(E + n, dtype=np.int64)
        deltas = np.empty(E + n, dtype=np.int64)
        codes = np.empty(E + n, dtype=np.int64)
        times[pos], times[old_pos] = t, self._times
        deltas[pos], deltas[old_pos] = d, self._deltas
        codes[pos], codes[old_pos] = c, self._codes
        self._times, self._deltas, self._codes = times, deltas, codes
        self._cum = None
        self._version += 1

    def remove(self, owner) -> None:
        """Drop one reservation's events (O(E)); no-op for unknown owners."""
        code = self._owners.pop(owner, None)
        if code is None:
            return
        self._releases.pop(owner, None)
        keep = self._codes != code
        self._times = self._times[keep]
        self._deltas = self._deltas[keep]
        self._codes = self._codes[keep]
        self._cum = None
        self._version += 1

    def expire(self, now: int) -> None:
        """Garbage-collect reservations fully released at or before key
        ``now``.

        A released reservation's deltas telescope to zero past its release,
        so dropping its events cannot change any probe at keys >= now — this
        only bounds the event count for long-running controllers.  The
        min-release fast path returns without touching the arrays, the
        cached cumulative sum, or ``version`` — a hit must leave every
        derived cache valid (see tests/test_timeline.py)."""
        if now < self._min_release:
            return
        gone = [o for o, r in self._releases.items() if r <= now]
        if not gone:
            # restore the fast path for the next caller; nothing changed, so
            # caches (and version) stay untouched
            self._min_release = min(self._releases.values(), default=NEVER)
            return
        codes = np.asarray([self._owners.pop(o) for o in gone], dtype=np.int64)
        for o in gone:
            self._releases.pop(o, None)
        self._min_release = min(self._releases.values(), default=NEVER)
        keep = ~np.isin(self._codes, codes)
        self._times = self._times[keep]
        self._deltas = self._deltas[keep]
        self._codes = self._codes[keep]
        self._cum = None
        self._version += 1

    def events(self) -> tuple[np.ndarray, np.ndarray]:
        """(event keys (E,), demand deltas (E,)) — the raw sorted event
        stream, the form the device scheduling program seeds its carry with
        (it maintains its own running sum).  Views of live arrays: treat as
        read-only; stale after any mutation (key on ``version``)."""
        return self._times, self._deltas

    def arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(event keys (E,), cumulative demand (E+1,)) — read exactly like
        ``step_demand_profile``'s output: the total at key ``p`` is
        ``cum[np.searchsorted(times, p, side="right")]``."""
        if self._cum is None:
            self._cum = np.concatenate([[0], np.cumsum(self._deltas)])
        return self._times, self._cum

    def demand_at(self, p):
        """Total demand at key(s) ``p`` (vectorized) — the canonical
        side="right" read of the cumulative profile."""
        times, cum = self.arrays()
        return cum[np.searchsorted(times, np.asarray(p), side="right")]

    def demand_exceeds(self, b, v, start: int, end: int, budget: int, *, inclusive_end: bool = False) -> bool:
        """``demand_exceeds`` against this timeline's cached profile."""
        times, cum = self.arrays()
        return demand_exceeds(times, cum, b, v, start, end, budget, inclusive_end=inclusive_end)

    def demand_exceeds_many(self, b, v, starts: np.ndarray, duration: int, budget: int) -> np.ndarray:
        """``demand_exceeds_many`` against this timeline's cached profile."""
        times, cum = self.arrays()
        return demand_exceeds_many(times, cum, b, v, starts, duration, budget)


# Historical name: the class began life as the serving admission
# controller's incremental backing store before becoming the shared
# timeline; existing callers and tests import it under this name.
IncrementalDemandProfile = Timeline
