"""Device programs over the event timeline (``repro.core.timeline``).

Both batched packers — the serving admission engine (``serve.admission``)
and the cluster scheduler's placement loop (``sim.cluster``) — evaluate the
same quantities per (candidate, probe instant); this module holds their
jitted programs so the boundary semantics live in exactly one place:

* ``candidate_probe_parts`` — the per-(candidate, probe) demand pieces every
  packing program needs (own allocation value, window membership, committed
  demand contribution): the jnp twin of what ``core.timeline`` expresses in
  numpy.
* ``admission_program`` — whole candidate batches admitted against the HBM
  budget with a ``lax.scan`` threading within-batch sequencing.
* ``schedule_epoch`` — the cluster scheduler's full scheduling-epoch
  program: the event clock and the per-node release heap live in the scan
  carry, so when a queued attempt fits no node the program advances time to
  the next release **in-program** and retries — no host round-trip per
  blocked row.  Each node's demand timeline (sorted event instants + deltas,
  seeded from ``Timeline.events()``) also lives in the carry; placements
  splice their events in with the same ``side="right"`` tie order the host
  ``Timeline`` uses, so the carry stays bit-identical to the profiles the
  sequential oracle probes.

All programs run in int32, in ``core.timeline``'s units: time keys (the
instant ``t`` ticks is ``2t``, "just after ``t``" is ``2t + 1`` — the
right-open step's tie rank) and whole demand units.  ``NEVER`` (int32 max)
pads every time axis and stands for "never"; ``_NEG`` (int32 min) is the
max identity of masked running sums.  Integer sums are exact in any order,
so the device's parallel prefix sums equal the host's sequential ones bit
for bit.  A key that would pass the horizon can wrap inside a program, so
the host wrappers check every placement they return
(``core.timeline.check_horizon``).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.timeline import NEVER, check_horizon
from repro.kernels.ops import compact_events
from repro.sim.traces import bucket_size, fine_bucket

_NEG = -(2**31)  # int32 min: the max identity of masked demand sums


def pad_rows(a: np.ndarray, n: int, fill: float) -> np.ndarray:
    """Pad axis 0 of ``a`` to ``n`` rows with ``fill`` (returns ``a``
    unchanged when already that size)."""
    if a.shape[0] == n:
        return a
    pad = np.full((n - a.shape[0], *a.shape[1:]), fill, dtype=a.dtype)
    return np.concatenate([a, pad], axis=0)


# ---------------------------------------------------------------------------
# Shared per-(candidate, probe) demand pieces.
# ---------------------------------------------------------------------------


def candidate_probe_parts(P, starts, ends, rels, bnd, val, valext, sw, live, *, inclusive_end: bool):
    """Per-candidate demand pieces at a shared probe set.

    Args (C candidates, Pp probes, k segments; int32 keys and units):
      P: (Pp,) probe keys, ``NEVER`` padded.
      starts/ends/rels: (C,) window starts, window ends, release keys.
      bnd/val: (C, k) each candidate's boundary keys / value units.
      valext: (C, k + 1) hold-last values.
      sw/live: (C, k) switch keys (``start + b + 1``) and the
        fired-before-release mask.
      inclusive_end: True probes the closed window [start, end] (admission's
        Eq. 1 domain), False the right-open [start, end) (a cluster
        reservation's occupancy window).

    Returns (A, M, D), each (C, Pp):
      A — the candidate's own allocation value at each probe,
      M — probe-membership mask of the candidate's window,
      D — the candidate's committed-profile demand contribution (its own
          step value while live on [start, release)), i.e. what later
          candidates must see once this one is admitted/placed.
    """
    k = bnd.shape[1]
    offs = P[None, :, None] - starts[:, None, None]  # (C, Pp, 1)-broadcast offsets
    idx = jnp.minimum(jnp.sum(bnd[:, None, :] < offs, axis=-1), k - 1)
    A = jnp.take_along_axis(val, idx, axis=1)  # alloc.at(P - start)
    below = (P[None, :] <= ends[:, None]) if inclusive_end else (P[None, :] < ends[:, None])
    M = (P[None, :] >= starts[:, None]) & below & (P != NEVER)[None, :]
    # value after the switches that fired by P, live on [start, release)
    nst = jnp.sum(live[:, None, :] & (sw[:, None, :] <= P[None, :, None]), axis=-1)
    inwin = (P[None, :] >= starts[:, None]) & (P[None, :] < rels[:, None])
    D = jnp.where(inwin, jnp.take_along_axis(valext, nst, axis=1), 0)
    return A, M, D


# ---------------------------------------------------------------------------
# Sparse-table fit probes: the O(log E) formulation of the blocked-row
# re-probe shared by the scheduling-epoch and sweep programs.
# ---------------------------------------------------------------------------


def _count_sorted(tl_t, pred, q_shape):
    """Per-row counts of the prefix satisfying a monotone predicate.

    ``tl_t`` is (N, L), each row ascending (``NEVER`` padded); ``pred`` maps
    gathered keys of shape ``q_shape = (N, Q)`` to a boolean mask and
    must be True on a prefix of every sorted row (e.g. ``t <= p``,
    ``t < end``, ``(t - c) <= b`` — offset predicates bisect exactly like
    the dense compare-counts).
    Returns int32 counts in [0, L]: O(log L) gathers instead of the dense
    O(L) compare-and-sum, with identical values.
    """
    L = tl_t.shape[-1]
    lo = jnp.zeros(q_shape, jnp.int32)
    step = 1 << max(L - 1, 0).bit_length()  # smallest power of two >= L
    while step:
        cand = lo + step
        t = jnp.take_along_axis(tl_t, jnp.minimum(cand - 1, L - 1), axis=1)
        lo = jnp.where((cand <= L) & pred(t), cand, lo)
        step >>= 1
    return lo


def _floor_log2_table(L: int) -> np.ndarray:
    """Static lookup ``floor(log2(n))`` for n in [0, L] (0 at n = 0): exact
    span selection for traced window lengths without float log2 rounding."""
    n = np.maximum(np.arange(L + 1), 1)
    return np.asarray([int(v).bit_length() - 1 for v in n], dtype=np.int32)


def _range_max_query(tbl, log2_tbl, l, r):
    """Range max over [l, r) per query from the doubling table.

    ``tbl`` is (N, P, L) (``kernels.ops.range_max_table`` layout); ``l``/``r``
    are (N, Q) int32 index bounds.  Two overlapping span lookups per query —
    the classic sparse-table read; ``_NEG`` for empty windows.
    """
    N, P, L = tbl.shape
    length = jnp.maximum(r - l, 0)
    p = log2_tbl[length]  # (N, Q): floor(log2(len))
    span = jnp.left_shift(1, p)
    flat = tbl.reshape(N, P * L)
    lo = jnp.take_along_axis(flat, p * L + jnp.minimum(l, L - 1), axis=1)
    hi = jnp.take_along_axis(flat, p * L + jnp.maximum(r - span, 0), axis=1)
    return jnp.where(length > 0, jnp.maximum(lo, hi), _NEG)


def _tie_last(tl_t):
    """(N, L) mask of tie-group-final positions: the sum after event i is a
    settled profile value only when no later event shares its instant (a
    partial mid-tie sum can overshoot and fabricate an overflow)."""
    return jnp.concatenate([tl_t[:, :-1] != tl_t[:, 1:], tl_t[:, -1:] != NEVER], axis=1)


def _plan_events(t_start, b, v, release):
    """One reservation's ~k+2 timeline events on device — the jnp twin of
    ``core.timeline.plan_profile_events``: +v_0 at the start, each step delta
    at key ``start + b + 1`` for a boundary that fires before ``release``
    (Eq. 1 steps are right-open), and -v_end at the release, where v_end
    counts only the switches that actually fired.  Unfired switches park at
    ``NEVER`` with a zero delta (``b < release - start`` is the overflow-free
    form of the fired test); the stable key sort keeps the host's event
    order on ties.

    Returns ``(t_new (k+2,), d_new (k+2,), live (k,))``.  Shared by every
    program that commits a placement into a carried timeline
    (``_schedule_program``, ``_sweep_lane``, ``_admission_shard``), so the
    event construction cannot drift from the host ``Timeline``'s.
    """
    live = b < release - t_start
    steps = jnp.concatenate([jnp.diff(v), jnp.zeros((1,), v.dtype)])
    vext = jnp.concatenate([v, v[-1:]])
    v_end = vext[jnp.sum(live)]
    t_new = jnp.concatenate([t_start[None], jnp.where(live, t_start + b + 1, NEVER), release[None]])
    d_new = jnp.concatenate([v[:1], jnp.where(live, steps, 0), -v_end[None]])
    order = jnp.argsort(t_new, stable=True)
    return t_new[order], d_new[order], live


def _splice_row(tn, t_new, channels):
    """Splice time-sorted new events into one sorted (L,) timeline row,
    ``side="right"``: time-tied newcomers land after existing events, exactly
    the host ``Timeline._splice`` order.  Dead (``NEVER``) slots pushed past
    the axis are dropped (compare-counts instead of searchsorted: its scan
    lowering is a sequential loop, the counts are one vectorized op).

    ``channels`` is a list of ``(old (L,), new (n,), fill)`` payload arrays
    spliced alongside the times (demand deltas, owner codes ...).  Returns
    ``(t2, *payloads2)``.
    """
    L = tn.shape[0]
    n = t_new.shape[0]
    pos_new = jnp.sum(tn[None, :] <= t_new[:, None], axis=1) + jnp.arange(n)
    old_tgt = jnp.arange(L) + jnp.sum(t_new[None, :] < tn[:, None], axis=1)
    t2 = (
        jnp.full((L,), NEVER, tn.dtype)
        .at[old_tgt].set(tn, mode="drop")
        .at[pos_new].set(t_new, mode="drop")
    )
    out = [t2]
    for old, new, fill in channels:
        out.append(
            jnp.full((L,), fill, old.dtype)
            .at[old_tgt].set(old, mode="drop")
            .at[pos_new].set(new, mode="drop")
        )
    return tuple(out)


def _fit_tables(tl_t, tl_d, base0):
    """Per-row precompute for the sparse fit probes: running sums and the
    range-max table over the tie-group-final cumulative demand.

    Returns ``(csm, tbl)``: ``csm`` (N, L) is the demand after event i
    (``base0`` included) with non-tie-last positions masked to ``_NEG``, and
    ``tbl`` (N, P, L) its doubling range-max levels
    (``kernels.ops.range_max_table``, the Pallas kernel).
    """
    from repro.kernels.ops import range_max_table

    cs = base0[:, None] + jnp.cumsum(tl_d, axis=1)
    csm = jnp.where(_tie_last(tl_t), cs, _NEG)
    return csm, range_max_table(csm)


def _fit_probes(tl_t, csm, qmax, base0, b, v, pd, budget, cc, nmask=None):
    """(C, N) fit masks of one row at clocks ``cc`` (C,) — the range-max
    formulation of the scalar ``demand_exceeds`` pass over the full-duration
    window [c, c + pd), decision-identical to the dense per-event scan:

    * own probes (the clock + the row's switch instants): profile reads at
      ``#(t <= p)`` via binary search instead of dense compare-counts —
      identical counts, identical gathered sums.  ``csm`` is the running
      demand sum with non-tie-last positions masked to ``_NEG``; a count
      always lands after a full tie group (every event at a key <= p is <= p),
      so the gathers only ever read settled profile values.
    * profile events inside the window: for segment j the dense pass tests
      events with offset > b[j-1] (a *suffix* of the in-window events, since
      v is non-decreasing); here that suffix is an index range from two
      binary searches and its demand max ONE range-max query — ``qmax(ls,
      r)`` maps (N, C, k) suffix starts and (N, C) window ends to suffix
      maxima of ``csm``, so the backend is pluggable: the scheduling-epoch
      program answers through the doubling sparse table (O(k log L) per
      re-probe), the sweep program through a masked reverse running max of
      the carried sums (no (N, P, L) table in its scan carry).  Identical
      maxima either way, and ``max(csm) + v_j > budget`` equals
      ``any(cs + v_j > budget)`` exactly: the max element alone decides.

    Every count the probe needs — own-probe positions, window ends, window
    starts and per-segment suffix starts — runs through ONE binary-lifting
    pass with per-query (offset, threshold, strictness) parameters: the
    counts are identical to four separate ``_count_sorted`` calls (same
    bisection, same predicate values at every step), but the fused pass
    costs one O(log L) op chain instead of four.
    """
    N, L = tl_t.shape
    k = b.shape[0]
    C = cc.shape[0]
    end = cc + pd  # (C,)
    # own switch probes inside the window; an out-of-window (or NEVER-padded)
    # boundary's key may wrap, and is masked by ``own_ok``
    p_sw = cc[:, None] + b[None, :] + 1  # (C, k)
    own_p = jnp.concatenate([cc[:, None], p_sw], axis=1)  # (C, k+1)
    own_ok = jnp.concatenate(
        [jnp.ones((C, 1), bool), jnp.broadcast_to(b[None, :] < pd, (C, k))], axis=1
    )
    offs = own_p - cc[:, None]
    oidx = jnp.minimum(jnp.sum(b[None, None, :] < offs[:, :, None], axis=2), k - 1)
    cand_own = v[oidx]  # alloc.at at own probes (C, k+1)
    # one lifting pass for all counts: queries are "#(t - off <= thr)"
    # (strict ``<`` for the right-open window ends) — the offset-then-compare
    # form every original predicate already had (off = 0 where it subtracted
    # nothing)
    n_own, n_lj = C * (k + 1), C * (k - 1) if k > 1 else 0
    zero_c = jnp.zeros((C,), cc.dtype)
    thr = [own_p.reshape(-1), end, cc]
    off = [jnp.zeros((n_own,), cc.dtype), zero_c, zero_c]
    if k > 1:
        thr.append(jnp.broadcast_to(b[None, : k - 1], (C, k - 1)).reshape(-1))
        off.append(jnp.broadcast_to(cc[:, None], (C, k - 1)).reshape(-1))
    thr_q = jnp.concatenate(thr)[None, :]
    off_q = jnp.concatenate(off)[None, :]
    strict = np.zeros(n_own + 2 * C + n_lj, bool)
    strict[n_own : n_own + C] = True  # window ends: t < end
    strict_q = jnp.asarray(strict)[None, :]
    cnt_all = _count_sorted(
        tl_t,
        lambda t: jnp.where(strict_q, t - off_q < thr_q, t - off_q <= thr_q),
        (N, n_own + 2 * C + n_lj),
    )
    cnt = cnt_all[:, :n_own]
    r_win = cnt_all[:, n_own : n_own + C]
    l0 = cnt_all[:, n_own + C : n_own + 2 * C]
    cs0 = jnp.concatenate([base0[:, None], csm], axis=1)
    prof_own = jnp.take_along_axis(cs0, cnt, axis=1).reshape(N, C, k + 1)
    over = jnp.any(
        own_ok[None, :, :] & (prof_own + cand_own[None, :, :] > budget), axis=2
    )  # (N, C)
    # in-window event suffixes: [l_j, r) index ranges per (clock, segment)
    if k > 1:
        lj = cnt_all[:, n_own + 2 * C :]
        ls = jnp.concatenate([l0[:, :, None], lj.reshape(N, C, k - 1)], axis=2)
    else:
        ls = l0[:, :, None]  # (N, C, k)
    m = qmax(ls, r_win)  # (N, C, k) suffix maxima over [l_j, r)
    over_ev = jnp.any(m + v[None, None, :] > budget, axis=2)
    fit = ~(over | over_ev)
    if nmask is not None:
        fit &= nmask[:, None]
    return fit.T  # (C, N)


def _suffix_max_query(csm, ls, r):
    """The table-free ``qmax`` backend: suffix maxima of ``csm`` over the
    windows [l_j, r) from one masked reverse running max per clock.

    ``rm[i] = max(csm[i:r])`` (elements at or past ``r`` masked to
    ``_NEG``), so the window max is a single gather at ``l_j`` — identical
    maxima to the sparse-table read over the same index range (max is
    associative with a ``_NEG`` identity), with O(N C L) streamed data and
    no carried table.
    """
    N, L = csm.shape
    C = r.shape[1]
    inwin = jnp.arange(L)[None, None, :] < r[:, :, None]  # (N, C, L)
    rm = jax.lax.cummax(jnp.where(inwin, csm[:, None, :], _NEG), axis=2, reverse=True)
    g = jnp.take_along_axis(rm, jnp.minimum(ls, L - 1), axis=2)
    return jnp.where(ls < r[:, :, None], g, _NEG)


@functools.lru_cache(maxsize=None)
def admission_program():
    """The jitted batch-admission program (compiled per padded shape bucket).

    Shapes: P/prof (Pp,) shared probe keys and profile reads; per-candidate
    starts/ends/rels/valid (Cp,); bnd/val/sw/live (Cp, k); valext (Cp, k+1).
    Padding: P with ``NEVER`` (masked), candidates with valid=False /
    start=``NEVER`` (their window and member masks are empty).

    Per candidate the fit check is the scalar ``demand_exceeds`` with
    ``inclusive_end=True``: max over every probe point in [start, end] of
    profile + earlier-admitted-batch demand + own allocation, compared
    strictly against the budget.  The probe set P is the deduped union
    (``core.timeline.shared_probe_set``) of all profile events and every
    candidate's start/switch instants, so it contains every point where
    combined demand can rise inside any candidate's window — dropped
    duplicates and extra in-window points only re-sample the step function
    and cannot change the max.  A ``lax.scan`` threads the within-batch
    dependency: an admitted candidate's demand (table-lookup of its own step
    function, live on [start, release)) is added to the carry that later
    candidates probe.
    """

    def admission_batch(P, prof, starts, ends, rels, bnd, val, valext, sw, live, valid, budget):
        A, M, D = candidate_probe_parts(
            P, starts, ends, rels, bnd, val, valext, sw, live, inclusive_end=True
        )

        def step(extra, row):
            a, d, m, ok = row
            admit = ok & ~jnp.any(m & (prof + extra + a > budget))
            return extra + jnp.where(admit, d, 0), admit

        _, admits = jax.lax.scan(step, jnp.zeros_like(P), (A, D, M, valid))
        return admits

    return jax.jit(admission_batch)


# ---------------------------------------------------------------------------
# The streaming window program: first-fit for a window of rows that all
# share the epoch clock (nobody waits).  The cheap common case — the probe
# set and profile reads are precomputed host-side, so the program is a few
# tiny (N, Pp) masked ops per row.
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _window_program_shared(n_nodes: int):
    """The jitted streaming-window program over ONE shared probe set.

    The cheap variant when the union of probe instants across nodes is
    small: per-candidate pieces (A/M/D) are precomputed once per call over
    the shared (Pp,) axis by ``candidate_probe_parts``, so each scan step is
    three fused (N, Pp) passes.  Decisions are identical to
    ``_window_program_pernode`` — extra probes only re-sample step
    functions — the host picks whichever costs less for the call's shapes.
    """

    def window_shared(P, prof, now, ends, rels, bnd, val, valid, cap):
        # Derive the per-row pieces on device (fewer host arrays per call):
        # all candidates share the epoch clock, switch keys are the same
        # ``now + b + 1`` the host used building P, and a cluster reservation
        # releases at its occupancy end (``rels``) while the fit window runs
        # to the full predicted duration (``ends``).
        starts = jnp.where(valid, now, NEVER)
        live = bnd < rels[:, None] - now
        sw = jnp.where(live, now + bnd + 1, NEVER)
        valext = jnp.concatenate([val, val[:, -1:]], axis=1)
        A, M, D = candidate_probe_parts(
            P, starts, ends, rels, bnd, val, valext, sw, live, inclusive_end=False
        )
        node_ids = jnp.arange(n_nodes)

        def step(carry, row):
            extra, blocked = carry  # extra: (N, Pp) this epoch's placed demand
            a, d, m, ok = row
            over = jnp.any(m[None, :] & (prof + extra + a[None, :] > cap), axis=-1)  # (N,)
            fit = ~over
            can = ok & ~blocked & jnp.any(fit)
            node = jnp.argmax(fit)  # first-fit: lowest fitting node index
            extra = extra + jnp.where((can & (node_ids == node))[:, None], d[None, :], 0)
            return (extra, blocked | (ok & ~can)), (can, node)

        init = (jnp.zeros_like(prof), jnp.asarray(False))
        # unroll: the step body is a handful of small (N, Pp) vector ops, so
        # the while-loop bookkeeping dominates on CPU without it
        _, (placed, node) = jax.lax.scan(step, init, (A, D, M, valid), unroll=8)
        return placed, node

    return jax.jit(window_shared)


@functools.lru_cache(maxsize=None)
def _window_program_pernode(n_nodes: int):
    """The jitted streaming-window program (per padded shape bucket).

    One call decides the whole (candidate x node) first-fit matrix for a
    window of queued attempt rows sharing the epoch clock: per candidate the
    fit check is the scalar ``NodeState.fits`` — any probe in the right-open
    fit window where node profile + earlier in-window placements + own
    allocation exceeds capacity(+eps) — evaluated against every node at
    once, with first-fit the lowest fitting node index.  A ``lax.scan``
    threads within-epoch sequencing: a placed candidate's demand is added to
    its node's carry, exactly as if the host had committed it before probing
    the next candidate (the ``BatchedAdmissionController`` pattern).  The
    first candidate that fits nowhere blocks every later one (it must wait —
    ``schedule_epoch`` takes over), so ``placed`` is always a prefix.

    Probes are **per node** — each node's own profile events plus the probe
    instants every candidate shares (the clock and all switch instants), so
    the padded probe axis is sized by one node's events, not the union
    across the cluster.  Candidate values and committed demand at the probes
    unroll into k fused passes over (N, Pp): for values via the monotone
    comparison trick (exists j <= #(b < off) with demand + v_j > cap — values
    are non-decreasing, so the decision equals reading v[#(b < off)]); for
    committed demand via the step-delta sum (v_0 + fired step deltas — the
    same deltas the host ``Timeline`` accumulates).
    """

    def window_pernode(P, prof, now, ends, rels, bnd, val, valid, cap):
        # all candidates share the epoch clock; every probe is at or after
        # it (the host builds P from the clock, switch instants past it and
        # strictly-future node events), so window membership per row is just
        # "before this row's end"
        off = P - now  # (N, Pp) candidate-relative offsets
        fin = P != NEVER
        live = bnd < rels[:, None] - now
        sw = jnp.where(live, now + bnd + 1, NEVER)  # (W, k)
        steps = jnp.concatenate([jnp.diff(val, axis=1), jnp.zeros_like(val[:, :1])], axis=1)
        k = bnd.shape[1]

        def step(carry, row):
            S, blocked = carry  # S: (N, Pp) profile + this epoch's placed demand
            b, v, sw_r, live_r, st_r, end, rel, ok = row
            m = fin & (P < end)  # right-open fit window
            over = jnp.any(m & (S + v[0] > cap), axis=-1)  # (N,)
            for j in range(1, k):
                over |= jnp.any(m & (off > b[j - 1]) & (S + v[j] > cap), axis=-1)
            fit = ~over
            can = ok & ~blocked & jnp.any(fit)
            node = jnp.argmax(fit)  # first-fit: lowest fitting node index
            # committed demand at the placed node's probes only (1, Pp): the
            # value after the fired switches, live on [now, release)
            Pn = P[node]
            inwin = (Pn != NEVER) & (Pn < rel)
            d = jnp.where(inwin, v[0], 0)
            for j in range(k):
                d = d + jnp.where(inwin & live_r[j] & (sw_r[j] <= Pn), st_r[j], 0)
            S = S.at[node].add(jnp.where(can, d, 0))
            return (S, blocked | (ok & ~can)), (can, node)

        init = (prof, jnp.asarray(False))
        # unroll: the step body is a handful of small (N, Pp) vector ops, so
        # the while-loop bookkeeping dominates on CPU without it
        _, (placed, node) = jax.lax.scan(
            step, init, (bnd, val, sw, live, steps, ends, rels, valid), unroll=8
        )
        return placed, node

    return jax.jit(window_pernode)


def first_fit_window(
    now: int,
    bnd: np.ndarray,
    val: np.ndarray,
    run_times: np.ndarray,
    probe_times: np.ndarray,
    profiles: list[tuple[np.ndarray, np.ndarray]],
    capacity_budget: int,
    window_bucket: int = 32,
) -> tuple[np.ndarray, np.ndarray]:
    """Decide first-fit placements for one window of rows at a fixed clock.

    Args (``core.timeline`` units: keys and demand units):
      now: the epoch clock — every candidate's start.
      bnd/val: (w, k) the rows' allocation steps (already node-capped).
      run_times: (w,) occupancy durations (release keys); probe_times:
        (w,) fit-window durations (the full predicted duration).
      profiles: per node, the cached ``(event keys, cumulative demand)``
        arrays of its ``Timeline`` (``NodeState.profile_arrays``).
      capacity_budget: the fits budget (``NodeState.budget``).
      window_bucket: rows are padded to this static size.

    Probes are the keys where combined step demand can rise: the clock,
    every candidate's switch keys inside its window, and profile events
    inside the widest fit window, always deduped
    (``core.timeline.shared_probe_set``).  Two exact, decision-identical
    program variants share the work differently:

    * **shared** — one probe union across nodes; per-candidate pieces
      precomputed once per call (cheap when the union stays small).
    * **per-node** — each node probes only its OWN events (+ the shared
      candidate switches), with the candidate pieces unrolled into k fused
      passes; cheap when cluster-wide events would blow the shared union up.

    The host estimates both costs from the probe counts and dispatches the
    cheaper one.  Profile reads happen host-side (numpy ``searchsorted``
    against each node's cached cumulative profile, the same expression the
    scalar path uses); the programs only probe, sequence and pick nodes.
    Returns ``(placed, node)``; ``placed`` is a prefix.
    """
    with obs.span("sched.window.prepare"):
        variant, program, args = _window_inputs(
            now, bnd, val, run_times, probe_times, profiles, capacity_budget, window_bucket
        )
    with obs.span("sched.window.launch"):
        out = program(*args)
    w = len(bnd)
    with obs.span("sched.window.wait"):
        placed = np.asarray(out[0])[:w]
    with obs.span("sched.window.readback"):
        node = np.asarray(out[1])[:w]
    # the compiled shape: variant, nodes, probe axis, row bucket, segments
    obs.distinct("sched.window.shapes", (variant, *args[1].shape, *args[5].shape))
    return placed, node


def _window_inputs(now, bnd, val, run_times, probe_times, profiles, capacity_budget, window_bucket):
    """``first_fit_window``'s host preparation: the probe set, the nodes'
    profile reads at it and the padded rows.  Returns ``(variant, program,
    args)``, the variant ``"shared"`` or ``"pernode"``."""
    from repro.core.timeline import shared_probe_set

    w, k = bnd.shape
    N = len(profiles)
    ends = now + probe_times
    rels = now + run_times
    check_horizon(ends)
    inwin = bnd < probe_times[:, None]  # switch keys below the window end
    csw = shared_probe_set(np.asarray([now]), now + bnd[inwin] + 1)
    tmax = int(ends.max())
    evs = [t[(t > now) & (t < tmax)] for t, _ in profiles]
    Wb = int(window_bucket)
    n_shared = len(csw) + sum(len(e) for e in evs)  # upper bound pre-dedup
    n_pernode = len(csw) + max((len(e) for e in evs), default=0)
    # per-step cost ~ Pp*(k + 3N) shared vs Pp'*(2k+2)*N per-node
    use_shared = n_shared * (k + 3 * N) <= n_pernode * (2 * k + 2) * N
    if use_shared:
        P = shared_probe_set(csw, *evs)
        Pp = fine_bucket(len(P), floor=128)
        prof = np.zeros((N, Pp), dtype=np.int32)
        for n, (t, c) in enumerate(profiles):
            prof[n, : len(P)] = c[np.searchsorted(t, P, side="right")]
        P = np.concatenate([P, np.full(Pp - len(P), NEVER)]).astype(np.int32)
        program = _window_program_shared(N)
    else:
        pns = [shared_probe_set(csw, e) for e in evs]
        Pp = fine_bucket(max(len(p) for p in pns), floor=128)
        P = np.full((N, Pp), NEVER, dtype=np.int32)
        prof = np.zeros((N, Pp), dtype=np.int32)
        for n, ((t, c), pn) in enumerate(zip(profiles, pns)):
            P[n, : len(pn)] = pn
            prof[n, : len(pn)] = c[np.searchsorted(t, pn, side="right")]
        program = _window_program_pernode(N)
    i32 = np.int32
    args = (
        P,
        prof,
        i32(now),
        pad_rows(ends.astype(i32), Wb, -1),
        pad_rows(rels.astype(i32), Wb, -1),
        pad_rows(bnd.astype(i32), Wb, NEVER),
        pad_rows(val.astype(i32), Wb, 0),
        pad_rows(np.ones(w, dtype=bool), Wb, False),
        i32(capacity_budget),
    )
    return ("shared" if use_shared else "pernode"), program, args


# ---------------------------------------------------------------------------
# The scheduling-epoch program: first-fit placement with the event clock and
# release heap in the carry.
# ---------------------------------------------------------------------------


@jax.jit
def _schedule_program(tl_t, tl_d, base0, ev, h0, now0, bnd, val, run, pdur, valid, budget):
    """One scheduling epoch on device (shapes fix the compiled variant).

    Args (int32 keys and demand units):
      tl_t/tl_d: (N, L) per-node event keys (sorted, ``NEVER`` padded) and
        demand deltas (0 padded) — ``Timeline.events()`` seeded.  Only
        events after the epoch clock are carried; ``base0`` (N,) is each
        node's cumulative demand at the clock (the folded prefix — every
        probe is at or after the clock, so earlier events only ever enter
        through this sum).
      ev: (H,) pending completion keys (``NEVER`` = free slot).
      h0: number of real entries in ``ev`` (placements push at ``h0 + row``).
      now0: the epoch's starting clock.
      bnd/val: (W, k) candidate allocation steps (``NEVER``-padded rows are
        the k = 1 baselines, which hold their value anyway).
      run: (W,) occupancy durations (a failed attempt holds its node only
        up to the kill); pdur: (W,) fit-check window durations (the
        scheduler probes the full predicted duration — it cannot know an
        attempt will die early); valid: (W,) real-row mask.
      budget: the fits budget (``NodeState.budget``).

    A ``lax.scan`` walks the rows in queue order.  Per row, a bounded
    ``while_loop`` mirrors the sequential oracle's ``_find_slot``: probe
    every node at the current clock (the scalar ``demand_exceeds``
    expressions, evaluated against the carried timelines); when no node
    fits, pop the earliest pending completion, advance the clock to it and
    re-probe.  A placed row's events are spliced into its node's carried
    timeline (``side="right"`` tie order, identical to the host
    ``Timeline``) and its completion pushed onto the heap, so later rows
    see it both as demand and as a wait target.  If the heap drains with no
    fit (unreachable for node-capped allocations), the row and everything
    after it return unplaced and the host takes over.

    Returns (placed, node, start) per row plus (final clock, events popped,
    rows that waited).  ``placed`` is always a prefix of the valid rows.
    """
    N, L = tl_t.shape
    W, k = bnd.shape
    CH = 8  # pending completions probed per wait iteration
    # Per-node in-epoch commit cap: bounds the timeline axis the host must
    # pad for (L = future events + CAP * (k + 2)).  A row whose first-fit
    # node has a full commit buffer aborts the epoch — its pops and clock
    # advance are DISCARDED so the host re-dispatch replays the row
    # identically against freshly folded timelines.  At the driver's wait
    # window (8 rows) the cap equals the window, so an abort is impossible;
    # it only guards larger callers.
    CAP = max(2, min(W, 8))

    log2_tbl = jnp.asarray(_floor_log2_table(L))

    def row_step(carry, x):
        now, tl_t, tl_d, ev, pops, waited, blocked, cnts, dead_any = carry
        b, v, dur, pd, ok, ridx = x
        # The profile is frozen while a row waits (nothing commits until it
        # places), so the running sums and the range-max table are built once
        # per row; every fit probe — the first try and each in-program wait
        # re-probe — is then O(k log L) sparse-table lookups.
        csm, tbl = _fit_tables(tl_t, tl_d, base0)

        def qmax(ls, r):
            N = tl_t.shape[0]
            r_q = jnp.broadcast_to(r[:, :, None], ls.shape)
            return _range_max_query(
                tbl, log2_tbl, ls.reshape(N, -1), r_q.reshape(N, -1)
            ).reshape(ls.shape)

        def fit_many(cc):
            return _fit_probes(tl_t, csm, qmax, base0, b, v, pd, budget, cc)

        fit0 = fit_many(now[None])[0]  # (N,)
        found0 = jnp.any(fit0)
        node0 = jnp.argmax(fit0).astype(jnp.int32)  # first-fit: lowest index

        def wcond(s):
            _, _, _, found, _, dead = s
            return ok & ~blocked & ~found & ~dead

        def wbody(s):
            t, ev_, p_, _, _, _ = s
            # pop up to CH earliest pending completions in one probe: the
            # oracle pops one event, re-probes, pops the next ... — the
            # chunk evaluates those same probes (each at max(now, t_i))
            # together and consumes exactly the events the oracle would
            neg, idx = jax.lax.top_k(-ev_, CH)  # CH smallest keys, ascending
            tt = -neg
            fin = tt != NEVER
            cc = jnp.maximum(t, tt)
            F = fit_many(jnp.where(fin, cc, t)) & fin[:, None]  # (CH, N)
            anyfit = jnp.any(F, axis=1)
            hit = jnp.any(anyfit)
            i = jnp.argmax(anyfit)
            npop = jnp.where(hit, i + 1, jnp.sum(fin)).astype(jnp.int32)
            ev2 = ev_.at[idx].set(jnp.where(jnp.arange(CH) < npop, NEVER, tt))
            last = jnp.maximum(npop - 1, 0)
            t2 = jnp.where(hit, cc[i], jnp.where(npop > 0, cc[last], t))
            node2 = jnp.argmax(F[i]).astype(jnp.int32)
            return (t2, ev2, p_ + npop, hit, node2, ~hit & (npop == 0))

        init = (now, ev, jnp.zeros((), jnp.int32), found0, node0, jnp.asarray(False))
        t_f, ev_f, row_pops, found, node, dead = jax.lax.while_loop(wcond, wbody, init)
        ran = ok & ~blocked
        full = cnts[node] >= CAP
        placed = found & ran & ~full
        aborted = found & ran & full

        def commit(args):
            tl_t, tl_d, ev_ = args
            end = t_f + dur
            # the row's ~k+2 timeline events (exactly plan_profile_events'),
            # spliced into the node's sorted timeline side="right"
            t_new, d_new, _ = _plan_events(t_f, b, v, end)
            t2, d2 = _splice_row(tl_t[node], t_new, [(tl_d[node], d_new, 0)])
            return tl_t.at[node].set(t2), tl_d.at[node].set(d2), ev_.at[h0 + ridx].set(end)

        tl_t2, tl_d2, ev2 = jax.lax.cond(placed, commit, lambda a: a, (tl_t, tl_d, ev_f))
        # an aborted row's pops, clock advance and heap state are discarded
        # (the re-dispatch replays it); a dead row keeps them — the oracle
        # consumed those events before discovering the heap was dry
        keep = placed | (ran & ~found)
        carry = (
            jnp.where(keep, t_f, now),
            tl_t2,
            tl_d2,
            jnp.where(keep, ev2, ev),
            pops + jnp.where(aborted, 0, row_pops),
            waited + (placed & (row_pops > 0)).astype(jnp.int32),
            blocked | (ok & ~placed),
            cnts.at[node].add(placed.astype(jnp.int32)),
            dead_any | (ran & dead),
        )
        return carry, (placed, node, t_f)

    xs = (bnd, val, run, pdur, valid, jnp.arange(W, dtype=jnp.int32))
    init = (
        now0,
        tl_t,
        tl_d,
        ev,
        jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32),
        jnp.asarray(False),
        jnp.zeros((N,), jnp.int32),
        jnp.asarray(False),
    )
    (now_f, _, _, _, pops, waited, _, _, dead_any), (placed, node, start) = jax.lax.scan(
        row_step, init, xs
    )
    return placed, node, start, now_f, pops, waited, dead_any


def schedule_epoch(
    now: int,
    bnd: np.ndarray,
    val: np.ndarray,
    run_times: np.ndarray,
    node_events: list[tuple[np.ndarray, np.ndarray]],
    pending: np.ndarray,
    capacity_budget: int,
    window_bucket: int = 32,
    probe_times: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int, int, int, bool]:
    """Place up to one window of attempt rows, resolving waits in-program.

    Args (``core.timeline`` units: keys and demand units):
      now: the scheduling clock at epoch start.
      bnd/val: (w, k) the rows' allocation steps (already node-capped).
      run_times: (w,) each row's occupancy duration.
      node_events: per node, ``Timeline.events()`` — the sorted event keys
        and demand deltas of its reservation profile.
      pending: (E,) completion keys still in the scheduler's wait heap.
      capacity_budget: the fits budget (``NodeState.budget``).
      window_bucket: rows are padded to this static size; timeline/heap axes
        are bucketed so compiled shapes stay bounded.
      probe_times: (w,) fit-check window durations — the full predicted
        duration when occupancy is kill-truncated (defaults to
        ``run_times``: probe what you occupy).

    Returns ``(placed, node, start, now_final, n_pops, n_waited, dead)``
    for the w real rows: ``placed`` is a prefix — False past the first row
    that aborted on a full per-node commit buffer (the caller re-dispatches;
    nothing about the row was consumed) or, with ``dead`` True, past a row
    that drained the heap with no fit (unreachable for capped allocations;
    the caller falls back to the oracle's one-second clock walk).  ``start``
    is each placed row's clock; ``n_pops`` pending events were consumed (the
    n_pops smallest of ``pending`` + this epoch's own completions — pop
    order among key-ties is unobservable); ``n_waited`` rows waited
    in-program.
    """
    with obs.span("sched.epoch.prepare"):
        args = _epoch_inputs(
            now, bnd, val, run_times, node_events, pending, capacity_budget, window_bucket, probe_times
        )
    with obs.span("sched.epoch.launch"):
        out = _schedule_program(*args)
    w = len(bnd)
    # the first read waits for the device, the rest only copy: the spans
    # time the reads a dispatch always made and add no call of their own
    with obs.span("sched.epoch.wait"):
        placed = np.asarray(out[0])[:w]
    with obs.span("sched.epoch.readback"):
        _, node, start, now_f, pops, waited, dead = out
        node = np.asarray(node, dtype=np.int64)[:w]
        start = np.asarray(start, dtype=np.int64)[:w]
        now_f, pops, waited, dead = int(now_f), int(pops), int(waited), bool(dead)
    # the compiled shape: timelines (N, L), heap H, rows (Wb, k)
    obs.distinct("sched.epoch.shapes", (*args[0].shape, *args[3].shape, *args[6].shape))
    return placed, node, start, now_f, pops, waited, dead


def _epoch_inputs(
    now, bnd, val, run_times, node_events, pending, capacity_budget, window_bucket, probe_times
):
    """``schedule_epoch``'s host preparation: the nodes' timelines folded at
    the clock and padded, the sorted pending heap and the padded rows, as
    ``_schedule_program``'s arguments."""
    w, k = bnd.shape
    Wb = int(window_bucket)
    N = len(node_events)
    # Fold each node's events at or before the clock into a scalar base
    # demand: every probe the program evaluates is at or after ``now``, so
    # the prefix only ever enters as its sum — carrying it as a scalar keeps
    # the padded timeline axis sized by *future* events.
    cuts = [np.searchsorted(t, now, side="right") for t, _ in node_events]
    base0 = np.asarray([d[:c].sum() for (_, d), c in zip(node_events, cuts)], dtype=np.int32)
    e0 = max((len(t) - c for (t, _), c in zip(node_events, cuts)), default=0)
    # capacity for one node's in-epoch commits (the program's CAP; beyond it
    # the epoch aborts and the host re-dispatches with fresh timelines)
    L = fine_bucket(e0 + max(2, min(Wb, 8)) * (k + 2), floor=64)
    tl_t = np.full((N, L), NEVER, dtype=np.int32)
    tl_d = np.zeros((N, L), dtype=np.int32)
    for n, ((t, d), c) in enumerate(zip(node_events, cuts)):
        tl_t[n, : len(t) - c] = t[c:]
        tl_d[n, : len(d) - c] = d[c:]
    h0 = len(pending)
    H = bucket_size(h0 + Wb, floor=32)
    ev = np.full(H, NEVER, dtype=np.int32)
    ev[:h0] = np.sort(np.asarray(pending, dtype=np.int64))
    if probe_times is None:
        probe_times = run_times
    i32 = np.int32
    return (
        tl_t,
        tl_d,
        base0,
        ev,
        i32(h0),
        i32(now),
        pad_rows(np.asarray(bnd, dtype=i32), Wb, NEVER),
        pad_rows(np.asarray(val, dtype=i32), Wb, 0),
        pad_rows(np.asarray(run_times, dtype=i32), Wb, 0),
        pad_rows(np.asarray(probe_times, dtype=i32), Wb, 0),
        pad_rows(np.ones(w, dtype=bool), Wb, False),
        i32(capacity_budget),
    )


# ---------------------------------------------------------------------------
# The sweep program: every simulation lane of a policy x capacity design
# space scheduled end to end in ONE vmapped dispatch.
# ---------------------------------------------------------------------------

_SWEEP_W = 8  # rows per fold chunk (the wait-window cadence of the driver)
_SWEEP_CH = 8  # pending completions probed per wait iteration


def _sweep_lane(bnd, val, run, pdur, valid, nmask, budget, *, L):
    """One simulation lane scheduled end to end (vmapped over lanes).

    The whole-lane generalization of ``_schedule_program``: a nested scan
    walks ALL attempt rows with the event clock, the per-node timelines, the
    release heap and the tie-masked running demand sums in the carry, so the
    host never re-dispatches between windows.  Structure:

    * outer scan (chunks of ``_SWEEP_W`` rows) — folds events at or before
      the clock into each node's base demand (the in-program twin of the
      host fold ``schedule_epoch`` does between epochs), then compacts the
      survivors: every zero-delta event is scatter-compacted away
      (``kernels.ops.compact_events``), so the carried axis stays sized by
      demand-shape-changing breakpoints — O(live breakpoints), not O(all
      events ever) — and the running demand sums are rebuilt over the
      compacted rows.  The staged head-sort splice ``_admission_shard`` uses
      per decision batch does not transplant here: the lane's probes are
      row-serial (each row must see the previous row's commit) and the
      streamed ``_suffix_max_query`` backend reads the whole axis anyway, so
      keeping that axis small IS the win a deferred splice would chase.
    * inner scan (rows, unrolled) — the ``_find_slot`` semantics of
      ``_schedule_program``: every probe (the unblocked clock probe and the
      CH x k suffix windows of each wait re-probe) runs ``_fit_probes`` with
      the table-free ``_suffix_max_query`` backend over the carried sums.
      The scheduling-epoch program carries the doubling sparse table instead
      (O(k log E) lookups amortized over many windows per host dispatch);
      here the whole (N, P, L) table would live in the row-scan carry, and
      on a bandwidth-bound host the per-row table rewrites plus the
      while-loop captures of it cost several times the streamed running max
      it replaces.  Commits refresh the sums for the placed node only, as
      masked single-node writes (a lax.cond would batch into whole-carry
      selects under the lane vmap, copying the carry twice per row).  The
      row scan is unrolled: each step is many small (N, ...) vector ops, so
      on CPU the scan bookkeeping dominates an un-unrolled body.

    Per-lane node counts are handled by ``nmask`` (invalid nodes never fit);
    rows are ``NEVER``/False padded to the lane grid's shared shape.  ``overflow``
    reports a node timeline outgrowing L — the commits' ``mode="drop"``
    splices silently lose events past it, so the host re-dispatches with a
    doubled axis.  ``dead`` is a drained heap with no fit (unreachable for
    node-capped allocations; the host falls back to the per-policy engine
    for that lane); once dead every later row returns unplaced.  Returns
    per-row (placed, node, start) plus the final (clock, pops, waited,
    dead, overflow, breakpoint high-water mark) — the high-water mark is the
    busiest node's carried breakpoint count sampled at the chunk boundaries,
    the bench's measure of how hard the compaction works.
    """
    R, k = bnd.shape
    N = nmask.shape[0]
    W, CH = _SWEEP_W, _SWEEP_CH
    dt = bnd.dtype

    def chunk_step(carry, xs):
        now, base, tl_t, tl_d, ev, pops, waited, dead_any, over_any, hw = carry
        # Fold events at or before the clock into each node's base demand
        # (the in-program twin of ``schedule_epoch``'s host-side cut): every
        # later probe is at or after ``now``, so the folded prefix only ever
        # enters as its cumulative sum, and compacting keeps the timeline
        # axis sized by *future* events.
        nowq = jnp.broadcast_to(now, (N, 1))
        cnt = _count_sorted(tl_t, lambda t: t <= nowq, (N, 1))
        gain = jnp.sum(jnp.where(jnp.arange(L)[None, :] < cnt, tl_d, 0), axis=1)
        base = base + gain
        idx = jnp.arange(L)[None, :] + cnt
        ahead = idx < L
        idxc = jnp.minimum(idx, L - 1)
        tl_t = jnp.where(ahead, jnp.take_along_axis(tl_t, idxc, axis=1), NEVER)
        tl_d = jnp.where(ahead, jnp.take_along_axis(tl_d, idxc, axis=1), 0)
        # Dominance compaction (the epoch re-fold of this lane's carry): the
        # clock fold above removes almost nothing under generous node memory
        # because reservations release late, but most surviving events do not
        # change the shape of future demand — zero steps from capped flat
        # profiles, coincident +/- cancellations, telescoped release groups,
        # equal-value runs.  Drop every zero-delta event: the recomputed
        # prefix sum then passes through exactly the same values at every
        # kept position, every probe count still lands at a tie-group
        # boundary, and a dropped breakpoint's settled value is always
        # re-read at its surviving predecessor (or the own probe at the
        # window start) under the same segment demand — so placements stay
        # exact against the windows engine while the carried axis stays
        # sized by live breakpoints instead of every event the run ever
        # placed.
        keep = (tl_t != NEVER) & (tl_d != 0)
        tl_t, tl_d = compact_events(tl_t, tl_d, keep)
        hw = jnp.maximum(hw, jnp.max(jnp.sum(keep, axis=1)).astype(jnp.int32))
        csm0 = jnp.where(_tie_last(tl_t), base[:, None] + jnp.cumsum(tl_d, axis=1), _NEG)

        def row_step(icarry, x):
            now, tl_t, tl_d, csm, ev, pops, waited, dead_any, over_any = icarry
            b, v, dur, pd, ok, ridx = x

            def fit_many(cc):
                return _fit_probes(
                    tl_t, csm, functools.partial(_suffix_max_query, csm),
                    base, b, v, pd, budget, cc, nmask,
                )

            # unblocked fast path: one clock probed against the carried sums
            fit0 = fit_many(now[None])[0]
            found0 = jnp.any(fit0)
            node0 = jnp.argmax(fit0).astype(jnp.int32)

            def wcond(s):
                _, _, _, found, _, dead = s
                return ok & ~dead_any & ~found & ~dead

            def wbody(s):
                t, ev_, p_, _, _, _ = s
                # pop up to CH earliest pending completions in one probe —
                # identical chunked-pop semantics to ``_schedule_program``
                neg, hidx = jax.lax.top_k(-ev_, CH)
                tt = -neg
                fin = tt != NEVER
                cc = jnp.maximum(t, tt)
                F = fit_many(jnp.where(fin, cc, t)) & fin[:, None]  # (CH, N)
                anyfit = jnp.any(F, axis=1)
                hit = jnp.any(anyfit)
                i = jnp.argmax(anyfit)
                npop = jnp.where(hit, i + 1, jnp.sum(fin)).astype(jnp.int32)
                ev2 = ev_.at[hidx].set(jnp.where(jnp.arange(CH) < npop, NEVER, tt))
                last = jnp.maximum(npop - 1, 0)
                t2 = jnp.where(hit, cc[i], jnp.where(npop > 0, cc[last], t))
                node2 = jnp.argmax(F[i]).astype(jnp.int32)
                return (t2, ev2, p_ + npop, hit, node2, ~hit & (npop == 0))

            init = (now, ev, jnp.zeros((), jnp.int32), found0, node0, jnp.asarray(False))
            t_f, ev_f, row_pops, found, node, dead = jax.lax.while_loop(wcond, wbody, init)
            ran = ok & ~dead_any
            placed = found & ran
            end = t_f + dur
            # the row's ~k+2 events spliced side="right" — byte-for-byte the
            # commit of ``_schedule_program`` (the shared ``_plan_events`` /
            # ``_splice_row`` pair).  Computed unconditionally on the placed
            # node's (L,) slices and written back under a ``placed`` mask: a
            # lax.cond here would batch (under the lane vmap) into a select
            # over the whole (N, L) carry, copying it twice per row — masked
            # single-node writes keep the per-row carry traffic at O(k L)
            # and let XLA update the scan carry in place.
            t_new, d_new, live = _plan_events(t_f, b, v, end)
            n_fin = jnp.sum(tl_t[node] != NEVER)
            over_loc = placed & (n_fin + 2 + jnp.sum(live) > L)
            tn, dn = tl_t[node], tl_d[node]
            t2, d2 = _splice_row(tn, t_new, [(dn, d_new, 0)])
            # probe state refresh for the placed node only: one O(L) running
            # sum (tie-masked in place) instead of an all-nodes rebuild
            tie_n = jnp.concatenate([t2[:-1] != t2[1:], t2[-1:] != NEVER])
            csm_n = jnp.where(tie_n, base[node] + jnp.cumsum(d2), _NEG)
            tl_t2 = tl_t.at[node].set(jnp.where(placed, t2, tn))
            tl_d2 = tl_d.at[node].set(jnp.where(placed, d2, dn))
            csm2 = csm.at[node].set(jnp.where(placed, csm_n, csm[node]))
            ev2 = ev_f.at[ridx].set(jnp.where(placed, end, ev_f[ridx]))
            # a dead row keeps its pops and clock — the oracle consumed those
            # events before discovering the heap was dry (the lane is handed
            # to the fallback engine anyway)
            keep_s = placed | (ran & dead)
            icarry = (
                jnp.where(keep_s, t_f, now),
                tl_t2,
                tl_d2,
                csm2,
                jnp.where(keep_s, ev2, ev),
                pops + row_pops,
                waited + (placed & (row_pops > 0)).astype(jnp.int32),
                dead_any | (ran & dead),
                over_any | over_loc,
            )
            return icarry, (placed, node, t_f)

        inner = (now, tl_t, tl_d, csm0, ev, pops, waited, dead_any, over_any)
        (now, tl_t, tl_d, _, ev, pops, waited, dead_any, over_any), outs = jax.lax.scan(
            row_step, inner, xs, unroll=W
        )
        return (now, base, tl_t, tl_d, ev, pops, waited, dead_any, over_any, hw), outs

    xs = (
        bnd.reshape(R // W, W, k),
        val.reshape(R // W, W, k),
        run.reshape(R // W, W),
        pdur.reshape(R // W, W),
        valid.reshape(R // W, W),
        jnp.arange(R, dtype=jnp.int32).reshape(R // W, W),
    )
    init = (
        jnp.zeros((), dt),  # the lane's cluster starts empty at clock 0
        jnp.zeros((N,), dt),
        jnp.full((N, L), NEVER, dt),
        jnp.zeros((N, L), dt),
        jnp.full((R,), NEVER, dt),  # release heap: one slot per row
        jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32),
        jnp.asarray(False),
        jnp.asarray(False),
        jnp.zeros((), jnp.int32),  # carried-breakpoint high-water mark
    )
    (now_f, _, _, _, _, pops, waited, dead, over, hw), (placed, node, start) = jax.lax.scan(
        chunk_step, init, xs
    )
    return (
        placed.reshape(R),
        node.reshape(R),
        start.reshape(R),
        now_f,
        pops,
        waited,
        dead,
        over,
        hw,
    )


# ---------------------------------------------------------------------------
# The carried-admission program: the serving controller's active set as a
# persistent device-resident control plane.  Where ``admission_program``
# rebuilds its shared probe set from host state on every decision batch,
# this program keeps each shard's demand timeline IN the program state
# across thousands of batches — releases, clock folds and commits are all
# incremental splices against the carried arrays.
# ---------------------------------------------------------------------------


def _admission_shard(
    base0, tl_t, tl_d, tl_c, slot_fold, rel_codes,
    starts, ends, rels, bnd, val, codes, valid, t0, budget,
    Lp=None,
):
    """One shard's decision batch against its carried timeline.

    Carried state (returned updated — the host keeps the returned arrays as
    the next call's inputs, so the active set never leaves the device):
      base0: () folded demand — the cumulative sum of every event at or
        before the shard's clock (the in-carry twin of ``schedule_epoch``'s
        host-side fold).
      tl_t/tl_d: (L,) sorted future event keys (``NEVER`` padded) and deltas.
      tl_c: (L,) int32 owner codes per event (-1 = empty slot).
      slot_fold: (Smax,) per-owner sums of the deltas already folded into
        ``base0`` — what a release must subtract back out when its plan's
        early events have long been folded away.

    Batch inputs: ``rel_codes`` (Rb,) owner codes released since the last
    call (-1 padded); candidates in arrival order as ``starts/ends/rels``
    (Cb,), ``bnd/val`` (Cb, k), ``codes`` (Cb,) int32 fresh owner codes and
    ``valid`` (Cb,); ``t0`` the batch clock (the first candidate's arrival —
    monotone across calls, enforced by the host wrapper).

    Steps: (1) releases — zero the released owners' future events (compact
    the survivors left, preserving sort order) and subtract their folded
    contributions from ``base0``; (2) fold — events at or before ``t0``
    collapse into ``base0`` (left-to-right cumulative order, the host
    profile's rounding) with per-owner sums scattered into ``slot_fold``,
    and the timeline compacts; (3) a ``lax.scan`` decides candidates in
    arrival order with the scalar oracle's exact probe expressions
    (``demand_exceeds`` with ``inclusive_end=True``: the start, each own
    switch instant under both of its filters, and every profile event in
    (start, end] read at tie-group-final positions), splicing an admitted
    candidate's events in before the next candidate probes.

    ``Lp`` (static) is the decision-prefix length: the probe tables below
    are built over ``tl[:Lp]`` only, sized by the host from the previous
    batch's returned ``n_live`` (releases and the fold only shrink the live
    prefix, so ``Lp >= n_live`` holds at decision time).  The full L axis is
    touched only by the O(L) bookkeeping (releases, fold, final splice) —
    that split is what keeps a long-lived timeline (large L, mostly ``NEVER``
    padding) from taxing every decision.

    Returns ``(admits (Cb,), overflow (), n_live (), *state)``; ``overflow``
    flags a splice that would have run past L — or a live prefix past Lp —
    (the host pre-sizes both from the returned ``n_live``, so this is a
    can't-happen guard that triggers a reseed + replay).
    """
    L = tl_t.shape[0]
    k = bnd.shape[1]
    Smax = slot_fold.shape[0]
    Lp = L if Lp is None else min(Lp, L)

    # 1. releases: a released plan's future events vanish; its already-folded
    # deltas leave through the per-owner fold sums.  Survivors compact left
    # (stable, so the sorted order is preserved) — the freed slots are what
    # keeps L sized by the *live* active set, not by churn.
    rv = rel_codes >= 0
    # membership via a scattered code table + gather: O(L + Rb), not the
    # O(L * Rb) broadcast-compare (codes are unique per shard by the host's
    # recycle-after-apply discipline, so the table is exact)
    rel_mask = (
        jnp.zeros((Smax + 1,), bool).at[jnp.where(rv, rel_codes, Smax)].set(True, mode="drop")
    )
    gone = rel_mask[jnp.where(tl_c >= 0, tl_c, Smax)]
    base0 = base0 - jnp.sum(jnp.where(rv, slot_fold[jnp.clip(rel_codes, 0)], 0))
    slot_fold = slot_fold.at[jnp.where(rv, rel_codes, Smax)].set(0, mode="drop")
    keep = ~gone
    tgt = jnp.cumsum(keep) - 1
    dst = jnp.where(keep, tgt, L)
    tl_t = jnp.full((L,), NEVER, tl_t.dtype).at[dst].set(tl_t, mode="drop")
    tl_d = jnp.zeros((L,), tl_d.dtype).at[dst].set(tl_d, mode="drop")
    tl_c = jnp.full((L,), -1, tl_c.dtype).at[dst].set(tl_c, mode="drop")

    # 2. fold events at or before the batch clock into base0 (+ per-owner
    # sums) and compact — every probe below is at or after t0, so the folded
    # prefix only ever enters as its cumulative sum.
    fold = tl_t <= t0
    cnt = jnp.sum(fold).astype(jnp.int32)
    dfold = jnp.where(fold, tl_d, 0)
    base0 = base0 + jnp.sum(dfold)
    slot_fold = slot_fold.at[jnp.where(fold & (tl_c >= 0), tl_c, Smax)].add(
        dfold, mode="drop"
    )
    idx = jnp.arange(L) + cnt
    kept = idx < L
    idxc = jnp.minimum(idx, L - 1)
    tl_t = jnp.where(kept, tl_t[idxc], NEVER)
    tl_d = jnp.where(kept, tl_d[idxc], 0)
    tl_c = jnp.where(kept, tl_c[idxc], -1)

    # 3. fresh fold slots for this batch's candidate codes (the host only
    # recycles a code after its release has been applied here, so these are
    # already zero — the scatter is a cheap idempotent guard).
    slot_fold = slot_fold.at[jnp.where(valid, codes, Smax)].set(0, mode="drop")

    # 4. probe parts, precomputed VECTORIZED over the whole batch — the
    # ``admission_program`` cost shape: the sequential scan below is down to
    # a few fused elementwise passes per candidate, with no per-candidate
    # sort/cumsum/scatter (those made the carried program slower than the
    # rebuild-per-batch engine it exists to beat).
    #
    # Two shared probe families cover every point where combined demand can
    # rise inside any candidate's window (extra points only re-sample the
    # step function — the ``shared_probe_set`` argument):
    #   * the carried timeline's event times, read at tie-group-final
    #     positions (a partial mid-tie sum exists at no real time), and
    #   * every candidate's start and live switch instants — each
    #     candidate's own probe points AND each earlier-admitted candidate's
    #     rise points.  Release events stay out of the family: a release is
    #     a drop (allocations are nonnegative), and a drop point can never
    #     carry the window maximum past a point already probed.
    # Demand at a probe = carried profile + admitted-so-far batch demand +
    # the probing candidate's own allocation; the first two live in the
    # scan carry as per-family accumulators, everything else is a table.
    pt = tl_t[:Lp]
    pd = tl_d[:Lp]
    # can't-happen guard: a live event beyond the decision prefix means the
    # host undersized Lp — flag it through the same reseed+replay overflow
    prefix_over = (tl_t[Lp] != NEVER) if Lp < L else jnp.asarray(False)
    cs = base0 + jnp.cumsum(pd)  # carried demand after event i
    cs0 = jnp.concatenate([base0[None], cs])
    tie = jnp.concatenate([pt[:-1] != pt[1:], pt[-1:] != NEVER])

    # candidate event tables: (Cb, k+2) keys/deltas in host splice order
    t_new, d_new, live = jax.vmap(_plan_events)(starts, bnd, val, rels)
    Q = jnp.concatenate(
        [starts[:, None], jnp.where(live, starts[:, None] + bnd + 1, NEVER)], axis=1
    ).reshape(-1)  # shared probe family 2: (Cb * (k+1),)

    # carried profile at the Q points: all deltas at or before q
    qprof = cs0[jnp.sum(pt[None, :] <= Q[:, None], axis=1)]
    # windows: family 1 events in (start, end]; family 2 in [start, end]
    # (the start point doubles as the scalar's first own probe; probing a
    # same-time event at the start re-samples the identical demand value)
    evwin = tie[None, :] & (pt[None, :] > starts[:, None]) & (pt[None, :] <= ends[:, None])
    qwin = (Q[None, :] >= starts[:, None]) & (Q[None, :] <= ends[:, None])
    # the probing candidate's own allocation at each probe point:
    # min(#(b < probe - start), k-1), the scalar's step lookup
    evself = jnp.take_along_axis(
        val,
        jnp.minimum(
            jnp.sum(bnd[:, :, None] < (pt[None, :] - starts[:, None])[:, None, :], axis=1),
            k - 1,
        ),
        axis=1,
    )
    qself = jnp.take_along_axis(
        val,
        jnp.minimum(
            jnp.sum(bnd[:, :, None] < (Q[None, :] - starts[:, None])[:, None, :], axis=1),
            k - 1,
        ),
        axis=1,
    )
    # an admitted candidate's contribution at each probe point: the sum of
    # its event deltas at or before the point (cum-profile linearity; the
    # release delta stays IN the contribution even though it is not a probe
    # point — the value at any later probe must see the drop)
    evcontrib = jnp.sum(d_new[:, :, None] * (t_new[:, :, None] <= pt[None, None, :]), axis=1)
    qcontrib = jnp.sum(d_new[:, :, None] * (t_new[:, :, None] <= Q[None, None, :]), axis=1)

    def cand_step(carry, x):
        extra_ev, extra_q = carry
        ew, qw, es, qs, ec, qc, ok = x
        over = jnp.any(ew & (cs + extra_ev + es > budget)) | jnp.any(
            qw & (qprof + extra_q + qs > budget)
        )
        admit = ok & ~over
        return (
            extra_ev + jnp.where(admit, ec, 0),
            extra_q + jnp.where(admit, qc, 0),
        ), admit

    _, admits = jax.lax.scan(
        cand_step,
        (jnp.zeros_like(pd), jnp.zeros_like(Q)),
        (evwin, qwin, evself, qself, evcontrib, qcontrib, valid),
        unroll=4,
    )

    # 5. one batched splice: every admitted candidate's events merge into
    # the carried timeline in a single stable sort (old events first on
    # ties, then candidates in admission order — the host splice order).
    new_t = jnp.where(admits[:, None], t_new, NEVER).reshape(-1)
    new_d = jnp.where(admits[:, None], d_new, 0).reshape(-1)
    new_c = (
        jnp.broadcast_to(jnp.where(admits, codes, -1)[:, None], t_new.shape)
        .astype(tl_c.dtype)
        .reshape(-1)
    )
    # only the decision prefix can hold live events (prefix_over guards
    # the rest), so the sort runs over Lp + Cb*(k+2) lanes and the NEVER
    # tail rides along unsorted — concat keeps global order because both
    # parts end in NEVER padding
    head_t = jnp.concatenate([pt, new_t])
    head_d = jnp.concatenate([pd, new_d])
    head_c = jnp.concatenate([tl_c[:Lp], new_c])
    order = jnp.argsort(head_t, stable=True)
    comb_t = jnp.concatenate([head_t[order], tl_t[Lp:]])
    comb_d = jnp.concatenate([head_d[order], tl_d[Lp:]])
    comb_c = jnp.concatenate([head_c[order], tl_c[Lp:]])
    # a real event falling off the axis, or a live prefix past Lp
    overflow = (comb_t[L] != NEVER) | prefix_over
    tl_t, tl_d, tl_c = comb_t[:L], comb_d[:L], comb_c[:L]
    n_live = jnp.sum(tl_t != NEVER).astype(jnp.int32)
    return admits, overflow, n_live, base0, tl_t, tl_d, tl_c, slot_fold


@functools.lru_cache(maxsize=None)
def admission_epoch(n_dev: int = 1, Lp: int | None = None):
    """The jitted carried-admission program over a leading shard axis S.

    ``_admission_shard`` vmapped over shards (state/batch inputs carry a
    leading S axis; ``t0``/``budget`` broadcast) and, for ``n_dev > 1``,
    ``jax.shard_map``-partitioned across that many devices — shards are
    independent (each owns its slice of the budget), so the program needs no
    collectives and the mapped body is embarrassingly parallel.  S must be
    divisible by ``n_dev``.

    ``Lp`` is the static decision-prefix length (see ``_admission_shard``);
    ``None`` probes the full timeline axis.

    One compiled variant per (n_dev, Lp, shapes): warm decision batches at
    seen (S, L, Lp, Smax, Cb, Rb, k) buckets must not retrace
    (tests/test_retrace.py).
    """
    body = functools.partial(_admission_shard, Lp=Lp)
    run = jax.vmap(body, in_axes=(0,) * 13 + (None, None))
    if n_dev > 1:
        from jax.sharding import PartitionSpec

        from repro.compat import device_mesh

        sh, rep = PartitionSpec("shards"), PartitionSpec()
        run = jax.shard_map(
            run,
            mesh=device_mesh(n_dev),
            in_specs=(sh,) * 13 + (rep, rep),
            out_specs=(sh,) * 8,
        )

    def admission_epoch(*args):  # the program's name in a trace
        return run(*args)

    return jax.jit(admission_epoch)


# Timeline-axis hint per padded grid signature: a grid that needed an
# overflow-doubled axis starts the next dispatch there, so warm calls are a
# single dispatch instead of re-walking the doubling ladder every time.
# Last known-good timeline axis per grid shape, so warm re-dispatches skip
# the doubling ladder.  A bounded LRU: long sessions sweep many grid shapes
# (every (lanes, rows, segments, nodes) combination is a key) and the hint is
# a pure performance cache — evicting one costs at most a re-probe from the
# floor, never correctness.
_SWEEP_L_HINT: "collections.OrderedDict[tuple, int]" = collections.OrderedDict()
_SWEEP_L_HINT_CAP = 64


def _hint_get(key: tuple) -> int:
    """LRU read: 0 when unknown (the floor decides)."""
    L = _SWEEP_L_HINT.get(key, 0)
    if L:
        _SWEEP_L_HINT.move_to_end(key)
    return L


def _hint_put(key: tuple, L: int) -> None:
    """LRU write with eviction at ``_SWEEP_L_HINT_CAP`` entries."""
    _SWEEP_L_HINT[key] = L
    _SWEEP_L_HINT.move_to_end(key)
    while len(_SWEEP_L_HINT) > _SWEEP_L_HINT_CAP:
        _SWEEP_L_HINT.popitem(last=False)


def sweep_axis_hint(S: int, rmax: int, kmax: int, N: int, *, timeline_floor: int = 256) -> int:
    """The timeline axis the sweep program would start from for this grid
    shape — the ``placement="auto"`` router's L-hat.

    Exact after one warm run at the shape (the LRU hint stores the L the
    grid settled on, doubling re-dispatches included); cold, an estimate
    from the compaction bound: the carried axis holds live breakpoints,
    measured ~0.4x the lane's attempt rows on the congested bench (hw 426
    of 1057 rows), never the full ``rows x (k+2)`` event volume.
    """
    R = _row_bucket(max(rmax, 1))
    hinted = _hint_get((S, R, kmax, N))
    if hinted:
        return hinted
    bound = bucket_size(max(rmax * 2 // 5, 1), floor=timeline_floor)
    return max(bucket_size(_SWEEP_W * (kmax + 2), floor=timeline_floor), min(bound, 8192))


def _row_bucket(n: int) -> int:
    """Static row-axis bucket with eighth-of-a-power-of-two granularity.

    The sweep scan pays full per-row cost for padding rows (their probes and
    masked commits still execute), so the usual power-of-two bucket wastes up
    to half the scan on dead rows — e.g. a 1.1k-row lane padding to 2048.
    Eighth-steps (1024, 1280, 1536, 1792, 2048, ...) cap the waste at 12.5%
    for a handful of extra compiled variants, each a multiple of the
    ``_SWEEP_W`` fold cadence."""
    p = bucket_size(n, floor=8 * _SWEEP_W)
    for eighths in (4, 5, 6, 7):
        c = p * eighths // 8
        if c >= n and c % _SWEEP_W == 0:
            return c
    return p


@functools.partial(jax.jit, static_argnames=("L",))
def _sweep_program(bnd, val, run, pdur, valid, nmask, budget, *, L):
    """All lanes at once: ``_sweep_lane`` vmapped over the leading lane axis
    (policy x node-count x corpus design points share one compiled program
    per padded shape bucket)."""
    return jax.vmap(functools.partial(_sweep_lane, L=L))(
        bnd, val, run, pdur, valid, nmask, budget
    )


def sweep_schedule(
    lane_rows: list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]],
    lane_nodes: list[int],
    lane_budgets: list[float],
    *,
    timeline_floor: int = 256,
    timeline_cap: int = 8192,
    stats: dict | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Schedule every lane of a design space in one vmapped dispatch.

    Args (``core.timeline`` units: keys and demand units):
      lane_rows: per lane, ``(bnd (r, k), val (r, k), run (r,), probe (r,))``
        attempt rows in queue order (``_policy_rows`` layout: values already
        node-capped, run = occupancy, probe = fit-check duration).
      lane_nodes: per lane, its cluster's node count (lanes may differ; the
        program masks nodes past each lane's count).
      lane_budgets: per lane, the fits budget (``NodeState.budget``).
      timeline_floor/timeline_cap: initial / maximal per-node timeline axis.
        A lane whose concurrent future events outgrow the axis flags
        overflow and the whole grid re-dispatches with the axis doubled
        (each axis size is its own compiled variant, so the floor is chosen
        generously); a lane still overflowing at the cap is reported dead.
      stats: optional ``{"program_calls", "waits_program"}`` accumulator
        (the bench's counters), plus the last dispatch's compaction health: ``carried_hw`` (per-lane
        carried-breakpoint high-water marks) and ``timeline_axis`` (the L
        the grid settled on).

    Rows are padded to a shared ``(S, R, k)`` grid: row axes with ``NEVER``
    boundaries / False valid, segment axes hold-last (padded segments have
    ``NEVER`` boundaries, so they never fire a switch and their suffix
    windows are empty).  Returns ``(node (S, R), start keys (S, R), pops
    (S,), waited (S,), dead (S,))``; rows of a dead lane are undefined — the
    caller replays that lane through the per-policy windows engine.  Raises
    ``OverflowError`` when a live lane's schedule passes the time horizon.
    """
    with obs.span("sched.sweep.prepare"):
        S = len(lane_rows)
        rmax = max((b.shape[0] for b, _, _, _ in lane_rows), default=1)
        R = _row_bucket(max(rmax, 1))
        kmax = max(b.shape[1] for b, _, _, _ in lane_rows)
        N = max(lane_nodes)
        bnd = np.full((S, R, kmax), NEVER, dtype=np.int32)
        val = np.zeros((S, R, kmax), dtype=np.int32)
        run = np.zeros((S, R), dtype=np.int32)
        pdur = np.zeros((S, R), dtype=np.int32)
        valid = np.zeros((S, R), dtype=bool)
        nmask = np.zeros((S, N), dtype=bool)
        for s, ((b, v, rr, pr), nn) in enumerate(zip(lane_rows, lane_nodes)):
            r, k = b.shape
            bnd[s, :r, :k] = b
            val[s, :r, :k] = v
            if k < kmax:
                val[s, :r, k:] = v[:, -1:]
            run[s, :r] = rr
            pdur[s, :r] = pr
            valid[s, :r] = True
            nmask[s, :nn] = True
        budget = np.asarray(lane_budgets, dtype=np.int32)
        hint_key = (S, R, kmax, N)
        L = max(
            bucket_size(_SWEEP_W * (kmax + 2), floor=timeline_floor),
            min(_hint_get(hint_key), timeline_cap),
        )
    while True:
        with obs.span("sched.sweep.launch"):
            placed, node, start, _, pops, waited, dead, over, hw = _sweep_program(
                bnd, val, run, pdur, valid, nmask, budget, L=L
            )
        # the first read waits for the device; the rest of the outputs are
        # read once, after the loop
        with obs.span("sched.sweep.wait"):
            placed = np.asarray(placed)
        with obs.span("sched.sweep.readback"):
            dead, over = np.asarray(dead), np.asarray(over)
        if stats is not None:
            stats["program_calls"] = stats.get("program_calls", 0) + 1
        if not over.any() or L >= timeline_cap:
            break
        L *= 2
    _hint_put(hint_key, L)
    dead = dead | over  # still overflowing at the cap: replay on the fallback
    start = np.asarray(start, dtype=np.int64)
    for s, (b, _, _, _) in enumerate(lane_rows):
        if dead[s]:
            continue
        if not placed[s, : b.shape[0]].all():
            raise RuntimeError(f"sweep lane {s} left rows unplaced")
        check_horizon(start[s, : b.shape[0]] + pdur[s, : b.shape[0]])
    if stats is not None:
        stats["waits_program"] = stats.get("waits_program", 0) + int(
            np.asarray(waited)[~dead].sum()
        )
        # compaction health: the carried-breakpoint high-water mark per lane
        # (busiest node, sampled at fold boundaries) and the axis it had to
        # fit in — the bench records both, so a compaction regression shows
        # up as hw growth long before it costs a doubling re-dispatch
        stats["carried_hw"] = np.asarray(hw, dtype=np.int64).tolist()
        stats["timeline_axis"] = L
    return (
        np.asarray(node, dtype=np.int64),
        start,
        np.asarray(pops, dtype=np.int64),
        np.asarray(waited, dtype=np.int64),
        dead,
    )
