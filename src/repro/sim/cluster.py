"""Event-driven cluster simulation with time-varying memory reservations.

The paper's Sec. IV-E limitation: real resource managers take ONE memory
figure per job, so k-Segments' step-function predictions can't pay off until
the manager supports *dynamic* reservations.  This module is that manager,
simulated: nodes track reserved memory as a step function over time, the
scheduler places tasks first-fit against the *future* reservation profile,
and OOM kills trigger the predictor's retry strategy.

Outputs per policy: makespan, wastage (reserved-minus-used GiB*s), retries —
so the scheduler-level benefit of segment-wise reservations (vs static peak
reservations) is measurable end to end, not just per task.

Two engines share the node bookkeeping (``NodeState``, backed by the serving
path's ``IncrementalDemandProfile``):

* ``run_cluster`` — the sequential oracle: one ``predict``/score/``observe``
  chain per task through the numpy predictors, placed by the scalar
  ``_find_slot`` loop (one ``fits`` probe per node per wait step).
* ``run_cluster_batched`` — every queued execution's predictions and full
  retry ladder (attempt -> allocation, failure index, wastage) precomputed
  for **all** policies in one pass of bucket-padded vmapped device programs
  (``repro.sim.batch_engine.compute_cluster_ladders``), and placement itself
  a sequence of device scheduling epochs
  (``repro.sim.device_timeline.schedule_epoch``): the event clock and the
  per-node release heap live in the program's scan carry, so a window of
  attempt rows is placed — *including* every wait on a future completion —
  in one dispatch, with no host round-trip per blocked row.  Predictions see
  exactly the executions the sequential protocol would have observed
  (completed earlier executions of the same task type), so per-task outcomes
  match the oracle run with ``KSegmentsConfig(error_mode="progressive")`` —
  see tests/test_cluster_batch.py, tests/test_cluster_placement.py and
  tests/test_cluster_congested.py.

With more than one policy, ``run_cluster_batched`` routes placement by a
measured per-row cost model (``_auto_sweep``): the lane-vmapped whole-run
sweep program (one dispatch for the whole policy set, carried timelines
compacted to live breakpoints at every chunk boundary) when the model
predicts its row-serial scan beats the windows loop's per-dispatch +
per-row cost — the dispatch-bound regime of many shallow lanes on small
clusters — and the per-policy windows loop otherwise
(``placement="windows"``/``"sweep"`` force either engine), and
``run_cluster_sweep`` extends the same program to the full
capacity-planning design space — (corpus x policy x node count) lanes in
one warm dispatch, Pareto-reducible via ``pareto_frontier`` — see
tests/test_cluster_sweep.py.
"""

from __future__ import annotations

import dataclasses
import heapq

import numpy as np

from repro import obs
from repro.core.allocation import StepAllocation, score_attempt_np
from repro.core.ksegments import KSegmentsConfig
from repro.core.predictor import AllocationMethod, make_method
from repro.core.timeline import (
    Timeline,
    budget_units,
    check_horizon,
    key_seconds,
    quantize_steps,
    span_keys,
    time_keys,
)
from repro.sim.traces import TaskTrace, WorkflowTrace

# Historical alias: NodeState's backing store predates the shared timeline.
IncrementalDemandProfile = Timeline

_ONE_SECOND = int(time_keys(1.0))  # the oracle's last-resort clock step


@dataclasses.dataclass
class NodeState:
    """One node's reservations, in timeline units (``core.timeline``):
    instants and durations are int keys, allocations ``(boundary keys,
    value units)`` step pairs, and the capacity rounds down to units."""

    capacity_mib: float
    # active reservations: (end key, boundary keys, value units, start key)
    active: list[tuple[int, np.ndarray, np.ndarray, int]] = dataclasses.field(default_factory=list)
    # The node's combined demand profile, maintained incrementally under
    # add()/expire() by the shared Timeline (O(E + k) per placement instead
    # of a packed-view re-sort).  Direct external mutation of ``active``
    # (append, rebind, element replacement) is detected via the row-identity
    # key — a mutating row must coexist with the row it replaces, so the key
    # change is deterministic — and triggers a full profile rebuild on the
    # next read.
    _prof: Timeline = dataclasses.field(default_factory=Timeline, init=False, repr=False, compare=False)
    _synced: tuple = dataclasses.field(default=(), init=False, repr=False, compare=False)
    _seq: int = dataclasses.field(default=0, init=False, repr=False, compare=False)

    @property
    def budget(self) -> int:
        """The fits budget: the capacity in whole demand units."""
        return int(budget_units(self.capacity_mib))

    def _key(self) -> tuple[int, ...]:
        return tuple(map(id, self.active))

    def _sync(self) -> Timeline:
        key = self._key()
        if key != self._synced:
            prof = Timeline()
            for end, b, v, start in self.active:
                prof.add(self._seq, b, v, start, end)
                self._seq += 1
            self._prof = prof
            self._synced = key
        return self._prof

    def profile_arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """The node's total reserved-demand step profile as (event keys,
        cumulative demand): ``cum[searchsorted(times, p, "right")]`` is the
        reservation sum at key ``p`` (a reservation end is its release
        instant — exclusive).  The same arrays back ``fits``, ``reserved_at``
        and the batched placement program's probe reads, so every consumer
        sees one source of truth."""
        return self._sync().arrays()

    def reserved_at(self, p: int) -> int:
        """Total reserved units at key ``p`` (one profile probe — same source
        of truth as fits())."""
        return int(self._sync().demand_at(p))

    def add(self, end: int, b: np.ndarray, v: np.ndarray, start: int) -> None:
        """Reserve steps ``(b, v)`` over [start, end) — one O(E + k) splice."""
        prof = self._sync()
        prof.add(self._seq, b, v, start, end)
        self._seq += 1
        self.active.append((end, b, v, start))
        self._synced = self._key()

    def expire(self, t: int) -> None:
        """Drop reservations that ended at or before key ``t`` (released
        events telescope to zero at probes >= t, so this only bounds event
        counts)."""
        if not self.active:
            return
        keep = [row[0] > t for row in self.active]
        if all(keep):
            return
        prof = self._sync()
        prof.expire(t)
        self.active = [row for row, k_ in zip(self.active, keep) if k_]
        self._synced = self._key()

    def fits(self, b: np.ndarray, v: np.ndarray, start: int, duration: int) -> bool:
        """Can the candidate's steps be reserved over [start, start +
        duration) without the combined step profile exceeding capacity?  One
        ``Timeline.demand_exceeds`` probe pass against the node's cached
        cumulative profile — the scheduler's placement inner loop."""
        return not self._sync().demand_exceeds(b, v, start, start + duration, self.budget)


@dataclasses.dataclass
class TaskRecord:
    """One queued execution's fate: every attempt's placement plus totals.

    Tasks are identified by (workflow, task) — task names can collide across
    workflows (same convention as ``simulator.fig7b_lowest_counts``)."""

    workflow: str
    task: str
    exec_index: int
    attempts: int  # retries + 1
    placements: list[tuple[int, float, float]]  # (node, start s, end s) per attempt
    wastage_gib_s: float

    @property
    def finish_s(self) -> float:
        """Completion time of the successful (final) attempt."""
        return self.placements[-1][2]


@dataclasses.dataclass
class ClusterResult:
    policy: str
    makespan_s: float
    wastage_gib_s: float
    retries: int
    tasks_run: int
    records: list[TaskRecord] = dataclasses.field(default_factory=list)


def _eligible_queue(
    workflows: list[WorkflowTrace],
    train_frac: float,
    max_tasks_per_type: int,
    min_executions: int,
) -> tuple[list[tuple[TaskTrace, int]], list[tuple[TaskTrace, int]]]:
    """Arrival-ordered (trace, execution index) rows + per-trace train split."""
    queue: list[tuple[TaskTrace, int]] = []
    traces: list[tuple[TaskTrace, int]] = []
    for wf in workflows:
        for trace in wf.eligible_tasks(min_executions):
            n_train = int(trace.n_executions * train_frac)
            traces.append((trace, n_train))
            for i in range(n_train, min(trace.n_executions, n_train + max_tasks_per_type)):
                queue.append((trace, i))
    return queue, traces


def _gc(nodes: list[NodeState], t: float) -> None:
    for nd in nodes:
        nd.expire(t)


def _find_slot(
    nodes: list[NodeState],
    events: list[tuple[int, int]],
    now: int,
    b: np.ndarray,
    v: np.ndarray,
    duration: int,
) -> tuple[int, int]:
    """First-fit placement against the future reservation profiles; waits on
    the completion heap when no node fits.  Returns (node index, start key)."""
    while True:
        _gc(nodes, now)
        for ni, nd in enumerate(nodes):
            if nd.fits(b, v, now, duration):
                return ni, now
        if events:
            now = max(now, heapq.heappop(events)[0])  # wait for a slot
        else:
            now += _ONE_SECOND  # last resort: walk the clock


def oracle_rows(
    workflows: list[WorkflowTrace],
    policy: str,
    node_mib: float = 128 * 1024.0,
    train_frac: float = 0.5,
    max_tasks_per_type: int = 40,
    min_executions: int = 10,
    ksegments_config: KSegmentsConfig | None = None,
):
    """The sequential oracle's attempt rows, from the float64 numpy
    predictors: per queued execution, predict, score, ``on_failure`` until
    an attempt succeeds, then ``observe``.  A prediction never depends on
    where earlier attempts were placed, so the ladders come first and
    ``place_rows`` places them.

    Returns ``(queue, rows)`` in ``_policy_rows``' layout, except that the
    boundary-key and value-unit rows are ragged (each row has its method's
    k): (boundary keys, value units, run keys, probe keys, attempts per
    task, wastage per task)."""
    queue, traces = _eligible_queue(workflows, train_frac, max_tasks_per_type, min_executions)
    # keyed by (workflow, task name): task names can collide across workflows
    methods: dict[tuple[str, str], AllocationMethod] = {}
    for trace, n_train in traces:
        m = make_method(policy, trace.default_mib, node_mib, ksegments_config)
        for e in trace.executions[:n_train]:
            m.observe(e.input_size, e.series)
        methods[(trace.workflow, trace.name)] = m

    bnds, vals, runs, probes, counts, waste = [], [], [], [], [], []
    for trace, i in queue:
        e = trace.executions[i]
        method = methods[(trace.workflow, trace.name)]
        duration = int(span_keys(len(e.series) * trace.interval_s))
        alloc = method.predict(e.input_size)
        attempts = 0
        task_waste = 0.0
        while True:  # retry loop: each attempt is a fresh placement row
            attempts += 1
            alloc = StepAllocation(alloc.boundaries, np.minimum(alloc.values, node_mib))
            b, v = quantize_steps(alloc.boundaries, alloc.values)
            out = score_attempt_np(e.series, trace.interval_s, alloc)
            bnds.append(b)
            vals.append(v)
            probes.append(duration)
            runs.append(int(span_keys((out.failure_index + 1) * trace.interval_s)) if out.failed else duration)
            task_waste += out.wastage_gib_s
            if not out.failed:
                break
            if attempts > 64:
                raise RuntimeError("unschedulable task")
            seg = alloc.segment_of((out.failure_index + 0.5) * trace.interval_s)
            alloc = method.on_failure(alloc, seg, node_mib)
        method.observe(e.input_size, e.series)
        counts.append(attempts)
        waste.append(task_waste)
    return queue, (
        bnds,
        vals,
        np.asarray(runs, dtype=np.int64),
        np.asarray(probes, dtype=np.int64),
        np.asarray(counts, dtype=np.int64),
        np.asarray(waste, dtype=np.float64),
    )


def place_rows(bnd_rows, val_rows, run_rows, probe_rows, n_nodes: int, node_mib: float):
    """The sequential oracle's placement of flat attempt rows (timeline
    units), one ``_find_slot`` per row: fit-check the full predicted
    duration (the scheduler cannot know an attempt will die early),
    first-fit across nodes, waiting on the completion heap, then occupy the
    node for the row's real run.  Returns per-row (node, start key, end
    key) int64 arrays."""
    nodes = [NodeState(node_mib) for _ in range(n_nodes)]
    # event heap of (end key, node_idx) completions to garbage-collect reservations
    events: list[tuple[int, int]] = []
    now = 0
    out = np.empty((3, len(run_rows)), dtype=np.int64)
    for r in range(len(run_rows)):
        b, v, probe = bnd_rows[r], val_rows[r], int(probe_rows[r])
        placed, now = _find_slot(nodes, events, now, b, v, probe)
        check_horizon(now + probe)
        end = now + int(run_rows[r])
        nodes[placed].add(end, b, v, now)
        heapq.heappush(events, (end, placed))
        out[:, r] = placed, now, end
    return out[0], out[1], out[2]


def run_cluster(
    workflows: list[WorkflowTrace],
    policy: str,
    n_nodes: int = 4,
    node_mib: float = 128 * 1024.0,
    train_frac: float = 0.5,
    max_tasks_per_type: int = 40,
    min_executions: int = 10,
    ksegments_config: KSegmentsConfig | None = None,
) -> ClusterResult:
    """Replay workflow executions through an n-node cluster under a policy
    ("ksegments-selective", "ppm-improved", "default", ...).

    Tasks arrive in trace order; each waits until some node fits its
    reservation.  Per-method online learning happens as tasks finish.  This
    is the sequential oracle (``oracle_rows`` then ``place_rows``);
    ``run_cluster_batched`` is the device-backed twin (pass
    ``ksegments_config=KSegmentsConfig(error_mode="progressive")`` here to
    compare them cell by cell).
    """
    queue, rows = oracle_rows(
        workflows, policy, node_mib, train_frac, max_tasks_per_type, min_executions, ksegments_config
    )
    bnd, val, run, probe, counts, waste = rows
    return _policy_result(policy, queue, counts, waste, *place_rows(bnd, val, run, probe, n_nodes, node_mib))


def _policy_rows(ladders, queue, policy: str):
    """Flatten one policy's retry ladders into placement rows (queue x
    attempt order), in timeline units: (boundary keys (R, k), value units
    (R, k), run keys (R,), probe keys (R,), attempts per task (Q,), wastage
    per task (Q,)).

    ``run`` keys are each attempt's node *occupancy* (up to and including
    the kill sample on failure); ``probe`` keys are the execution's full
    duration — the window the scheduler fit-checks, since it cannot know an
    attempt will die early (``run_cluster`` probes ``_find_slot`` with the
    full duration and only occupies the truncated window).  Seconds and MiB
    are quantized by the same ``core.timeline`` functions ``run_cluster``
    applies per attempt.

    Works trace-block-wise straight off the ``TaskLadders`` tensors
    (``_eligible_queue`` emits each trace's executions contiguously) — the
    per-row quantities are ``AttemptLadder.run_time_s`` /
    ``total_wastage_gib_s`` vectorized, including ``row()``'s convergence
    check."""
    bnds, vals, runs, probes, counts_all, waste = [], [], [], [], [], []
    Q = len(queue)
    i0 = 0
    while i0 < Q:
        trace = queue[i0][0]
        i1 = i0
        while i1 < Q and queue[i1][0] is trace:
            i1 += 1
        execs = np.asarray([i for _, i in queue[i0:i1]])
        tl = ladders[(trace.workflow, trace.name)]
        mi = tl.methods.index(policy)
        counts = tl.n_attempts[mi, execs]  # (q,)
        fi = tl.failure_index[mi, execs]  # (q, A)
        final_fi = np.take_along_axis(fi, (counts - 1)[:, None], axis=1)[:, 0]
        if np.any(final_fi >= 0):
            bad = int(execs[np.argmax(final_fi >= 0)])
            tl.row(policy, bad)  # raises with the scalar path's diagnostics
        durations = (
            np.asarray([len(trace.executions[i].series) for i in execs]) * trace.interval_s
        )
        mask = np.arange(fi.shape[1])[None, :] < counts[:, None]
        runs.append(np.where(fi < 0, durations[:, None], (fi + 1) * trace.interval_s)[mask])
        probes.append(np.broadcast_to(durations[:, None], mask.shape)[mask])
        vals.append(tl.values[mi, execs][mask])
        k = tl.boundaries.shape[-1]
        bnds.append(np.broadcast_to(tl.boundaries[mi, execs][:, None, :], (*mask.shape, k))[mask])
        counts_all.append(counts)
        waste.append(np.sum(tl.wastage_gib_s[mi, execs] * mask, axis=1))
        i0 = i1
    bnd, val = quantize_steps(np.concatenate(bnds), np.concatenate(vals))
    return (
        bnd,
        val,
        span_keys(np.concatenate(runs)),
        span_keys(np.concatenate(probes)),
        np.concatenate(counts_all),
        np.concatenate(waste),
    )


def batched_rows(
    workflows: list[WorkflowTrace],
    policies: tuple[str, ...],
    node_mib: float = 128 * 1024.0,
    train_frac: float = 0.5,
    max_tasks_per_type: int = 40,
    min_executions: int = 10,
    ksegments_config: KSegmentsConfig | None = None,
    max_attempts: int = 32,
    ladder_x64: bool = False,
):
    """The device engine's attempt rows: every policy's retry ladders from
    one batched ladder pass (``compute_cluster_ladders``), flattened by
    ``_policy_rows``.  Returns ``(queue, {policy: rows})`` — the rows both
    placement engines consume, and ``oracle_rows``' twin."""
    from repro.sim.batch_engine import compute_cluster_ladders  # deferred: keeps the oracle jax-free

    kcfg = ksegments_config or KSegmentsConfig(error_mode="progressive")
    if kcfg.error_mode == "insample" and kcfg.insample_window is None:
        raise ValueError(
            "the batched cluster engines support progressive or bounded-history insample "
            "offsets; set KSegmentsConfig(insample_window=W) for insample"
        )
    queue, traces = _eligible_queue(workflows, train_frac, max_tasks_per_type, min_executions)
    # The ladder scan is forward-only (an execution's prediction sees only
    # earlier executions), so executions past the last one the queue can
    # reach are dead weight — truncating them shrinks the biggest buckets
    # without changing any consumed row.
    trunc = [
        dataclasses.replace(t, executions=t.executions[: n_train + max_tasks_per_type])
        for t, n_train in traces
    ]
    ladders = compute_cluster_ladders(trunc, tuple(policies), node_mib, kcfg, max_attempts, x64=ladder_x64)
    with obs.span("sched.ladder.rows"):
        return queue, {p: _policy_rows(ladders, queue, p) for p in policies}


def _place_rows_batched(
    bnd_rows: np.ndarray,
    val_rows: np.ndarray,
    run_rows: np.ndarray,
    probe_rows: np.ndarray,
    n_nodes: int,
    node_mib: float,
    window: int,
    stats: dict | None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Place all of one policy's attempt rows (``_policy_rows`` units) with
    the device timeline programs.  Returns per-row (node, start key, end key)
    arrays with the sequential oracle's exact placement semantics.

    Two device regimes, zero host-resolved waits:

    * **streaming** — while rows keep placing at the current clock, the
      cheap fixed-clock window program (``device_timeline.first_fit_window``)
      decides a whole window per dispatch against host-precomputed probe
      reads.
    * **congested** — from the first blocked row, the scheduling-epoch
      program (``device_timeline.schedule_epoch``) takes over: the event
      clock and the pending-completion heap live in its carry, so a blocked
      row waits **in-program** — the program pops upcoming releases,
      advances the clock and re-probes, exactly the oracle's ``_find_slot``
      event-pop semantics — instead of paying a host round-trip per wait.
      The scheduler returns to streaming once an epoch resolves without
      waiting.

    Between dispatches the host mirrors the commits into the per-node
    ``Timeline``s (one ``add_many`` splice per node, identical event order)
    and drops the consumed completions, so the next epoch is seeded from the
    same profiles the oracle probes.  The only remaining host placement is
    the oracle's last-resort one-second clock walk when the completion heap
    drains with a row still unplaced — unreachable for node-capped
    allocations, counted in ``waits_host``."""
    # deferred import keeps the oracle path (run_cluster) jax-free
    from repro.sim.device_timeline import first_fit_window, schedule_epoch

    R = len(run_rows)
    profs = [Timeline() for _ in range(n_nodes)]
    pending: list[int] = []  # completion keys not yet consumed by a wait
    budget = int(budget_units(node_mib))  # NodeState.fits budget
    row_node = np.empty(R, dtype=np.int64)
    row_start = np.empty(R, dtype=np.int64)
    row_end = np.empty(R, dtype=np.int64)
    owner = 0
    now = 0
    r = 0
    congested = False

    def _commit(npl, nidx, starts):
        """Mirror one dispatch's placements into the host timelines/outputs."""
        nonlocal owner, r
        if stats is not None:
            stats["program_calls"] += 1
        # the device keys are int32: a fit window past the horizon would
        # have wrapped, so every placement is checked before it is kept
        check_horizon(starts[:npl] + probe_rows[r : r + npl])
        ends = starts[:npl] + run_rows[r : r + npl]
        # committing per node in row order splices key-tied events in
        # exactly the order the oracle's one-at-a-time add() would
        for n in np.unique(nidx[:npl]):
            m = np.flatnonzero(nidx[:npl] == n)
            profs[n].add_many(
                range(owner, owner + len(m)),
                bnd_rows[r + m],
                val_rows[r + m],
                starts[m],
                ends[m],
            )
            owner += len(m)
        row_node[r : r + npl] = nidx[:npl]
        row_start[r : r + npl] = starts[:npl]
        row_end[r : r + npl] = ends
        r += npl
        return [int(e) for e in ends]

    expired_at = -1
    while r < R:
        if now > expired_at:
            # the clock only moves when a row waits, so most windows skip
            # the N-node expiry sweep entirely
            with obs.span("sched.place.expire"):
                for prof in profs:
                    prof.expire(now)
            expired_at = now
        w = min(window, R - r)
        if not congested:
            with obs.span("sched.place.profiles"):
                profiles = [prof.arrays() for prof in profs]
            placed, nidx = first_fit_window(
                now,
                bnd_rows[r : r + w],
                val_rows[r : r + w],
                run_rows[r : r + w],
                probe_rows[r : r + w],
                profiles,
                budget,
                window,
            )
            npl = w if placed.all() else int(np.argmin(placed))
            with obs.span("sched.place.commit"):
                pending += _commit(npl, nidx, np.full(npl, now, dtype=np.int64))
            if r < R and npl < w:
                congested = True  # row r must wait: epoch program takes over
            continue
        # small wait windows: every row-step of the epoch program pays
        # for its carried clock/heap machinery, so congested dispatches
        # place a handful of rows per call and hand back to streaming
        # as soon as a window resolves without waiting
        w = min(w, 8)
        with obs.span("sched.place.profiles"):
            node_events = [prof.events() for prof in profs]
            heap = np.asarray(pending, dtype=np.int64)
        placed, nidx, starts, now, n_pops, n_waited, dead = schedule_epoch(
            now,
            bnd_rows[r : r + w],
            val_rows[r : r + w],
            run_rows[r : r + w],
            node_events,
            heap,
            budget,
            min(window, 8),
            probe_times=probe_rows[r : r + w],
        )
        if stats is not None:
            stats["waits_program"] += n_waited
        npl = w if placed.all() else int(np.argmin(placed))
        with obs.span("sched.place.commit"):
            ends = _commit(npl, nidx, starts)
        # the program consumed the n_pops earliest completions of the
        # merged heap (pop order among key-ties is unobservable)
        with obs.span("sched.place.pending"):
            pending = sorted(pending + ends)[n_pops:]
        congested = n_waited > 0  # stream again once a window stops waiting
        if r < R and npl < w and not dead:
            # a full per-node commit buffer aborted the epoch; nothing of
            # row r was consumed — re-dispatch from fresh timelines
            congested = True
            continue
        if r < R and npl < w:
            # heap drained with row r unplaced: the oracle's last-resort
            # clock walk (unreachable for node-capped allocations — an
            # empty node always fits once everything released)
            if stats is not None:
                stats["waits_host"] += 1
            b, v = bnd_rows[r], val_rows[r]
            pdur = int(probe_rows[r])  # fit-check the full duration ...
            ni = None
            while ni is None:
                now += _ONE_SECOND
                for prof in profs:
                    prof.expire(now)
                for i, prof in enumerate(profs):
                    if not prof.demand_exceeds(b, v, now, now + pdur, budget):
                        ni = i
                        break
            check_horizon(now + pdur)
            end = now + int(run_rows[r])  # ... but occupy the real run
            profs[ni].add(owner, b, v, now, end)
            owner += 1
            pending = sorted(pending + [end])
            row_node[r], row_start[r], row_end[r] = ni, now, end
            r += 1
    return row_node, row_start, row_end


def _policy_result(
    policy: str,
    queue: list[tuple[TaskTrace, int]],
    counts: np.ndarray,
    waste: np.ndarray,
    row_node: np.ndarray,
    row_start: np.ndarray,
    row_end: np.ndarray,
) -> ClusterResult:
    """Assemble one policy's ``ClusterResult`` from its placed attempt rows
    (start/end keys; shared by the windows and sweep placement engines)."""
    offsets = np.concatenate([[0], np.cumsum(counts)])
    start_s = key_seconds(row_start).tolist()
    end_s = key_seconds(row_end).tolist()
    records = [
        TaskRecord(
            trace.workflow,
            trace.name,
            i,
            int(counts[q]),
            [
                (int(row_node[j]), start_s[j], end_s[j])
                for j in range(offsets[q], offsets[q + 1])
            ],
            float(waste[q]),
        )
        for q, (trace, i) in enumerate(queue)
    ]
    return ClusterResult(
        policy=policy,
        makespan_s=float(key_seconds(row_end.max())) if len(row_end) else 0.0,
        wastage_gib_s=float(waste.sum()),
        retries=int((counts - 1).sum()),
        tasks_run=len(queue),
        records=records,
    )


# "auto" placement routes by a per-row cost model instead of the old fixed
# row threshold (_SWEEP_AUTO_ROWS = 128): with the sweep program's chunk
# boundaries now compacting the carried timelines down to live breakpoints
# (``device_timeline._sweep_lane``), lane depth alone no longer decides —
# what matters is each engine's predicted wall.  Constants are measured on
# the bench host (BENCH_cluster.json shapes, warm placement walls):
#
# * windows: ~_WIN_DISPATCH_S per program dispatch (device round-trip plus
#   the host loop's bookkeeping between windows) + ~_WIN_ROW_S per attempt
#   row (fits well from 12-dispatch/144-row up to 96-dispatch/6805-row
#   workloads).
# * sweep: one row-step per (padded) attempt row, each costing per lane
#   ~_SWEEP_STEP_S fixed + _SWEEP_CELL_S per carried timeline cell (N x
#   L-hat, the compacted axis): predicts 1.9 ms/row-step at (4 lanes, 16
#   nodes, L=512) vs 1.8 measured, 6.1 ms at the congested (7, 32, 512)
#   grid vs 6.7 measured.
#
# The sweep therefore wins the dispatch-bound regime — many lanes of
# shallow rows on small clusters, where the windows loop pays one dispatch
# per policy-window — and the windows loop wins once per-row compute
# dominates (large N x L-hat or deep lanes on few lanes).  The congested
# bench (1k-row lanes, 32 nodes) honestly routes to windows on a serial
# CPU host; the forced-sweep twin of that workload is benched and
# parity-gated as the ``sweep_deep`` variant.
_WIN_DISPATCH_S = 1.5e-3
_WIN_ROW_S = 4.0e-5
_SWEEP_STEP_S = 7.0e-5
_SWEEP_CELL_S = 5.0e-8


def _auto_sweep(rows: dict, policies: tuple, n_nodes: int, window: int) -> bool:
    """The ``placement="auto"`` router: True when the cost model predicts
    the single-dispatch sweep beats the per-policy windows loop."""
    from repro.sim.device_timeline import sweep_axis_hint

    if len(policies) < 2:
        return False  # nothing to amortize: one lane costs a whole sweep scan
    lane_rows = [len(rows[p][2]) for p in policies]
    rmax, kmax = max(lane_rows), max(rows[p][0].shape[1] for p in policies)
    L_hat = sweep_axis_hint(len(policies), rmax, kmax, n_nodes)
    est_sweep = rmax * len(policies) * (_SWEEP_STEP_S + _SWEEP_CELL_S * n_nodes * L_hat)
    est_windows = sum(
        -(-r // window) * _WIN_DISPATCH_S + r * _WIN_ROW_S for r in lane_rows
    )
    return est_sweep <= est_windows


def _merge_stats(acc: dict, stats: dict) -> None:
    """Fold one run's placement stats into the caller's accumulator:
    counters add, per-lane lists replace, the timeline axis keeps its max."""
    for k, v in stats.items():
        if isinstance(v, list):
            acc[k] = v
        elif k == "timeline_axis":
            acc[k] = max(acc.get(k, 0), v)
        else:
            acc[k] = acc.get(k, 0) + v


@obs.call("sched.call")
def run_cluster_batched(
    workflows: list[WorkflowTrace],
    policies: tuple[str, ...],
    n_nodes: int = 4,
    node_mib: float = 128 * 1024.0,
    train_frac: float = 0.5,
    max_tasks_per_type: int = 40,
    min_executions: int = 10,
    ksegments_config: KSegmentsConfig | None = None,
    max_attempts: int = 32,
    placement_window: int = 128,
    placement_stats: dict | None = None,
    ladder_x64: bool = False,
    placement: str = "auto",
) -> dict[str, ClusterResult]:
    """Evaluate every policy through the cluster in one device pass.

    All queued executions' predictions and retry ladders — for **all**
    policies at once — come from one shared tensor of (attempt -> allocation,
    failure index, wastage) rows computed by bucket-padded vmapped scans
    (``compute_cluster_ladders``, truncated to the executions the queue can
    reach); placement itself runs as device scheduling epochs
    (``device_timeline.schedule_epoch``): each dispatch places a whole window
    of attempt rows with the event clock and release heap in the program's
    carry, so blocked rows wait in-program instead of paying a host
    round-trip per wait.  Returns {policy: ClusterResult} with the same
    per-task records as the sequential oracle
    (tests/test_cluster_placement.py asserts exact (node, start, end) parity
    per attempt; tests/test_cluster_congested.py stresses the wait path).

    ``placement_stats``, when passed, accumulates ``{"program_calls",
    "waits_program", "waits_host", "rows", "lanes_replayed"}`` for the
    bench (``waits_program`` = rows whose wait was resolved inside the
    device program; ``waits_host`` = last-resort host clock walks, 0 in
    practice; ``lanes_replayed`` = sweep lanes that overflowed and were
    replayed through the windows engine).  The call's spans and counters
    (``repro.obs``) are kept as ``obs.last("sched.call")``.

    k-Segments policies run with progressive error offsets (the device
    engine's bounded-carry mode) by default; ``error_mode="insample"`` is
    accepted when ``insample_window`` is an explicit bound (the ladder
    engine's ring-buffer mode — the sequential oracle with the same window
    is the parity twin), and rejected unbounded to keep results honest.
    ``ladder_x64`` runs the ladder scan in float64 — a CPU reference that
    closes the rare f32 ulp-boundary parity gap against the float64 numpy
    predictors at ~1.5x ladder cost; no TPU path enters it.

    ``placement`` picks the placement engine: ``"windows"`` runs the
    per-policy streaming/epoch windows loop above; ``"sweep"`` schedules
    every policy as one lane of a single vmapped whole-run program
    (``device_timeline.sweep_schedule`` — identical decisions, one dispatch
    for the whole policy set instead of a host loop of windows); ``"auto"``
    (default) picks by the measured per-row cost model ``_auto_sweep``:
    the sweep costs one row-step per attempt row, each ~linear in its
    carried timeline cells (lanes x nodes x compacted axis — the chunk
    boundaries fold and compact the carry down to demand-shape-changing
    breakpoints, so the axis tracks live breakpoints, not run depth),
    while the windows loop costs one dispatch per policy-window plus a
    small per-row term.  Many shallow lanes on small clusters route to the
    sweep; large ``nodes x axis`` grids or few deep lanes to the windows
    loop.  A sweep lane that overflows the program's bounded timeline axis
    falls back to the windows engine for that policy alone.
    """
    if placement not in ("auto", "sweep", "windows"):
        raise ValueError(f"unknown placement engine: {placement!r}")
    policies = tuple(policies)
    queue, rows = batched_rows(
        workflows,
        policies,
        node_mib,
        train_frac,
        max_tasks_per_type,
        min_executions,
        ksegments_config,
        max_attempts,
        ladder_x64,
    )
    stats = {"program_calls": 0, "waits_program": 0, "waits_host": 0, "rows": 0, "lanes_replayed": 0}
    placed: dict[str, tuple[np.ndarray, np.ndarray, np.ndarray]] = {}
    if placement == "sweep" or (
        placement == "auto" and _auto_sweep(rows, policies, n_nodes, placement_window)
    ):
        from repro.sim.device_timeline import sweep_schedule

        node_s, start_s, _, _, dead = sweep_schedule(
            [rows[p][:4] for p in policies],
            [n_nodes] * len(policies),
            [int(budget_units(node_mib))] * len(policies),
            stats=stats,
        )
        stats["lanes_replayed"] += int(np.sum(dead))
        for s, p in enumerate(policies):
            if not dead[s]:
                run_rows = rows[p][2]
                r = len(run_rows)
                placed[p] = (node_s[s, :r], start_s[s, :r], start_s[s, :r] + run_rows)
    # Remaining policies (windows engine, or sweep lanes that overflowed):
    # independent simulations sharing the process's device stream — threads
    # serialize on the jit dispatch lock (measured ~2x slower), so
    # sequentially.
    for p in policies:
        if p not in placed:
            bnd_rows, val_rows, run_rows, probe_rows = rows[p][:4]
            placed[p] = _place_rows_batched(
                bnd_rows, val_rows, run_rows, probe_rows, n_nodes, node_mib, placement_window, stats
            )
    results: dict[str, ClusterResult] = {}
    for p in policies:
        counts, waste = rows[p][4], rows[p][5]
        stats["rows"] += len(rows[p][2])
        results[p] = _policy_result(p, queue, counts, waste, *placed[p])
    if placement_stats is not None:
        _merge_stats(placement_stats, stats)
    return results


@obs.call("sched.call")
def run_cluster_sweep(
    corpora: dict[str, list[WorkflowTrace]] | list[WorkflowTrace],
    policies: tuple[str, ...],
    node_counts: tuple[int, ...] = (4,),
    node_mib: float = 128 * 1024.0,
    train_frac: float = 0.5,
    max_tasks_per_type: int = 40,
    min_executions: int = 10,
    ksegments_config: KSegmentsConfig | None = None,
    max_attempts: int = 32,
    placement_window: int = 128,
    placement_stats: dict | None = None,
    ladder_x64: bool = False,
) -> dict[tuple[str, str, int], ClusterResult]:
    """Capacity-planning sweep: the whole (corpus x policy x node count)
    design space scheduled in ONE warm device dispatch.

    Every design point becomes one lane of the vmapped whole-run program
    (``device_timeline.sweep_schedule``): per-lane event clocks, node
    timelines and release heaps are stacked along a leading lane axis, with
    heterogeneous node counts masked up to the grid maximum.  Retry ladders
    are computed once per corpus (they depend on ``node_mib``, not the node
    count) and shared across that corpus's lanes.  Each lane's placements
    carry the sequential oracle's exact (node, start, end) semantics — the
    same correctness bar as ``run_cluster_batched`` — and a lane that
    overflows the program's bounded timeline axis is replayed through the
    per-policy windows engine (counted in ``placement_stats``).

    ``corpora`` maps corpus names to workflow lists (a bare list is treated
    as the single corpus ``""``).  Returns ``{(corpus, policy, n_nodes):
    ClusterResult}`` — feed ``(makespan_s, wastage_gib_s)`` pairs per corpus
    to ``pareto_frontier`` for the capacity-planning frontier.
    """
    from repro.sim.device_timeline import sweep_schedule

    if not isinstance(corpora, dict):
        corpora = {"": corpora}
    policies = tuple(policies)
    stats = {"program_calls": 0, "waits_program": 0, "waits_host": 0, "rows": 0, "lanes_replayed": 0}
    lane_rows, lane_nodes, lane_keys = [], [], []
    meta: dict[str, tuple[list, dict]] = {}
    for cname, wfs in corpora.items():
        queue, rows = batched_rows(
            wfs,
            policies,
            node_mib,
            train_frac,
            max_tasks_per_type,
            min_executions,
            ksegments_config,
            max_attempts,
            ladder_x64,
        )
        meta[cname] = (queue, rows)
        for p in policies:
            for nn in node_counts:
                lane_rows.append(rows[p][:4])
                lane_nodes.append(int(nn))
                lane_keys.append((cname, p, int(nn)))
    node_s, start_s, _, _, dead = sweep_schedule(
        lane_rows, lane_nodes, [int(budget_units(node_mib))] * len(lane_rows), stats=stats
    )
    stats["lanes_replayed"] += int(np.sum(dead))
    results: dict[tuple[str, str, int], ClusterResult] = {}
    for s, (cname, p, nn) in enumerate(lane_keys):
        queue, rows = meta[cname]
        bnd_rows, val_rows, run_rows, probe_rows, counts, waste = rows[p]
        stats["rows"] += len(run_rows)
        if dead[s]:
            node, start, end = _place_rows_batched(
                bnd_rows, val_rows, run_rows, probe_rows, nn, node_mib, placement_window, stats
            )
        else:
            r = len(run_rows)
            node, start = node_s[s, :r], start_s[s, :r]
            end = start + run_rows
        results[(cname, p, nn)] = _policy_result(p, queue, counts, waste, node, start, end)
    if placement_stats is not None:
        _merge_stats(placement_stats, stats)
    return results


def pareto_frontier(points) -> np.ndarray:
    """Boolean mask of the non-dominated rows of ``points`` (minimize every
    column): row i is kept unless some row is <= it everywhere and < it
    somewhere.  Ties keep both rows — duplicate design points stay visible
    in the capacity-planning report."""
    pts = np.asarray(points, dtype=float)
    if pts.ndim != 2:
        raise ValueError(f"points must be 2-D, got shape {pts.shape}")
    keep = np.ones(len(pts), dtype=bool)
    for i in range(len(pts)):
        dom = (pts <= pts[i]).all(axis=1) & (pts < pts[i]).any(axis=1)
        keep[i] = not dom.any()
    return keep
