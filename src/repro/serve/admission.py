"""Beyond-paper application of k-Segments: HBM admission control for decoding.

A decode request's device-memory footprint grows monotonically with its KV
cache — the exact shape the paper's monotone step function (Eq. 1) models.
Treating "serve one request" as a workflow task whose input size is the
prompt length, the k-Segments predictor learns (runtime, per-segment peak
HBM) online from finished requests and the admission controller packs
requests against the HBM budget *segment-wise*: a new request is admitted if
the *sum of concurrent step functions* stays under budget at every future
boundary, instead of reserving every request's worst-case peak at admission
(the static baseline).  Wastage here = reserved-but-unused HBM x seconds —
the paper's metric applied to serving.

Three controllers implement the same policy:

* ``AdmissionController`` — the sequential oracle: one Python
  ``demand_exceeds`` probe per candidate against a profile rebuilt from the
  active set whenever it changes.
* ``BatchedAdmissionController`` — the device engine: active plans live in an
  incrementally-maintained event timeline (``core.timeline.Timeline``), and
  whole *batches* of candidates are decided by one jitted program
  (``sim.device_timeline.admission_program``) — the union-of-switch-points
  probe becomes a ``searchsorted`` read of the cached profile at a shared
  deduped probe set (``core.timeline.shared_probe_set``), and a ``lax.scan``
  over the batch threads the within-batch sequential dependency (an admitted
  candidate's demand is visible to every later candidate, exactly as if the
  scalar controller had processed them one at a time).  Decision parity with
  the oracle is exact on randomized streams (``tests/test_serve_batch.py``).
* ``ShardedAdmissionController`` — the long-lived control plane: the active
  set is sharded across ``n_shards`` by a deterministic crc32 placement,
  each shard owns ``budget / n_shards`` HBM, and the whole per-shard state
  (clock-folded base demand, sorted event timeline, per-owner fold sums)
  lives ON DEVICE between calls — ``sim.device_timeline.admission_epoch``
  applies releases, folds the clock forward and decides the batch in one
  dispatch, so nothing is rebuilt from host state per batch.  The per-shard
  oracle is ``ShardedScalarController`` (one scalar ``AdmissionController``
  per shard over the same placement), which the parity suite
  (``tests/test_serve_sharded.py``) holds it to decision-for-decision.

Every controller decides in ``core.timeline``'s integer units: arrival
times and step boundaries become time keys (1 ms ticks), allocations round
up and budgets down to whole MiB, so the host oracles and the int32 device
programs compute the same exact sums.  A plan holds through its final
boundary inclusive (Eq. 1's domain [0, r_e]), so it releases at the key just
after its end.  Plans keep the predictor's float allocation for the
wastage accounting.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

from repro.core.allocation import StepAllocation
from repro.core.ksegments import KSegmentsConfig, KSegmentsModel
from repro.core.timeline import (
    NEVER,
    Timeline,
    budget_units,
    check_horizon,
    demand_exceeds,
    pack_steps,
    plan_profile_events,
    quantize_plan,
    shared_probe_set,
    step_demand_profile,
    time_keys,
)

# Historical alias kept for external callers of the controller internals.
IncrementalDemandProfile = Timeline


@dataclasses.dataclass
class RequestPlan:
    request_id: str
    admitted_at: float
    alloc: StepAllocation  # MiB over seconds since admission


def cache_bytes_per_token(cfg) -> int:
    """KV-cache bytes per decoded token (attention layers only).

    Counts every attention-bearing layer kind (dense / local / global / moe —
    cross-checked against ``jax.eval_shape`` of ``models.init_cache`` in
    tests); recurrent kinds (rwkv / rglru) carry O(1) state and contribute
    nothing per token."""
    dt = 2 if cfg.dtype == "bfloat16" else 4
    n_attn = sum(1 for k in cfg.layer_kinds if k in ("dense", "local", "global", "moe"))
    return n_attn * 2 * cfg.num_kv_heads * cfg.head_dim * dt


class _AdmissionBase:
    """State and accounting shared by the scalar and batched controllers."""

    def __init__(self, hbm_budget_mib: float, k: int = 4, interval_s: float = 0.5):
        self.budget = float(hbm_budget_mib)
        self.model = KSegmentsModel(KSegmentsConfig(k=k, interval_s=interval_s, floor_mib=1.0))
        self.active: dict[str, RequestPlan] = {}
        self._static_reserved = 0.0  # what peak-reservation would hold (baseline)

    # -- learning ----------------------------------------------------------

    def observe(self, prompt_len: int, hbm_series_mib: np.ndarray) -> None:
        """Fold a finished request's memory-over-time into the model."""
        self.model.observe(float(prompt_len), np.asarray(hbm_series_mib))

    # -- accounting ---------------------------------------------------------

    def reservation_wastage(self, plans: list[tuple[RequestPlan, np.ndarray, float]]) -> dict:
        """Compare segment-wise vs peak-at-admission reservation wastage.

        plans: (plan, actual hbm series MiB, interval) per finished request.
        Returns GiB*s wasted under both policies (the Fig. 7a metric applied
        to serving)."""
        seg, peak = 0.0, 0.0
        for plan, series, interval in plans:
            t = (np.arange(len(series)) + 0.5) * interval
            a = plan.alloc.at(t)
            seg += float(np.sum(np.maximum(a - series, 0.0)) * interval) / 1024.0
            peak += float(np.sum(np.maximum(plan.alloc.values[-1] - series, 0.0)) * interval) / 1024.0
        return {"segmentwise_gib_s": seg, "peak_reservation_gib_s": peak}

    def _default_alloc(self) -> StepAllocation:
        """Before any observation the model has no fit: admit against a flat
        5%-of-budget placeholder reservation."""
        return StepAllocation(np.asarray([1.0]), np.asarray([self.budget * 0.05]))

    def _predict_many(self, prompt_lens, C: int) -> tuple[np.ndarray, np.ndarray]:
        """(C, k) boundaries (seconds) and values (MiB) for a candidate batch."""
        if self.model.n_observations == 0:
            d = self._default_alloc()
            return np.tile(d.boundaries, (C, 1)), np.tile(d.values, (C, 1))
        return self.model.predict_batch(np.asarray(prompt_lens, dtype=np.float64))


def _windows(starts: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Admission windows in keys: a plan from ``starts`` holds through its
    final boundary inclusive (rounded up by ``quantize_plan``) and releases
    just after it.  Returns (end keys, release keys), checked against the
    horizon."""
    ends = starts + b[..., -1]
    check_horizon(ends)
    return ends, ends + 1


class AdmissionController(_AdmissionBase):
    """Online segment-wise HBM packing for a decode engine (scalar oracle)."""

    def __init__(self, hbm_budget_mib: float, k: int = 4, interval_s: float = 0.5):
        super().__init__(hbm_budget_mib, k, interval_s)
        self._units = int(budget_units(self.budget))
        self._steps: dict[str, tuple[np.ndarray, np.ndarray, int]] = {}  # rid -> (b, v, start)
        self._prof: tuple | None = None  # cached demand profile; dropped on admit/release

    # -- admission ----------------------------------------------------------

    def _profile(self) -> tuple[np.ndarray, np.ndarray]:
        """Active plans' total demand as a cumulative step profile (event
        keys, running sum) — ``core.timeline.step_demand_profile``, shared
        with the cluster simulator's ``NodeState``, so admission stays
        O(P k log) per request instead of re-summing every plan at every
        probe.  A plan holds through its final boundary inclusive (the
        paper's Eq. 1 domain [0, r_e]) and releases at the key just after."""
        if self._prof is None:
            steps = list(self._steps.values())
            bnd, val = pack_steps([(b, v) for b, v, _ in steps])
            starts = np.asarray([s for _, _, s in steps], dtype=np.int64)
            releases = np.asarray([s + b[-1] + 1 for b, _, s in steps], dtype=np.int64)
            self._prof = step_demand_profile(bnd, val, starts, releases)
        return self._prof

    def _combined_demand(self, horizon: tuple[float, ...]) -> np.ndarray:
        """Total predicted MiB demand of active requests at absolute times.

        A request's reservation covers its predicted lifetime [0, r_e] (the
        paper's Eq. 1 domain): past its final boundary it is expected to have
        released — that expiry is what lets staggered admissions overlap a
        newcomer's cheap early segments with a leader's remaining window.
        (Requests that outlive r_e are the retry/preemption path.)"""
        times, cum = self._profile()
        keys = time_keys(np.asarray(horizon))
        return cum[np.searchsorted(times, keys, side="right")]

    def try_admit(self, request_id: str, prompt_len: int, now: float) -> RequestPlan | None:
        """Admit if the segment-wise demand fits the budget at every point
        where it can rise during the newcomer's reservation window.

        The probe horizon is the union of the newcomer's boundaries and every
        *active* plan's future switch points (as ``NodeState.fits`` checks in
        the cluster simulator): an active request stepping up between two of
        the newcomer's boundaries would otherwise push combined demand over
        budget undetected.  Steps are right-open (Eq. 1), so switch points are
        probed just after the boundary, where the higher value applies."""
        if self.model.n_observations == 0:
            alloc = self._default_alloc()
        else:
            alloc = self.model.predict(float(prompt_len))
        b, v, start = quantize_plan(alloc.boundaries, alloc.values, now)
        start = int(start)
        end, _ = _windows(start, b)
        times, cum = self._profile()
        # inclusive end: a plan holds through its final boundary (Eq. 1
        # domain [0, r_e]), unlike a cluster reservation's right-open window.
        if demand_exceeds(times, cum, b, v, start, int(end), self._units, inclusive_end=True):
            return None
        plan = RequestPlan(request_id, now, alloc)
        self.active[request_id] = plan
        self._steps[request_id] = (b, v, start)
        self._static_reserved += float(alloc.values[-1])
        self._prof = None
        return plan

    def release(self, request_id: str) -> None:
        plan = self.active.pop(request_id, None)
        if plan is not None:
            del self._steps[request_id]
            self._static_reserved -= float(plan.alloc.values[-1])
            self._prof = None


# ---------------------------------------------------------------------------
# Batched admission engine
# ---------------------------------------------------------------------------


class BatchedAdmissionController(_AdmissionBase):
    """Device-batched twin of ``AdmissionController``.

    Same policy, same decisions (exact admit/reject parity on randomized
    streams — tests/test_serve_batch.py), but the hot path is batched: active
    plans back an ``IncrementalDemandProfile`` (O(E + k) add/remove instead
    of a rebuild per decision) and ``try_admit_many`` decides a whole batch
    of candidates in one compiled program, with sequential-equivalent
    semantics inside the batch.  ``try_admit`` is the batch-of-one special
    case, so the two controllers are drop-in interchangeable.
    """

    def __init__(
        self,
        hbm_budget_mib: float,
        k: int = 4,
        interval_s: float = 0.5,
        device_min_batch: int = 32,
    ):
        super().__init__(hbm_budget_mib, k, interval_s)
        self._units = int(budget_units(self.budget))
        self._prof = IncrementalDemandProfile()
        # Below this batch size the per-call device dispatch outweighs the
        # batched probe; the host path runs the same ``demand_exceeds``
        # expressions against the same incremental profile (identical
        # decisions — both paths are parity-tested against the oracle).
        self.device_min_batch = int(device_min_batch)

    # -- admission ----------------------------------------------------------

    def try_admit(self, request_id: str, prompt_len: int, now: float) -> RequestPlan | None:
        """The batch-of-one case: the oracle's exact probe expressions
        against the incremental profile, no rebuild."""
        return self.try_admit_many([request_id], [prompt_len], now)[0]

    def try_admit_many(
        self, request_ids: list[str], prompt_lens, now
    ) -> list[RequestPlan | None]:
        """Decide a batch of candidates in arrival order, one device program.

        ``now`` is a scalar (all candidates share the clock) or a
        non-decreasing (C,) array of per-candidate arrival times (seconds).
        Decisions are sequential-equivalent: candidate i is probed against
        the active profile plus every candidate j < i admitted in this same
        call."""
        C = len(request_ids)
        if C == 0:
            return []
        bnd_s, val_s = self._predict_many(prompt_lens, C)
        now_s = np.broadcast_to(np.asarray(now, dtype=np.float64), (C,))
        bnd, val, starts = quantize_plan(bnd_s, val_s, now_s)
        ends, rels = _windows(starts, bnd)
        self._prof.expire(int(starts[0]))
        if C < self.device_min_batch:
            admits = self._admit_host(request_ids, bnd, val, starts, ends, rels)
        else:
            admits = self._admit_device(request_ids, bnd, val, starts, ends, rels)
        plans: list[RequestPlan | None] = []
        for i, rid in enumerate(request_ids):
            if admits[i]:
                plan = RequestPlan(rid, float(now_s[i]), StepAllocation(bnd_s[i], val_s[i]))
                self.active[rid] = plan
                self._static_reserved += float(val_s[i, -1])
                plans.append(plan)
            else:
                plans.append(None)
        return plans

    def _admit_host(self, request_ids, bnd, val, starts, ends, rels) -> list[bool]:
        """Small-batch path: the oracle's probe against the incremental
        profile, committing admitted plans as it goes (so within-batch
        sequencing matches the device scan exactly)."""
        admits = []
        for i, rid in enumerate(request_ids):
            times, cum = self._prof.arrays()
            over = demand_exceeds(
                times, cum, bnd[i], val[i], int(starts[i]), int(ends[i]), self._units, inclusive_end=True
            )
            if not over:
                self._prof.add(rid, bnd[i], val[i], int(starts[i]), int(rels[i]))
            admits.append(not over)
        return admits

    def _admit_device(self, request_ids, bnd, val, starts, ends, rels) -> list[bool]:
        from repro.sim.batch_engine import bucket_size, pad_rows
        from repro.sim.device_timeline import admission_program

        C = len(request_ids)
        live = bnd < (rels - starts)[:, None]
        sw = np.where(live, starts[:, None] + np.where(live, bnd, 0) + 1, NEVER)  # switch keys
        valext = np.concatenate([val, val[:, -1:]], axis=1)  # hold-last (C, k+1)
        times, _ = self._prof.arrays()

        # Shared probe set: all profile events + every candidate's start and
        # live switch keys, deduped (overlapping candidate boundaries repeat
        # heavily and would inflate the padded probe bucket) and padded to a
        # bucket so compiled shapes are bounded.
        P = shared_probe_set(times, starts, sw[live])
        Pp = bucket_size(len(P))
        prof_at_p = self._prof.demand_at(P)
        i32 = np.int32
        P = np.concatenate([P, np.full(Pp - len(P), NEVER)]).astype(i32)
        prof_at_p = np.concatenate([prof_at_p, np.zeros(Pp - len(prof_at_p), np.int64)]).astype(i32)
        Cp = bucket_size(C)
        args = (
            P,
            prof_at_p,
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
        admits = np.asarray(admission_program()(*args, i32(self._units)))[:C]

        adm = np.flatnonzero(admits)
        if len(adm):
            # add_many validates owners before touching anything, so a
            # duplicate id aborts with controller state clean
            self._prof.add_many(
                [request_ids[i] for i in adm], bnd[adm], val[adm], starts[adm], rels[adm]
            )
        return admits.tolist()

    def release(self, request_id: str) -> None:
        plan = self.active.pop(request_id, None)
        if plan is not None:
            self._static_reserved -= float(plan.alloc.values[-1])
            self._prof.remove(request_id)


# ---------------------------------------------------------------------------
# Sharded carried-timeline control plane
# ---------------------------------------------------------------------------


def shard_of(request_id: str, n_shards: int) -> int:
    """Deterministic request -> shard placement: crc32 of the id.  Python's
    ``hash`` is salted per process, which would re-deal every replay — crc32
    keeps placement (and therefore every per-shard decision sequence) a pure
    function of the request ids."""
    return zlib.crc32(str(request_id).encode()) % int(n_shards)


class ShardedScalarController(_AdmissionBase):
    """The per-shard oracle: ``n_shards`` independent scalar controllers.

    Each shard is one ``AdmissionController`` owning ``budget / n_shards``
    HBM; requests route by ``shard_of`` and all shards share ONE k-Segments
    model (predictions are global — only admission state is sharded).  This
    is the reference the carried-timeline engine is parity-tested against:
    shard independence means a sequential per-shard replay defines the
    sharded policy exactly.
    """

    def __init__(
        self, hbm_budget_mib: float, k: int = 4, interval_s: float = 0.5, n_shards: int = 4
    ):
        super().__init__(hbm_budget_mib, k, interval_s)
        self.n_shards = int(n_shards)
        self.shard_budget = self.budget / self.n_shards
        self._shards = [
            AdmissionController(self.shard_budget, k, interval_s) for _ in range(self.n_shards)
        ]
        for c in self._shards:
            c.model = self.model  # one shared predictor across shards

    def shard_of(self, request_id: str) -> int:
        return shard_of(request_id, self.n_shards)

    def try_admit(self, request_id: str, prompt_len: int, now: float) -> RequestPlan | None:
        plan = self._shards[self.shard_of(request_id)].try_admit(request_id, prompt_len, now)
        if plan is not None:
            self.active[request_id] = plan
            self._static_reserved += float(plan.alloc.values[-1])
        return plan

    def try_admit_many(self, request_ids, prompt_lens, now) -> list[RequestPlan | None]:
        ts = np.broadcast_to(np.asarray(now, dtype=np.float64), (len(request_ids),))
        return [
            self.try_admit(r, p, float(t)) for r, p, t in zip(request_ids, prompt_lens, ts)
        ]

    def release(self, request_id: str) -> None:
        plan = self.active.pop(request_id, None)
        if plan is not None:
            self._static_reserved -= float(plan.alloc.values[-1])
            self._shards[self.shard_of(request_id)].release(request_id)


class ShardedAdmissionController(_AdmissionBase):
    """Sharded admission on carried device timelines — the serving control
    plane that lives across thousands of decision batches.

    Same placement and per-shard policy as ``ShardedScalarController``
    (decision parity is exact — tests/test_serve_sharded.py), but nothing is
    rebuilt per batch: each shard's demand timeline, clock-folded base and
    per-owner fold sums persist as device arrays between calls, and one
    ``admission_epoch`` dispatch applies the queued releases, folds the
    clock forward and decides the whole batch for every shard at once
    (vmapped; ``shard_map`` across devices when more than one is visible).

    Host-side bookkeeping is O(batch): a free-list of per-shard owner codes
    (recycled only after a release is applied on device), the pending-release
    queues, and capacity management — the timeline axis L grows by padding
    (+inf tail keeps it sorted) sized from the device-reported live-event
    count BEFORE a batch could overflow, so the in-program overflow flag is a
    can't-happen guard (it triggers a host reseed from the active plan set
    plus a replay, counted in ``reseeds``).

    The batch clock must be non-decreasing across calls (folded events never
    come back) — arrival streams are monotone by construction; a regressing
    clock raises.
    """

    def __init__(
        self,
        hbm_budget_mib: float,
        k: int = 4,
        interval_s: float = 0.5,
        n_shards: int = 4,
        use_shard_map: bool | None = None,
    ):
        super().__init__(hbm_budget_mib, k, interval_s)
        import jax

        self.n_shards = int(n_shards)
        self.shard_budget = self.budget / self.n_shards
        self._units = int(budget_units(self.shard_budget))
        if use_shard_map is None:
            use_shard_map = jax.device_count() > 1
        # the mesh wants equal per-device shard slices: the largest divisor
        # of n_shards that the visible devices can carry
        self.n_dev = (
            max(d for d in range(1, min(jax.device_count(), self.n_shards) + 1) if self.n_shards % d == 0)
            if use_shard_map
            else 1
        )
        self._state = None  # (base0, tl_t, tl_d, tl_c, slot_fold) device arrays
        self._L = 64  # per-shard timeline axis (grows by padding)
        self._Smax = 64  # per-shard owner-code capacity (grows by padding)
        self._free: list[list[int]] = [[] for _ in range(self.n_shards)]
        self._next_slot = [0] * self.n_shards
        self._pending_rel: list[list[int]] = [[] for _ in range(self.n_shards)]
        self._code: dict[str, tuple[int, int]] = {}  # rid -> (shard, code)
        self._steps: dict[str, tuple[np.ndarray, np.ndarray, int]] = {}  # rid -> (b, v, start)
        self._evtimes: dict[str, np.ndarray] = {}  # rid -> event-key row (-1 padded)
        # event-time rows of queued releases: counted (vectorized) at the
        # next batch, against the clock they were released under
        self._pend_times: list[list[np.ndarray]] = [[] for _ in range(self.n_shards)]
        self._n_live = np.zeros(self.n_shards, dtype=np.int64)
        self._clock = -1
        self.reseeds = 0  # overflow-recovery reseeds (0 on healthy streams)

    # -- policy -------------------------------------------------------------

    def shard_of(self, request_id: str) -> int:
        return shard_of(request_id, self.n_shards)

    def _default_alloc(self) -> StepAllocation:
        # the placeholder scales with the SHARD budget: each shard's oracle
        # is a scalar controller over budget/n_shards, and parity requires
        # the same flat 5% reservation it would use
        return StepAllocation(np.asarray([1.0]), np.asarray([self.shard_budget * 0.05]))

    # -- device-state plumbing ----------------------------------------------

    def _place(self, *arrays):
        """Device arrays of the carried state: with a mesh, sharded over its
        devices along the leading shard axis (each device holds its own
        shards' timelines); otherwise on the default device."""
        import jax

        if self.n_dev == 1:
            return tuple(jax.numpy.asarray(a) for a in arrays)
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.compat import device_mesh

        sharding = NamedSharding(device_mesh(self.n_dev), PartitionSpec("shards"))
        return tuple(jax.device_put(a, sharding) for a in arrays)

    def _ensure_state(self):
        if self._state is not None:
            return
        S, L, Smax = self.n_shards, self._L, self._Smax
        self._state = self._place(
            np.zeros((S,), np.int32),
            np.full((S, L), NEVER, np.int32),
            np.zeros((S, L), np.int32),
            np.full((S, L), -1, np.int32),
            np.zeros((S, Smax), np.int32),
        )

    def _grow_L(self, new_L: int):
        base0, tl_t, tl_d, tl_c, slot_fold = (np.asarray(a) for a in self._state)
        S, pad = self.n_shards, new_L - self._L
        self._state = self._place(
            base0,
            np.concatenate([tl_t, np.full((S, pad), NEVER, np.int32)], axis=1),
            np.concatenate([tl_d, np.zeros((S, pad), np.int32)], axis=1),
            np.concatenate([tl_c, np.full((S, pad), -1, np.int32)], axis=1),
            slot_fold,
        )
        self._L = new_L

    def _grow_smax(self, new_smax: int):
        base0, tl_t, tl_d, tl_c, slot_fold = (np.asarray(a) for a in self._state)
        pad = new_smax - self._Smax
        self._state = self._place(
            base0,
            tl_t,
            tl_d,
            tl_c,
            np.concatenate([slot_fold, np.zeros((self.n_shards, pad), np.int32)], axis=1),
        )
        self._Smax = new_smax

    def _alloc_code(self, s: int) -> int:
        if self._free[s]:
            return self._free[s].pop()
        if self._next_slot[s] >= self._Smax:
            from repro.sim.traces import fine_bucket

            self._ensure_state()
            self._grow_smax(fine_bucket(self._Smax + 1, floor=64))
        code = self._next_slot[s]
        self._next_slot[s] += 1
        return code

    def _reseed(self, t0: int):
        """Rebuild the carried device state from the host plan set at key
        ``t0`` — the recovery path for in-program overflow (and the
        correctness anchor: the rebuilt state is exactly what the
        incremental splices maintain)."""
        S, L, Smax = self.n_shards, self._L, self._Smax
        base0 = np.zeros(S, np.int64)
        tl_t = np.full((S, L), NEVER, np.int32)
        tl_d = np.zeros((S, L), np.int32)
        tl_c = np.full((S, L), -1, np.int32)
        slot_fold = np.zeros((S, Smax), np.int64)
        counts = np.zeros(S, dtype=np.int64)
        per: list[list] = [[] for _ in range(S)]
        for rid in self.active:
            s, code = self._code[rid]
            b, v, start = self._steps[rid]
            t, d = plan_profile_events(b, v, start, start + b[-1] + 1)
            per[s].append((t, d, np.full(len(t), code, dtype=np.int32)))
        for s in range(S):
            if not per[s]:
                continue
            t = np.concatenate([e[0] for e in per[s]])
            d = np.concatenate([e[1] for e in per[s]])
            c = np.concatenate([e[2] for e in per[s]])
            order = np.argsort(t, kind="stable")
            t, d, c = t[order], d[order], c[order]
            cut = int(np.searchsorted(t, t0, side="right"))
            if cut:
                base0[s] = d[:cut].sum()
                np.add.at(slot_fold[s], c[:cut], d[:cut])
            nf = len(t) - cut
            if nf > L:
                raise RuntimeError("reseed needs a larger timeline axis (grow L first)")
            tl_t[s, :nf], tl_d[s, :nf], tl_c[s, :nf] = t[cut:], d[cut:], c[cut:]
            counts[s] = nf
        self._state = self._place(
            base0.astype(np.int32), tl_t, tl_d, tl_c, slot_fold.astype(np.int32)
        )
        self._n_live = counts
        # pending releases are already reflected (released rids left
        # ``active`` before this rebuild): their codes free immediately
        for s in range(S):
            self._free[s].extend(self._pending_rel[s])
            self._pending_rel[s] = []
        self._pend_times = [[] for _ in range(S)]
        self.reseeds += 1

    # -- admission ----------------------------------------------------------

    def try_admit(self, request_id: str, prompt_len: int, now: float) -> RequestPlan | None:
        return self.try_admit_many([request_id], [prompt_len], now)[0]

    def try_admit_many(self, request_ids, prompt_lens, now) -> list[RequestPlan | None]:
        from repro.sim.device_timeline import admission_epoch
        from repro.sim.traces import bucket_size, fine_bucket

        C = len(request_ids)
        if C == 0:
            return []
        bnd_s, val_s = self._predict_many(prompt_lens, C)
        now_s = np.broadcast_to(np.asarray(now, dtype=np.float64), (C,))
        bnd, val, starts = quantize_plan(bnd_s, val_s, now_s)
        t0 = int(starts[0])
        if t0 < self._clock:
            raise ValueError(
                f"batch clock regressed: {t0} < {self._clock} (folded events never return)"
            )
        ends, rels = _windows(starts, bnd)
        # the events a plan splices in (start + live switches + release): one
        # row per candidate, -1 where a switch never fires — at release, the
        # entries still above the clock (the unfolded ones) tighten the Lp
        # prefix of the following batches
        live_all = bnd < (rels - starts)[:, None]
        times_all = np.concatenate(
            [starts[:, None], np.where(live_all, starts[:, None] + np.where(live_all, bnd, 0) + 1, -1), rels[:, None]],
            axis=1,
        )
        S, k = self.n_shards, bnd.shape[1]
        shards = [self.shard_of(r) for r in request_ids]
        per: list[list[int]] = [[] for _ in range(S)]
        for i, s in enumerate(shards):
            per[s].append(i)
        self._ensure_state()
        codes = [self._alloc_code(s) for s in shards]
        # capacity: worst case ignores the batch's own releases/folds, so
        # growth (pure NEVER padding — the sorted tail) runs strictly ahead
        # of any possible in-program overflow
        need = max(
            int(self._n_live[s]) + (k + 2) * len(per[s]) for s in range(S)
        )
        if need > self._L:
            self._grow_L(fine_bucket(need, floor=64))
        # decision-prefix bucket: the probe tables only need the carried live
        # events, and the queued releases (whose per-plan event counts the
        # host tracks exactly) plus the fold only shrink the prefix below
        # last batch's returned n_live — the O(L) tail stays out of the
        # decision tensors (fine_bucket: the prefix is the hot axis).  A
        # released plan's events still in the timeline are the ones above
        # the clock (everything at or under it was folded at a prior t0);
        # -1 pads (dead switches) drop out
        pend_ev = [
            int((np.stack(rows) > self._clock).sum()) if rows else 0
            for rows in self._pend_times
        ]
        Lp_need = max(int(self._n_live[s]) - pend_ev[s] for s in range(S))
        Lp = min(self._L, fine_bucket(max(Lp_need, 1), floor=64))
        Cb = fine_bucket(max(len(p) for p in per), floor=8)
        Rb = bucket_size(max(max(len(q) for q in self._pending_rel), 1), floor=8)
        st_p = np.full((S, Cb), NEVER, dtype=np.int32)
        en_p = np.full((S, Cb), -1, dtype=np.int32)
        rl_p = np.full((S, Cb), -1, dtype=np.int32)
        bnd_p = np.full((S, Cb, k), NEVER, dtype=np.int32)
        val_p = np.zeros((S, Cb, k), dtype=np.int32)
        code_p = np.full((S, Cb), -1, dtype=np.int32)
        valid_p = np.zeros((S, Cb), dtype=bool)
        codes_np = np.asarray(codes, dtype=np.int32)
        for s in range(S):
            iv = per[s]
            n = len(iv)
            st_p[s, :n], en_p[s, :n], rl_p[s, :n] = starts[iv], ends[iv], rels[iv]
            bnd_p[s, :n], val_p[s, :n] = bnd[iv], val[iv]
            code_p[s, :n], valid_p[s, :n] = codes_np[iv], True
        rel_p = np.full((S, Rb), -1, dtype=np.int32)
        rel_lists, self._pending_rel = self._pending_rel, [[] for _ in range(S)]
        self._pend_times = [[] for _ in range(S)]
        for s in range(S):
            rel_p[s, : len(rel_lists[s])] = rel_lists[s]
        prog = admission_epoch(self.n_dev, Lp)
        batch = (st_p, en_p, rl_p, bnd_p, val_p, code_p, valid_p)
        scalars = (np.int32(t0), np.int32(self._units))
        admits, overflow, n_live, *state = prog(*self._state, rel_p, *batch, *scalars)
        if bool(np.asarray(overflow).any()):
            # can't-happen guard (growth pre-sizes L): rebuild from the host
            # plan set — the queued releases are already reflected there —
            # and replay this batch against the fresh state
            self._grow_L(fine_bucket(2 * self._L + (k + 2) * C, floor=64))
            self._reseed(t0)
            rel_lists = [[] for _ in range(S)]
            prog = admission_epoch(self.n_dev)  # replay probes the full axis
            admits, overflow, n_live, *state = prog(
                *self._state, np.full((S, Rb), -1, dtype=np.int32), *batch, *scalars
            )
            if bool(np.asarray(overflow).any()):
                raise RuntimeError("carried timeline overflowed after a reseed")
        self._state = tuple(state)
        self._n_live = np.asarray(n_live, dtype=np.int64)
        self._clock = t0
        for s in range(S):  # releases applied on device: codes recycle now
            self._free[s].extend(rel_lists[s])
        admits = np.asarray(admits)
        plans: list[RequestPlan | None] = []
        pos = [0] * S
        for i, rid in enumerate(request_ids):
            s = shards[i]
            j = pos[s]
            pos[s] += 1
            if bool(admits[s, j]):
                plan = RequestPlan(rid, float(now_s[i]), StepAllocation(bnd_s[i], val_s[i]))
                self.active[rid] = plan
                self._static_reserved += float(val_s[i, -1])
                self._code[rid] = (s, codes[i])
                self._steps[rid] = (bnd[i], val[i], int(starts[i]))
                self._evtimes[rid] = times_all[i]
                plans.append(plan)
            else:
                self._free[s].append(codes[i])  # rejected: code never went live
                plans.append(None)
        return plans

    def release(self, request_id: str) -> None:
        plan = self.active.pop(request_id, None)
        if plan is None:
            return
        self._static_reserved -= float(plan.alloc.values[-1])
        s, code = self._code.pop(request_id)
        del self._steps[request_id]
        # the code stays reserved until the release is applied on device —
        # recycling it earlier would let a newcomer's events alias a plan
        # still spliced into the carried timeline
        self._pending_rel[s].append(code)
        # events of this plan still in the carried timeline: everything at or
        # before the clock was folded at a previous batch (and is accounted
        # by slot_fold, not the event axis) — counting is deferred to the
        # next batch, which still sees the same clock value
        times = self._evtimes.pop(request_id, None)
        if times is not None:
            self._pend_times[s].append(times)
