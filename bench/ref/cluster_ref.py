"""Plain reference of the cluster scheduler: retry ladders and placement.

Written for the benchmark from the paper's description (Bader et al., Sec.
III and IV) and the semantics the program documents; it imports nothing of
the program.  Two layers, each checked on its own:

* ``ladder_rows`` — every queued execution's attempts under one policy, in
  float64 numpy: predict, score against the recorded series, retry on an
  out-of-memory kill, then learn from the execution.  Each attempt becomes a
  placement row in timeline units (1 ms ticks doubled into keys, whole MiB).
* ``place`` — first-fit placement of attempt rows on nodes, against the
  future step profile of each node, waiting on the earliest completion when
  no node fits.  A node's profile is rebuilt from its reservations whenever
  they change; nothing is updated in place.

``q`` rounds what a computation stores; the default keeps float64.  The
control passes a rounding to bfloat16 (see ``bench/control.py``).
"""

from __future__ import annotations

import heapq

import numpy as np

TICKS_PER_S = 1000
NEVER = 2**31 - 1
TICK_SNAP = 2.0**-10
UNIT_SNAP = 2.0**-11
MIB_PER_GIB = 1024.0


def _exact(x):
    return x


# -- units --------------------------------------------------------------------


def time_keys(seconds) -> np.ndarray:
    """Instants and step boundaries: ticks rounded down, doubled into keys."""
    s = np.asarray(seconds, dtype=np.float64)
    return 2 * np.floor(s * TICKS_PER_S + TICK_SNAP).astype(np.int64)


def span_keys(seconds) -> np.ndarray:
    """Run lengths: ticks rounded up, doubled into keys."""
    s = np.asarray(seconds, dtype=np.float64)
    return 2 * np.ceil(s * TICKS_PER_S - TICK_SNAP).astype(np.int64)


def demand_units(mib) -> np.ndarray:
    """Allocations: whole MiB, rounded up."""
    return np.ceil(np.asarray(mib, dtype=np.float64) - UNIT_SNAP).astype(np.int64)


# -- predictors ----------------------------------------------------------------


class _LinReg:
    """Least squares ``y ~ u`` from running sums; slope 0 when degenerate."""

    def __init__(self, width: int, q):
        self.s = np.zeros((width, 5))  # n, Su, Suu, Sy, Suy
        self.q = q

    def add(self, u: float, y) -> None:
        y = np.asarray(y, dtype=np.float64)
        self.s = self.q(self.s + np.stack([np.ones_like(y), u + 0 * y, u * u + 0 * y, y, u * y], axis=-1))

    def predict(self, u):
        n, su, suu, sy, suy = (self.s[:, i] for i in range(5))
        den = n * suu - su * su
        ok = np.abs(den) > 1e-9
        slope = np.where(ok, (n * suy - su * sy) / np.where(ok, den, 1.0), 0.0)
        icpt = np.where(n > 0, (sy - slope * su) / np.maximum(n, 1.0), 0.0)
        return self.q(icpt + slope * u)


def segment_peaks(y: np.ndarray, k: int) -> np.ndarray:
    """The paper's segmentation: k-1 segments of floor(j/k) samples, the last
    takes the rest; an empty segment repeats the peak before it."""
    j = len(y)
    i = max(j // k, 1)
    out = np.empty(k)
    prev = float(y[0])
    for s in range(k):
        lo = min(s * i, j)
        hi = j if s == k - 1 else min((s + 1) * i, j)
        if hi > lo:
            prev = float(np.max(y[lo:hi]))
        out[s] = prev
    return out


class Default:
    """The workflow's static directive; doubles on failure."""

    def __init__(self, default_mib, cap, q):
        self.default, self.cap, self.q = default_mib, cap, q

    def observe(self, x, y):
        pass

    def predict(self, x):
        return np.asarray([1.0]), np.asarray([self.default])

    def retry(self, b, v, seg):
        return b, self.q(np.asarray([min(v[-1] * 2.0, self.cap)]))


class WittLR(Default):
    """Witt et al.: peak ~ input size, plus the standard deviation of the
    residuals of the current fit over every observation."""

    def __init__(self, default_mib, cap, q):
        super().__init__(default_mib, cap, q)
        self.lr = _LinReg(1, q)
        self.u, self.peak = [], []
        self.x0 = None

    def observe(self, x, y):
        if self.x0 is None:
            self.x0 = x
        p = float(np.max(y))
        self.lr.add(x - self.x0, [p])
        self.u.append(x - self.x0)
        self.peak.append(p)

    def predict(self, x):
        if self.x0 is None:
            return super().predict(x)
        e = np.asarray(self.peak) - self.lr.predict(np.asarray(self.u)[:, None])[:, 0]
        off = float(e.std()) if len(e) >= 2 else 0.0
        v = float(self.lr.predict(x - self.x0)[0]) + off
        return np.asarray([1.0]), self.q(np.asarray([max(v, 100.0)]))


class PPMImproved(Default):
    """Tovar et al.'s peak-probability sizing with doubling retries: the first
    allocation is the observed peak that minimises the expected wastage over
    the observed executions, each weighted by its run length.  Every observed
    peak is a candidate."""

    def __init__(self, default_mib, cap, q):
        super().__init__(default_mib, cap, q)
        self.peaks, self.lens = [], []

    def observe(self, x, y):
        self.peaks.append(float(np.max(y)))
        self.lens.append(float(len(y)))

    def predict(self, x):
        if not self.peaks:
            return super().predict(x)
        q = self.q
        order = np.argsort(self.peaks, kind="stable")
        p = q(np.asarray(self.peaks)[order])
        rt = np.asarray(self.lens)[order]
        n = len(p)
        C, S = q(np.cumsum(rt)), q(np.cumsum(p * rt))
        last = np.flatnonzero(np.diff(p, append=np.inf) > 0)  # last index of each distinct peak
        cand = p[last]
        ok = cand * C[last] - S[last]  # successes waste (cand - p) * rt
        # failures climb the doubling ladder cand * 2^d (capped); an
        # execution that first fits at level a wastes (2a - cand - p) * rt
        bad = np.zeros_like(cand)
        lo = last + 1
        a = cand.copy()
        live = lo < n
        while live.any():
            a = np.where(live, np.minimum(a * 2.0, self.cap), a)
            hi = np.searchsorted(p, a, side="right")
            at_cap = a >= self.cap
            hi = np.where(at_cap, np.maximum(hi, lo + 1), hi)
            got = live & (hi > lo)
            h, l0 = np.clip(hi - 1, 0, n - 1), np.clip(lo - 1, 0, n - 1)
            add = (2.0 * a - cand) * (C[h] - C[l0]) - (S[h] - S[l0])
            bad = np.where(got, bad + add, bad)
            lo = np.where(got, hi, lo)
            live = live & (lo < n) & ~at_cap
        best = int(np.argmin(q(ok + bad)))
        return np.asarray([1.0]), np.asarray([max(float(cand[best]), 100.0)])


class KSegments:
    """k-Segments (paper Sec. III) with progressive offsets: runtime ~ input
    size offset down by the largest one-step-ahead overprediction so far,
    each segment's peak ~ input size offset up by its largest
    underprediction; a failure doubles the failed segment only."""

    def __init__(self, default_mib, cap, q, k=4, interval_s=2.0, floor=100.0, factor=2.0):
        self.default, self.cap, self.q = default_mib, cap, q
        self.k, self.dt, self.floor, self.factor = k, interval_s, floor, factor
        self.rt = _LinReg(1, q)
        self.seg = _LinReg(k, q)
        self.rt_over = 0.0
        self.seg_under = np.zeros(k)
        self.x0 = None

    def observe(self, x, y):
        runtime = len(y) * self.dt
        peaks = segment_peaks(np.asarray(y, dtype=np.float64), self.k)
        if self.x0 is None:
            self.x0 = x
        else:
            u = x - self.x0
            self.rt_over = float(self.q(max(self.rt_over, float(self.rt.predict(u)[0]) - runtime)))
            self.seg_under = self.q(np.maximum(self.seg_under, peaks - self.seg.predict(u)))
        u = x - self.x0
        self.rt.add(u, [runtime])
        self.seg.add(u, peaks)

    def predict(self, x):
        if self.x0 is None:
            return np.asarray([1.0]), np.asarray([self.default])
        u = x - self.x0
        r_e = max(float(self.rt.predict(u)[0]) - max(self.rt_over, 0.0), self.dt)
        b = np.arange(1, self.k + 1) * (r_e / self.k)
        b[-1] = r_e
        v = self.seg.predict(u) + np.maximum(self.seg_under, 0.0)
        if v[0] < 0:
            v[0] = self.floor
        v = np.maximum(np.maximum.accumulate(v), self.floor)
        return self.q(b), self.q(v)

    def retry(self, b, v, seg):
        v = v.copy()
        v[seg] = v[seg] * self.factor
        return b, self.q(np.minimum(np.maximum.accumulate(v), self.cap))


POLICIES = {
    "default": Default,
    "witt-lr": WittLR,
    "ppm-improved": PPMImproved,
    "ksegments-selective": KSegments,
}


def queue_of(workflows, train_frac: float, max_tasks_per_type: int, min_executions: int):
    """(tasks, queue): the task types with enough executions, and the queued
    (task, execution index) pairs in arrival order — each type's executions
    after its first ``train_frac`` share, capped at ``max_tasks_per_type``."""
    tasks, queue = [], []
    for wf in workflows:
        for t in wf.tasks:
            if len(t.executions) < min_executions:
                continue
            n_train = int(len(t.executions) * train_frac)
            tasks.append((t, n_train))
            queue += [(t, i) for i in range(n_train, min(len(t.executions), n_train + max_tasks_per_type))]
    return tasks, queue


def ladder_rows(workflows, policy: str, node_mib: float, train_frac: float, max_tasks_per_type: int,
                min_executions: int, q=_exact):
    """One policy's attempt rows for the whole backlog.

    Returns ``(rows, attempts, wastage)``: rows is a list of ``(boundary keys,
    value units, run keys, probe keys)`` in queue and attempt order (run =
    time on the node, up to the kill sample for a failed attempt; probe = the
    execution's whole length, the window a scheduler has to fit); attempts
    and wastage (GiB*s, float64) per queued execution."""
    tasks, queue = queue_of(workflows, train_frac, max_tasks_per_type, min_executions)
    models = {}
    for t, n_train in tasks:
        m = POLICIES[policy](t.default_mib, node_mib, q)
        for e in t.executions[:n_train]:
            m.observe(e.input_size, e.series)
        models[id(t)] = m
    rows, attempts, wastage = [], [], []
    for t, i in queue:
        e, m = t.executions[i], models[id(t)]
        y = np.asarray(e.series, dtype=np.float64)
        mid = (np.arange(len(y)) + 0.5) * t.interval_s
        probe = int(span_keys(len(y) * t.interval_s))
        b, v = m.predict(e.input_size)
        v = np.minimum(v, node_mib)
        n, waste = 0, 0.0
        while True:
            n += 1
            a = v[np.minimum(np.searchsorted(b, mid, side="left"), len(b) - 1)]
            over = y > a
            fail = int(np.argmax(over)) if over.any() else -1
            if fail >= 0:
                waste += float(np.sum(a[: fail + 1]) * t.interval_s) / MIB_PER_GIB
                run = int(span_keys((fail + 1) * t.interval_s))
            else:
                waste += float(np.sum(a - y) * t.interval_s) / MIB_PER_GIB
                run = probe
            rows.append((time_keys(b), demand_units(v), run, probe))
            if fail < 0:
                break
            if n > 64:
                raise RuntimeError(f"{t.name}#{i}: no allocation fits")
            seg = int(min(np.searchsorted(b, (fail + 0.5) * t.interval_s, side="left"), len(b) - 1))
            b, v = m.retry(b, v, seg)
            v = np.minimum(v, node_mib)
        m.observe(e.input_size, e.series)
        attempts.append(n)
        wastage.append(waste)
    return rows, np.asarray(attempts), np.asarray(wastage)


# -- placement -----------------------------------------------------------------


def _step_value(b: np.ndarray, v: np.ndarray, off: np.ndarray) -> np.ndarray:
    """A step allocation's value at key offsets from its start: a step at
    boundary b holds from b + 1 (steps are right-open; boundaries sorted)."""
    return v[np.minimum(np.searchsorted(b, off, side="left"), len(v) - 1)]


class _Node:
    """One node's reservations: start and end keys (R,), boundary keys and
    value units (R, k).  Their sum is read from a cumulative profile — each
    reservation's first value at its start, each step at boundary + 1 while
    it runs, its last value off at its end, sorted by key and summed —
    rebuilt from the reservations whenever they change."""

    def __init__(self, k: int, q):
        self.s = np.zeros(0, dtype=np.int64)
        self.e = np.zeros(0, dtype=np.int64)
        self.b = np.zeros((0, k), dtype=np.int64)
        self.v = np.zeros((0, k), dtype=np.int64)
        self.q = q
        self._prof = None

    def add(self, s: int, e: int, b: np.ndarray, v: np.ndarray) -> None:
        self.s = np.append(self.s, s)
        self.e = np.append(self.e, e)
        self.b = np.vstack([self.b, b[None]])
        self.v = np.vstack([self.v, v[None]])
        self._prof = None

    def expire(self, now: int) -> None:
        keep = self.e > now
        if not keep.all():
            self.s, self.e, self.b, self.v = self.s[keep], self.e[keep], self.b[keep], self.v[keep]
            self._prof = None

    def _profile(self):
        if self._prof is None:
            s, e, b, v = self.s, self.e, self.b, self.v
            live = b < (e - s)[:, None]
            steps = np.concatenate([np.diff(v, axis=1), np.zeros((len(v), 1), np.int64)], axis=1)
            v_end = np.take_along_axis(np.concatenate([v, v[:, -1:]], axis=1), live.sum(1)[:, None], axis=1)[:, 0]
            t = np.concatenate([s, (s[:, None] + b + 1)[live], e])
            d = np.concatenate([v[:, 0], steps[live], -v_end])
            order = np.argsort(t, kind="stable")
            self._prof = (t[order], self.q(np.concatenate([[0], np.cumsum(d[order])]).astype(np.float64)))
        return self._prof

    def fits(self, b, v, start: int, length: int, budget: int) -> bool:
        """Does the node's demand plus the candidate stay within budget at
        every key of [start, start + length)?  The sum of step functions can
        only rise at the window's start, at the candidate's steps and at the
        node's events inside the window; it is read at all of them."""
        end = start + length
        times, cum = self._profile()
        inside = times[(times > start) & (times < end)]
        p = np.concatenate([[start], start + b[b < length] + 1, inside])
        total = self.q(cum[np.searchsorted(times, p, side="right")] + _step_value(b, v, p - start))
        return bool(np.all(total <= budget))


def _pad(b, v, k):
    """Pad a row to k steps: boundaries that never come, the last value held."""
    n = len(b)
    return np.concatenate([b, np.full(k - n, NEVER)]), np.concatenate([v, np.full(k - n, v[-1])])


def place(rows, n_nodes: int, budget: int, q=_exact):
    """First-fit placement of attempt rows in order.  A row is fit-checked
    over its probe window from the clock and occupies its node for its run;
    when no node fits, the clock moves to the next completion.  Returns
    (node, start key, end key) per row."""
    k = max(len(r[0]) for r in rows)
    nodes = [_Node(k, q) for _ in range(n_nodes)]
    done: list[int] = []  # completion keys
    now, expired = 0, -1
    out = np.empty((len(rows), 3), dtype=np.int64)
    for r, (b, v, run, probe) in enumerate(rows):
        b, v = _pad(np.asarray(b, dtype=np.int64), np.asarray(v, dtype=np.int64), k)
        while True:
            if now != expired:
                for nd in nodes:
                    nd.expire(now)
                expired = now
            hit = next((i for i, nd in enumerate(nodes) if nd.fits(b, v, now, int(probe), budget)), None)
            if hit is not None:
                break
            now = max(now, heapq.heappop(done)) if done else now + 2 * TICKS_PER_S
        end = now + int(run)
        nodes[hit].add(now, end, b, v)
        heapq.heappush(done, end)
        out[r] = hit, now, end
    return out
