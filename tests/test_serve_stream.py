"""Serving-stream bookkeeping regressions.

Two classes of bug are pinned here:

* Eviction leaks: an evicted request used to leave its ``info`` entry and
  ``evicted_ids`` tombstone alive forever, and its stale finish event was
  popped with a bare ``continue`` — skipping the makespan update and the
  eviction recheck that every other event performs.  A drained loop must end
  with every bookkeeping map empty.
* Warmup/serving RNG coupling: both series used to draw from one shared
  generator, so changing ``n_warmup`` perturbed every serving arrival.  The
  serving stream must be a function of the seed alone.
"""

import numpy as np
import pytest

from repro.serve.stream import StreamConfig, _actual_usage, generate_arrivals, run_stream


def _bursty_cfg(**kw):
    base = dict(
        n_requests=120,
        n_warmup=24,
        rate_per_s=8.0,
        arrival="bursty",
        burst_factor=8.0,
        hbm_budget_mib=20_000.0,
        growth_mib_per_step=8.0,
        seed=2,
    )
    base.update(kw)
    return StreamConfig(**base)


def _underpredicted(cfg):
    """Serve series 3x the learned footprint: forces the OOM backstop."""
    warm, arrivals = generate_arrivals(cfg)
    for a in arrivals:
        a.series = a.series * 3.0
    return warm, arrivals


def test_bursty_stream_ends_with_empty_bookkeeping():
    """Long bursty stream with evictions: live/info/plans/evicted_ids all
    drain to empty — evicted requests are fully cleaned up, at eviction time
    and at their stale finish events."""
    cfg = _bursty_cfg()
    state: dict = {}
    res = run_stream(cfg, "batched", arrivals=_underpredicted(cfg), debug_state=state)
    assert res.evicted > 0  # the regression is only meaningful under eviction
    assert res.finished > 0
    assert state["live"] == {}
    assert state["info"] == {}
    assert state["plans"] == {}
    assert state["evicted_ids"] == set()


def test_clean_stream_ends_with_empty_bookkeeping():
    cfg = _bursty_cfg(hbm_budget_mib=200_000.0)
    state: dict = {}
    res = run_stream(cfg, "scalar", debug_state=state)
    assert res.evicted == 0 and res.finished > 0
    assert state["live"] == {} and state["info"] == {} and state["plans"] == {}
    assert state["evicted_ids"] == set()


def test_stale_finish_advances_makespan_and_rechecks_eviction():
    """The stale-event path participates in time accounting: makespan covers
    every popped event time, evicted or not, on both engines."""
    cfg = _bursty_cfg()
    pair = _underpredicted(cfg)
    res = run_stream(cfg, "batched", arrivals=pair)
    # every admitted request's scheduled finish is a lower bound on makespan:
    # finish events of evicted requests are popped too, and must advance it
    warm, arrivals = pair
    admitted = {rid for rid, ok in res.decisions if ok}
    latest = max(a.t + len(a.series) * cfg.interval_s for a in arrivals if a.request_id in admitted)
    assert res.makespan_s >= latest - 1e-9


def test_serving_stream_independent_of_warmup_count():
    """Changing n_warmup resizes the warmup set only: serving arrivals are
    identical in times, prompt lengths, and replayed series."""
    streams = {}
    for nw in (0, 16, 48):
        warm, arrivals = generate_arrivals(StreamConfig(n_warmup=nw, seed=5))
        assert len(warm) == nw
        streams[nw] = arrivals
    ref = streams[48]
    for nw in (0, 16):
        got = streams[nw]
        assert len(got) == len(ref)
        for a, b in zip(got, ref):
            assert a.t == b.t and a.prompt_len == b.prompt_len
            np.testing.assert_array_equal(a.series, b.series)


def test_warmup_deterministic_prefix():
    """Warmup draws are a deterministic prefix: growing n_warmup only
    appends, never reshuffles."""
    small, _ = generate_arrivals(StreamConfig(n_warmup=8, seed=5))
    large, _ = generate_arrivals(StreamConfig(n_warmup=24, seed=5))
    for a, b in zip(small, large[:8]):
        assert a.prompt_len == b.prompt_len
        np.testing.assert_array_equal(a.series, b.series)


def _brute_force_kills(live, t, interval_s, budget):
    """The pre-vectorization backstop, verbatim: recompute the O(live) total
    on every kill iteration (O(live^2)) — the parity oracle for the
    single-pass evictor now in run_stream."""
    live = dict(live)
    kills = []
    while live and _actual_usage(live, t, interval_s) > budget:
        rid = max(live, key=lambda r: (live[r][0], r))
        live.pop(rid)
        kills.append(rid)
    return kills


def _vectorized_kills(live, t, interval_s, budget):
    """The run_stream evictor's algorithm: gather usage once, re-total
    incrementally per pop."""
    usage = {
        rid: float(series[min(max(int((t - start) / interval_s), 0), len(series) - 1)])
        for rid, (start, series) in live.items()
    }
    total = float(np.asarray(list(usage.values())).sum())
    kills = []
    for rid in sorted(live, key=lambda r: (live[r][0], r), reverse=True):
        if total <= budget:
            break
        total -= usage[rid]
        kills.append(rid)
    return kills


def test_evictor_matches_brute_force():
    """Property check over random live sets: the single-pass evictor kills
    exactly the requests the quadratic reference would, in the same order,
    across budgets from kill-nothing to kill-everything."""
    rng = np.random.default_rng(9)
    for trial in range(40):
        n = int(rng.integers(1, 30))
        live = {
            f"r{i}": (
                float(rng.uniform(0.0, 50.0)),
                (rng.uniform(100.0, 4000.0) + 8.0 * np.arange(int(rng.integers(4, 120)))).astype(
                    np.float32
                ),
            )
            for i in range(n)
        }
        t = float(rng.uniform(0.0, 80.0))
        total = _actual_usage(live, t, 1.0)
        for budget in (total * 1.1, total * 0.6, total * 0.2, 0.0):
            assert _brute_force_kills(live, t, 1.0, budget) == _vectorized_kills(
                live, t, 1.0, budget
            ), (trial, budget)


def test_high_eviction_stream_decision_parity():
    """End to end under an eviction storm (tiny budget, 5x underprediction):
    engines agree decision for decision and kill for kill — the vectorized
    backstop changed complexity, not policy."""
    cfg = _bursty_cfg(hbm_budget_mib=12_000.0)
    warm, arrivals = generate_arrivals(cfg)
    for a in arrivals:
        a.series = a.series * 5.0
    rs = run_stream(cfg, "scalar", arrivals=(warm, arrivals))
    rb = run_stream(cfg, "batched", arrivals=(warm, arrivals))
    assert rs.decisions == rb.decisions
    assert rs.evicted == rb.evicted
    assert rs.evicted > 10  # a storm, not a stray kill
    assert rs.finished == rb.finished


def test_empty_stream_reports_nan_latency():
    """No decisions -> no measurement: percentiles are nan and throughput is
    zero, never a fabricated 0.0-latency sample."""
    res = run_stream(StreamConfig(n_requests=0, n_warmup=4), "batched")
    assert np.isnan(res.p50_latency_s) and np.isnan(res.p99_latency_s)
    assert res.decisions_per_s == 0.0
    assert np.isnan(res.slo["violation_frac"]) and res.slo["violations"] == 0


def test_nonempty_stream_reports_finite_latency_and_slo():
    res = run_stream(StreamConfig(n_requests=40, n_warmup=8), "batched")
    assert np.isfinite(res.p50_latency_s) and np.isfinite(res.p99_latency_s)
    assert res.decisions_per_s > 0
    assert 0.0 <= res.slo["violation_frac"] <= 1.0
    assert res.shards is None  # single-host engines report no shard rows


def test_diurnal_arrivals_deterministic_and_modulated():
    """The diurnal mix is reproducible in the seed and actually modulates:
    inter-arrival gaps at the peak phase run shorter than at the trough."""
    cfg = StreamConfig(arrival="diurnal", n_requests=600, rate_per_s=4.0, diurnal_amp=0.9, seed=3)
    _, a1 = generate_arrivals(cfg)
    _, a2 = generate_arrivals(cfg)
    assert [x.t for x in a1] == [x.t for x in a2]
    ts = np.asarray([x.t for x in a1])
    gaps = np.diff(ts)
    phase = (ts[:-1] % cfg.diurnal_period_s) / cfg.diurnal_period_s
    peak = gaps[(phase > 0.15) & (phase < 0.35)]  # sin ~ +1: fastest arrivals
    trough = gaps[(phase > 0.65) & (phase < 0.85)]  # sin ~ -1: slowest
    assert peak.mean() < 0.5 * trough.mean()


def test_diurnal_amp_validated():
    with pytest.raises(ValueError):
        generate_arrivals(StreamConfig(arrival="diurnal", diurnal_amp=1.0, n_requests=1))


def test_batched_decisions_each_wait_the_whole_dispatch(monkeypatch):
    """A batch of n decisions is known only when its dispatch returns, so it
    gives n latencies each equal to the dispatch's wall, per shard too."""
    import types

    import repro.serve.stream as stream

    wall = 0.004
    now = [0.0]
    monkeypatch.setattr(stream, "time", types.SimpleNamespace(perf_counter=lambda: now[0]))

    class Clocked:
        """Rejects every candidate (so nothing finishes and the whole stream
        is one batch), taking ``wall`` of the fake clock per dispatch."""

        n_shards = 2

        def shard_of(self, rid):
            return int(rid[1:]) % 2

        def observe(self, *a):
            pass

        def try_admit_many(self, ids, plens, ts):
            now[0] += wall
            return [None] * len(ids)

        def release(self, rid):
            pass

        def reservation_wastage(self, plans):
            return {}

    cfg = StreamConfig(n_requests=12, n_warmup=0, batch_window_s=1e9)
    res = run_stream(cfg, "batched", controller=Clocked())
    assert len(res.decisions) == 12 and res.rejected == 12
    assert res.wall_s == pytest.approx(wall)
    assert res.p50_latency_s == res.p99_latency_s == pytest.approx(wall)
    assert res.slo["violations"] == 12  # each of the 12 waited 4 ms against a 2 ms target
    assert [s["decisions"] for s in res.shards] == [6, 6]
    assert all(s["p50_latency_s"] == s["p99_latency_s"] == pytest.approx(wall) for s in res.shards)
