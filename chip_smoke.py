#!/usr/bin/env python3
"""On-chip smoke test: the system's main path, once, on a TPU.

    python chip_smoke.py               # one chip: predictor, cluster, serve
    python chip_smoke.py --four-chips  # four chips: sharded serving only

Phases, in order, all through the normal entry points:

1. **Device check** — prints the platform, device kind and count; exits
   non-zero unless JAX's devices are TPUs (no phase runs on the CPU).
2. **Predictor** — the batch engine's fig7 grid (``simulate_grid``) on the
   full synthetic suite (sarek + eager at ``scale=1.0``, every task type:
   no cap and no floor on executions per type),
   the benchmark's 8 methods at training fractions 0.25/0.5/0.75, checked
   against the sequential engine the way the ``fig7a`` bench does (mean
   wastage per (method, fraction) within 5%).
3. **Cluster** — ``run_cluster_batched`` on the same full corpus (every
   queued execution, ``max_tasks_per_type=1512``), 16 nodes of 128 GiB, the
   benchmark's 4 policies, with ``placement="auto"`` and ``"sweep"``; every
   attempt's (node, start, end) must equal the sequential oracle's placement
   of the same attempt rows, and ``run_cluster``'s own (float64 numpy
   predictions) up to the first row whose allocation the chip's float32
   ladder rounds to other units (see ``phase_cluster``).
4. **Serve** — one ``run_stream`` through the ``sharded`` engine (carried
   device timelines, ``admission_epoch``), sized to keep over 1,024
   requests live at its peak; every decision must equal ``sharded-scalar``'s.

Before the last line each phase prints its sizes, compile seconds (backend
compiles summed, concurrent ones included), cold and warm seconds, device
dispatches, sweep lanes replayed through the windows engine
and the Pallas kernels it lowered (with their mode: "compiled" on a TPU).
The last line is one JSON object: ``{"ok": true, "device": {...}}``.  Any
failed phase or parity mismatch raises, so the exit code is non-zero.

``--four-chips`` runs only the serve phase, with its 4 shards on 4 chips
(``jax.shard_map``), against the same oracle, and checks that the carried
state is laid out over all four devices.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

POLICIES = ("default", "witt-lr", "ppm-improved", "ksegments-selective")
METHODS = (
    "default",
    "witt-lr",
    "ppm",
    "ppm-improved",
    "ksegments-selective",
    "ksegments-partial",
    "sizey",
    "ksplus",
)
FRACS = (0.25, 0.5, 0.75)


class PhaseFailed(AssertionError):
    """A phase's output disagreed with its reference."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def _report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def _kernels_since(before: dict) -> dict:
    """Kernel -> mode of the Pallas kernels lowered since ``before`` (a
    ``kernel_traces`` snapshot)."""
    from repro.kernels.ops import kernel_traces

    return {k: m for (k, m), n in sorted(kernel_traces().items()) if n > before.get((k, m), 0)}


def _timed(fn):
    """(result, cold seconds, backend-compile seconds) of one call."""
    from repro.analysis.trace_audit import CompileCounter

    with CompileCounter() as cc:
        t0 = time.perf_counter()
        out = fn()
        cold = time.perf_counter() - t0
    return out, cold, cc.compile_s


def phase_predictor(wfs) -> None:
    from repro.core.ksegments import KSegmentsConfig
    from repro.kernels.ops import kernel_traces
    from repro.sim import simulate_suite
    from repro.sim.batch_engine import simulate_grid
    from repro.sim.simulator import SimConfig, fig7a_mean_wastage

    # the fig7 bench's grid on every task type of the suite (no floor on
    # executions per type), insample offsets over a 64-execution window
    cfg = SimConfig(
        min_executions=1, ksegments=KSegmentsConfig(error_mode="insample", insample_window=64)
    )
    tasks = [t for wf in wfs for t in wf.eligible_tasks(cfg.min_executions)]
    before = kernel_traces()
    _, cold, compile_s = _timed(lambda: simulate_grid(wfs, METHODS, FRACS, cfg))
    stats: dict = {}
    t0 = time.perf_counter()
    res_b = simulate_grid(wfs, METHODS, FRACS, cfg, grid_stats=stats)
    warm = time.perf_counter() - t0
    _check(stats["program_calls"] > 0, "predictor: no device dispatch")
    t0 = time.perf_counter()
    res_py = simulate_suite(wfs, METHODS, FRACS, cfg)
    seq = time.perf_counter() - t0
    w_b, w_py = fig7a_mean_wastage(res_b), fig7a_mean_wastage(res_py)
    worst = 0.0
    for frac in FRACS:
        for m in METHODS:
            wb, wp = w_b[(m, frac)], w_py[(m, frac)]
            worst = max(worst, abs(wb - wp) / max(abs(wp), 1e-12))
            _check(
                bool(abs(wb - wp) <= 1e-2 + 0.05 * abs(wp)),
                f"fig7a {m}@{frac}: batch {wb:.3f} vs sequential {wp:.3f} GiB*s",
            )
    _report(
        "predictor",
        suite_task_types=sum(len(wf.tasks) for wf in wfs),
        suite_executions=sum(t.n_executions for wf in wfs for t in wf.tasks),
        task_types=len(tasks),
        executions=sum(t.n_executions for t in tasks),
        methods=len(METHODS),
        fractions=len(FRACS),
        grid_cells=len(res_b),
        compile_s=f"{compile_s:.2f}",
        cold_s=f"{cold:.2f}",
        warm_s=f"{warm:.2f}",
        sequential_s=f"{seq:.2f}",
        dispatches=stats["program_calls"],
        lanes_replayed="n/a (no sweep)",
        kernels=json.dumps(_kernels_since(before)),
        fig7a_parity=f"{len(FRACS) * len(METHODS)} cells within rtol 0.05 (worst {worst!r})",
    )


def _row_keys(rows) -> list:
    """Each attempt row as a comparable key: its step function (first value
    unit, then (boundary key, new value unit) at every change), run and
    probe keys.  Padding steps that change nothing drop out, so the device's
    k-padded rows compare with the oracle's ragged ones."""
    out = []
    for b, v, run, probe in zip(*rows[:4]):
        b, v = np.asarray(b), np.asarray(v)
        ch = np.flatnonzero(np.diff(v))
        out.append((int(v[0]), tuple(zip(b[ch].tolist(), v[ch + 1].tolist())), int(run), int(probe)))
    return out


def _flat(result) -> list:
    return [pl for rec in result.records for pl in rec.placements]


def rows_differing(rows, host_rows) -> tuple[int, int]:
    """(index of the first attempt row whose units differ from the numpy
    oracle's, count of differing rows) for one policy's batched rows."""
    kc, kh = _row_keys(rows), _row_keys(host_rows)
    diffs = [i for i, (a, b) in enumerate(zip(kc, kh)) if a != b]
    first = diffs[0] if diffs else min(len(kc), len(kh))
    return first, len(diffs) + abs(len(kc) - len(kh))


def phase_cluster(wfs) -> None:
    """Placement on the chip is checked in two layers, as the repo's tests do.

    * Every attempt's (node, start, end) equals the oracle's own placement
      loop (``place_rows``, the placement half of ``run_cluster``) over the
      same attempt rows — the decisions the device programs own.
    * Against ``run_cluster`` with its float64 numpy predictors, every
      attempt up to the first row whose allocation differs in units from the
      chip's float32 ladder must agree (placement is causal: identical rows
      so far mean identical decisions).  Past that row the schedules may
      part; how many records still agree is printed.  The default policy
      predicts nothing, so its rows never differ: its wastage (the paper's
      metric, scored on the host in float64 by both engines) must equal
      ``run_cluster``'s bit for bit.

    The reference is changed in one respect, for this phase only: the numpy
    PPM's ``TovarPPM.MAX_CANDIDATES`` cap is lifted to match the batch
    engine's rule (every observed peak is a candidate), and restored after.
    """
    from repro.core.baselines import TovarPPM
    from repro.core.ksegments import KSegmentsConfig
    from repro.core.timeline import key_seconds
    from repro.kernels.ops import kernel_traces
    from repro.sim.cluster import batched_rows, oracle_rows, place_rows, run_cluster, run_cluster_batched

    n_nodes, node_mib = 16, 128 * 1024.0
    rows_kw = dict(node_mib=node_mib, max_tasks_per_type=1512, train_frac=0.5)
    kw = dict(n_nodes=n_nodes, **rows_kw)
    kcfg = KSegmentsConfig(error_mode="progressive")
    # A reference change: the batch engine's PPM weighs every observed peak;
    # the numpy PPM caps its candidates at MAX_CANDIDATES quantiles for
    # speed, which only matters past that many distinct peaks — as at this
    # size.  The oracle runs with the engine's rule so that parity is
    # decision for decision.
    cap = TovarPPM.MAX_CANDIDATES
    TovarPPM.MAX_CANDIDATES = 1 << 30
    try:
        t0 = time.perf_counter()
        oracle = {p: run_cluster(wfs, p, ksegments_config=kcfg, **kw) for p in POLICIES}
        oracle_s = time.perf_counter() - t0
        host_rows = {p: oracle_rows(wfs, p, ksegments_config=kcfg, **rows_kw)[1] for p in POLICIES}
    finally:
        TovarPPM.MAX_CANDIDATES = cap
    _, chip_rows = batched_rows(wfs, POLICIES, ksegments_config=kcfg, **rows_kw)
    given_rows, first_diff, rows_differ = {}, {}, {}
    for p in POLICIES:
        node, start, end = place_rows(*chip_rows[p][:4], n_nodes, node_mib)
        given_rows[p] = list(zip(node.tolist(), key_seconds(start).tolist(), key_seconds(end).tolist()))
        first_diff[p], rows_differ[p] = rows_differing(chip_rows[p], host_rows[p])
        d = first_diff[p]
        _check(
            given_rows[p][:d] == _flat(oracle[p])[:d],
            f"{p}: place_rows disagrees with run_cluster on identical rows",
        )
    for placement in ("auto", "sweep"):
        before = kernel_traces()
        stats: dict = {}
        _, cold, compile_s = _timed(
            lambda: run_cluster_batched(wfs, POLICIES, placement=placement, placement_stats=stats, **kw)
        )
        warm_stats: dict = {}
        t0 = time.perf_counter()
        res = run_cluster_batched(wfs, POLICIES, placement=placement, placement_stats=warm_stats, **kw)
        warm = time.perf_counter() - t0
        attempts, records_equal, records, worst = 0, 0, 0, 0.0
        for p in POLICIES:
            got = _flat(res[p])
            _check(res[p].tasks_run == oracle[p].tasks_run > 0, f"{placement}/{p}: task counts differ")
            # the paper's metric, as the fig7a check reads it
            w_chip, w_ref = res[p].wastage_gib_s, oracle[p].wastage_gib_s
            worst = max(worst, abs(w_chip - w_ref) / max(abs(w_ref), 1e-12))
            _check(
                abs(w_chip - w_ref) <= 1e-2 + 0.05 * abs(w_ref),
                f"{placement}/{p}: wastage {w_chip:.3f} vs run_cluster {w_ref:.3f} GiB*s",
            )
            if p == "default":
                _check(w_chip == w_ref, f"{placement}/default: wastage {w_chip!r} != run_cluster {w_ref!r}")
            for r, (g, want) in enumerate(zip(got, given_rows[p])):
                _check(g == want, f"{placement}/{p}: attempt row {r} placed {g}, oracle loop {want}")
            _check(len(got) == len(given_rows[p]), f"{placement}/{p}: attempt counts differ")
            d = first_diff[p]
            _check(got[:d] == _flat(oracle[p])[:d], f"{placement}/{p}: differs from run_cluster before row {d}")
            attempts += len(got)
            records += len(res[p].records)
            records_equal += sum(
                rs.placements == rb.placements for rs, rb in zip(oracle[p].records, res[p].records)
            )
        _check(warm_stats["program_calls"] > 0, f"{placement}: no device dispatch")
        _check(warm_stats["waits_host"] == 0, f"{placement}: host-resolved waits")
        _report(
            f"cluster/{placement}",
            nodes=n_nodes,
            node_gib=128,
            policies=len(POLICIES),
            tasks=oracle[POLICIES[0]].tasks_run,
            attempt_rows=warm_stats["rows"],
            routed="sweep" if "timeline_axis" in warm_stats else "windows",
            timeline_axis=warm_stats.get("timeline_axis", "-"),
            compile_s=f"{compile_s:.2f}",
            cold_s=f"{cold:.2f}",
            warm_s=f"{warm:.2f}",
            oracle_s=f"{oracle_s:.2f}",
            oracle_ppm_candidates="every_observed_peak",
            dispatches=warm_stats["program_calls"],
            waits_in_program=warm_stats["waits_program"],
            lanes_replayed=warm_stats["lanes_replayed"],
            kernels=json.dumps(_kernels_since(before)),
            parity=f"{attempts} attempts (node, start, end) equal to run_cluster's placement of the same rows",
            f32_rows_differing=json.dumps(rows_differ),
            run_cluster_equal_through_first_differing_row=json.dumps(first_diff),
            run_cluster_records_equal=f"{records_equal}/{records}",
            wastage_vs_run_cluster=(
                f"{len(POLICIES)} policies within rtol 0.05 (worst {worst!r}), default bit-equal"
            ),
        )


def phase_serve(n_dev: int) -> None:
    from repro.kernels.ops import kernel_traces
    from repro.serve.admission import ShardedAdmissionController
    from repro.serve.engine import make_admission_controller
    from repro.serve.stream import StreamConfig, generate_arrivals, run_stream

    class Counted(ShardedAdmissionController):
        """Counts admission dispatches and the live-set peak."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.dispatches = 0
            self.peak_live = 0

        def try_admit_many(self, *a, **kw):
            out = super().try_admit_many(*a, **kw)
            self.dispatches += 1
            self.peak_live = max(self.peak_live, len(self.active))
            return out

    # 16 arrivals/s of ~110 s decodes against a budget that holds ~1,200 of
    # them: the live set peaks past 1,024 and the budget still binds
    cfg = StreamConfig(
        n_requests=4000, rate_per_s=16.0, hbm_budget_mib=750_000.0, n_shards=4, seed=0
    )
    arrivals = generate_arrivals(cfg)

    def controller():
        return Counted(
            cfg.hbm_budget_mib, cfg.k, cfg.interval_s, n_shards=cfg.n_shards, use_shard_map=n_dev > 1
        )

    ref_ctl = make_admission_controller(
        "sharded-scalar", hbm_budget_mib=cfg.hbm_budget_mib, k=cfg.k,
        interval_s=cfg.interval_s, n_shards=cfg.n_shards,
    )
    t0 = time.perf_counter()
    ref = run_stream(cfg, "sharded-scalar", controller=ref_ctl, arrivals=arrivals)
    oracle_s = time.perf_counter() - t0
    before = kernel_traces()
    _, cold, compile_s = _timed(lambda: run_stream(cfg, "sharded", controller=controller(), arrivals=arrivals))
    ctl = controller()
    _check(ctl.n_dev == n_dev, f"controller spans {ctl.n_dev} devices, want {n_dev}")
    t0 = time.perf_counter()
    got = run_stream(cfg, "sharded", controller=ctl, arrivals=arrivals)
    warm = time.perf_counter() - t0
    _check(got.decisions == ref.decisions, "sharded decisions differ from sharded-scalar")
    _check(
        (got.admitted, got.rejected, got.evicted, got.finished)
        == (ref.admitted, ref.rejected, ref.evicted, ref.finished),
        "sharded counts differ from sharded-scalar",
    )
    _check(ref.rejected > 0, "budget never bound: parity would be trivial")
    _check(ctl.peak_live >= 1024, f"peak live set {ctl.peak_live} < 1024")
    _check(ctl.dispatches > 0, "no device dispatch")
    layout = {len(a.sharding.device_set) for a in ctl._state}
    _check(layout == {n_dev}, f"carried state spans {layout} devices, want {n_dev}")
    _report(
        "serve",
        requests=cfg.n_requests,
        shards=cfg.n_shards,
        devices=n_dev,
        budget_mib=int(cfg.hbm_budget_mib),
        peak_live=ctl.peak_live,
        decisions=len(got.decisions),
        admitted=got.admitted,
        rejected=got.rejected,
        evicted=got.evicted,
        compile_s=f"{compile_s:.2f}",
        cold_s=f"{cold:.2f}",
        warm_s=f"{warm:.2f}",
        oracle_s=f"{oracle_s:.2f}",
        dispatches=ctl.dispatches + ctl.reseeds,
        reseeds=ctl.reseeds,
        lanes_replayed="n/a (no sweep)",
        state_devices=n_dev,
        kernels=json.dumps(_kernels_since(before)),
        parity=f"{len(got.decisions)} decisions equal to sharded-scalar",
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the serve phase, its 4 shards on 4 chips",
    )
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"[device] platform={device['platform']} kind={device['kind']} count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing runs on the CPU", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if device["count"] < want:
        print(f"chip_smoke: needs {want} chips, found {device['count']}", file=sys.stderr)
        return 1

    from repro.compat import enable_compile_cache

    print(f"[setup] compile_cache={enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_serve(n_dev=4)
    else:
        from repro.sim import generate_suite

        wfs = generate_suite(seed=0, scale=1.0)
        phase_predictor(wfs)
        phase_cluster(wfs)
        phase_serve(n_dev=1)
    print(f"[done] total_s={time.perf_counter() - t0:.2f}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
