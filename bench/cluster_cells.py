"""What the cluster driver and the control share: the corpus as the program
takes it, the benchmark's spans around the scheduler's layers, and the
numbers that compare a call's outputs with the plain reference."""

from __future__ import annotations

import numpy as np

from bench import common
from bench.gen import suite
from bench.ref import cluster_ref


def corpus(config: dict, seed: int):
    """The configuration's corpus with each task type's queued executions
    permuted by ``seed`` (``suite.reorder``)."""
    c = config["corpus"]
    return suite.reorder(suite.generate_suite(c["seed"], c["scale"]), seed, config["params"]["train_frac"])


def to_program(workflows):
    """The corpus as the program's own trace objects (the same arrays)."""
    from repro.sim.traces import Execution, TaskTrace, WorkflowTrace

    return [
        WorkflowTrace(
            wf.name,
            [
                TaskTrace(
                    t.name, t.workflow, t.family, t.default_mib, t.interval_s,
                    [Execution(e.input_size, e.series) for e in t.executions],
                )
                for t in wf.tasks
            ],
        )
        for wf in workflows
    ]


_SINK: dict = {"rows": []}


def install_spans(rows_sink: list, spans: bool) -> None:
    """Capture the attempt rows each call computes (``batched_rows``) into
    ``rows_sink``, and, when tracing, put the benchmark's spans around each
    layer's entry:

    * ``bench.ladder`` — the retry-ladder pass (``batched_rows``), with
      ``bench.ladder.host_wastage`` — its float64 scoring on the host;
    * ``bench.place.loop`` — the windows loop of one policy, with
      ``bench.place.window`` / ``bench.place.epoch`` — one dispatch of
      ``first_fit_window`` / ``schedule_epoch``;
    * ``bench.place.sweep`` — one ``sweep_schedule`` (all lanes), where
      ``placement="auto"`` routes to it;
    * ``bench.results`` — assembling one policy's records.

    Each wrapper goes in once per process."""
    import repro.sim.batch_engine as batch_engine
    import repro.sim.cluster as cluster
    import repro.sim.device_timeline as device_timeline

    _SINK["rows"] = rows_sink
    if not getattr(cluster.batched_rows, "_bench_capture", False):
        orig = cluster.batched_rows

        def batched_rows(*a, **kw):
            out = orig(*a, **kw)
            _SINK["rows"].append(out[1])
            return out

        batched_rows._bench_capture = True
        cluster.batched_rows = batched_rows
    if spans:
        for obj, attr, name in (
            (cluster, "batched_rows", "bench.ladder"),
            (batch_engine, "_host_wastage", "bench.ladder.host_wastage"),
            (cluster, "_place_rows_batched", "bench.place.loop"),
            (cluster, "_policy_result", "bench.results"),
            (device_timeline, "first_fit_window", "bench.place.window"),
            (device_timeline, "schedule_epoch", "bench.place.epoch"),
            (device_timeline, "sweep_schedule", "bench.place.sweep"),
        ):
            common.wrap(obj, attr, name)


def placements(result) -> np.ndarray:
    """(rows, 3) node, start tick, end tick of every attempt of one policy's
    ``ClusterResult``, in queue and attempt order."""
    pl = [p for rec in result.records for p in rec.placements]
    a = np.asarray(pl, dtype=np.float64).reshape(-1, 3)
    return np.stack([a[:, 0], np.round(a[:, 1] * 1000), np.round(a[:, 2] * 1000)], axis=1).astype(np.int64)


def ref_ladder(corpus_, policy: str, p: dict, q=cluster_ref._exact):
    """One policy's ladder by the plain reference: ``(rows in the program's
    layout, total wastage in GiB*s)``."""
    rows, attempts, wastage = cluster_ref.ladder_rows(
        corpus_, policy, p["node_mib"], p["train_frac"], p["max_tasks_per_type"], p["min_executions"], q
    )
    return layout(rows, attempts, p["k"]), float(wastage.sum())


def layout(rows, attempts, k: int):
    """The reference's attempt rows as the program lays them out
    (``batched_rows``): boundary keys (R, k), value units (R, k), run keys
    (R,), probe keys (R,), attempts per queued execution (Q,).  Rows are
    padded to k steps with boundaries that never come and the last value
    held; a one-step allocation has no boundary at all."""
    b = np.full((len(rows), k), cluster_ref.NEVER, dtype=np.int64)
    v = np.empty((len(rows), k), dtype=np.int64)
    for i, (rb, rv, _, _) in enumerate(rows):
        n = len(rv)
        if n > 1:
            b[i, :n] = rb
        v[i, :n] = rv
        v[i, n:] = rv[-1]
    run = np.asarray([r[2] for r in rows], dtype=np.int64)
    probe = np.asarray([r[3] for r in rows], dtype=np.int64)
    return b, v, run, probe, np.asarray(attempts, dtype=np.int64)


def row_gap(got, want) -> float:
    """The largest relative gap between two ladders' attempt rows (``layout``
    order): over every attempt, each boundary key, value unit and run key
    against ``want``'s, relative to ``want``'s (at least 1).  A boundary that
    one side has and the other does not, and an execution whose number of
    attempts differs, read 1; the attempts of such an execution are not
    compared."""
    gb, gv, grun, _, gc = (np.asarray(a, dtype=np.int64) for a in got[:5])
    wb, wv, wrun, _, wc = (np.asarray(a, dtype=np.int64) for a in want[:5])
    if len(gc) != len(wc):
        return 1.0
    same = gc == wc
    gap = 0.0 if same.all() else 1.0
    g, w = np.repeat(same, gc), np.repeat(same, wc)
    gb, gv, grun, wb, wv, wrun = gb[g], gv[g], grun[g], wb[w], wv[w], wrun[w]

    def rel(a, b):
        return np.abs(a - b) / np.maximum(np.abs(b), 1)

    never = cluster_ref.NEVER
    both = (gb != never) & (wb != never)
    one = (gb != never) != (wb != never)
    parts = [rel(gv, wv), rel(grun, wrun), np.where(both, rel(gb, wb), 0.0), one.astype(np.float64)]
    return max([gap] + [float(x.max()) for x in parts if x.size])


def row_list(rows) -> list:
    """A program policy's attempt rows (``batched_rows`` layout) as the
    reference's list of (boundary keys, value units, run key, probe key)."""
    b, v, run, probe = rows[:4]
    return [(b[i], v[i], int(run[i]), int(probe[i])) for i in range(len(run))]


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Attempt rows whose (node, start, end) differ, counting missing rows."""
    n = min(len(got), len(want))
    return int(np.sum(np.any(got[:n] != want[:n], axis=1))) + abs(len(got) - len(want))


def place_ticks(rows, n_nodes: int, budget: int, q=cluster_ref._exact) -> np.ndarray:
    """The plain reference's placement of a program policy's rows, in ticks."""
    out = cluster_ref.place(row_list(rows), n_nodes, budget, q)
    return np.stack([out[:, 0], out[:, 1] // 2, out[:, 2] // 2], axis=1)
