"""The in-program wait path under congestion: corpora whose queued tasks far
exceed what the cluster can hold at once, so placement is dominated by waits
on future completions.

The batched scheduler must resolve every one of those waits inside the
device scheduling-epoch program (``device_timeline.schedule_epoch`` — the
event clock and release heap live in the scan carry) with **exact** (node,
start, end) per-attempt parity against the sequential ``run_cluster``
oracle, and the placement counters must show zero host-resolved waits.

Seeded corpora plus a hypothesis variant over random densities (skipped
cleanly by the conftest shim when hypothesis is absent).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.ksegments import KSegmentsConfig
from repro.sim.cluster import run_cluster, run_cluster_batched
from repro.sim.traces import generate_workflow

POLICIES = ("default", "witt-lr", "ppm-improved", "ksegments-selective")


def _assert_congested_parity(wfs, policies, min_waits: int, **kw):
    """Exact per-attempt parity + the wait-path invariants.

    Pinned to ``placement="windows"``: these corpora stress the epoch
    program's carry hand-off between windows dispatches, which the
    whole-run sweep engine (tests/test_cluster_sweep.py) never takes.
    """
    cfg = KSegmentsConfig(error_mode="progressive")
    stats: dict = {}
    batched = run_cluster_batched(wfs, policies, placement_stats=stats, placement="windows", **kw)
    # the point of the corpus: placement must actually have waited, and every
    # wait must have been resolved inside the device program
    assert stats["waits_host"] == 0
    assert stats["waits_program"] >= min_waits, stats
    seq_kw = {k: v for k, v in kw.items() if k != "placement_window"}
    for policy in policies:
        seq = run_cluster(wfs, policy, ksegments_config=cfg, **seq_kw)
        bat = batched[policy]
        assert seq.tasks_run == bat.tasks_run > 0
        assert seq.retries == bat.retries
        assert seq.makespan_s == bat.makespan_s
        for rs, rb in zip(seq.records, bat.records):
            assert (rs.workflow, rs.task, rs.exec_index) == (rb.workflow, rb.task, rb.exec_index)
            assert rs.attempts == rb.attempts
            assert rs.placements == rb.placements  # exact (node, start, end)
            np.testing.assert_allclose(rs.wastage_gib_s, rb.wastage_gib_s, rtol=1e-3, atol=1e-6)
    return stats


@pytest.mark.parametrize(
    "seed,name,scale,n_nodes,node_gib,mtpt,min_exec",
    [
        # single node, 24 GiB: every co-resident task contends
        (3, "eager", 0.25, 1, 24, 25, 6),
        (7, "eager", 0.25, 2, 24, 25, 6),
        (13, "sarek", 0.12, 2, 32, 8, 8),
    ],
)
def test_congested_corpus_exact_parity(seed, name, scale, n_nodes, node_gib, mtpt, min_exec):
    wfs = [generate_workflow(name, seed=seed, scale=scale)]
    # small nodes (vs the 128 GiB default): the corpora's biggest tasks
    # reserve a sizable fraction of a node, so the queue saturates the
    # cluster and rows genuinely wait on future completions
    _assert_congested_parity(
        wfs,
        POLICIES,
        min_waits=5,
        n_nodes=n_nodes,
        node_mib=node_gib * 1024.0,
        max_tasks_per_type=mtpt,
        min_executions=min_exec,
        train_frac=0.5,
    )


def test_congested_small_window_epochs():
    """Tiny placement windows force many epoch boundaries mid-wait: the
    carry hand-off (commits, heap, clock) between consecutive epoch
    dispatches must be seamless."""
    wfs = [generate_workflow("eager", seed=3, scale=0.25)]
    _assert_congested_parity(
        wfs,
        ("default", "ksegments-selective"),
        min_waits=5,
        n_nodes=1,
        node_mib=24 * 1024.0,
        max_tasks_per_type=25,
        min_executions=6,
        train_frac=0.5,
        placement_window=4,
    )


@settings(deadline=None, max_examples=5)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 3),
    st.integers(6, 14),
)
def test_property_congested_parity(seed, n_nodes, mtpt):
    """Random densities: whatever wait pattern the corpus produces, the
    batched engine must reproduce the oracle exactly and never fall back to
    a host-resolved wait."""
    wfs = [generate_workflow("eager", seed=seed, scale=0.06)]
    _assert_congested_parity(
        wfs,
        ("default", "ksegments-selective"),
        min_waits=0,
        n_nodes=n_nodes,
        node_mib=32 * 1024.0,
        max_tasks_per_type=mtpt,
        min_executions=6,
        train_frac=0.5,
    )


def test_schedule_epoch_waits_in_program():
    """Direct unit check of the epoch program's wait mechanics: a second row
    that cannot fit alongside the first must start exactly at the first's
    completion, consuming exactly one pending event."""
    from repro.sim.device_timeline import schedule_epoch

    from repro.core.timeline import quantize_steps, time_keys

    bnd, val = quantize_steps([[5.0], [5.0]], [[700.0], [700.0]])  # 2 x 700 > 1000: row 1 must wait
    run = time_keys([10.0, 10.0])
    empty = np.empty(0, np.int64)
    placed, node, start, now_f, pops, waited, dead = schedule_epoch(
        0, bnd, val, run, [(empty, empty)], empty, 1000, 8
    )
    t10 = int(time_keys(10.0))
    assert placed.tolist() == [True, True]
    assert node.tolist() == [0, 0]
    assert start.tolist() == [0, t10]  # row 1 waits for row 0's release
    assert now_f == t10
    assert pops == 1 and waited == 1 and not dead
