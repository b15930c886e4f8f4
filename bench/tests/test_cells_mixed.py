"""A whole run of the mixed-pool cell, driven on the CPU at a small corpus
with the harness's look for a chip skipped: correct on the program as it
is, and not correct with a fault planted in the timed path or in the
reference's pool.  Its bfloat16 control fails the cell's limits."""

import json
import os

import numpy as np
import pytest

from bench import control_mixed, run_cell

CELL = "sched.nfcore.mix16n"
SMALL = {"config": {"corpus": {"scale": 0.08}}}
SEED = 2**31 + 7
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run():
    code, result = run_cell.run(CELL, SEED, 0.0, False, check_device=False, overrides=SMALL)
    assert code == 0
    return result


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


def _wide_row_on_a_small_node(monkeypatch):
    """In each policy, the first row wider than the smallest node moved
    onto the first such node, which cannot hold it."""
    import repro.sim.cluster as cluster

    orig = cluster._place_rows_batched

    def altered(bnd, val, run, probe, n_nodes, node_mib, *a):
        node, start, end = orig(bnd, val, run, probe, n_nodes, node_mib, *a)
        caps = cluster.node_capacities(node_mib, n_nodes)
        small = int(np.argmin(caps))
        wide = np.flatnonzero(val.max(axis=1) > caps[small])
        node = np.array(node)
        node[wide[:1]] = small
        return node, start, end

    monkeypatch.setattr(cluster, "_place_rows_batched", altered)


def _uniform_reference_pool(monkeypatch):
    """The reference places on a pool of equal nodes, each the largest."""
    from bench.ref import mixed_ref

    orig = mixed_ref.place
    monkeypatch.setattr(
        mixed_ref, "place", lambda rows, budgets, *a: orig(rows, np.full(len(budgets), max(budgets)), *a)
    )


def _alter_one_row(monkeypatch):
    """One attempt's allocation doubled where the ladder's rows are made."""
    import repro.sim.cluster as cluster

    orig = cluster._policy_rows

    def altered(ladders, queue, policy):
        b, v, run, probe, counts, waste = orig(ladders, queue, policy)
        v = np.array(v)
        i = len(v) // 2 + int(np.argmax(v[len(v) // 2 :, -1] < 1000))
        v[i] *= 2
        return b, v, run, probe, counts, waste

    monkeypatch.setattr(cluster, "_policy_rows", altered)


FAULTS = [_wide_row_on_a_small_node, _uniform_reference_pool, _alter_one_row]


@pytest.mark.parametrize("fault", FAULTS, ids=[f.__name__.strip("_") for f in FAULTS])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = _run()
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_control_fails_a_limit(seed):
    got = control_mixed.readings(CELL, seed, SMALL)
    with open(os.path.join(BENCH, "traffic", "backlog.mix16n.json")) as f:
        limits = json.load(f)["limits"]
    assert set(got) == set(limits)
    assert any(got[k] > limits[k] for k in got), got
