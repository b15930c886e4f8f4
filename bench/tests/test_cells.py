"""A whole run of each cell, driven on the CPU at a small size with the
harness's look for a chip skipped: correct on the program as it is, and not
correct with a fault planted in the timed path."""

import numpy as np
import pytest

from bench import run_cell

SMALL = {
    "sched.nfcore.16n": {"config": {"corpus": {"scale": 0.08}}},
}
SEED = 2**31 + 7


def _run(cell):
    code, result = run_cell.run(cell, SEED, 0.0, False, check_device=False, overrides=SMALL[cell])
    assert code == 0
    return result


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"


def _alter_one_placement(monkeypatch):
    import repro.sim.cluster as cluster

    orig = cluster._policy_result

    def altered(policy, queue, counts, waste, node, start, end):
        node = np.array(node)
        node[len(node) // 2] = (node[len(node) // 2] + 1) % 2
        return orig(policy, queue, counts, waste, node, start, end)

    monkeypatch.setattr(cluster, "_policy_result", altered)


def _alter_one_row(monkeypatch):
    """One attempt's allocation doubled where the ladder's rows are made:
    placement then places the altered row, and the wastage (scored before)
    stays, so only the comparison of rows sees it."""
    import repro.sim.cluster as cluster

    orig = cluster._policy_rows

    def altered(ladders, queue, policy):
        b, v, run, probe, counts, waste = orig(ladders, queue, policy)
        v = np.array(v)
        i = len(v) // 2 + int(np.argmax(v[len(v) // 2 :, -1] < 1000))
        v[i] *= 2
        return b, v, run, probe, counts, waste

    monkeypatch.setattr(cluster, "_policy_rows", altered)


def _half_the_rows(monkeypatch):
    import repro.sim.cluster as cluster

    orig = cluster._policy_rows

    def half(ladders, queue, policy):
        b, v, run, probe, counts, waste = orig(ladders, queue, policy)
        q = len(counts) // 2
        r = int(counts[:q].sum())
        return b[:r], v[:r], run[:r], probe[:r], counts[:q], waste[:q] * 2.0

    orig_result = cluster._policy_result
    monkeypatch.setattr(cluster, "_policy_rows", half)
    monkeypatch.setattr(cluster, "_policy_result", lambda p, queue, *a: orig_result(p, queue[: len(a[0])], *a))


def _state_unchanged(monkeypatch):
    """The nodes' timelines, the placement loop's state between dispatches,
    never take a commit."""
    import repro.sim.cluster as cluster

    monkeypatch.setattr(cluster.Timeline, "add_many", lambda self, *a, **kw: None)


FAULTS = [
    ("sched.nfcore.16n", _alter_one_placement),
    ("sched.nfcore.16n", _alter_one_row),
    ("sched.nfcore.16n", _half_the_rows),
    ("sched.nfcore.16n", _state_unchanged),
]


@pytest.mark.parametrize("cell,fault", FAULTS, ids=[f"{c}-{f.__name__.strip('_')}" for c, f in FAULTS])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = _run(cell)
    assert not r["correct"], r["checks"]
