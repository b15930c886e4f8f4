"""Cluster-level scheduling with dynamic (segment-wise) reservations."""

import numpy as np
import pytest

from repro.sim import generate_eager
from repro.sim.cluster import NodeState, run_cluster
from repro.core.timeline import quantize_steps, time_keys


def test_node_fits_profile():
    nd = NodeState(capacity_mib=1000.0)
    k = lambda s: int(time_keys(s))  # noqa: E731  (seconds -> key)
    a1 = quantize_steps([10.0, 20.0], [400.0, 800.0])
    assert nd.fits(*a1, 0, k(20.0))
    nd.active.append((k(20.0), *a1, 0))
    # second task peaking at 300 fits only while the first is in its 400-phase
    a2 = quantize_steps([5.0], [300.0])
    assert nd.fits(*a2, 0, k(5.0))  # 400+300 <= 1000 in [0,5)
    a3 = quantize_steps([15.0], [300.0])
    assert not nd.fits(*a3, 0, k(15.0))  # overlaps the 800-phase: 1100 > 1000


@pytest.fixture(scope="module")
def wf():
    return [generate_eager(seed=9, scale=0.12)]


def test_cluster_policies(wf):
    res_k = run_cluster(wf, "ksegments-selective", n_nodes=3, max_tasks_per_type=15)
    res_d = run_cluster(wf, "default", n_nodes=3, max_tasks_per_type=15)
    assert res_k.tasks_run == res_d.tasks_run > 0
    # dynamic reservations waste (much) less than the developers' defaults
    assert res_k.wastage_gib_s < res_d.wastage_gib_s
    # and never deadlock
    assert np.isfinite(res_k.makespan_s) and res_k.makespan_s > 0
