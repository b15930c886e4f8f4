"""The control (the reference in bfloat16, ``bench/control.py``) is not
correct by the cells' own limits: each cell's control fails at least one of
its compared numbers, here at a small corpus."""

import json
import os

import pytest

from bench import control

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = {
    "sched.nfcore.16n": {"config": {"corpus": {"scale": 0.08}}},
}


def _limits(cell):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        traffic = next(w for w in json.load(f)["workloads"] if w["name"] == cell)["traffic"]
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
@pytest.mark.parametrize("cell", sorted(SIZES))
def test_control_fails_a_limit(cell, seed):
    got = control.readings(cell, seed, SIZES[cell])
    limits = _limits(cell)
    assert set(got) == set(limits)
    assert any(got[k] > limits[k] for k in got), got
