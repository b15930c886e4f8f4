"""The benchmark's generators start as faithful copies of the program's."""

import numpy as np
import pytest

from bench.gen import suite


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_suite_equals_program(seed):
    from repro.sim.traces import generate_suite

    ours, theirs = suite.generate_suite(seed, 1.0), generate_suite(seed, 1.0)
    assert [w.name for w in ours] == [w.name for w in theirs]
    for wo, wt in zip(ours, theirs):
        assert len(wo.tasks) == len(wt.tasks)
        for to, tt in zip(wo.tasks, wt.tasks):
            assert (to.name, to.workflow, to.family, to.default_mib, to.interval_s) == (
                tt.name, tt.workflow, tt.family, tt.default_mib, tt.interval_s)
            assert len(to.executions) == len(tt.executions)
            for eo, et in zip(to.executions, tt.executions):
                assert eo.input_size == et.input_size
                assert eo.series.dtype == et.series.dtype and np.array_equal(eo.series, et.series)


def test_reorder_keeps_the_set():
    wfs = suite.generate_suite(0, 0.08)
    big = 2**31 + 12345
    key = lambda ws: sorted((t.name, e.input_size) for w in ws for t in w.tasks for e in t.executions)  # noqa: E731
    a, b = suite.reorder(wfs, big, 0.5), suite.reorder(wfs, big, 0.5)
    assert [e.input_size for w in a for t in w.tasks for e in t.executions] == [
        e.input_size for w in b for t in w.tasks for e in t.executions]
    assert key(a) == key(wfs)
    for wo, wq in zip(wfs, a):
        assert [t.name for t in wo.tasks] == [t.name for t in wq.tasks]
        for to, tq in zip(wo.tasks, wq.tasks):
            n = int(len(to.executions) * 0.5)
            assert [e.input_size for e in to.executions[:n]] == [e.input_size for e in tq.executions[:n]]
