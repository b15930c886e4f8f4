"""The program's own spans and counters (``repro.obs``): the record's
arithmetic, spans on worker threads, agreement with the scheduler's
``placement_stats`` counters, and the spans in a profiler trace."""

import glob
import sys
import threading
import types
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro import obs
from repro.sim.cluster import run_cluster_batched
from repro.sim.traces import generate_workflow


@pytest.fixture
def clock(monkeypatch):
    """A fake ``perf_counter_ns`` that moves only when the test says."""
    now = [0]
    monkeypatch.setattr(obs, "time", types.SimpleNamespace(perf_counter_ns=lambda: now[0]))

    def tick(ns):
        now[0] += ns

    return tick


def test_nested_spans_count_total_and_self(clock):
    with obs.call("t.call"):
        clock(5)
        for _ in range(2):
            with obs.span("t.outer"):
                clock(10)
                with obs.span("t.inner"):
                    clock(7)
                    with obs.span("t.leaf"):
                        clock(3)
                clock(1)
        obs.distinct("t.kinds", (1, 2))
        obs.distinct("t.kinds", (1, 2))
        obs.distinct("t.kinds", (3,))
    rec = obs.last("t.call")
    assert rec.spans["t.outer"] == [2, 2 * 21, 2 * 11]
    assert rec.spans["t.inner"] == [2, 2 * 10, 2 * 7]
    assert rec.spans["t.leaf"] == [2, 6, 6]
    assert rec.spans["t.call"] == [1, 47, 5]
    assert rec.total_s("t.outer", "t.leaf") == pytest.approx(48e-9)
    assert rec.self_s("t.outer", "t.inner", "t.leaf") == pytest.approx(42e-9)
    assert rec.n("t.inner") == 2 and rec.n("t.absent") == 0
    assert rec.count("t.kinds") == 2 and rec.count("t.absent") == 0


def test_outside_a_call_nothing_is_recorded(clock):
    with obs.call("t.call2"):
        pass
    before = obs.last("t.call2")
    with obs.span("t.loose"):
        clock(4)
    obs.distinct("t.loose_kinds", 1)
    assert obs.last("t.call2") is before
    assert before.spans == {"t.call2": [1, 0, 0]} and before.counts == {}


def test_a_call_that_raises_keeps_the_last_whole_one():
    with obs.call("t.call3"):
        obs.distinct("t.n", 1)
    whole = obs.last("t.call3")
    with pytest.raises(RuntimeError):
        with obs.call("t.call3"):
            obs.distinct("t.n", 2)
            raise RuntimeError("boom")
    assert obs.last("t.call3") is whole and whole.count("t.n") == 1
    with obs.span("t.after"):  # the span stack of this thread is balanced again
        pass


def test_worker_thread_spans_count_toward_the_open_call(clock):
    def work(i):
        with obs.span("t.work"):
            clock(2)
            obs.distinct("t.done", i)
        return i

    with obs.call("t.pool"):
        with obs.span("t.map"):
            with ThreadPoolExecutor(max_workers=4) as ex:
                assert list(ex.map(work, range(16))) == list(range(16))
    rec = obs.last("t.pool")
    assert rec.n("t.work") == 16 and rec.count("t.done") == 16
    assert rec.total_s("t.work") == pytest.approx(32e-9)
    # other threads' spans are not the main thread's children
    assert rec.self_s("t.map") == rec.total_s("t.map")


def test_no_update_is_lost_under_contention():
    """More threads than cores, a short switch interval: every span and
    counted key of every thread lands in the record."""
    n_threads, n_each = 32, 200
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.call("t.stress"):

            def work(k):
                for j in range(n_each):
                    with obs.span("t.s"):
                        obs.distinct("t.c", (k, j))

            threads = [threading.Thread(target=work, args=(k,)) for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    rec = obs.last("t.stress")
    assert rec.n("t.s") == n_threads * n_each
    assert rec.count("t.c") == n_threads * n_each


# A congested corpus (tests/test_cluster_congested.py): rows stream through
# the window program and wait in the epoch program.
CONGESTED = dict(n_nodes=2, node_mib=24 * 1024.0, max_tasks_per_type=25, min_executions=6, train_frac=0.5)
POLICIES = ("default", "ksegments-selective")


@pytest.fixture(scope="module")
def wfs():
    return [generate_workflow("eager", seed=7, scale=0.25)]


@pytest.mark.parametrize("window", [128, 4])
def test_stage_spans_agree_with_placement_stats(wfs, window):
    """Each dispatch opens each of its four stage spans once, so the spans'
    counts agree with ``placement_stats``, the one count of dispatches."""
    stats: dict = {}
    run_cluster_batched(
        wfs, POLICIES, placement_stats=stats, placement="windows", placement_window=window, **CONGESTED
    )
    rec = obs.last("sched.call")
    epoch, window_ = rec.n("sched.epoch.launch"), rec.n("sched.window.launch")
    assert epoch > 0 and window_ > 0
    assert epoch + window_ == stats["program_calls"]
    assert stats["waits_program"] > 0 and stats["waits_host"] == 0  # the wait path ran on the device
    for stage in ("prepare", "wait", "readback"):
        assert rec.n(f"sched.epoch.{stage}") == epoch
        assert rec.n(f"sched.window.{stage}") == window_
    assert 0 < rec.count("sched.epoch.shapes") <= epoch
    assert 0 < rec.count("sched.window.shapes") <= window_
    assert rec.n("sched.ladder.dispatch") == rec.n("sched.ladder.fetch") > 0
    assert rec.n("sched.call") == 1
    # the call's stages lie inside it
    parts = rec.total_s("sched.ladder.pack", "sched.ladder.unpack", "sched.ladder.rows")
    parts += rec.total_s(*(f"sched.epoch.{s}" for s in ("prepare", "launch", "wait", "readback")))
    assert parts < rec.total_s("sched.call")


def test_sweep_counts_its_dispatches(wfs):
    stats: dict = {}
    run_cluster_batched(wfs, POLICIES, placement_stats=stats, placement="sweep", **CONGESTED)
    rec = obs.last("sched.call")
    assert rec.n("sched.sweep.launch") == stats["program_calls"] >= 1
    assert rec.n("sched.sweep.wait") == rec.n("sched.sweep.readback") == rec.n("sched.sweep.launch")
    assert rec.n("sched.sweep.prepare") == 1
    assert rec.n("sched.epoch.launch") == rec.n("sched.window.launch") == 0


def test_spans_reach_the_profiler_trace(wfs, tmp_path):
    import jax
    from jax.profiler import ProfileData

    run_cluster_batched(wfs, POLICIES, placement="windows", **CONGESTED)  # warm: no compile in the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_cluster_batched(wfs, POLICIES, placement="windows", **CONGESTED)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {
        e.name
        for plane in ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("sched.")
    }
    rec = obs.last("sched.call")
    assert set(rec.spans) <= names
    assert {"sched.call", "sched.epoch.launch", "sched.window.readback", "sched.ladder.dispatch"} <= names
    assert np.isfinite(rec.total_s("sched.call"))
