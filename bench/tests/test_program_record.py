"""The per-layer metrics that read the program's own record of a call
(``bench/program_record.py``): a traced run on the CPU at a small size
reports each of them, beside the metrics the benchmark had."""

import json
import os

from bench import program_record, run_cell

CELL = "sched.nfcore.16n"
SMALL = {"config": {"corpus": {"scale": 0.08}}}
SEED = 2**31 + 11


def _record_metrics():
    """The per-layer metrics whose reader reads the program's record."""
    with open(os.path.join(run_cell.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    names = []
    for m in spec["per_layer"]:
        with open(os.path.join(run_cell.BENCH, "metrics", f"{m['name']}.py")) as f:
            if "program_record" in f.read():
                names.append(m["name"])
    return names


def test_traced_run_reports_the_program_record():
    code, r = run_cell.run(CELL, SEED, 0.0, True, check_device=False, overrides=SMALL)
    assert code == 0 and r["correct"], r["checks"]
    names = _record_metrics()
    assert len(names) == 7
    m = r["metrics"]
    for name in names:
        assert m[name]["value"] is not None and m[name]["value"] > 0, name
    # the benchmark's own counters read as before, and agree with the record
    rec = program_record.last_call()
    dispatches = rec.n("sched.epoch.launch") + rec.n("sched.window.launch")
    assert m["placement_dispatches.cluster"]["value"] == dispatches
    assert m["window_compiles.cluster"]["value"] == 0
    # the CPU has no device plane: the trace's metrics stay out
    assert "device_idle_share.cluster" not in m and "ladder_device_ms.cluster" not in m


def test_a_program_without_the_record_gives_nothing(monkeypatch):
    import builtins

    real = builtins.__import__

    def no_obs(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "repro" and fromlist and "obs" in fromlist:
            raise ImportError("no repro.obs")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_obs)
    assert program_record.last_call() is None
    assert program_record.span_ms("sched.epoch.prepare") is None
    assert program_record.counter("sched.epoch.shapes") is None
