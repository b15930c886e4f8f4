#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator this process finds.

    python bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name: the
cell in ``BENCHMARK.json``, the configuration in ``bench/configs/<name>.json``,
the traffic mix in ``bench/traffic/<name>.json``, which names its driver
(``bench/drivers/<driver>.py``).  Per-layer metrics are read by
``bench/metrics/<metric>.py``.  Nothing here lists them.

A run: check that the devices are TPUs (exit 2 otherwise, with no result);
turn on the compile cache at the checkout's fixed ``.jax_cache``; build the
inputs from ``--seed``; warm up with one whole untimed call (``setup_s`` is
the time from process start to here); call the entry point again and again
until ``--seconds`` have passed, the window ending with its last whole call;
read the device's memory peak; free the program's state; check what the
window produced against the plain reference; print the result.

With ``--trace 1`` the window's first whole call runs under the profiler,
with the benchmark's own spans around each call into the program, and the
result carries the cell's per-layer metrics, the device's busy and traced
window seconds and a breakdown of device time and idle time.  Without it,
the end-to-end metrics.

The last line of standard output is one JSON object; the numbers compared
with the reference, each beside its limit, are the last lines of standard
error and the last key of that object.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str):
    """Import a file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fail(msg: str, code: int) -> int:
    print(f"run_cell: {msg}", file=sys.stderr, flush=True)
    return code


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def run(workload: str, seed: int, seconds: float, trace_on: bool, *, check_device: bool = True,
        overrides: dict | None = None) -> tuple[int, dict | None]:
    """One run of one cell: ``(exit code, result)``.  ``check_device`` and
    ``overrides`` (merged into the configuration and traffic files) exist for
    the benchmark's own tests, which drive a run on the CPU at a small size;
    the command line always checks the device and never overrides."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        return _fail(f"no workload {workload!r} in BENCHMARK.json", 2), None
    cell = cells[workload]
    with open(os.path.join(BENCH, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json")) as f:
        traffic = json.load(f)
    if overrides:
        config = _merge(config, overrides.get("config", {}))
        traffic = _merge(traffic, overrides.get("traffic", {}))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return _fail("the system under test (src/repro) is not in this checkout", 3), None
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"[device] {json.dumps(device)}", file=sys.stderr, flush=True)
    if check_device and device["platform"] != "tpu":
        return _fail("no TPU found; the benchmark does not run on another platform", 2), None
    if device["count"] < cell["chips"]:
        return _fail(f"the cell needs {cell['chips']} chips, found {device['count']}", 2), None

    from bench import common, trace

    common.enable_compile_cache(os.path.join(ROOT, ".jax_cache"))
    compiles = common.CompileCount()
    driver = load_module(os.path.join(BENCH, "drivers", f"{traffic['driver']}.py"), f"bench_driver_{traffic['driver']}")
    drv = driver.Driver(config, traffic, seed, spans=trace_on)
    drv.warm()
    setup_s = time.perf_counter() - T_START

    # A traced run profiles the window's first whole call only: the trace of
    # one call is what the per-layer metrics read, and its size stays bounded.
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace_on else None
    before = compiles.snapshot()
    n_calls = 0
    window = contextlib.ExitStack()
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1  # the benchmark's own spans, not every host event
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window.enter_context(common.span("bench.window"))
    t0 = time.perf_counter()
    while True:
        with common.span("bench.call"):
            drv.call()
        n_calls += 1
        if n_calls == 1 and trace_dir:
            window.close()
            jax.profiler.stop_trace()
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    window.close()
    t_check = time.perf_counter()
    window_compiles = compiles.snapshot() - before
    used = devs[: cell["chips"]]
    device["memory_peak_bytes"] = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in used)

    e2e = drv.end_to_end(window_s)
    e2e["setup_s"] = setup_s
    counters = drv.counters()
    counters.update(n_calls=n_calls, window_compiles=window_compiles)
    drv.release()
    checks = drv.check()
    result: dict = {"correct": all(c["ok"] for c in checks), "attempted": drv.attempted(), "failed": drv.failed()}
    metrics: dict = {}
    if trace_dir:
        reduced = trace.reduce_dir(trace_dir, n_devices=cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        with open(os.path.join(BENCH, "peaks.json")) as f:
            peaks = json.load(f)
        ctx = common.Context(reduced, counters, 1, peaks, device["kind"])
        for m in spec["per_layer"]:
            if not _applies(m, workload):
                continue
            if "workloads" not in m and not any(
                e["name"] == m["moves"] and _applies(e, workload) for e in spec["end_to_end"]
            ):
                continue
            reader = load_module(os.path.join(BENCH, "metrics", f"{m['name']}.py"), "bench_metric")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            if _applies(m, workload) and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if trace_dir:
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in checks}

    print(
        f"[timing] setup_s={setup_s!r} window_s={window_s!r} after_window_s={time.perf_counter() - t_check!r}",
        file=sys.stderr,
        flush=True,
    )
    print(
        f"[window] calls={n_calls} window_s={window_s!r} compiles_in_window={window_compiles} "
        f"counters={json.dumps(counters, default=str)}",
        file=sys.stderr,
        flush=True,
    )
    for c in checks:
        print(
            f"[check] {c['name']} value={c['value']!r} limit={c['limit']!r} ok={c['ok']}",
            file=sys.stderr,
            flush=True,
        )
    return 0, result


def main() -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    code, result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
