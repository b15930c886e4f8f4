"""The synthetic nf-core sarek + eager corpus, generated by the benchmark.

A faithful copy of the program's suite generator (``repro.sim.traces``), kept
here so that the benchmark's inputs stay fixed when the program changes.
``bench/tests/test_gen.py`` holds it equal to the program's generator.  It is
calibrated to the statistics Bader et al. publish (Sec. IV-B): sarek has 29
task types with up to 1,512 executions, eager 18 types with up to 136.

The corpus is plain data (``Task`` / ``Execution`` below); the drivers turn it
into the program's own trace objects, and the references read it as it is.
Units: MiB and seconds.
"""

from __future__ import annotations

import dataclasses
import zlib

import numpy as np

MIB = 1.0
GIB = 1024.0
INTERVAL_S = 2.0  # the paper's monitoring interval

FAMILIES = ("plateau", "ramp", "spike", "staged", "sawtooth", "decline")


@dataclasses.dataclass
class Execution:
    input_size: float  # bytes
    series: np.ndarray  # (j,) float32 MiB, one sample per interval


@dataclasses.dataclass
class Task:
    name: str
    workflow: str
    family: str
    default_mib: float
    interval_s: float
    executions: list[Execution]


@dataclasses.dataclass
class Workflow:
    name: str
    tasks: list[Task]


def _curve(family: str, t: np.ndarray, rng: np.random.Generator, p: dict) -> np.ndarray:
    if family == "plateau":
        rise = p["rise"] * rng.uniform(0.8, 1.2)
        return np.minimum(t / max(rise, 1e-3), 1.0)
    if family == "ramp":
        return t ** p["gamma"]
    if family == "spike":
        c = np.clip(p["center"] + rng.normal(0, 0.04), 0.05, 0.95)
        w = p["width"]
        spike = np.exp(-0.5 * ((t - c) / w) ** 2)
        return p["base"] + (1.0 - p["base"]) * spike
    if family == "staged":
        c = np.clip(p["center"] + rng.normal(0, 0.03), 0.1, 0.9)
        lo, width = p["base"], 0.02
        s = 1.0 / (1.0 + np.exp(-(t - c) / width))
        ramp_in = np.minimum(t / 0.05, 1.0)
        return np.clip(ramp_in * (lo + (1.0 - lo) * s + 0.05 * t), 0.0, 1.0)
    if family == "sawtooth":
        period = p["period"] * rng.uniform(0.9, 1.1)
        phase = rng.uniform(0, period)
        saw = ((t + phase) % period) / period
        return p["base"] + (1.0 - p["base"]) * saw
    if family == "decline":
        c = np.clip(p["center"] + rng.normal(0, 0.03), 0.15, 0.7)
        up = np.minimum(t / c, 1.0)
        down = 1.0 - (1.0 - p["floor"]) * np.maximum((t - c) / max(1.0 - c, 1e-3), 0.0)
        return np.where(t <= c, up, down)
    raise ValueError(f"unknown family {family!r}")


def _specs(workflow: str, rng: np.random.Generator, scale: float) -> list[dict]:
    if workflow == "sarek":
        n_tasks, max_exec = 29, 1512
        rt_lo, rt_hi = 2.0, 3600.0
        pk_lo, pk_hi = 10 * MIB, 23 * GIB
        n_eligible = 21
    elif workflow == "eager":
        n_tasks, max_exec = 18, 136
        rt_lo, rt_hi = 8.0, 4 * 3600.0
        pk_lo, pk_hi = 19 * MIB, 14 * GIB
        n_eligible = 12
    else:
        raise ValueError(workflow)
    runtimes = np.exp(rng.permutation(np.linspace(np.log(rt_lo), np.log(rt_hi), n_tasks)))
    peaks = np.exp(rng.permutation(np.linspace(np.log(pk_lo), np.log(pk_hi), n_tasks)))
    counts = np.full(n_tasks, 0, dtype=int)
    elig = rng.permutation(n_tasks)[:n_eligible]
    tail = np.exp(np.linspace(np.log(20), np.log(max_exec / 2), n_eligible - 1))
    counts[elig] = np.concatenate([[max_exec], np.maximum(np.round(tail), 20).astype(int)])
    small = counts == 0
    counts[small] = rng.integers(3, 19, size=small.sum())
    specs = []
    for i in range(n_tasks):
        family = FAMILIES[i % len(FAMILIES)]
        params = {
            "rise": rng.uniform(0.03, 0.15),
            "gamma": rng.uniform(0.5, 2.0),
            "center": rng.uniform(0.3, 0.8),
            "width": rng.uniform(0.02, 0.08),
            "base": rng.uniform(0.25, 0.5),
            "period": rng.uniform(0.08, 0.25),
            "floor": rng.uniform(0.3, 0.6),
        }
        specs.append(
            dict(
                name=f"{workflow}:task{i:02d}_{family}",
                family=family,
                n_exec=max(int(counts[i] * scale), 3),
                mean_runtime_s=float(runtimes[i] * scale if runtimes[i] > 600 else runtimes[i]),
                mean_peak_mib=float(peaks[i]),
                input_mu=float(np.log(rng.uniform(50e6, 20e9))),
                input_sigma=float(rng.uniform(0.2, 0.7)),
                rt_correlated=bool(rng.random() < 0.85),
                mem_correlated=bool(rng.random() < 0.5),
                rt_noise=float(rng.uniform(0.02, 0.08)),
                mem_noise=float(rng.uniform(0.02, 0.08)),
                mem_saturation=float(rng.uniform(1.8, 3.0)),
                params=params,
            )
        )
    return specs


def _round_default(mib: float) -> float:
    """nf-core-style memory directives: 0.25 .. 128 GB."""
    ladder = np.array([0.25, 0.5, 1, 2, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128]) * GIB
    idx = np.searchsorted(ladder, mib, side="left")
    return float(ladder[min(idx, len(ladder) - 1)])


def _task(s: dict, rng: np.random.Generator, interval_s: float) -> Task:
    execs = []
    x_mean = np.exp(s["input_mu"] + s["input_sigma"] ** 2 / 2)
    for _ in range(s["n_exec"]):
        x = float(rng.lognormal(s["input_mu"], s["input_sigma"]))
        rel = x / x_mean
        rt = s["mean_runtime_s"] * (0.35 + 0.65 * rel if s["rt_correlated"] else 1.0)
        rn = s["rt_noise"]
        rt *= 1.0 + float(np.clip(rng.normal(0.0, rn), -2.5 * rn, 2.5 * rn))
        j = max(int(round(rt / interval_s)), 2)
        mem_rel = min(rel, s["mem_saturation"])
        peak = s["mean_peak_mib"] * (0.4 + 0.6 * mem_rel if s["mem_correlated"] else 1.0)
        sigma = s["mem_noise"] * (0.6 + 0.4 * min(rel, 2.0))
        peak *= 1.0 + float(np.clip(rng.normal(0.0, sigma), -2.5 * sigma, 2.5 * sigma))
        peak = float(np.clip(peak, 8.0, 100 * GIB))
        t = (np.arange(j) + 0.5) / j
        curve = _curve(s["family"], t, rng, s["params"])
        base = 0.02 * peak + 8.0
        y = base + (peak - base) * np.clip(curve, 0.0, 1.0)
        y *= 1.0 + rng.normal(0.0, 0.015, size=j)
        y = np.clip(y, 1.0, 100 * GIB).astype(np.float32)
        execs.append(Execution(input_size=x, series=y))
    max_peak = max(float(e.series.max()) for e in execs)
    default = _round_default(max_peak * rng.uniform(1.15, 2.2))
    return Task(s["name"], s["name"].split(":")[0], s["family"], default, interval_s, execs)


def generate_workflow(name: str, seed: int = 0, scale: float = 1.0, interval_s: float = INTERVAL_S) -> Workflow:
    rng = np.random.default_rng(np.random.SeedSequence([zlib.crc32(name.encode()) & 0xFFFF, seed]))
    return Workflow(name, [_task(s, rng, interval_s) for s in _specs(name, rng, scale)])


def generate_suite(seed: int = 0, scale: float = 1.0) -> list[Workflow]:
    """The paper's corpus: sarek then eager."""
    return [generate_workflow("sarek", seed, scale), generate_workflow("eager", seed, scale)]


def reorder(workflows: list[Workflow], seed: int, train_frac: float) -> list[Workflow]:
    """The same corpus in another order, drawn from ``seed``: each task type's
    queued executions (those after its first ``train_frac`` share, which
    train the predictors) are permuted; the types and the training
    executions keep their order.  Every seed keeps the same executions and
    the backlog's shape, which types arrive when, so every seed asks about
    the same amount of scheduling work."""
    rng = np.random.default_rng(int(seed))
    out = []
    for wf in workflows:
        tasks = []
        for t in wf.tasks:
            n = int(len(t.executions) * train_frac)
            q = t.executions[n:]
            tasks.append(dataclasses.replace(t, executions=t.executions[:n] + [q[j] for j in rng.permutation(len(q))]))
        out.append(Workflow(wf.name, tasks))
    return out
