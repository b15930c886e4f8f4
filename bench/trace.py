"""Reduction of a profiler trace to what the per-layer metrics read.

The window is the benchmark's own ``bench.window`` span on the host.  Inside
it, on the devices the cell uses (``/device:TPU:<n>`` planes):

* busy time — the union of the intervals in which an operation ran (the
  ``XLA Ops`` line), averaged over the devices;
* device time per program — the ``XLA Modules`` line, each module under its
  name without the trailing ``(<id>)``;
* device time per operation — ``XLA Ops`` events, each named
  ``<module>/<instruction>`` (the instruction's name is the text before
  `` = `` of the HLO the event carries);
* Pallas kernels — operations whose instruction is named after the kernel
  (``%range_max_table.12 = s32[16,9,384]{...} custom-call(...)``), with the
  result shapes their HLO states;
* idle time by host span — the complement of the first device's busy
  union, each stretch of it put to the innermost ``bench.*`` span open on
  the host at that time;
* device time under a span — the busy union inside the benchmark's spans of
  one name: the device work of the layer that span wraps, whatever its
  programs are named.

``events_of`` reads an ``.xplane.pb`` into plain ``Event`` tuples, so that
the reduction (``reduce_events``) runs on small hand-made traces in the
tests.  Only the operations that are custom calls keep their HLO text.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
OUTSIDE = "outside the benchmark's spans"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _instruction(hlo: str) -> str:
    return hlo.split(" = ", 1)[0]


def events_of(xplane_path: str) -> list[Event]:
    """The device planes' module and op events, and every ``bench.*`` host
    span.  An op keeps its instruction name; a custom call also keeps its
    HLO text under ``hlo``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ops = device and line.name == OPS_LINE
            for e in line.events:
                name = e.name
                if not device and not name.startswith(SPAN_PREFIX):
                    continue
                stats = {}
                if ops:
                    if " custom-call(" in name:
                        stats = {"hlo": name}
                    name = _instruction(name)
                out.append(Event(plane.name, line.name, name, float(e.start_ns), float(e.duration_ns), stats))
    return out


def _union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """(k, 2) disjoint sorted intervals covering the given ones."""
    if not len(starts):
        return np.zeros((0, 2))
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], np.maximum.accumulate(ends[o])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    return np.stack([s[first], e[last]], axis=1)


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted interval sets."""
    total, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            total += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
    return total


def _idle_before(busy: np.ndarray, w0: float, x: np.ndarray) -> np.ndarray:
    """Idle time in [w0, x) for each x, given the sorted busy union."""
    if not len(busy):
        return x - w0
    before = np.concatenate([[0.0], np.cumsum(busy[:, 1] - busy[:, 0])])
    i = np.searchsorted(busy[:, 0], x, side="right") - 1
    inside = np.where(i >= 0, np.minimum(x, busy[np.maximum(i, 0), 1]) - busy[np.maximum(i, 0), 0], 0.0)
    return (x - w0) - (before[np.maximum(i, 0)] * (i >= 0) + inside)


def _innermost(spans: list[Event]):
    """The innermost open span as a step function of time: (segment starts,
    span name or None per segment).  Innermost is the latest-started span
    still open, which is the nesting order on one thread."""
    edges = sorted([(s.start_ns, 1, i) for i, s in enumerate(spans)] + [(s.end_ns, 0, i) for i, s in enumerate(spans)])
    heap: list = []  # (-start, index) of open spans
    closed = set()
    t, names = [], []
    for when, opening, i in edges:
        if opening:
            heapq.heappush(heap, (-spans[i].start_ns, i))
        else:
            closed.add(i)
        while heap and heap[0][1] in closed:
            heapq.heappop(heap)
        t.append(when)
        names.append(spans[heap[0][1]].name if heap else None)
    return np.asarray(t), names


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over the cell's devices
    devices: list  # the device planes read
    programs: dict  # module name -> device seconds in the window
    program_counts: dict
    ops: dict  # "<module>/<instruction>" -> device seconds
    kernels: list  # custom-call Events inside the window
    gaps: dict  # innermost host span -> idle seconds
    busy_intervals: np.ndarray  # the first device's busy union (k, 2), ns
    spans: list  # the benchmark's host spans inside the window

    def kernel_events(self, kernel: str) -> list[Event]:
        """The op events of one Pallas kernel: instructions named after it."""
        pat = re.compile(rf"^%{re.escape(kernel)}(\.\d+)?$")
        return [e for e in self.kernels if pat.match(e.name)]

    def busy_in(self, names) -> float:
        """Device busy seconds while one of the host spans ``names`` was open.
        Each such span calls into the program and waits for its results, so
        the device work it caused lies inside it."""
        sel = [e for e in self.spans if e.name in names]
        cover = _union(np.asarray([e.start_ns for e in sel]), np.asarray([e.end_ns for e in sel]))
        return _overlap(self.busy_intervals, cover) / 1e9

    def span_count(self, name: str) -> int:
        return sum(1 for e in self.spans if e.name == name)

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
        return {"device_ops": top(self.ops), "idle_gaps": top(self.gaps)}


def reduce_events(events: list[Event], n_devices: int = 1) -> Reduced:
    host = [e for e in events if not DEVICE_PLANE.match(e.plane)]
    windows = [e for e in host if e.name == "bench.window"]
    if not windows:
        raise ValueError("no bench.window span in the trace")
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    spans = [e for e in host if e.name != "bench.window" and e.end_ns > w0 and e.start_ns < w1]
    planes = {e.plane for e in events if DEVICE_PLANE.match(e.plane)}
    devices = sorted(planes, key=lambda p: int(DEVICE_PLANE.match(p).group(1)))[:n_devices]
    by_plane: dict = collections.defaultdict(lambda: ([], []))
    for e in events:
        if e.plane in devices and e.end_ns > w0 and e.start_ns < w1:
            by_plane[e.plane][0 if e.line == MODULES_LINE else 1].append(e)
    programs: dict = collections.defaultdict(float)
    counts: dict = collections.defaultdict(int)
    ops: dict = collections.defaultdict(float)
    kernels, unions = [], []
    for dev in devices:
        mods, opev = by_plane[dev]
        mods.sort(key=lambda e: e.start_ns)
        m_start = np.asarray([e.start_ns for e in mods])
        m_name = [re.sub(r"\(\d+\)$", "", e.name) for e in mods]
        for e, name in zip(mods, m_name):
            programs[name] += (min(e.end_ns, w1) - max(e.start_ns, w0)) / 1e9
            counts[name] += 1
        if not opev:  # no op line: the modules stand for the busy time
            opev = mods
        s = np.asarray([e.start_ns for e in opev])
        en = s + np.asarray([e.dur_ns for e in opev])
        s, en = np.maximum(s, w0), np.minimum(en, w1)
        unions.append(_union(s, en))
        if opev is not mods:
            mi = np.searchsorted(m_start, s, side="right") - 1
            for e, m, a, b in zip(opev, mi, s, en):
                ops[f"{m_name[m] if m >= 0 else '?'}/{e.name}"] += (b - a) / 1e9
            kernels += [e for e in opev if e.stats.get("hlo")]
    busy_s = sum(float((u[:, 1] - u[:, 0]).sum()) for u in unions) / 1e9 / max(len(unions), 1)
    busy0 = unions[0] if unions else np.zeros((0, 2))
    gaps: dict = collections.defaultdict(float)
    if unions:
        t, names = _innermost(spans)
        edges = np.clip(np.concatenate([[w0], t, [w1]]), w0, w1)
        idle = _idle_before(busy0, w0, edges)
        for name, dt in zip([None] + names, np.diff(idle)):
            if dt > 0:
                gaps[name or OUTSIDE] += float(dt) / 1e9
    return Reduced((w1 - w0) / 1e9, busy_s, devices, dict(programs), dict(counts), dict(ops), kernels,
                   dict(gaps), busy0, spans)


def reduce_dir(trace_dir: str, n_devices: int = 1) -> Reduced:
    """Reduce the one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return reduce_events(events_of(paths[0]), n_devices)
