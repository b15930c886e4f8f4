"""The reduction from a trace to busy time, program and op time, and idle
gaps, on small traces whose answers are worked out by hand, and on one
recorded on the chip."""

import dataclasses
import os

import pytest

from bench import trace
from bench.trace import Event

MS = 1e6  # ns


def _ev(plane, line, name, start_ms, dur_ms, **stats):
    return Event(plane, line, name, start_ms * MS, dur_ms * MS, stats)


D, H = "/device:TPU:0", "/host:CPU"
RMT = "%range_max_table.3 = s32[8,10,512]{2,1,0:T(8,128)} custom-call(s32[8,512]{1,0} %x), custom_call_target=\"tpu_custom_call\""


def _hand_trace():
    return [
        _ev(H, "python", "bench.window", 10, 100),
        _ev(H, "python", "bench.call", 10, 60),
        _ev(H, "python", "bench.place.loop", 20, 40),
        _ev(H, "python", "bench.call", 70, 40),
        # modules: one straddles the window's start, two share a name
        _ev(D, "XLA Modules", "jit__schedule_program(12)", 5, 10),
        _ev(D, "XLA Modules", "jit_run(7)", 30, 10),
        _ev(D, "XLA Modules", "jit_run(7)", 80, 20),
        # ops: overlapping pairs merge into one busy interval
        _ev(D, "XLA Ops", "%fusion.1", 5, 10),
        _ev(D, "XLA Ops", "%fusion.2", 30, 6),
        _ev(D, "XLA Ops", "%range_max_table.3", 34, 6, hlo=RMT),
        _ev(D, "XLA Ops", "%copy-start.2", 36, 1),
        _ev(D, "XLA Ops", "%fusion.2", 80, 20),
    ]


def test_busy_idle_programs_and_ops():
    r = trace.reduce_events(_hand_trace())
    assert r.window_s == pytest.approx(0.100)
    # busy: [10, 15) + [30, 40) + [80, 100) inside [10, 110)
    assert r.busy_s == pytest.approx(0.035)
    assert r.programs == pytest.approx({"jit__schedule_program": 0.005, "jit_run": 0.030})
    assert r.program_counts == {"jit__schedule_program": 1, "jit_run": 2}
    assert r.ops == pytest.approx({
        "jit__schedule_program/%fusion.1": 0.005,
        "jit_run/%fusion.2": 0.026,
        "jit_run/%range_max_table.3": 0.006,
        "jit_run/%copy-start.2": 0.001,
    })
    assert [e.name for e in r.kernel_events("range_max_table")] == ["%range_max_table.3"]
    assert r.kernel_events("compact_events") == []


def test_idle_time_goes_to_the_innermost_open_span():
    r = trace.reduce_events(_hand_trace())
    # idle [15, 30) and [40, 60) are under place.loop from 20 to 60, the rest
    # of [15, 20), [60, 80) and [100, 110) under the two calls
    assert r.gaps == pytest.approx({"bench.place.loop": 0.030, "bench.call": 0.035})
    assert r.busy_s + sum(r.gaps.values()) == pytest.approx(r.window_s)
    b = r.breakdown()
    assert b["idle_gaps"][0] == ["bench.call", pytest.approx(0.035)]
    assert [name for name, _ in b["device_ops"]][:2] == ["jit_run/%fusion.2", "jit_run/%range_max_table.3"]


def test_device_time_under_a_span():
    r = trace.reduce_events(_hand_trace())
    # place.loop [20, 60) holds busy [30, 40); the calls hold all 35 ms
    assert r.busy_in({"bench.place.loop"}) == pytest.approx(0.010)
    assert r.busy_in({"bench.call"}) == pytest.approx(0.035)
    assert r.busy_in({"bench.ladder"}) == 0.0
    assert r.span_count("bench.call") == 2


def test_a_gap_outside_every_span():
    evs = [_ev(H, "python", "bench.window", 0, 10), _ev(D, "XLA Ops", "f", 2, 2)]
    r = trace.reduce_events(evs)
    assert r.gaps == pytest.approx({trace.OUTSIDE: 0.008})
    assert r.busy_s == pytest.approx(0.002)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events([_ev(D, "XLA Ops", "f", 2, 2)])


def test_roofline_share_from_the_kernel_shapes():
    from bench import common, kernel_bytes
    from bench.roofline import hbm_share

    r = trace.reduce_events(_hand_trace())
    peaks = {"devices": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}}
    ctx = common.Context(r, {}, 1, peaks, "TPU v5 lite")
    moved = kernel_bytes.range_max_table_bytes([("s32", (8, 10, 512))])
    assert moved == 4 * 8 * 512 * 11
    assert hbm_share(ctx, "range_max_table") == pytest.approx(100 * moved / 819e9 / 0.006)
    assert hbm_share(ctx, "compact_events") is None
    with pytest.raises(KeyError):
        hbm_share(common.Context(r, {}, 1, peaks, "TPU v4"), "range_max_table")


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "range_max_table.xplane.pb")


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: one
    ``range_max_table`` call on (16, 512) int32 rows.  The device's clock in it
    reads about 0.8 ms before the host's: the kernel's op starts before the
    host opened the span that called it, so read as it is the call falls
    before the window.  Moved onto the host's clock, the op is the window's
    busy time and the kernel's roofline share follows from its shapes."""
    from bench import common, kernel_bytes
    from bench.roofline import hbm_share

    evs = trace.events_of(FIXTURE)
    device = [e for e in evs if trace.DEVICE_PLANE.match(e.plane)]
    (k,) = [e for e in device if e.stats.get("hlo")]
    assert k.name == "%range_max_table.1"
    result = kernel_bytes.shapes_of(k.stats["hlo"].split(" = ", 1)[1].split(" custom-call(", 1)[0])
    assert result == [("s32", (16, kernel_bytes.num_levels(512), 512))]
    call = next(e for e in evs if e.name == "bench.call")
    assert k.start_ns < call.start_ns

    r = trace.reduce_events(evs)
    assert r.devices == ["/device:TPU:0"] and r.busy_s == 0.0
    assert r.busy_s + sum(r.gaps.values()) == pytest.approx(r.window_s)

    shift = call.start_ns - min(e.start_ns for e in device)
    moved = trace.reduce_events([dataclasses.replace(e, start_ns=e.start_ns + shift) if e in device else e for e in evs])
    ops = [e for e in device if e.line == trace.OPS_LINE]
    assert moved.busy_s == pytest.approx(sum(e.dur_ns for e in ops) / 1e9)
    assert moved.busy_in({"bench.call"}) == pytest.approx(moved.busy_s)
    ctx = common.Context(moved, {}, 1, {"devices": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}}, "TPU v5 lite")
    share = hbm_share(ctx, "range_max_table")
    assert share == pytest.approx(100 * kernel_bytes.range_max_table_bytes(result) / 819e9 / (k.dur_ns / 1e9))
    assert 0 < share < 100
