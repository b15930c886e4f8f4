"""Main-path programs compile for a TPU v5e chip, with no float64 in them.

Nothing here runs on a chip: each test lowers a program for one chip of a
described ``v5e:2x2`` topology and compiles it with the TPU compiler that is
installed with jax.  That catches what the CPU backend and the Pallas
interpreter accept but the chip refuses — float64 ops such as ``nextafter``
and bitcasts, Pallas primitives without a TPU lowering (an in-kernel
``cumsum``), a kernel that silently falls back to jnp — before any chip time
is spent.  The topology is described inside a module-scoped fixture (never
at import: only one process at a time may hold the TPU library), and the
persistent compilation cache is off around these compiles, since an entry
written for a described chip cannot be read back without one.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

K = 4  # allocation segments (the cluster ladders' and the serve model's k)
N = 16  # cluster nodes, as in chip_smoke.py


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_compile(one_chip):
    """``compile(fn, *specs)`` -> the compiled program's HLO text, for one
    described chip.  Kernels lower compiled (the backend here is the CPU,
    which would otherwise pick interpret mode) and the persistent cache is
    off for the duration."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.kernels import ops

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_use_interpret", lambda kernel: False)

        def compile_(fn, *shapes):
            args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
            lowered = jax.jit(fn).lower(*args)
            text = lowered.as_text()
            assert "f64" not in text, "a float64 array in a main-path program"
            assert "nextafter" not in text, "nextafter in a main-path program"
            return lowered.compile().as_text()

        yield compile_
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


I32, F32, BOOL = jnp.int32, jnp.float32, jnp.bool_


@pytest.mark.parametrize("L", [512, 1024])
def test_range_max_kernel_compiles(chip_compile, L):
    from repro.kernels.ops import range_max_table

    hlo = chip_compile(range_max_table, ((N, L), I32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("L", [512, 1024])
def test_compaction_kernel_compiles(chip_compile, L):
    from repro.kernels.ops import compact_events

    hlo = chip_compile(compact_events, ((N, L), I32), ((N, L), I32), ((N, L), BOOL))
    assert "tpu_custom_call" in hlo


def test_schedule_epoch_compiles(chip_compile):
    """The scheduling-epoch program, with the range-max kernel inside."""
    from repro.sim.device_timeline import _schedule_program

    L, H, W = 128, 64, 8
    hlo = chip_compile(
        _schedule_program,
        ((N, L), I32), ((N, L), I32), ((N,), I32), ((H,), I32), ((), I32), ((), I32),
        ((W, K), I32), ((W, K), I32), ((W,), I32), ((W,), I32), ((W,), BOOL), ((), I32),
    )
    assert "tpu_custom_call" in hlo


def test_sweep_schedule_compiles(chip_compile):
    """The lane-vmapped sweep, with the compaction kernel inside."""
    from repro.sim.device_timeline import _sweep_program

    S, R, L = 4, 128, 256
    hlo = chip_compile(
        functools.partial(_sweep_program, L=L),
        ((S, R, K), I32), ((S, R, K), I32), ((S, R), I32), ((S, R), I32), ((S, R), BOOL),
        ((S, N), BOOL), ((S,), I32),
    )
    assert "tpu_custom_call" in hlo


def test_admission_epoch_compiles(chip_compile):
    from repro.sim.device_timeline import admission_epoch

    S, L, Lp, Smax, Cb, Rb = 4, 256, 128, 64, 16, 8
    chip_compile(
        admission_epoch(1, Lp),
        ((S,), I32), ((S, L), I32), ((S, L), I32), ((S, L), I32), ((S, Smax), I32),
        ((S, Rb), I32), ((S, Cb), I32), ((S, Cb), I32), ((S, Cb), I32),
        ((S, Cb, K), I32), ((S, Cb, K), I32), ((S, Cb), I32), ((S, Cb), BOOL),
        ((), I32), ((), I32),
    )


@pytest.mark.parametrize("variant", ["shared", "pernode"])
def test_first_fit_window_compiles(chip_compile, variant):
    from repro.sim import device_timeline as dt

    Pp, W = 128, 32
    program = {"shared": dt._window_program_shared, "pernode": dt._window_program_pernode}[variant](N)
    P = ((Pp,), I32) if variant == "shared" else ((N, Pp), I32)
    chip_compile(
        program, P, ((N, Pp), I32), ((), I32), ((W,), I32), ((W,), I32),
        ((W, K), I32), ((W, K), I32), ((W,), BOOL), ((), I32),
    )


def test_admission_program_compiles(chip_compile):
    from repro.sim.device_timeline import admission_program

    Pp, C = 128, 32
    chip_compile(
        admission_program(),
        ((Pp,), I32), ((Pp,), I32), ((C,), I32), ((C,), I32), ((C,), I32),
        ((C, K), I32), ((C, K), I32), ((C, K + 1), I32), ((C, K), I32), ((C, K), BOOL),
        ((C,), BOOL), ((), I32),
    )


@pytest.mark.parametrize("program", ["grid", "ladders"])
def test_ladder_scan_compiles(chip_compile, program):
    """The predictor's float32 scan: the fig7 grid and the cluster ladders."""
    from repro.sim.batch_engine import GRID_METHODS, _ladder_batched, _lane_batched

    lanes, B, T = 2, 64, 256
    if program == "grid":
        fn = _lane_batched(GRID_METHODS, K, 2.0, 2.0, 100.0, 128 * 1024.0, "insample", 64)
    else:
        fn = _ladder_batched(
            ("default", "ksegments-selective"), K, 2.0, 2.0, 100.0, 128 * 1024.0, 32, False
        )
    chip_compile(
        fn, ((lanes, B), F32), ((lanes, B, T), F32), ((lanes, B), I32), ((lanes,), F32), ((), I32)
    )


def test_timeline_units_fit_int32():
    """The integer units the programs run in fit int32 at the horizon."""
    from repro.core.timeline import HORIZON_TICKS, MAX_UNITS, NEVER, time_keys

    assert 2 * (HORIZON_TICKS - 1) + 1 < NEVER == np.iinfo(np.int32).max
    assert MAX_UNITS * 128 <= np.iinfo(np.int32).max + 1
    with pytest.raises(OverflowError):
        time_keys(HORIZON_TICKS / 1000.0)
