"""Layer-2 audit primitives: CompileCounter, the no_recompiles guard, the
scan-carry dtype checker and the closure-constant walk — against tiny
throwaway programs whose compile behaviour is fully controlled here.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from repro.analysis.trace_audit import (  # noqa: E402
    CompileCounter,
    RecompileError,
    check_scan_carry_stability,
    closure_constants,
    no_recompiles,
    scan_carries,
)


def test_counter_sees_cold_trace_then_silent_warm():
    @jax.jit
    def f(x):
        return jnp.cumsum(x) * 2.0

    x = jnp.arange(7.0)
    with CompileCounter() as cold:
        f(x).block_until_ready()
    assert cold.traces >= 1
    # with a persistent compilation cache on, the backend compile may be answered by
    # the persistent cache instead — either way the counter must see it
    assert cold.compiles >= 1 or cold.cache_hits >= 1

    x2 = x + 1.0  # eager add compiles here, OUTSIDE the warm counter
    with CompileCounter() as warm:
        f(x2).block_until_ready()  # same shape/dtype: jit-cache hit
    assert warm.snapshot() == {
        "traces": 0,
        "compiles": 0,
        "cache_hits": 0,
        "cache_misses": 0,
    }


def test_counter_detects_shape_driven_retrace():
    @jax.jit
    def f(x):
        return x.sum()

    f(jnp.ones(4))
    with CompileCounter() as cc:
        f(jnp.ones(5))  # new shape: must retrace
    assert cc.traces >= 1


def test_counter_stops_counting_after_exit():
    @jax.jit
    def f(x):
        return x * x

    with CompileCounter() as cc:
        pass
    f(jnp.ones(3))  # fresh compile AFTER the context closed
    assert cc.traces == 0 and cc.compiles == 0


def test_no_recompiles_passes_warm_and_raises_cold():
    @jax.jit
    def f(x):
        return x - 1.0

    warm_in = jnp.zeros(6)
    f(jnp.ones(6))
    with no_recompiles("warm repeat"):
        f(warm_in)

    with pytest.raises(RecompileError, match="retrace"):
        with no_recompiles("cold section"):
            f(jnp.ones(9))  # new shape inside the guard


def test_no_recompiles_allowance():
    @jax.jit
    def f(x):
        return x + 2.0

    cold_in = jnp.ones(11)
    # one fresh pjit call logs two jaxpr_trace events on jax 0.4.37 (the
    # abstract trace and the lowering pass) — the allowance is per event
    with no_recompiles("first compile allowed", allow_traces=2, allow_compiles=1):
        f(cold_in)


def test_no_recompiles_fixture(no_recompiles):
    @jax.jit
    def f(x):
        return x * 3.0

    warm_in = jnp.ones(13) * 2
    f(jnp.ones(13))
    with no_recompiles("fixture warm"):
        f(warm_in)


# ---------------------------------------------------------------------------
# jaxpr structure checks
# ---------------------------------------------------------------------------


def test_scan_carries_reports_nested_dtypes():
    def step(c, x):
        s, n = c
        return (s + x, n + 1), s

    @jax.jit
    def run(xs):
        return lax.scan(step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)), xs)

    reps = scan_carries(run, jnp.ones(5, jnp.float32))
    scan_reps = [r for r in reps if r.primitive == "scan"]
    assert {r.dtype for r in scan_reps} == {"float32", "int32"}
    assert all(r.shape == () for r in scan_reps)


def test_carry_stability_flags_forbidden_dtype():
    def step(c, x):
        return c + x.astype(c.dtype), None

    def run32(xs):
        return lax.scan(step, jnp.zeros((), jnp.float32), xs)

    def run64(xs):
        return lax.scan(step, jnp.zeros((), jnp.float64), xs)

    xs = jnp.ones(4, jnp.float32)
    assert check_scan_carry_stability(run32, xs, forbid_dtypes=("float32",))
    with jax.enable_x64(True):
        xs64 = jnp.ones(4, jnp.float64)
        assert not check_scan_carry_stability(run64, xs64, forbid_dtypes=("float32",))


def test_closure_constants_flags_only_giants():
    big = np.ones((1 << 16,), np.float32)  # 256 KiB
    small = np.ones((8,), np.float32)

    def with_big(x):
        return x + jnp.asarray(big)

    def with_small(x):
        return x * jnp.asarray(small)

    giants = closure_constants(with_big, jnp.ones(1 << 16), min_bytes=1 << 17)
    assert len(giants) == 1 and giants[0].nbytes == big.nbytes

    assert closure_constants(with_small, jnp.ones(8), min_bytes=1 << 17) == []


def test_closure_constants_recurses_into_scan():
    table = np.arange(1 << 15, dtype=np.float32)  # 128 KiB, captured in the body

    def step(c, x):
        return c + jnp.asarray(table)[0] * x, None

    def run(xs):
        return lax.scan(step, jnp.zeros(()), xs)

    giants = closure_constants(run, jnp.ones(3), min_bytes=1 << 16)
    assert any(g.nbytes == table.nbytes for g in giants)
