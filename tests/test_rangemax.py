"""Sparse-table range-max probes vs brute force.

The scheduling programs' wait path answers "max cumulative demand over an
event window" with two doubling-table lookups (``kernels.rangemax`` +
``device_timeline._range_max_query``) and turns probe instants into index
bounds with binary searches (``device_timeline._count_sorted``).  Both must
be *decision-identical* to the dense per-event pass they replaced, so every
check here is an exact (bitwise) comparison against a brute-force oracle:

* every [l, r) window of random tables vs a naive ``max(x[l:r])`` scan,
* probe counts at boundary keys (exactly at an event key, one key before,
  one key after — the right-open switch keys the programs actually probe),
* the Pallas kernels (interpret mode) vs their jnp twins, bit for bit, in
  the programs' int32 and in float32.

Plus a hypothesis variant over random shapes (skip-shimmed by conftest when
hypothesis is absent).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp
from repro.core.timeline import NEVER
from repro.kernels import rangemax
from repro.kernels.ops import range_max_table
from repro.sim.device_timeline import (
    _NEG,
    _count_sorted,
    _floor_log2_table,
    _range_max_query,
)


def _demand_rows(rng, shape, scale=1000):
    """int32 cumulative-demand-like rows (the scheduling programs' sums)."""
    return np.cumsum(rng.integers(-scale, scale, size=shape), axis=-1).astype(np.int32)


def _brute_table(x: np.ndarray) -> np.ndarray:
    """(B, L) -> (B, P, L) doubling table by definition."""
    B, L = x.shape
    P = rangemax.num_levels(L)
    out = np.full((B, P, L), rangemax.max_identity(x.dtype), dtype=x.dtype)
    for p in range(P):
        span = 1 << p
        for i in range(L):
            out[:, p, i] = x[:, i : i + span].max(axis=1)
    return out


def _query_all_windows(tbl, x):
    """Every [l, r) window answered by the two-lookup read vs naive max."""
    N, _, L = tbl.shape
    log2_tbl = jnp.asarray(_floor_log2_table(L))
    ls, rs = np.meshgrid(np.arange(L + 1), np.arange(L + 1), indexing="ij")
    ls, rs = ls.reshape(-1), rs.reshape(-1)
    got = np.asarray(
        _range_max_query(
            jnp.asarray(tbl),
            log2_tbl,
            jnp.asarray(np.broadcast_to(ls, (N, len(ls)))),
            jnp.asarray(np.broadcast_to(rs, (N, len(rs)))),
        )
    )
    for q, (l, r) in enumerate(zip(ls, rs)):
        want = x[:, l:r].max(axis=1) if r > l else np.full(x.shape[0], _NEG)
        np.testing.assert_array_equal(got[:, q], want, err_msg=f"window [{l}, {r})")


def test_table_levels_match_brute_force():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 37)).astype(np.float32)
    x[0, 30:] = -np.inf  # padded tail, the programs' fill
    tbl = np.asarray(rangemax.table_levels_jnp(jnp.asarray(x)))
    np.testing.assert_array_equal(tbl, _brute_table(x).astype(np.float32))


def test_every_window_exact():
    rng = np.random.default_rng(1)
    x = _demand_rows(rng, (2, 19))
    tbl = np.asarray(range_max_table(jnp.asarray(x)))
    assert tbl.dtype == np.int32
    _query_all_windows(tbl, x)


def test_pallas_kernel_matches_jnp_twin():
    rng = np.random.default_rng(2)
    # tile-aligned and ragged shapes; ops.range_max_table pads the latter
    for B, L in ((8, 128), (5, 37), (16, 300)):
        for x in (rng.normal(size=(B, L)).astype(np.float32), _demand_rows(rng, (B, L))):
            got = np.asarray(range_max_table(jnp.asarray(x)))
            want = np.asarray(rangemax.table_levels_jnp(jnp.asarray(x)))
            assert got.dtype == x.dtype
            np.testing.assert_array_equal(got, want)


def test_compact_events_int32_keys_exact():
    """int32 key rows (the scheduling programs' time representation) run
    the compaction kernel in int32: exact stable front-compaction with
    (NEVER, 0) identities behind, keys above 2**24 intact."""
    from repro.kernels.ops import compact_events

    big = 2**30 + 1  # not representable in float32
    t = jnp.asarray(np.array([[1, big, big + 2, NEVER]]), jnp.int32)
    d = jnp.asarray(np.array([[5, -5, 7, 0]]), jnp.int32)
    keep = jnp.asarray(np.array([[False, True, True, False]]))
    out_t, out_d = compact_events(t, d, keep)
    assert out_t.dtype == jnp.int32 and out_d.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out_t)[0], [big, big + 2, NEVER, NEVER])
    np.testing.assert_array_equal(np.asarray(out_d)[0], [-5, 7, 0, 0])


def test_kernels_refuse_other_dtypes():
    """No silent fallback: rows the kernels cannot take raise instead of
    leaving the kernel path."""
    from repro.kernels.ops import compact_events

    x = jnp.zeros((2, 8), jnp.int16)
    with pytest.raises(TypeError):
        range_max_table(x)
    with pytest.raises(TypeError):
        compact_events(x, x, x != 0)
    with pytest.raises(TypeError):
        range_max_table(jnp.zeros((2, 3, 8), jnp.int32))


def test_count_sorted_boundary_epsilon():
    """Counts at event keys, one key before and one key after — the exact
    probe placements the scheduling programs use."""
    rng = np.random.default_rng(3)
    t = np.sort(rng.integers(0, 200_000, size=11)).astype(np.int32)
    t[7] = t[6]  # tied event keys
    tl = np.full((1, 16), NEVER, np.int32)
    tl[0, : len(t)] = t
    probes = np.concatenate([t, t - 1, t + 1, [-1, NEVER - 1]]).astype(np.int32)
    q = np.broadcast_to(probes, (1, len(probes)))
    got_le = np.asarray(_count_sorted(jnp.asarray(tl), lambda v: v <= jnp.asarray(q), (1, len(probes))))
    got_lt = np.asarray(_count_sorted(jnp.asarray(tl), lambda v: v < jnp.asarray(q), (1, len(probes))))
    np.testing.assert_array_equal(got_le[0], np.searchsorted(t, probes, side="right"))
    np.testing.assert_array_equal(got_lt[0], np.searchsorted(t, probes, side="left"))


def test_count_sorted_offset_predicate():
    """The wait path's segment predicate ``(t - c) <= b`` bisects on the
    subtract-then-compare form — it must equal the dense compare-count of
    the same expression, ``NEVER`` padding included (``NEVER - c`` cannot
    wrap for a clock c >= 0)."""
    rng = np.random.default_rng(4)
    t = np.sort(rng.integers(0, 100_000, size=13)).astype(np.int32)
    tl = np.full((1, 16), NEVER, np.int32)
    tl[0, : len(t)] = t
    for c, b in [(int(t[4]), 14_600), (2, 0), (int(t[0]), 1), (0, NEVER - 1)]:
        pred = lambda v: (v - c) <= b  # noqa: E731
        got = int(np.asarray(_count_sorted(jnp.asarray(tl), pred, (1, 1)))[0, 0])
        want = int(np.sum((tl[0].astype(np.int64) - c) <= b))
        assert got == want, (c, b)


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**31 - 1), st.integers(1, 40), st.integers(1, 4))
def test_property_windows_exact(seed, L, B):
    rng = np.random.default_rng(seed)
    x = _demand_rows(rng, (B, L), scale=int(rng.choice([10, 1_000_000])))
    x[rng.random(size=x.shape) < 0.2] = _NEG  # masked mid-tie positions
    tbl = np.asarray(range_max_table(jnp.asarray(x)))
    _query_all_windows(tbl, x)


@pytest.mark.parametrize("L", [1, 2, 3, 8, 100])
def test_num_levels_covers_all_windows(L):
    P = rangemax.num_levels(L)
    # the longest window (length L) must be answerable: floor(log2(L)) < P
    assert (1 << (P - 1)) <= L < (1 << P)
