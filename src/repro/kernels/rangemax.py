"""Pallas TPU kernel: doubling (sparse-table) range-max levels over batches
of sorted-event demand rows.

The cluster scheduler's wait path re-probes a blocked row against every
node's demand profile each time the clock advances to a pending completion.
The sparse-table formulation builds, once per frozen profile, the classic
range-max doubling table over the per-event cumulative demand — level ``p``
at position ``i`` holds ``max(x[i : i + 2**p])`` — so each re-probe window
collapses to two table lookups (O(log E)) instead of a dense pass over all
events (see ``repro.sim.device_timeline``).

TPU adaptation: rows are tiled 8-sublane blocks with the whole event axis
resident in VMEM (event axes are bucketed to a few hundred entries, far
under the lane budget), so all ``P = floor(log2(L)) + 1`` levels are
computed from one HBM read per row: each level is a circular lane roll of
the previous one, masked past the row end with the -inf identity.

The kernel keeps its input dtype (the scheduling programs' int32 demand
sums, or float32), with the dtype's max identity as the fill.  The jnp twin
(``table_levels_jnp``) is the same recurrence, the reference the tests
compare the kernel with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU-native tile: 8 sublanes; the event axis stays lane-resident per block.
BLOCK_B = 8
LANE = 128



def max_identity(dtype):
    """The max identity of ``dtype`` as a plain Python scalar (jnp constants
    would be captured): -inf for floats, the minimum for integers."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return float("-inf")
    return int(jnp.iinfo(dtype).min)


def num_levels(L: int) -> int:
    """Levels needed to answer any [l, r) window over an ``L``-long axis:
    ``floor(log2(L)) + 1`` (level p spans ``2**p`` elements)."""
    assert L >= 1
    return max(L.bit_length() - 1, 0) + 1


def table_levels_jnp(x: jax.Array) -> jax.Array:
    """(..., L) -> (..., P, L) doubling range-max table (any dtype).

    ``out[..., p, i] = max(x[..., i : i + 2**p])``; positions whose span
    runs past the end hold the max of the in-range suffix (queries never
    read them with a longer span than the window, so the tail values only
    need to be <= the true max over any window containing them — which a
    max-identity fill guarantees).
    """
    L = x.shape[-1]
    P = num_levels(L)
    neg = jnp.asarray(max_identity(x.dtype), x.dtype)
    levels = [x]
    span = 1
    for _ in range(1, P):
        prev = levels[-1]
        pad = jnp.broadcast_to(neg, (*prev.shape[:-1], span))
        shifted = jnp.concatenate([prev[..., span:], pad], axis=-1)
        levels.append(jnp.maximum(prev, shifted))
        span *= 2
    return jnp.stack(levels, axis=-2)


def _rangemax_kernel(x_ref, out_ref, *, P: int, L: int, neg):
    """Grid (B/BLOCK_B,); one block computes every level of its rows."""
    x = x_ref[...]  # (BLOCK_B, L)
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    out_ref[:, 0, :] = x
    span = 1
    for p in range(1, P):
        # level p = max of two level p-1 spans offset by 2**(p-1): a circular
        # lane roll (Mosaic-native) with the wrapped tail masked to the
        # max identity
        rolled = pltpu.roll(x, L - span, 1)
        x = jnp.maximum(x, jnp.where(pos < L - span, rolled, neg))
        out_ref[:, p, :] = x
        span *= 2


def rangemax_pallas(x: jax.Array, *, interpret: bool) -> jax.Array:
    """Raw pallas_call wrapper: (B, L) int32 or float32 -> (B, P, L) table
    levels in the input dtype.

    Requires B % BLOCK_B == 0 and L % LANE == 0 (ops.py pads).
    """
    B, L = x.shape
    assert B % BLOCK_B == 0 and L % LANE == 0, (B, L)
    P = num_levels(L)
    grid = (B // BLOCK_B,)
    return pl.pallas_call(
        functools.partial(_rangemax_kernel, P=P, L=L, neg=max_identity(x.dtype)),
        grid=grid,
        in_specs=[pl.BlockSpec((BLOCK_B, L), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_B, P, L), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, P, L), x.dtype),
        interpret=interpret,
        name="range_max_table",
    )(x)
