"""Pallas TPU kernel: stream compaction of carried demand-event timelines.

The whole-run sweep program (``sim.device_timeline._sweep_lane``) carries one
sorted (key, delta) event row per node in its scan state.  At every
``_SWEEP_W``-row chunk boundary it folds events at or before the lane clock
into a scalar base and drops every surviving event with a zero delta — zero
steps from capped flat profiles and equal-value runs.  What remains is the
set of demand-shape-changing breakpoints, so the carried axis stays sized by
*live breakpoints* instead of every event the run ever placed.

The scatter/compact step itself is this kernel: given a keep mask, move the
kept entries to the front of each row (stable, order-preserving) and pad the
tail with the timeline identities (``fill`` time, zero delta).

TPU adaptation: Pallas TPU has no scan primitive, so both passes are
log-step lane rolls over the VMEM-resident row block (``pltpu.roll``, the
Mosaic-native lane rotate):

* the shift of each kept entry — the number of dropped entries before it —
  is an inclusive prefix sum of the drop mask (Hillis-Steele: ``log2 L``
  roll-and-add steps) minus the entry's own drop bit;
* entries then move left by their shift one bit at a time, least
  significant first.  Shifts are non-decreasing along the kept entries and
  two kept entries ``a < b`` stay ``rank_b - rank_a >= 1`` slots apart after
  every step, so no two entries ever land on one slot.

The kernel only moves values — it adds nothing to a kept one — so it works
in any 32-bit dtype.  The jnp twin (``compact_events_jnp``) is the
rank-scatter reference the tests compare it with.
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


def compact_events_jnp(
    tl_t: jax.Array, tl_d: jax.Array, keep: jax.Array, fill
) -> tuple[jax.Array, jax.Array]:
    """(N, L) sorted event rows + keep mask -> front-compacted rows.

    Kept entries keep their relative order (ranks are monotone in the source
    index), dropped and padding slots become (``fill``, 0).  A pure
    permutation: no value is recomputed, so the surviving prefix is
    identical to the input's kept subsequence in any dtype.
    """
    L = tl_t.shape[-1]
    tgt = jnp.where(keep, jnp.cumsum(keep, axis=-1) - 1, L)  # L = dropped
    rows = jnp.arange(tl_t.shape[0])[:, None]
    t2 = jnp.full_like(tl_t, fill).at[rows, tgt].set(tl_t, mode="drop")
    d2 = jnp.zeros_like(tl_d).at[rows, tgt].set(tl_d, mode="drop")
    return t2, d2


def _compact_kernel(t_ref, d_ref, k_ref, to_ref, do_ref, *, L: int, fill):
    """Grid (B/BLOCK_B,); one block compacts its rows in place in VMEM."""
    t = t_ref[...]  # (BLOCK_B, L)
    d = d_ref[...]
    keep = k_ref[...] != 0
    pos = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    drop = jnp.where(keep, 0, 1).astype(jnp.int32)
    # inclusive prefix count of dropped slots (roll by s: x[i - s])
    c = drop
    span = 1
    while span < L:
        c = c + jnp.where(pos >= span, pltpu.roll(c, span, 1), 0)
        span *= 2
    sh = jnp.where(keep, c - drop, 0)  # slots each kept entry moves left
    live = keep.astype(jnp.int32)
    span = 1
    while span < L:
        # roll by L - s brings x[i + s] to slot i; the wrapped tail is masked
        nxt = pos < L - span
        moving = live * ((sh & span) != 0).astype(jnp.int32)
        arrive = jnp.where(nxt, pltpu.roll(moving, L - span, 1), 0) != 0
        t = jnp.where(arrive, pltpu.roll(t, L - span, 1), t)
        d = jnp.where(arrive, pltpu.roll(d, L - span, 1), d)
        sh = jnp.where(arrive, pltpu.roll(sh, L - span, 1), sh)
        live = jnp.where(arrive | ((live != 0) & (moving == 0)), 1, 0).astype(jnp.int32)
        span *= 2
    to_ref[...] = jnp.where(live != 0, t, fill)
    do_ref[...] = jnp.where(live != 0, d, jnp.zeros_like(d))


def compact_pallas(
    t: jax.Array, d: jax.Array, keep: jax.Array, fill, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    """Raw pallas_call wrapper: (B, L) times/deltas (32-bit, kept in their
    dtype) + int32 keep mask -> front-compacted (B, L) times/deltas, with
    ``fill`` behind the kept prefix.

    Requires B % BLOCK_B == 0 and L % LANE == 0 (ops.py pads).
    """
    B, L = t.shape
    assert B % BLOCK_B == 0 and L % LANE == 0, (B, L)
    spec = pl.BlockSpec((BLOCK_B, L), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_compact_kernel, L=L, fill=fill),
        grid=(B // BLOCK_B,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, L), t.dtype),
            jax.ShapeDtypeStruct((B, L), d.dtype),
        ],
        interpret=interpret,
        name="compact_events",
    )(t, d, keep.astype(jnp.int32))
