"""Jitted public wrappers around the Pallas kernels.

Handles padding to TPU tile multiples, sentinel finalization, and backend
selection: on a TPU every kernel runs compiled; on the CPU backend (the
test suite) the same kernel bodies run in interpret mode.  There is no
switch between the two and no silent fallback to a jnp twin: a kernel on
the main path runs in that path's own dtype, and an input it cannot take
raises.  ``kernel_traces`` reports which kernels were lowered and how.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.kernels import compaction as _compaction
from repro.kernels import fitstats as _fitstats
from repro.kernels import rangemax as _rangemax
from repro.kernels import segmax as _segmax
from repro.kernels import wastage as _wastage

MIB_PER_GIB = 1024.0


_TRACED: collections.Counter = collections.Counter()  # (kernel, mode) -> traces


def _use_interpret(kernel: str) -> bool:
    """Interpret mode on the CPU backend only; compiled everywhere else.
    Counts the call (a trace, inside a jitted program) under ``kernel``."""
    interpret = jax.default_backend() == "cpu"
    _TRACED[(kernel, "interpret" if interpret else "compiled")] += 1
    return interpret


def kernel_traces() -> dict[tuple[str, str], int]:
    """``(kernel, mode) -> count`` of the kernel wrappers this process has
    traced or called, mode "compiled" or "interpret": what ``chip_smoke.py``
    diffs per phase to show that the main path's kernels ran compiled."""
    return dict(_TRACED)


def _check_kernel_input(kernel: str, x: jax.Array) -> None:
    """The kernels take 2-D int32 or float32 rows; anything else is a caller
    bug, not a reason to leave the kernel path."""
    if x.ndim != 2 or x.dtype not in (jnp.int32, jnp.float32):
        raise TypeError(f"{kernel} takes 2-D int32/float32 rows, got {x.dtype}{x.shape}")


def _pad_rows(a: jax.Array, mult: int, fill=0):
    B = a.shape[0]
    pad = (-B) % mult
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


def _pad_cols(a: jax.Array, mult: int, fill=0):
    T = a.shape[1]
    pad = (-T) % mult
    if pad == 0:
        return a
    return jnp.pad(a, [(0, 0), (0, pad)], constant_values=fill)


def segment_peaks(y: jax.Array, lengths: jax.Array, k: int) -> jax.Array:
    """(B, T) padded series + (B,) lengths -> (B, k) segment peaks.

    Matches ``core.segmentation.segment_peaks`` (the jnp oracle): empty
    segments inherit the running peak from the left.
    """
    return _segment_peaks_jit(y, lengths, k, interpret=_use_interpret("segment_peaks"))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _segment_peaks_jit(y: jax.Array, lengths: jax.Array, k: int, *, interpret: bool) -> jax.Array:
    B = y.shape[0]
    yp = _pad_cols(_pad_rows(y, _segmax.BLOCK_B), _segmax.BLOCK_T)
    lp = _pad_rows(jnp.maximum(lengths, 1), _segmax.BLOCK_B, fill=1)
    peaks = _segmax.segmax_pallas(yp, lp, k, interpret=interpret)[:B]
    # forward-fill empty segments (sentinel -big) with the previous segment's
    # peak (matching core.segmentation semantics)
    neg = peaks <= jnp.float32(-1.0e38)
    pos = jnp.arange(k)[None, :]
    last_idx = jax.lax.cummax(jnp.where(~neg, pos, -1), axis=1)
    filled = jnp.take_along_axis(peaks, jnp.maximum(last_idx, 0), axis=-1)
    out = jnp.where(neg, filled, peaks)
    return jnp.where(out <= jnp.float32(-1.0e38), 0.0, out)


def fit_stats(x: jax.Array, peaks: jax.Array, valid: jax.Array) -> jax.Array:
    """(B,) inputs + (B, k) segment peaks + (B,) mask -> (k, 5) OLS bank.

    ``x`` should be pre-shifted (u = x - x0) for f32 conditioning.
    """
    return _fit_stats_jit(x, peaks, valid, interpret=_use_interpret("fit_stats"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fit_stats_jit(x: jax.Array, peaks: jax.Array, valid: jax.Array, *, interpret: bool) -> jax.Array:
    xp = _pad_rows(x.reshape(-1), _fitstats.BLOCK_B)
    pp = _pad_rows(peaks, _fitstats.BLOCK_B)
    vp = _pad_rows(valid.astype(jnp.float32).reshape(-1), _fitstats.BLOCK_B)
    return _fitstats.fitstats_pallas(xp, pp, vp, interpret=interpret)


def attempt_wastage(
    y: jax.Array,
    lengths: jax.Array,
    bounds: jax.Array,
    values: jax.Array,
    interval_s: float,
) -> tuple[jax.Array, jax.Array]:
    """Batch attempt scoring -> (wastage GiB*s (B,), failure index (B,), -1 on success).

    Matches ``core.allocation.attempt_outcomes_batch`` / ``score_attempt_np``.
    """
    return _attempt_wastage_jit(
        y, lengths, bounds, values, interval_s, interpret=_use_interpret("attempt_wastage")
    )


@functools.partial(jax.jit, static_argnames=("interval_s", "interpret"))
def _attempt_wastage_jit(
    y: jax.Array,
    lengths: jax.Array,
    bounds: jax.Array,
    values: jax.Array,
    interval_s: float,
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    B = y.shape[0]
    yp = _pad_cols(_pad_rows(y, _wastage.BLOCK_B), _wastage.BLOCK_T)
    lp = _pad_rows(jnp.maximum(lengths, 0), _wastage.BLOCK_B)
    bp = _pad_rows(bounds, _wastage.BLOCK_B)
    vp = _pad_rows(values, _wastage.BLOCK_B)
    raw = _wastage.wastage_pallas(yp, lp, bp, vp, interval_s, interpret=interpret)[:B]
    failed = raw[:, 3] > 0.0
    waste = jnp.where(failed, raw[:, 1], raw[:, 0]) * interval_s / MIB_PER_GIB
    fail_idx = jnp.where(failed, raw[:, 2].astype(jnp.int32), -1)
    return waste, fail_idx


def range_max_table(x: jax.Array) -> jax.Array:
    """(B, L) demand rows -> (B, P, L) sparse-table range-max levels.

    ``out[..., p, i] = max(x[..., i : i + 2**p])`` — the doubling table the
    scheduling programs' wait probes query in O(log L) (two lookups per
    window; see ``sim.device_timeline``).  Padded/masked positions should
    carry the dtype's max identity (``rangemax.max_identity``).  int32 (the
    scheduling programs' demand sums) and float32 rows run the Pallas kernel
    in their own dtype; anything else raises.  Safe to call from inside
    traced programs: dispatch happens at trace time.
    """
    _check_kernel_input("range_max_table", x)
    return _range_max_table_jit(x, interpret=_use_interpret("range_max_table"))


def compact_events(tl_t: jax.Array, tl_d: jax.Array, keep: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(N, L) sorted event rows + keep mask -> rows with the kept entries
    moved to the front (order preserved) and (``NEVER`` or +inf, 0)
    identities behind.

    The sweep program's chunk-boundary compaction step
    (``sim.device_timeline._sweep_lane``): the keep mask marks the
    demand-shape-changing breakpoints, everything else is dropped so the
    carried axis stays sized by live breakpoints.  A pure permutation — no
    kept value is recomputed.  int32 key rows (the scheduling programs') and
    float32 rows run the Pallas kernel in their own dtype; anything else
    raises.  Safe to call from inside traced (and vmapped) programs:
    dispatch happens at trace time.
    """
    _check_kernel_input("compact_events", tl_t)
    return _compact_events_jit(tl_t, tl_d, keep, interpret=_use_interpret("compact_events"))


def _time_fill(dtype):
    """The empty-slot time of a timeline row: ``NEVER`` for int32 keys, +inf
    for float rows."""
    from repro.core.timeline import NEVER

    return NEVER if jnp.issubdtype(dtype, jnp.integer) else float("inf")


@functools.partial(jax.jit, static_argnames=("interpret",))
def _compact_events_jit(
    tl_t: jax.Array, tl_d: jax.Array, keep: jax.Array, *, interpret: bool
) -> tuple[jax.Array, jax.Array]:
    B, L = tl_t.shape
    fill = _time_fill(tl_t.dtype)
    tp = _pad_cols(_pad_rows(tl_t, _compaction.BLOCK_B, fill=fill), _compaction.LANE, fill=fill)
    dp = _pad_cols(_pad_rows(tl_d, _compaction.BLOCK_B), _compaction.LANE)
    kp = _pad_cols(_pad_rows(keep.astype(jnp.int32), _compaction.BLOCK_B), _compaction.LANE)
    t2, d2 = _compaction.compact_pallas(tp, dp, kp, fill, interpret=interpret)
    # kept counts never exceed L, so the compacted prefix fits the caller's axis
    return t2[:B, :L], d2[:B, :L]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _range_max_table_jit(x: jax.Array, *, interpret: bool) -> jax.Array:
    B, L = x.shape
    neg = _rangemax.max_identity(x.dtype)
    xp = _pad_cols(_pad_rows(x, _rangemax.BLOCK_B, fill=neg), _rangemax.LANE, fill=neg)
    P = _rangemax.num_levels(L)
    out = _rangemax.rangemax_pallas(xp, interpret=interpret)[:B]
    # the padded axis may add levels the caller's L never needs; the first P
    # levels are span-identical because the pad region is the max identity
    return out[:, :P, :L]
