"""Fully-JAX online simulator: the paper's evaluation loop as device programs.

The sequential Python simulator (simulator.py) is the reference oracle; this
module expresses the *online recurrence* natively so whole tasks — and, via
``repro.sim.batch_engine``, the whole fig7 grid — evaluate as a handful of
device dispatches instead of ~10^4 Python-level calls.

Architecture of ``simulate_task_methods`` (the multi-method engine):

* One ``lax.scan`` walks a task's executions in order.  The scan carry holds
  the method state that is a true sufficient-statistic recurrence: the
  k-Segments runtime/segment regression banks and their progressive error
  offsets (exactly ``KSegmentsModel.state()``).
* Method state that no bounded carry can hold — PPM's full empirical peak
  distribution, and Witt-LR's residual extremes under a continually *refitted*
  model — depends only on the observation prefix, never on replay outcomes.
  Those predictions are therefore evaluated for **all** steps up front as
  batched prefix programs (masked prefix cumsums / one pairwise matmul) and
  fed to the scan as per-step inputs.  Same math, no sequential dependency.
* Each scan step replays the execution against **every** method at once: the
  allocations form an (M, k) matrix (the k = 1 baselines broadcast with +inf
  boundaries) and a single bounded ``lax.while_loop`` advances all retry
  ladders together, with per-method retry modes (selective / partial bump,
  node-cap jump) selected branch-free.

Because training executions and test executions are observed identically, the
model-state trajectory is independent of the training fraction: execution i is
always scored against the prediction from executions [0, i) (the default
allocation at i = 0).  A training fraction is therefore *pure aggregation* —
callers slice the per-execution outputs at ``n_train`` — and the fig7a/b/c
fraction axis costs nothing extra on device.

Both of the paper's error modes run on device.  "progressive" offsets are the
O(1) running-max recurrence.  "insample" offsets — extremes of the *current*
fit's residuals over history — cannot ride an unbounded carry, so the engine
carries a fixed-size ring of the last ``insample_window`` observations
``(u, runtime, peaks)`` and rescans it under the live fit at every prediction;
observations that age out are frozen at their eviction-time residuals
(monotone running maxima, so the bound stays conservative).  This is exactly
``KSegmentsModel``'s bounded-history formulation (``insample_window=W``),
and the parity tests hold the two bit-equal for histories within the window.
The segment count ``k_eff`` is traced (static upper bound ``k``), so the fig8
k-sweep is a ``vmap`` over k instead of one compile per k.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import regression
from repro.core.predictor import METHODS, retry_flags
from repro.core.segmentation import segment_peaks_dynamic
from repro.core.sizey import RAQ_EPS, SIZEY_QUANTILE_PCT, SIZEY_UNDER_PENALTY

MIB_PER_GIB = 1024.0
MAX_RETRIES = 64

# Method rows the multi-method scan can score, in output-row order.  The
# per-row retry policy (selective / partial bump, node-cap jump) is the
# shared table in repro.core.predictor (see retry_flags).
ENGINE_METHODS = METHODS


def _predict(rt_stats, rt_over, seg_stats, seg_under, u, k: int, k_eff, interval_s: float, floor_mib: float):
    """jnp twin of KSegmentsModel.predict (progressive offsets).

    ``k`` is the static array size; ``k_eff <= k`` is the traced number of
    live segments.  Segments beyond ``k_eff`` are replicas of the last real
    one (their stats learned replicated peaks, see segment_peaks_dynamic) and
    get +inf boundaries, so they act as the hold-last-value overflow region.
    Arithmetic runs in the stats' dtype (float32, or float64 for the x64
    ladder variant).
    """
    dt = rt_stats.dtype
    r_e = regression.predict(rt_stats, u) - jnp.maximum(rt_over, 0.0)
    r_e = jnp.maximum(r_e, interval_s)
    s = jnp.arange(k)
    bounds = (s + 1).astype(dt) * (r_e / k_eff.astype(dt))
    bounds = jnp.where(s == k_eff - 1, r_e, bounds)  # exact last edge, as the Python model
    bounds = jnp.where(s >= k_eff, jnp.inf, bounds)
    v = regression.predict(seg_stats, u) + jnp.maximum(seg_under, 0.0)
    v = v.at[0].set(jnp.where(v[0] < 0, floor_mib, v[0]))
    v = jax.lax.cummax(v, axis=0)
    return bounds, jnp.maximum(v, floor_mib)


def _predict_rel(rt_stats, rt_over_rel, seg_stats, seg_under_rel, u, k: int, k_eff, interval_s: float, floor_mib: float):
    """jnp twin of KSegmentsModel.predict with ``offset_mode="relative"`` —
    the KS+ method: offsets are residuals normalized by the (floored)
    prediction, rescaled by it at application time, so the safety margin
    tracks the allocation's magnitude instead of being a fixed MiB amount."""
    dt = rt_stats.dtype
    raw = regression.predict(rt_stats, u)
    r_e = raw - jnp.maximum(rt_over_rel, 0.0) * jnp.maximum(raw, interval_s)
    r_e = jnp.maximum(r_e, interval_s)
    s = jnp.arange(k)
    bounds = (s + 1).astype(dt) * (r_e / k_eff.astype(dt))
    bounds = jnp.where(s == k_eff - 1, r_e, bounds)
    bounds = jnp.where(s >= k_eff, jnp.inf, bounds)
    v = regression.predict(seg_stats, u)
    v = v + jnp.maximum(seg_under_rel, 0.0) * jnp.maximum(v, floor_mib)
    v = v.at[0].set(jnp.where(v[0] < 0, floor_mib, v[0]))
    v = jax.lax.cummax(v, axis=0)
    return bounds, jnp.maximum(v, floor_mib)


def _attempt(y, length, interval_s, bounds, values):
    """Single-row attempt scorer (same semantics as core.allocation)."""
    T = y.shape[0]
    t = (jnp.arange(T, dtype=y.dtype) + 0.5) * interval_s
    idx = jnp.minimum(jnp.sum(t[:, None] > bounds[None, :], axis=1), len(values) - 1)
    a = values[idx]
    valid = jnp.arange(T) < length
    over = (y > a) & valid
    failed = jnp.any(over)
    fail_idx = jnp.where(failed, jnp.argmax(over), T + 1)
    pos = jnp.arange(T)
    succ_w = jnp.sum(jnp.where(valid, a - y, 0.0))
    fail_w = jnp.sum(jnp.where((pos <= fail_idx) & valid, a, 0.0))
    waste = jnp.where(failed, fail_w, succ_w) * interval_s / MIB_PER_GIB
    return failed, fail_idx, waste


def _replay_multi(
    y, length, bounds, values, selective, capjump, k_eff, *, interval_s, factor, cap_mib, max_attempts=None
):
    """Shared retry loop for all methods: one bounded while_loop advances every
    method's retry ladder together (finished rows hold their state).

    Args: y (T,), length scalar, bounds/values (M, k), selective/capjump (M,)
    per-method retry-mode flags.  Returns (waste (M,), retries (M,)), plus —
    when ``max_attempts`` is set — the recorded per-attempt ladder
    (values (M, A, k), failure index (M, A) with -1 = success,
    n_attempts (M,)): the rows the cluster scheduler replays
    placement against.  A row that would exceed A attempts stops with its
    last recorded failure index >= 0; the host consumer detects and raises.
    """
    M, k = values.shape
    seg_pos = jnp.arange(k)[None, :]
    record = max_attempts is not None

    def attempt_all(vals):
        return jax.vmap(lambda b, v: _attempt(y, length, interval_s, b, v))(bounds, vals)

    def cond(c):
        done, *_ = c
        return jnp.any(~done)

    def body(c):
        done, retries, waste, vals, rec = c
        failed, fail_idx, w = attempt_all(vals)
        active = ~done
        waste = waste + jnp.where(active, w, 0.0)
        if record:
            vbuf, fbuf, natt = rec
            rows = jnp.arange(M)
            att = jnp.minimum(natt, max_attempts - 1)
            fi = jnp.where(failed, fail_idx, -1).astype(jnp.int32)
            vbuf = vbuf.at[rows, att].set(jnp.where(active[:, None], vals, vbuf[rows, att]))
            fbuf = fbuf.at[rows, att].set(jnp.where(active, fi, fbuf[rows, att]))
            natt = natt + active.astype(jnp.int32)
            rec = (vbuf, fbuf, natt)
        t_fail = (fail_idx.astype(bounds.dtype) + 0.5) * interval_s
        seg = jnp.minimum(jnp.sum(t_fail[:, None] > bounds, axis=1), k_eff - 1)  # (M,)
        bump_sel = vals * jnp.where(seg_pos == seg[:, None], factor, 1.0)
        bump_par = jnp.where(seg_pos >= seg[:, None], vals * factor, vals)
        bumped = jnp.where(capjump[:, None], cap_mib, jnp.where(selective[:, None], bump_sel, bump_par))
        bumped = jnp.minimum(jax.lax.cummax(bumped, axis=1), cap_mib)
        step_fail = active & failed
        retries = retries + step_fail.astype(jnp.int32)
        vals = jnp.where(step_fail[:, None], bumped, vals)
        done = done | (active & ~failed) | (retries > MAX_RETRIES)
        if record:
            done = done | (rec[2] >= max_attempts)  # ladder buffer full
        return done, retries, waste, vals, rec

    rec0 = ()
    if record:
        rec0 = (
            jnp.zeros((M, max_attempts, k), values.dtype),
            jnp.full((M, max_attempts), -1, jnp.int32),
            jnp.zeros((M,), jnp.int32),
        )
    _, retries, waste, _, rec = jax.lax.while_loop(
        cond,
        body,
        (
            jnp.zeros((M,), bool),
            jnp.zeros((M,), jnp.int32),
            jnp.zeros((M,), values.dtype),
            jnp.minimum(values, cap_mib),
            rec0,
        ),
    )
    if record:
        return waste, retries, rec
    return waste, retries


# ---------------------------------------------------------------------------
# Prefix programs: per-step predictions for the methods whose state cannot
# live in a bounded scan carry.  Row i is always the model fitted on
# observations j < i (row 0 = no history; the scan substitutes the default).
# ---------------------------------------------------------------------------


def _witt_prefix_values(u, gpeak, floor_mib):
    """Witt-LR allocation values for every step as one prefix program.

    Returns (val_std, val_max): (B,) predictions for the "std" and "max"
    residual-offset variants.  The residual matrix e[i, j] is the step-i fit's
    error on historical execution j — the exact quantity WittLR._offset_value
    recomputes per prediction, here built once for all steps.
    """
    B = u.shape[0]
    dt = u.dtype
    upd = regression.update_stats(jnp.zeros((B, regression.NUM_STATS), dt), u, gpeak)
    pref = jnp.concatenate([jnp.zeros((1, regression.NUM_STATS), dt), jnp.cumsum(upd, axis=0)[:-1]], axis=0)
    intercept, slope = regression.fit(pref)  # (B,) step-i fits
    e = gpeak[None, :] - intercept[:, None] - slope[:, None] * u[None, :]  # (B, B)
    seen = jnp.arange(B)[None, :] < jnp.arange(B)[:, None]
    n = jnp.maximum(jnp.sum(seen, axis=1), 1).astype(dt)
    mean = jnp.sum(jnp.where(seen, e, 0.0), axis=1) / n
    var = jnp.sum(jnp.where(seen, e * e, 0.0), axis=1) / n - mean * mean
    std = jnp.where(jnp.arange(B) >= 2, jnp.sqrt(jnp.maximum(var, 0.0)), 0.0)  # Witt: >= 2 residuals
    emax = jnp.max(jnp.where(seen, e, -jnp.inf), axis=1)
    off_max = jnp.maximum(jnp.where(jnp.isfinite(emax), emax, 0.0), 0.0)
    base = intercept + slope * u
    return jnp.maximum(base + std, floor_mib), jnp.maximum(base + off_max, floor_mib)


def _ppm_prefix_values(gpeak, rt_samples, cap_mib, floor_mib):
    """Tovar PPM candidate selection for every observation prefix.

    Sort the peaks once; at step i a sorted position m is a candidate iff its
    execution was observed before i, and the expected-wastage terms are masked
    prefix cumsums — so all B selections evaluate together.  PPM-improved's
    doubling-ladder cost decomposes per (candidate, peak) pair into a matrix
    computed once and contracted against the prefix mask with one matmul.

    Unlike TovarPPM.MAX_CANDIDATES, every observed peak is a candidate (no
    quantile subsetting); the two engines can differ once a task has > 256
    distinct peaks, which the parity tests stay below.

    Returns (val_orig, val_improved): (B,) allocation values.
    """
    B = gpeak.shape[0]
    dt = gpeak.dtype
    order = jnp.argsort(gpeak)
    p = gpeak[order]  # sorted candidate/peak values
    rt = rt_samples[order]
    seen = order[None, :] < jnp.arange(B)[:, None]  # (B_steps, B_sorted)
    seen_f = seen.astype(dt)
    C = jnp.cumsum(seen_f * rt[None, :], axis=1)  # masked prefix runtime sums
    S = jnp.cumsum(seen_f * (p * rt)[None, :], axis=1)
    waste_ok = p[None, :] * C - S  # successes: (q - p_i) * rt_i
    rt_bad = C[:, -1:] - C
    s_bad = S[:, -1:] - S
    # original: failed first attempt wastes q*rt; retry at node cap wastes (cap - p)*rt
    waste_orig = waste_ok + p[None, :] * rt_bad + cap_mib * rt_bad - s_bad
    # improved: smallest ladder level a = q * 2^ceil(log2(p/q)) >= p (capped)
    # wastes (2a - q - p) * rt — the failed geometric attempts + final overshoot.
    q = jnp.maximum(p, 1e-6)[:, None]
    ratio = p[None, :] / q
    a = jnp.minimum(q * jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(ratio, 1.0)))), cap_mib)
    w_pair = jnp.where(p[None, :] > p[:, None], (2.0 * a - p[:, None] - p[None, :]) * rt[None, :], 0.0)
    # contracting w_pair against the prefix mask is not a matmul: step i adds
    # exactly execution i-1's column, so the whole (step, candidate) table is
    # an exclusive cumsum of columns gathered into execution order — O(B^2).
    contrib = w_pair[:, jnp.argsort(order)].T  # (B_exec, B_cand)
    waste_imp = waste_ok + jnp.concatenate(
        [jnp.zeros((1, B), dt), jnp.cumsum(contrib, axis=0)[:-1]], axis=0
    )
    val_orig = p[jnp.argmin(jnp.where(seen, waste_orig, jnp.inf), axis=1)]
    val_imp = p[jnp.argmin(jnp.where(seen, waste_imp, jnp.inf), axis=1)]
    return jnp.maximum(val_orig, floor_mib), jnp.maximum(val_imp, floor_mib)


def _sizey_prefix_values(u, gpeak, floor_mib):
    """Sizey portfolio allocation for every step as one prefix program.

    Mirrors ``core.sizey.SizeyPortfolio`` exactly: at step i both models are
    fitted on observations j < i — the linear model via the same prefix-stats
    construction as Witt, the quantile model via masked ranks over one global
    sort (the PPM trick, with the target rank in exact integer arithmetic so
    f32/f64 agree) — their one-step-ahead offsets are exclusive running
    maxima, and the allocation-quality scores are exclusive prefix means over
    j in [1, i).  Returns the winning model's offset + floored allocation at
    each step ((B,); row 0 is masked by the scan's has_obs gate).
    """
    B = u.shape[0]
    dt = u.dtype
    steps = jnp.arange(B)
    # linear model: step-i prefix fits, evaluated at the step's own input
    upd = regression.update_stats(jnp.zeros((B, regression.NUM_STATS), dt), u, gpeak)
    pref = jnp.concatenate([jnp.zeros((1, regression.NUM_STATS), dt), jnp.cumsum(upd, axis=0)[:-1]], axis=0)
    intercept, slope = regression.fit(pref)
    pred_lin = intercept + slope * u  # (B,)
    # quantile model: the SIZEY_QUANTILE_PCT order statistic of peaks seen
    # before step i (n seen = i), selected by 1-based rank among seen rows
    order = jnp.argsort(gpeak)
    p = gpeak[order]
    seen = order[None, :] < steps[:, None]  # (B_steps, B_sorted)
    rank = jnp.cumsum(seen.astype(jnp.int32), axis=1)
    target = -((-SIZEY_QUANTILE_PCT * (steps - 1)) // 100) + 1  # ceil, exact ints
    hit = seen & (rank == target[:, None])
    pred_q = p[jnp.argmax(hit, axis=1)]  # step 0 has no hit -> p[0], masked later
    preds = jnp.stack([pred_lin, pred_q])  # (2, B)
    # per-model one-step-ahead offsets: exclusive cummax of underpredictions
    # over j >= 1 (row 0's "model" never saw data, as on the host)
    res = jnp.where(steps[None, :] >= 1, gpeak[None, :] - preds, -jnp.inf)
    off = jnp.maximum(
        jnp.concatenate([jnp.full((2, 1), -jnp.inf, dt), jax.lax.cummax(res, axis=1)[:, :-1]], axis=1),
        0.0,
    )
    v = jnp.maximum(preds + off, floor_mib)  # each model's step-j proposal
    # allocation-quality scores: exclusive prefix means of the efficiency
    # ratio minus the penalized underprediction frequency
    ratio = jnp.minimum(v, gpeak[None, :]) / jnp.maximum(jnp.maximum(v, gpeak[None, :]), RAQ_EPS)
    under = (v < gpeak[None, :]).astype(dt)
    m1 = (steps[None, :] >= 1).astype(dt)

    def excl(a):  # exclusive cumsum along the step axis
        return jnp.concatenate([jnp.zeros((2, 1), dt), jnp.cumsum(a, axis=1)[:, :-1]], axis=1)

    cnt = jnp.maximum(steps - 1, 1).astype(dt)
    score = (excl(ratio * m1) - SIZEY_UNDER_PENALTY * excl(under * m1)) / cnt[None, :]
    choose_q = (steps >= 2) & (score[1] > score[0])  # cold start/ties -> linear
    return jnp.where(choose_q, v[1], v[0])


# ---------------------------------------------------------------------------
# Bounded-history insample offsets: rescan the carried observation window
# under the live fit (KSegmentsModel._observe_insample with insample_window).
# ---------------------------------------------------------------------------


def _window_residuals(rt_stats, seg_stats, hu, hrt, hpk, interval_s, floor_mib):
    """Residuals of history rows under the fit of the given stats banks.

    Args: hu (W,) shifted inputs, hrt (W,) runtimes, hpk (W, k) segment peaks.
    Returns (rt_res (W,), seg_res (W, k), rt_rel (W,), seg_rel (W, k)) — the
    absolute over/under-prediction residuals and their KS+-normalized twins
    (divided by the floored prediction; ``KSegmentsModel._residuals``).
    """
    rt_pred = regression.predict(rt_stats, hu)  # (W,)
    a, b = regression.fit(seg_stats)  # (k,), (k,)
    seg_pred = a[None, :] + b[None, :] * hu[:, None]  # (W, k)
    rt_res = rt_pred - hrt
    seg_res = hpk - seg_pred
    rt_rel = rt_res / jnp.maximum(rt_pred, interval_s)
    seg_rel = seg_res / jnp.maximum(seg_pred, floor_mib)
    return rt_res, seg_res, rt_rel, seg_rel


def _window_offsets(rt_stats, seg_stats, hist, n_obs, ev, interval_s, floor_mib):
    """Insample error offsets at prediction time: masked extremes of the
    window residuals under the *current* fit, combined with the frozen
    eviction-time extremes (max is ring-order-invariant, so the ring buffer
    needs no unrolling).

    Args: hist = (hist_u, hist_rt, hist_pk) ring buffers, n_obs the traced
    observation count, ev = (ev_rt, ev_seg, ev_rt_rel, ev_seg_rel) frozen
    extremes (-inf when nothing has been evicted).
    Returns (rt_over, seg_under, rt_over_rel, seg_under_rel).
    """
    hist_u, hist_rt, hist_pk = hist
    ev_rt, ev_seg, ev_rt_rel, ev_seg_rel = ev
    W = hist_u.shape[0]
    rt_res, seg_res, rt_rel, seg_rel = _window_residuals(
        rt_stats, seg_stats, hist_u, hist_rt, hist_pk, interval_s, floor_mib
    )
    filled = jnp.arange(W) < jnp.minimum(n_obs, W)
    rt_over = jnp.maximum(jnp.max(jnp.where(filled, rt_res, -jnp.inf)), ev_rt)
    seg_under = jnp.maximum(jnp.max(jnp.where(filled[:, None], seg_res, -jnp.inf), axis=0), ev_seg)
    rt_over_rel = jnp.maximum(jnp.max(jnp.where(filled, rt_rel, -jnp.inf)), ev_rt_rel)
    seg_under_rel = jnp.maximum(jnp.max(jnp.where(filled[:, None], seg_rel, -jnp.inf), axis=0), ev_seg_rel)
    return rt_over, seg_under, rt_over_rel, seg_under_rel


# ---------------------------------------------------------------------------
# The multi-method engine.
# ---------------------------------------------------------------------------


def _simulate_methods(
    x,
    y,
    lengths,
    default_mib,
    k_eff=None,
    *,
    methods: tuple[str, ...] = ENGINE_METHODS,
    k: int = 4,
    interval_s: float = 2.0,
    factor: float = 2.0,
    floor_mib: float = 100.0,
    cap_mib: float = 128 * 1024.0,
    max_attempts: int | None = None,
    error_mode: str = "progressive",
    insample_window: int = 0,
    dtype=jnp.float32,
):
    """Shared body of the multi-method engines (see the jitted entry points
    ``simulate_task_methods`` and ``simulate_task_ladders``).  ``dtype`` is
    the working precision: float32 (default), or float64 for the x64 ladder
    variant, a CPU reference (callers must hold a ``jax.enable_x64`` context).

    ``error_mode="insample"`` switches the k-Segments family (including KS+)
    to bounded-history insample offsets over the last ``insample_window``
    observations (see module docstring); the window bound must be explicit
    (>= 1) — the host parity twin is ``KSegmentsConfig(insample_window=W)``.
    """
    if error_mode not in ("progressive", "insample"):
        raise ValueError(f"unknown error mode: {error_mode!r}")
    if error_mode == "insample" and insample_window < 1:
        raise ValueError("insample error mode needs an explicit history bound (insample_window >= 1)")
    if error_mode == "progressive" and insample_window:
        raise ValueError("insample_window only applies to error_mode='insample' (pass 0)")
    B, T = y.shape
    y = y.astype(dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    u = (x - x[0]).astype(dtype)  # conditioning shift (see regression.py)
    default_mib = jnp.asarray(default_mib, dtype)
    k_eff = jnp.asarray(k if k_eff is None else k_eff, jnp.int32)

    peaks_all = segment_peaks_dynamic(y, lengths, k_eff, k)  # (B, k) — the segmax kernel's job
    gpeak = jnp.max(jnp.where(jnp.arange(T)[None, :] < lengths[:, None], y, 0.0), axis=1)

    need = set(methods)
    zeros = jnp.zeros((B,), dtype)
    witt_std, witt_max = (
        _witt_prefix_values(u, gpeak, floor_mib) if need & {"witt-lr", "witt-lr-max"} else (zeros, zeros)
    )
    ppm_orig, ppm_imp = (
        _ppm_prefix_values(gpeak, lengths.astype(dtype), cap_mib, floor_mib)
        if need & {"ppm", "ppm-improved"}
        else (zeros, zeros)
    )
    sizey_vals = _sizey_prefix_values(u, gpeak, floor_mib) if "sizey" in need else zeros

    selective, cap_jump = retry_flags(methods)
    sel_flags = jnp.asarray(selective)
    cap_flags = jnp.asarray(cap_jump)
    inf_bounds = jnp.full((k,), jnp.inf, dtype)
    ones_k = jnp.ones((k,), dtype)
    need_ks = bool(need & {"ksegments-selective", "ksegments-partial"})
    need_rel = "ksplus" in need
    # Bounded insample offsets only matter for the k-Segments family; other
    # methods ignore the mode, so an all-baseline scan skips the ring buffer.
    use_insample = error_mode == "insample" and (need_ks or need_rel)

    def step(carry, inp):
        rt_stats, seg_stats, i = carry["rt_stats"], carry["seg_stats"], carry["i"]
        ui, yi, li, peaks_i, vals_i = inp
        has_obs = i >= 1

        if use_insample:
            hist = (carry["hist_u"], carry["hist_rt"], carry["hist_pk"])
            ev = (carry["ev_rt"], carry["ev_seg"], carry["ev_rt_rel"], carry["ev_seg_rel"])
            rt_over, seg_under, rt_over_rel, seg_under_rel = _window_offsets(
                rt_stats, seg_stats, hist, i, ev, interval_s, floor_mib
            )
        else:
            rt_over, seg_under = carry["rt_over"], carry["seg_under"]
            rt_over_rel, seg_under_rel = carry["rt_over_rel"], carry["seg_under_rel"]

        if need_ks:
            ks_bounds, ks_values = _predict(
                rt_stats, rt_over, seg_stats, seg_under, ui, k, k_eff, interval_s, floor_mib
            )
        if need_rel:
            kp_bounds, kp_values = _predict_rel(
                rt_stats, rt_over_rel, seg_stats, seg_under_rel, ui, k, k_eff, interval_s, floor_mib
            )
        rows_b, rows_v = [], []
        for m in methods:
            if m.startswith("ksegments"):
                rows_b.append(jnp.where(has_obs, ks_bounds, inf_bounds))
                rows_v.append(jnp.where(has_obs, ks_values, default_mib * ones_k))
            elif m == "ksplus":
                rows_b.append(jnp.where(has_obs, kp_bounds, inf_bounds))
                rows_v.append(jnp.where(has_obs, kp_values, default_mib * ones_k))
            elif m == "default":
                rows_b.append(inf_bounds)
                rows_v.append(default_mib * ones_k)
            else:
                rows_b.append(inf_bounds)
                rows_v.append(jnp.where(has_obs, vals_i[m], default_mib) * ones_k)
        bounds_m = jnp.stack(rows_b)
        replayed = _replay_multi(
            yi,
            li,
            bounds_m,
            jnp.stack(rows_v),
            sel_flags,
            cap_flags,
            k_eff,
            interval_s=interval_s,
            factor=factor,
            cap_mib=cap_mib,
            max_attempts=max_attempts,
        )
        if max_attempts is None:
            waste, retries = replayed
            out = (waste, retries)
        else:
            waste, retries, (vbuf, fbuf, natt) = replayed
            out = (waste, retries, bounds_m, vbuf, fbuf, natt)

        # observe
        runtime = li.astype(dtype) * interval_s
        new_carry = {"i": i + 1}
        if use_insample:
            # Fold first: the host evicts under the post-fold fit, and the
            # next prediction rescans the ring under these same stats.
            rt_stats = regression.update_stats(rt_stats, ui, runtime)
            seg_stats = regression.update_stats(seg_stats, ui, peaks_i)
            hist_u, hist_rt, hist_pk = hist
            slot = jnp.mod(i, insample_window)
            evict = i >= insample_window
            rt_res, seg_res, rt_rel, seg_rel = _window_residuals(
                rt_stats,
                seg_stats,
                hist_u[slot][None],
                hist_rt[slot][None],
                hist_pk[slot][None],
                interval_s,
                floor_mib,
            )
            ev_rt, ev_seg, ev_rt_rel, ev_seg_rel = ev
            new_carry.update(
                ev_rt=jnp.where(evict, jnp.maximum(ev_rt, rt_res[0]), ev_rt),
                ev_seg=jnp.where(evict, jnp.maximum(ev_seg, seg_res[0]), ev_seg),
                ev_rt_rel=jnp.where(evict, jnp.maximum(ev_rt_rel, rt_rel[0]), ev_rt_rel),
                ev_seg_rel=jnp.where(evict, jnp.maximum(ev_seg_rel, seg_rel[0]), ev_seg_rel),
                hist_u=hist_u.at[slot].set(ui),
                hist_rt=hist_rt.at[slot].set(runtime),
                hist_pk=hist_pk.at[slot].set(peaks_i),
            )
        else:
            # progressive offsets: score-then-update
            has_data = rt_stats[regression.N] > 0
            rt_pred = regression.predict(rt_stats, ui)
            rt_err = rt_pred - runtime
            seg_pred = regression.predict(seg_stats, ui)
            seg_err = peaks_i - seg_pred
            new_carry.update(
                rt_over=jnp.where(has_data, jnp.maximum(rt_over, rt_err), rt_over),
                seg_under=jnp.where(has_data, jnp.maximum(seg_under, seg_err), seg_under),
                rt_over_rel=jnp.where(
                    has_data,
                    jnp.maximum(rt_over_rel, rt_err / jnp.maximum(rt_pred, interval_s)),
                    rt_over_rel,
                ),
                seg_under_rel=jnp.where(
                    has_data,
                    jnp.maximum(seg_under_rel, seg_err / jnp.maximum(seg_pred, floor_mib)),
                    seg_under_rel,
                ),
            )
            rt_stats = regression.update_stats(rt_stats, ui, runtime)
            seg_stats = regression.update_stats(seg_stats, ui, peaks_i)
        new_carry.update(rt_stats=rt_stats, seg_stats=seg_stats)
        return new_carry, out

    init = {
        "rt_stats": regression.empty_stats(dtype=dtype),
        "seg_stats": regression.empty_stats(k, dtype=dtype),
        "i": jnp.asarray(0, jnp.int32),
    }
    if use_insample:
        W = insample_window
        init.update(
            hist_u=jnp.zeros((W,), dtype),
            hist_rt=jnp.zeros((W,), dtype),
            hist_pk=jnp.zeros((W, k), dtype),
            ev_rt=jnp.asarray(-jnp.inf, dtype),
            ev_seg=jnp.full((k,), -jnp.inf, dtype),
            ev_rt_rel=jnp.asarray(-jnp.inf, dtype),
            ev_seg_rel=jnp.full((k,), -jnp.inf, dtype),
        )
    else:
        init.update(
            rt_over=jnp.asarray(0.0, dtype),
            seg_under=jnp.zeros((k,), dtype),
            rt_over_rel=jnp.asarray(0.0, dtype),
            seg_under_rel=jnp.zeros((k,), dtype),
        )
    per_step_vals = {
        "witt-lr": witt_std,
        "witt-lr-max": witt_max,
        "ppm": ppm_orig,
        "ppm-improved": ppm_imp,
        "sizey": sizey_vals,
    }
    xs = (u, y, lengths, peaks_all, per_step_vals)
    _, outs = jax.lax.scan(step, init, xs)
    return outs


@functools.partial(
    jax.jit,
    static_argnames=("methods", "k", "interval_s", "factor", "floor_mib", "cap_mib", "error_mode", "insample_window"),
)
def simulate_task_methods(
    x,
    y,
    lengths,
    default_mib,
    k_eff=None,
    *,
    methods: tuple[str, ...] = ENGINE_METHODS,
    k: int = 4,
    interval_s: float = 2.0,
    factor: float = 2.0,
    floor_mib: float = 100.0,
    cap_mib: float = 128 * 1024.0,
    error_mode: str = "progressive",
    insample_window: int = 0,
):
    """Score every requested method on one task type's executions in one scan.

    Args: x (B,) input sizes, y (B, T) padded MiB series, lengths (B,),
      default_mib scalar (the workflow's static directive), k_eff traced
      segment count (defaults to the static k).

    Returns (waste, retries): (M, B) per-method, per-execution outcomes.
    Execution i is scored against each method's prediction from executions
    [0, i) — the default allocation at i = 0 — so any training fraction is a
    pure slice at ``n_train`` over the B axis (see module docstring).
    Executions past a caller's valid count must sit at the tail; their
    updates only ever feed later (also-invalid) rows.
    """
    waste, retries = _simulate_methods(
        x,
        y,
        lengths,
        default_mib,
        k_eff,
        methods=methods,
        k=k,
        interval_s=interval_s,
        factor=factor,
        floor_mib=floor_mib,
        cap_mib=cap_mib,
        error_mode=error_mode,
        insample_window=insample_window,
    )
    return waste.T, retries.T  # (M, B)


@functools.partial(
    jax.jit,
    static_argnames=(
        "methods",
        "k",
        "interval_s",
        "factor",
        "floor_mib",
        "cap_mib",
        "max_attempts",
        "x64",
        "error_mode",
        "insample_window",
    ),
)
def simulate_task_ladders(
    x,
    y,
    lengths,
    default_mib,
    k_eff=None,
    *,
    methods: tuple[str, ...] = ENGINE_METHODS,
    k: int = 4,
    interval_s: float = 2.0,
    factor: float = 2.0,
    floor_mib: float = 100.0,
    cap_mib: float = 128 * 1024.0,
    max_attempts: int = 32,
    x64: bool = False,
    error_mode: str = "progressive",
    insample_window: int = 0,
):
    """The cluster scheduler's device program: the same online scan as
    ``simulate_task_methods``, but returning every execution's full retry
    ladder instead of aggregate outcomes.

    Returns a dict of per-method, per-execution tensors (A = max_attempts):

    * ``boundaries`` (M, B, k) — prediction step boundaries (attempt-invariant;
      +inf rows for the k = 1 baselines, which hold their value anyway).
    * ``values`` (M, B, A, k) — allocation values of each attempt (node-capped).
    * ``failure_index`` (M, B, A) — OOM-kill sample of each attempt, -1 on the
      final (successful) attempt.
    * ``n_attempts`` (M, B) — recorded attempts (retries + 1).

    The host-side scheduler replays placement against these rows; nothing
    about them depends on placement (predictions see only completed earlier
    executions of the same task type — identical to the sequential
    ``run_cluster`` protocol).

    ``x64=True`` runs the whole scan in float64 (the caller must hold a
    ``jax.enable_x64`` context): a CPU reference for the rare ulp-boundary
    case where a float32 prediction flips a comparison against the float64
    numpy oracle, at ~1.5x ladder cost.  The TPU path is float32.
    """
    _, _, bounds, vbuf, fbuf, natt = _simulate_methods(
        x,
        y,
        lengths,
        default_mib,
        k_eff,
        methods=methods,
        k=k,
        interval_s=interval_s,
        factor=factor,
        floor_mib=floor_mib,
        cap_mib=cap_mib,
        max_attempts=max_attempts,
        error_mode=error_mode,
        insample_window=insample_window,
        dtype=jnp.float64 if x64 else jnp.float32,
    )
    return {
        "boundaries": bounds.transpose(1, 0, 2),  # (M, B, k)
        "values": vbuf.transpose(1, 0, 2, 3),  # (M, B, A, k)
        "failure_index": fbuf.transpose(1, 0, 2),  # (M, B, A)
        "n_attempts": natt.T,  # (M, B)
    }


@functools.partial(
    jax.jit, static_argnames=("k", "interval_s", "selective", "factor", "floor_mib", "cap_mib", "n_train")
)
def simulate_task_scan(
    x,
    y,
    lengths,
    *,
    k: int = 4,
    interval_s: float = 2.0,
    selective: bool = True,
    factor: float = 2.0,
    floor_mib: float = 100.0,
    cap_mib: float = 128 * 1024.0,
    n_train: int = 0,
):
    """Online k-Segments over one task type's padded executions (single-method
    wrapper around the multi-method engine; API kept for existing callers).

    Args: x (B,) input sizes, y (B, T) padded MiB series, lengths (B,).
    Returns (wastage (B,), retries (B,)) — zeros for the training prefix.
    """
    method = "ksegments-selective" if selective else "ksegments-partial"
    waste, retries = simulate_task_methods(
        x,
        y,
        lengths,
        jnp.asarray(1024.0, jnp.float32),  # default alloc only matters pre-first-observation, which is masked below
        methods=(method,),
        k=k,
        interval_s=interval_s,
        factor=factor,
        floor_mib=floor_mib,
        cap_mib=cap_mib,
    )
    scored = jnp.arange(y.shape[0]) >= max(n_train, 1)
    return jnp.where(scored, waste[0], 0.0), jnp.where(scored, retries[0], 0)
