"""Serving: generation loop + the k-Segments admission controller."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.registry import ARCHS
from repro.core.allocation import StepAllocation
from repro.models import init_cache, init_params
from repro.serve import AdmissionController
from repro.serve.admission import cache_bytes_per_token
from repro.serve.engine import greedy_generate


def test_greedy_generate():
    cfg = get_config("llama3.2-3b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    out = greedy_generate(params, cfg, tokens, steps=5)
    assert out.shape == (2, 5)
    assert int(out.min()) >= 0 and int(out.max()) < cfg.vocab_size
    # greedy decode is deterministic
    out2 = greedy_generate(params, cfg, tokens, steps=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def _fake_request_series(prompt_len, decode_steps, bpt_mib, interval):
    """HBM MiB over time for one request: prefill jump then linear growth."""
    base = prompt_len * bpt_mib
    return np.asarray([base + i * bpt_mib for i in range(decode_steps)], np.float32)


def _growth_series(prompt_len, decode_steps):
    """Growth-dominated footprint: small prefill, steep KV accumulation —
    the regime where segment-wise reservations have real headroom over
    peak-at-admission (a near-flat footprint has none)."""
    return (prompt_len * 0.08 + 8.0 * np.arange(decode_steps)).astype(np.float32)


def test_admission_learns_and_packs_more():
    """Segment-wise packing admits more concurrent requests than
    peak-at-admission reservation for growing (KV-cache) footprints."""
    rng = np.random.default_rng(0)
    ctl = AdmissionController(hbm_budget_mib=10_000.0, k=4, interval_s=1.0)
    # learn from finished requests: memory grows linearly with decode steps
    for _ in range(50):
        plen = int(rng.integers(100, 2000))
        steps = int(60 + plen * 0.05 + rng.normal(0, 2))
        ctl.observe(plen, _growth_series(plen, steps))
    alloc = ctl.model.predict(1000.0)
    # predicted allocation must be monotone-growing (KV growth), not flat
    assert alloc.values[-1] > alloc.values[0]
    # arrival/release simulation: staggered phases let segment-wise packing
    # hold MORE concurrent requests than static peak reservation would
    lifetime = float(alloc.boundaries[-1])
    dt = lifetime / 20.0
    now, max_concurrent, rejections = 0.0, 0, 0
    for i in range(200):
        # release requests past their predicted end
        for rid, plan in list(ctl.active.items()):
            if now - plan.admitted_at > float(plan.alloc.boundaries[-1]):
                ctl.release(rid)
        if ctl.try_admit(f"r{i}", 1000, now) is None:
            rejections += 1
        max_concurrent = max(max_concurrent, len(ctl.active))
        now += dt
    peak = float(alloc.values[-1])
    static_fit = int(10_000.0 // peak)
    assert rejections > 0  # the budget does bind
    assert max_concurrent > static_fit, (max_concurrent, static_fit)


class _FixedModel:
    """Stub predictor: returns a fixed allocation (lets tests construct exact
    admission geometries)."""

    def __init__(self, alloc):
        self.alloc = alloc
        self.n_observations = 1

    def predict(self, _prompt_len):
        return self.alloc


def test_try_admit_probes_active_switch_points():
    """Regression: an active request stepping up BETWEEN two of the
    newcomer's boundaries must be seen by admission.  The old try_admit
    probed only the newcomer's own boundaries and admitted a combination
    that overshoots the budget at the leader's switch point."""
    ctl = AdmissionController(hbm_budget_mib=1000.0, k=2, interval_s=1.0)
    leader = StepAllocation(np.asarray([10.0, 30.0]), np.asarray([100.0, 900.0]))
    ctl.model = _FixedModel(leader)
    assert ctl.try_admit("leader", 100, 0.0) is not None
    # newcomer's probe points (5, 40) straddle the leader's step at t=10:
    # combined demand on (10, 30] is 900 + 200 = 1100 > 1000.
    newcomer = StepAllocation(np.asarray([5.0, 40.0]), np.asarray([50.0, 200.0]))
    ctl.model = _FixedModel(newcomer)
    assert ctl.try_admit("newcomer", 100, 0.0) is None
    # the same newcomer fits once the leader is gone
    ctl.release("leader")
    assert ctl.try_admit("newcomer", 100, 0.0) is not None


def test_try_admit_boundary_probe_at_large_timestamps():
    """The switch-point probe must step past the boundary at any clock a
    long-lived controller reaches: integer keys have the same resolution
    near the end of the tick horizon as at zero, so probing ON the boundary
    reads the pre-step value there too."""
    from repro.core.timeline import HORIZON_TICKS, TICKS_PER_S

    now = HORIZON_TICKS / TICKS_PER_S - 100.0  # ~12.4 days of controller clock
    ctl = AdmissionController(hbm_budget_mib=1000.0, k=2, interval_s=1.0)
    ctl.model = _FixedModel(StepAllocation(np.asarray([10.0, 30.0]), np.asarray([100.0, 900.0])))
    assert ctl.try_admit("leader", 100, now) is not None
    ctl.model = _FixedModel(StepAllocation(np.asarray([5.0, 40.0]), np.asarray([50.0, 200.0])))
    assert ctl.try_admit("newcomer", 100, now) is None


def test_try_admit_past_the_horizon_raises():
    """A plan that would end past the int32 tick horizon raises instead of
    wrapping its keys."""
    from repro.core.timeline import HORIZON_TICKS, TICKS_PER_S

    ctl = AdmissionController(hbm_budget_mib=1000.0, k=2, interval_s=1.0)
    ctl.model = _FixedModel(StepAllocation(np.asarray([10.0, 30.0]), np.asarray([100.0, 900.0])))
    with pytest.raises(OverflowError):
        ctl.try_admit("late", 100, HORIZON_TICKS / TICKS_PER_S - 10.0)


def test_combined_demand_release_at_final_boundary():
    """A plan holds its last value AT its final boundary (Eq. 1 domain is
    closed at r_e) and is released immediately after."""
    ctl = AdmissionController(hbm_budget_mib=10_000.0, k=2, interval_s=1.0)
    plan_alloc = StepAllocation(np.asarray([10.0, 20.0]), np.asarray([100.0, 500.0]))
    ctl.model = _FixedModel(plan_alloc)
    assert ctl.try_admit("r0", 100, 0.0) is not None
    at_end = ctl._combined_demand((20.0,))
    next_tick = ctl._combined_demand((20.001,))
    assert at_end[0] == 500
    assert next_tick[0] == 0


def test_reservation_wastage_segmentwise_lower():
    ctl = AdmissionController(hbm_budget_mib=50_000.0, k=4, interval_s=1.0)
    rng = np.random.default_rng(1)
    for _ in range(40):
        plen = int(rng.integers(100, 2000))
        ctl.observe(plen, _fake_request_series(plen, 60 + int(plen * 0.05), 0.8, 1.0))
    plans = []
    for i in range(10):
        plen = int(rng.integers(200, 1800))
        plan = ctl.try_admit(f"q{i}", plen, 0.0)
        assert plan is not None
        series = _fake_request_series(plen, 60 + int(plen * 0.05), 0.8, 1.0)
        plans.append((plan, series, 1.0))
    w = ctl.reservation_wastage(plans)
    assert w["segmentwise_gib_s"] < w["peak_reservation_gib_s"]


def test_cache_bytes_per_token():
    cfg = get_config("mistral-large-123b")
    # 88 layers * 2 (k+v) * 8 kv heads * 128 head_dim * 2 bytes
    assert cache_bytes_per_token(cfg) == 88 * 2 * 8 * 128 * 2
    rwkv = get_config("rwkv6-1.6b")
    assert cache_bytes_per_token(rwkv) == 0  # attention-free: O(1) state


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_cache_bytes_per_token_matches_init_cache(name):
    """Cross-check the analytic count against ``jax.eval_shape`` of the real
    cache skeleton for every registered architecture, so layer-kind counting
    (dense/local/global/moe vs O(1) recurrent state) can't silently drift.

    KV bytes per token = the k/v leaves' bytes divided by (batch * max_len);
    those are exactly the float leaves shaped (..., batch, max_len, kv_heads,
    head_dim) — possibly under a leading scan-stack axis — while ``pos``
    bookkeeping and recurrent state carry no per-token payload.  ``max_len``
    is a prime no other cache dimension uses and stays below every window
    size, so the axis match is unambiguous and local layers are not
    window-clipped."""
    cfg = ARCHS[name]
    batch, max_len = 1, 7
    assert max_len <= cfg.window_size
    for dim in (cfg.num_kv_heads, cfg.head_dim, cfg.conv_width - 1, cfg.rnn_width, cfg.d_model):
        assert dim != max_len, "pick a max_len that no other cache dimension collides with"
    shapes = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    kv_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(shapes)
        if leaf.ndim >= 4 and leaf.shape[-3] == max_len and not jnp.issubdtype(leaf.dtype, jnp.integer)
    )
    assert kv_bytes % (batch * max_len) == 0
    assert kv_bytes // (batch * max_len) == cache_bytes_per_token(cfg), name


def test_admission_profile_cache_invalidation():
    """Regression: every state change that alters demand must drop the
    cached profile — admit and release change the active set (the next probe
    must see it), observe changes the model (the next prediction must see
    it)."""
    ctl = AdmissionController(hbm_budget_mib=1000.0, k=2, interval_s=1.0)
    big = StepAllocation(np.asarray([10.0, 30.0]), np.asarray([300.0, 900.0]))
    ctl.model = _FixedModel(big)

    # admit drops the cache: a second identical request must see the first
    assert ctl.try_admit("a", 100, 0.0) is not None
    assert ctl._prof is None
    assert ctl._combined_demand((15.0,))[0] == 900.0
    assert ctl.try_admit("b", 100, 0.0) is None  # 2 x 900 > 1000 seen

    # release drops the cache: the same request fits again afterwards
    assert ctl._prof is not None  # probe above cached it
    ctl.release("a")
    assert ctl._prof is None
    assert ctl._combined_demand((15.0,))[0] == 0.0
    assert ctl.try_admit("c", 100, 0.0) is not None

    # observe retrains the model: the next predict must reflect the new
    # history even with a probe-warmed profile cache
    real = AdmissionController(hbm_budget_mib=10_000.0, k=2, interval_s=1.0)
    for _ in range(3):
        real.observe(100, np.full(10, 50.0, np.float32))
    low = float(real.model.predict(100.0).values[-1])
    real._profile()  # warm the cache
    real.observe(100, np.full(10, 5000.0, np.float32))
    high = float(real.model.predict(100.0).values[-1])
    assert high > low  # the spike raised the prediction immediately
