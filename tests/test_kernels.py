"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp refs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(3, 100, 4), (8, 512, 4), (17, 1333, 7), (1, 5, 4), (5, 2048, 1), (12, 600, 16), (9, 513, 3)]
DTYPES = [np.float32, np.float64]  # inputs cast to f32 inside; f64 checks the cast path


@pytest.mark.parametrize("B,T,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_segmax_matches_ref(B, T, k, dtype):
    rng = np.random.default_rng(B * 1000 + T + k)
    y = rng.uniform(1, 1e4, (B, T)).astype(dtype)
    lengths = rng.integers(1, T + 1, B).astype(np.int32)
    out = np.asarray(ops.segment_peaks(jnp.asarray(y, jnp.float32), jnp.asarray(lengths), k))
    want = np.asarray(ref.segment_peaks(jnp.asarray(y, jnp.float32), jnp.asarray(lengths), k))
    np.testing.assert_allclose(out, want, rtol=1e-6)


@pytest.mark.parametrize("B,T,k", SHAPES)
def test_fitstats_matches_ref(B, T, k):
    rng = np.random.default_rng(B + T + k)
    x = rng.uniform(-50, 50, B)
    peaks = rng.uniform(0, 1e3, (B, k)).astype(np.float32)
    valid = rng.integers(0, 2, B)
    out = np.asarray(ops.fit_stats(jnp.asarray(x), jnp.asarray(peaks), jnp.asarray(valid)))
    want = np.asarray(ref.fit_stats(jnp.asarray(x), jnp.asarray(peaks), jnp.asarray(valid)))
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-2)
    assert out.shape == (k, 5)


@pytest.mark.parametrize("B,T,k", SHAPES)
def test_wastage_matches_ref(B, T, k):
    rng = np.random.default_rng(B * 7 + T + k)
    y = rng.uniform(1, 1200, (B, T)).astype(np.float32)
    lengths = rng.integers(1, T + 1, B).astype(np.int32)
    bounds = np.sort(rng.uniform(1, T * 2.0, (B, k)), axis=1).astype(np.float32)
    values = np.maximum.accumulate(rng.uniform(10, 1400, (B, k)), axis=1).astype(np.float32)
    wk, ik = ops.attempt_wastage(jnp.asarray(y), jnp.asarray(lengths), jnp.asarray(bounds), jnp.asarray(values), 2.0)
    wr, ir = ref.attempt_wastage(jnp.asarray(y), jnp.asarray(lengths), jnp.asarray(bounds), jnp.asarray(values), 2.0)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(wk), np.asarray(wr), rtol=1e-4, atol=1e-3)


def test_wastage_failure_state_machine_across_blocks():
    """A failure in a later T-block must not double-count earlier blocks."""
    B, T = 8, 1536  # 3 blocks of 512
    y = np.full((B, T), 10.0, np.float32)
    y[:, 1100] = 1e6  # fail in block 3
    lengths = np.full(B, T, np.int32)
    bounds = np.asarray([[T * 2.0]] * B, np.float32)
    values = np.asarray([[50.0]] * B, np.float32)
    w, fi = ops.attempt_wastage(jnp.asarray(y), jnp.asarray(lengths), jnp.asarray(bounds), jnp.asarray(values), 2.0)
    assert np.all(np.asarray(fi) == 1100)
    np.testing.assert_allclose(np.asarray(w), 50.0 * 1101 * 2.0 / 1024.0, rtol=1e-5)


@pytest.mark.parametrize("B,L", [(1, 8), (3, 100), (8, 512), (17, 640), (5, 2048)])
def test_compact_events_pallas_matches_jnp(B, L):
    """The sweep's chunk-boundary compaction: the Pallas roll-and-shift
    kernel vs the jnp rank-scatter twin, bit for bit — kept entries move to
    the front in order, (+inf, 0) identities fill the tail."""
    from repro.kernels import compaction

    rng = np.random.default_rng(B * 101 + L)
    t = np.sort(rng.uniform(0.0, 1e4, (B, L)), axis=1).astype(np.float32)
    d = rng.uniform(-200.0, 200.0, (B, L)).astype(np.float32)
    keep = rng.random((B, L)) < rng.uniform(0.05, 0.9)
    # padded tails carry the identity and are never kept
    n_pad = rng.integers(0, L // 2 + 1, B)
    for i, p in enumerate(n_pad):
        if p:
            t[i, L - p :], d[i, L - p :], keep[i, L - p :] = np.inf, 0.0, False
    out_t, out_d = ops.compact_events(jnp.asarray(t), jnp.asarray(d), jnp.asarray(keep))
    ref_t, ref_d = compaction.compact_events_jnp(
        jnp.asarray(t), jnp.asarray(d), jnp.asarray(keep), float("inf")
    )
    np.testing.assert_array_equal(np.asarray(out_t), np.asarray(ref_t))
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(ref_d))
    # semantics against a python oracle: stable front-compaction
    for i in range(B):
        kt, kd = t[i, keep[i]], d[i, keep[i]]
        n = len(kt)
        np.testing.assert_array_equal(np.asarray(out_t)[i, :n], kt)
        np.testing.assert_array_equal(np.asarray(out_d)[i, :n], kd)
        assert np.all(np.isinf(np.asarray(out_t)[i, n:]))
        assert np.all(np.asarray(out_d)[i, n:] == 0.0)


def test_kernels_against_trace_corpus():
    """Integration: kernels reproduce the oracle on generated workflow traces."""
    from repro.sim import generate_eager

    wf = generate_eager(seed=3, scale=0.1)
    trace = wf.eligible_tasks(5)[0]
    x, y, lengths = trace.padded()
    k = 4
    peaks = np.asarray(ops.segment_peaks(jnp.asarray(y), jnp.asarray(lengths), k))
    want = np.stack([np.asarray(ref.segment_peaks(jnp.asarray(y), jnp.asarray(lengths), k))])[0]
    np.testing.assert_allclose(peaks, want, rtol=1e-5)


# ---------------------------------------------------------------------------
# Pallas flash attention vs the XLA flash path (models/layers)
# ---------------------------------------------------------------------------

FLASH_CASES = [
    (2, 64, 64, 4, 2, 16, True, None, None),
    (1, 300, 300, 8, 8, 32, True, None, 50.0),   # softcap
    (2, 37, 37, 6, 2, 16, True, 16, None),        # local window
    (2, 1, 80, 4, 4, 16, True, None, None),       # decode (ragged cache)
    (1, 128, 128, 4, 2, 64, False, None, None),   # encoder
]


@pytest.mark.parametrize("B,T,S,H,KV,hd,causal,window,cap", FLASH_CASES)
def test_flash_kernel_matches_xla(B, T, S, H, KV, hd, causal, window, cap):
    from repro.kernels.flash import flash_attention_pallas
    from repro.models.layers import flash_attention

    rng = np.random.default_rng(B * 31 + T)
    q = jnp.asarray(rng.normal(0, 1, (B, T, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (B, S, KV, hd)).astype(np.float32))
    if T == 1:
        qpos = jnp.full((B, 1), 40, jnp.int32)
        kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
        kpos = jnp.where(kpos < 60, kpos, -1)
    else:
        qpos = jnp.broadcast_to(jnp.arange(T)[None], (B, T)).astype(jnp.int32)
        kpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    want = flash_attention(q, k, v, qpos, kpos, causal=causal, window=window, softcap=cap)
    got = flash_attention_pallas(
        q, k, v, qpos, kpos, causal=causal, window=window, softcap=cap, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-5, rtol=1e-4
    )


def test_flash_kernel_end_to_end_gemma():
    """Whole-model equivalence with the kernel enabled (softcap + local/global)."""
    from repro.configs import get_config
    from repro.models import forward, init_params
    from repro.models import flags

    cfg = get_config("gemma2-9b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    import jax as _jax

    tokens = _jax.random.randint(_jax.random.PRNGKey(1), (2, 50), 0, cfg.vocab_size)
    a, _, _ = forward(params, cfg, tokens)
    flags.USE_FLASH_KERNEL = True
    try:
        b, _, _ = forward(params, cfg, tokens)
    finally:
        flags.USE_FLASH_KERNEL = False
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-2, rtol=1e-2)
