"""Composable model layers, pure-functional JAX.

Conventions:
* params are nested dicts of jnp arrays; every layer has ``init_*`` and a
  matching apply function.
* activations flow in the config compute dtype (bf16 by default); norms,
  softmax statistics and logits are f32.
* attention is a flash-style KV-chunk ``lax.scan`` with online softmax — the
  (T, S) score matrix never materializes, which is what makes the 32k-prefill
  cells lowerable with bounded activation memory on TPU.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax.sharding import get_abstract_mesh
from repro.configs.base import ModelConfig

NEG_INF = -1.0e30


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def maybe_constrain(x, spec: P):
    """with_sharding_constraint if a mesh is in context, with per-dim
    sanitization: axes that are absent from the mesh or do not divide the
    dimension are dropped (single-device smoke tests run without a mesh)."""
    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    names = set(mesh.axis_names)
    out = []
    for dim, axes in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if axes is None:
            out.append(None)
            continue
        axes_t = tuple(a for a in (axes if isinstance(axes, tuple) else (axes,)) if a in names)
        size = lambda t: int(__import__("numpy").prod([mesh.shape[a] for a in t])) if t else 1
        while axes_t and dim % size(axes_t) != 0:
            axes_t = axes_t[:-1]
        out.append(axes_t if len(axes_t) > 1 else (axes_t[0] if axes_t else None))
    return jax.lax.with_sharding_constraint(x, P(*out))


def act_batch_axes(cfg: ModelConfig) -> tuple[str, ...]:
    """Mesh axes the activation *batch* dim shards over.

    fsdp mode: ("data", "model", "pod") — the model axis joins the batch
    FIRST (no tensor split to keep it busy) and the pod axis last, so a
    pod-sized batch (e.g. 256 on the 2x16x16 mesh) still shards 256 ways
    within each pod and the sanitizer drops only "pod" (which then carries
    pure parameter-FSDP + gradient sync) instead of idling the model axis."""
    mesh = get_abstract_mesh()
    names = mesh.axis_names if mesh is not None else ()
    if cfg.parallelism == "fsdp":
        order = ("data", "model", "pod")
    else:
        order = ("pod", "data")
    return tuple(a for a in order if a in names)


def constrain_act(cfg: ModelConfig, x, *rest):
    """Constrain an activation: batch over the data axes, then ``rest``."""
    ba = act_batch_axes(cfg)
    return maybe_constrain(x, P(ba if ba else None, *rest))


def constrain_logits(cfg: ModelConfig, logits):
    """Logits: vocab over "model" whenever the batch doesn't occupy it.

    tp mode: batch over ("pod","data"), vocab over "model" (always).
    fsdp mode: the batch prefers to span every axis; only when the global
    batch can't use the model axis (sanitizer would drop it) does the vocab
    take it.  REPRO_FSDP_VOCAB=off disables fsdp vocab sharding entirely
    (A/B measurement knob, see EXPERIMENTS SPerf)."""
    import os

    mesh = get_abstract_mesh()
    names = mesh.axis_names if mesh is not None else ()
    ba = tuple(a for a in ("pod", "data") if a in names)
    if cfg.parallelism == "tp":
        return maybe_constrain(logits, P(ba if ba else None, None, "model"))
    if os.environ.get("REPRO_FSDP_VOCAB", "tp") == "off":
        return constrain_act(cfg, logits)
    import numpy as _np

    B = logits.shape[0]
    full = ba + (("model",) if "model" in names else ())
    if full and B % int(_np.prod([mesh.shape[a] for a in full])) == 0:
        return maybe_constrain(logits, P(full, None, None))  # batch owns every axis
    return maybe_constrain(logits, P(ba if ba else None, None, "model"))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rms_norm(p, x, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + p["scale"])
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def _rope_angles(positions, head_dim: int, theta: float, sections=None):
    """positions: (B, T) or (3, B, T) for M-RoPE.  Returns (B, T, head_dim/2)
    angles.  M-RoPE: frequency slots are split into (t, h, w) sections, each
    driven by its own position row (Qwen2-VL Sec. 3)."""
    half = head_dim // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim))
    if sections is None:
        pos = positions if positions.ndim == 2 else positions[0]
        return pos[..., None].astype(jnp.float32) * freq
    assert positions.ndim == 3, "M-RoPE needs (3, B, T) positions"
    sec_id = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=half)
    pos = jnp.take(positions, sec_id, axis=0)  # (half, B, T)
    pos = jnp.moveaxis(pos, 0, -1).astype(jnp.float32)  # (B, T, half)
    return pos * freq


def apply_rope(x, positions, theta: float, sections=None):
    """x: (B, T, N, head_dim) -> rotated (pairs interleaved as [::2, 1::2])."""
    ang = _rope_angles(positions, x.shape[-1], theta, sections)  # (B, T, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash attention (KV-chunk scan, online softmax)
# ---------------------------------------------------------------------------


def flash_attention(
    q,
    k,
    v,
    q_pos,
    k_pos,
    *,
    causal: bool,
    window: int | None,
    softcap: float | None,
    kv_chunk: int = 1024,
    cfg: ModelConfig | None = None,
    unroll: bool = False,
):
    """q: (B,T,H,hd); k,v: (B,S,KV,hd) with H % KV == 0 (GQA expansion happens
    per chunk, so caches stay KV-sized); q_pos: (B,T); k_pos: (B,S), -1 marks
    invalid slots.  Returns (B,T,H,hd) in q.dtype.

    Layout note: the (B,T,H,hd) form keeps the head axis intact so the
    "model"-axis head sharding survives GSPMD propagation (a (KV,G,hd) split
    is not evenly shardable for most GQA configs)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    S = k.shape[1]
    scale = hd**-0.5
    C = min(kv_chunk, S)
    n_chunks = -(-S // C)
    pad = n_chunks * C - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    kc = k.reshape(B, n_chunks, C, KV, hd).swapaxes(0, 1)
    vc = v.reshape(B, n_chunks, C, KV, hd).swapaxes(0, 1)
    pc = k_pos.reshape(B, n_chunks, C).swapaxes(0, 1)

    from repro.models import flags as _flags

    if _flags.USE_FLASH_KERNEL:
        from repro.kernels.flash import flash_attention_pallas

        return flash_attention_pallas(
            q, k, v,
            jnp.broadcast_to(q_pos, (B, T)).astype(jnp.int32),
            k_pos.astype(jnp.int32),
            causal=causal, window=window, softcap=softcap,
            interpret=jax.default_backend() != "tpu",
        )

    qf = q.astype(jnp.float32)
    head_spec = ("model",) if (cfg is None or cfg.parallelism == "tp") else ()

    def constrain(x, *rest):
        if cfg is None:
            return x
        return constrain_act(cfg, x, *rest)

    def step(carry, chunk):
        m, l, acc = carry
        kci, vci, pci = chunk
        # GQA expansion is chunk-local: (B,C,KV,hd) -> (B,C,H,hd).  Expanded
        # copies stay in bf16 (halved HBM traffic, SPerf iteration 5); the
        # MXU accumulates the scores in f32 via preferred_element_type.
        kx = jnp.repeat(kci, G, axis=2)
        vx = jnp.repeat(vci, G, axis=2)
        kx = constrain(kx, None, *head_spec, None)
        vx = constrain(vx, None, *head_spec, None)
        s = jnp.einsum("bthd,bchd->bthc", qf.astype(kx.dtype), kx,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        ok = pci[:, None, :] >= 0  # (B, 1, C) valid slots
        if causal:
            ok &= pci[:, None, :] <= q_pos[:, :, None]
        if window is not None:
            ok &= pci[:, None, :] > q_pos[:, :, None] - window
        s = jnp.where(ok[:, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bthc,bchd->bthd", p.astype(vx.dtype), vx, preferred_element_type=jnp.float32
        )
        return (m_new, l_new, acc_new), None

    init = (
        constrain(jnp.full((B, T, H), NEG_INF, jnp.float32), None, *head_spec),
        constrain(jnp.zeros((B, T, H), jnp.float32), None, *head_spec),
        constrain(jnp.zeros((B, T, H, hd), jnp.float32), None, *head_spec, None),
    )
    from repro.models import flags

    unroll_n = 1
    if unroll or flags.COST_MODE:
        unroll_n = min(n_chunks, flags.COST_CHUNK_CAP) if flags.COST_MODE else n_chunks
    (m, l, acc), _ = jax.lax.scan(step, init, (kc, vc, pc), unroll=unroll_n)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig):
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = D**-0.5
    dt = cdtype(cfg)
    p = {
        "wq": (jax.random.normal(k1, (D, H * hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (D, KV * hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (D, KV * hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (H * hd, D)) * (H * hd) ** -0.5).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def attention(
    p,
    x,
    q_pos,
    cfg: ModelConfig,
    *,
    local: bool,
    cache=None,
    mrope_positions=None,
):
    """Returns (out, new_cache).  Modes:
    * cache is None           — train/prefill forward over T tokens.
    * cache is a dict         — decode: x is (B, 1, D); cache {k, v, pos} is
      updated at slot ``pos % S_c`` (rolling for local windows).
    """
    B, T, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.window_size if local else None
    causal = not cfg.is_encoder
    head_spec = ("model",) if cfg.parallelism == "tp" else ()

    q = (x @ p["wq"]).reshape(B, T, H, hd)
    k = (x @ p["wk"]).reshape(B, T, KV, hd)
    v = (x @ p["wv"]).reshape(B, T, KV, hd)
    q = constrain_act(cfg, q, None, *head_spec, None)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    sections = cfg.mrope_sections
    rope_pos = mrope_positions if sections is not None else q_pos
    if sections is None and rope_pos.ndim == 1:
        rope_pos = rope_pos[:, None]  # decode: (B,) -> (B, 1)
    q = apply_rope(q, rope_pos, cfg.rope_theta, sections)
    k = apply_rope(k, rope_pos, cfg.rope_theta, sections)

    if cache is None:
        k_pos = q_pos
        out = flash_attention(
            q, k, v, q_pos, k_pos, causal=causal, window=window, softcap=cfg.attn_softcap, cfg=cfg
        )
        new_cache = None
    else:
        S_c = cache["k"].shape[1]
        slot = (q_pos % S_c).astype(jnp.int32)  # (B,) rolling slot
        bidx = jnp.arange(B)
        ck = cache["k"].at[bidx, slot].set(k[:, 0])
        cv = cache["v"].at[bidx, slot].set(v[:, 0])
        cp = cache["pos"].at[bidx, slot].set(q_pos)
        # NOTE (SPerf iteration 6, REFUTED): constraining q to the cache's
        # hd sharding here makes GSPMD psum partial f32 score buffers per
        # chunk — 6x MORE bytes than the cache all-gather it avoids.  The
        # head-sharded q + per-layer cache gather below is the better XLA
        # plan; the real fix is the fused kernel (kernels/flash.py), which
        # reads the hd-sharded cache locally and never materializes scores.
        out = flash_attention(
            q, ck, cv, q_pos[:, None], cp, causal=causal, window=window, softcap=cfg.attn_softcap, cfg=cfg
        )
        new_cache = {"k": ck, "v": cv, "pos": cp}

    out = out.reshape(B, T, H * hd) @ p["wo"]
    return out, new_cache


def build_cache(cfg: ModelConfig, batch: int, seq_len: int, *, local: bool):
    """Empty KV cache for one attention layer (pos = -1 marks invalid)."""
    S_c = min(cfg.window_size, seq_len) if local else seq_len
    dt = cdtype(cfg)
    return {
        "k": jnp.zeros((batch, S_c, cfg.num_kv_heads, cfg.head_dim), dt),
        "v": jnp.zeros((batch, S_c, cfg.num_kv_heads, cfg.head_dim), dt),
        "pos": jnp.full((batch, S_c), -1, jnp.int32),
    }


def cache_from_prefill(cfg: ModelConfig, k, v, positions, *, local: bool, max_len: int | None = None):
    """Build a decode cache from prefill-computed k/v.

    Entries land at slot ``pos % S_c`` — the same rolling mapping decode
    writes with — so prefill+decode agree for local windows, and global
    caches sized ``max_len > T`` leave room for decoded tokens."""
    B, T = positions.shape
    max_len = max_len or T
    S_c = min(cfg.window_size, max_len) if local else max_len
    if T > S_c:  # only the last window can matter
        k, v, positions = k[:, -S_c:], v[:, -S_c:], positions[:, -S_c:]
    cache = build_cache(cfg, B, max_len, local=local)
    bidx = jnp.arange(B)[:, None]
    slot = positions % S_c
    return {
        "k": cache["k"].at[bidx, slot].set(k.astype(cache["k"].dtype)),
        "v": cache["v"].at[bidx, slot].set(v.astype(cache["v"].dtype)),
        "pos": cache["pos"].at[bidx, slot].set(positions),
    }


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None):
    D, F = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = cdtype(cfg)
    return {
        "wi": (jax.random.normal(k1, (D, F)) * D**-0.5).astype(dt),
        "wg": (jax.random.normal(k2, (D, F)) * D**-0.5).astype(dt),
        "wo": (jax.random.normal(k3, (F, D)) * F**-0.5).astype(dt),
    }


def mlp(p, x, activation: str, cfg: ModelConfig | None = None):
    act = jax.nn.gelu if activation == "gelu" else jax.nn.silu
    h = act(x @ p["wg"]) * (x @ p["wi"])
    if cfg is not None and cfg.parallelism == "tp":
        h = constrain_act(cfg, h, None, "model")
    return h @ p["wo"]


# ---------------------------------------------------------------------------
# Mixture of Experts (sort-based dispatch; no one-hot matmuls, so the HLO
# FLOP count stays ~= the active-expert FLOPs and dispatch is data movement)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig):
    D, E, Fe = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = cdtype(cfg)
    return {
        "router": (jax.random.normal(k1, (D, E)) * D**-0.5).astype(jnp.float32),
        "wi": (jax.random.normal(k2, (E, D, Fe)) * D**-0.5).astype(dt),
        "wg": (jax.random.normal(k3, (E, D, Fe)) * D**-0.5).astype(dt),
        "wo": (jax.random.normal(k4, (E, Fe, D)) * Fe**-0.5).astype(dt),
    }


def _moe_dispatch_compute(xf, router, wi, wg, wo, *, cfg: ModelConfig, e_offset, E_local: int, capacity: int):
    """Core MoE math over a flat token block against an expert/FFN slice.

    xf: (N, D); router: (D, E_total); wi/wg: (E_local, D, F[_local]);
    wo: (E_local, F[_local], D).  Returns (out (N, D) [partial if FFN is
    sliced], aux, probs).  Pure function of local data — also the body of the
    shard_map path (per-device tokens x per-device expert slice)."""
    N, D = xf.shape
    E, k = cfg.num_experts, cfg.experts_per_token

    logits = xf.astype(jnp.float32) @ router  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)  # (N, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    flat_ids = ids.reshape(-1)  # (N*k,) global expert ids
    flat_tok = jnp.repeat(jnp.arange(N), k)
    flat_w = weights.reshape(-1)
    local_e = flat_ids - e_offset
    in_slice = (local_e >= 0) & (local_e < E_local)
    sort_key = jnp.where(in_slice, local_e, E_local)  # out-of-slice -> end
    order = jnp.argsort(sort_key)
    s_e = jnp.clip(local_e[order], 0, E_local - 1)
    s_tok, s_w, s_in = flat_tok[order], flat_w[order], in_slice[order]
    counts = jnp.bincount(jnp.where(in_slice, local_e, E_local), length=E_local + 1)[:E_local]
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(N * k) - starts[s_e]
    keep = s_in & (pos_in_e < capacity)
    pos_c = jnp.where(keep, pos_in_e, 0)

    buf = jnp.zeros((E_local, capacity, D), xf.dtype)
    buf = buf.at[s_e, pos_c].add(jnp.where(keep[:, None], xf[s_tok], 0))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum("ecd,edf->ecf", buf, wi)
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo)
    gathered = out_buf[s_e, pos_c] * jnp.where(keep, s_w, 0.0)[:, None].astype(xf.dtype)
    out = jnp.zeros((N, D), xf.dtype).at[s_tok].add(gathered)

    density = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(density * jnp.mean(probs, axis=0))
    return out, aux


def moe_shard_map(p, x, cfg: ModelConfig):
    """Sharded MoE: per-device local dispatch + one psum over "model".

    GSPMD cannot partition a token scatter/gather whose indices span the
    global batch — it replicates (N_global, D) buffers and all-reduces them
    (the measured 20x collective blowup on qwen3, EXPERIMENTS SPerf).  Under
    shard_map each device dispatches only its LOCAL tokens:

      ep: against its expert slice (E/16 experts, full FFN); a token's top-k
          experts live on up to k model ranks, so partial outputs psum over
          "model" — the same collective shape as a TP MLP.
      tp: against all experts with the FFN dim sliced; the wo contraction is
          partial over F, psum over "model" again.

    Weights enter gathered over their FSDP axes (in_specs below) — the same
    per-layer weight gather every dense layer pays under FSDP.
    """
    mesh = get_abstract_mesh()
    ba = act_batch_axes(cfg)
    B, T, D = x.shape
    # drop trailing batch axes the (micro)batch doesn't divide (e.g. a
    # 16-row microbatch on the 2x16x16 mesh shards over "data" only)
    size = lambda axes: int(__import__("numpy").prod([mesh.shape[a] for a in axes])) if axes else 1
    while ba and B % size(ba) != 0:
        ba = ba[:-1]
    E, k = cfg.num_experts, cfg.experts_per_token
    ep = cfg.moe_sharding == "ep"
    model_n = mesh.shape["model"]
    dp = size(ba)
    N_local = (B // dp) * T
    capacity = int(N_local * k / E * cfg.capacity_factor) + 1
    E_local = E // model_n if ep else E

    def local_fn(xl, router, wi, wg, wo):
        B_, T_, D_ = xl.shape
        xf = xl.reshape(B_ * T_, D_)
        e_off = jax.lax.axis_index("model") * E_local if ep else 0
        out, aux = _moe_dispatch_compute(
            xf, router, wi, wg, wo, cfg=cfg, e_offset=e_off, E_local=E_local, capacity=capacity
        )
        out = jax.lax.psum(out, "model")
        # aux varies only over the batch axes (tokens are replicated across
        # "model"); pmean over exactly those keeps the vma checker happy
        aux = jax.lax.pmean(aux, ba) if ba else aux
        return out.reshape(B_, T_, D_), aux

    wspec = P("model", None, None) if ep else P(None, None, "model")
    wospec = P("model", None, None) if ep else P(None, "model", None)
    ba_spec = ba if len(ba) != 1 else ba[0]
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(ba_spec), P(), wspec, wspec, wospec),
        out_specs=(P(ba_spec), P()),
    )(x, p["router"], p["wi"], p["wg"], p["wo"])


def moe(p, x, cfg: ModelConfig):
    """Top-k routed experts with capacity-bounded sort-based dispatch.

    Returns (out, aux_loss).  Dropped tokens (over capacity) contribute zero —
    standard GShard semantics.  Expert sharding: "ep" places whole experts on
    the model axis (per-device expert subsets), "tp" shards every expert's
    FFN over the model axis.  Under a mesh, dispatch runs per device via
    ``moe_shard_map`` (see there); the plain path below serves single-device
    smoke tests and is the semantic reference.
    """
    mesh = get_abstract_mesh()
    if mesh is not None and not mesh.empty and "model" in mesh.axis_names:
        return moe_shard_map(p, x, cfg)
    B, T, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    N = B * T
    xf = x.reshape(N, D)

    logits = (xf.astype(jnp.float32)) @ p["router"]  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)  # (N, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch): E * sum_e f_e * p_e
    density = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(density * jnp.mean(probs, axis=0))

    capacity = int(N * k / E * cfg.capacity_factor) + 1

    flat_ids = ids.reshape(-1)  # (N*k,)
    flat_tok = jnp.repeat(jnp.arange(N), k)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_ids)
    s_ids, s_tok, s_w = flat_ids[order], flat_tok[order], flat_w[order]
    counts = jnp.bincount(flat_ids, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(N * k) - starts[s_ids]
    keep = pos_in_e < capacity
    pos_c = jnp.where(keep, pos_in_e, 0)

    buf = jnp.zeros((E, capacity, D), x.dtype)
    buf = buf.at[s_ids, pos_c].add(jnp.where(keep[:, None], xf[s_tok], 0))
    if cfg.moe_sharding == "ep":
        buf = maybe_constrain(buf, P("model", None, None))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    if cfg.moe_sharding == "ep":
        out_buf = maybe_constrain(out_buf, P("model", None, None))

    gathered = out_buf[s_ids, pos_c] * jnp.where(keep, s_w, 0.0)[:, None].astype(x.dtype)
    out = jnp.zeros((N, D), x.dtype).at[s_tok].add(gathered)
    return out.reshape(B, T, D), aux
