"""Core neural-net layers in pure JAX (functional: init_* / apply pairs).

Conventions
-----------
* params are nested dicts of jnp arrays;
* activations are (batch, seq, ...) with compute in ``cfg.compute_dtype``;
* attention is implemented *blockwise* (static q-chunk loop with exact
  causal/windowed kv prefixes) so the lowered HLO never materialises an
  S x S score tensor and FLOPs stay ~2 * S^2/2 * D for causal attention.
  This is the pure-JAX analogue of the Pallas flash kernel in
  ``repro.kernels.flash_attention`` (the TPU-target version); the dry-run
  lowers this one because Pallas TPU kernels cannot lower on the CPU
  backend used for the 512-device placeholder mesh.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# mesh-aware activation sharding constraint
# ---------------------------------------------------------------------------


def shard_batch(x, n_batch_dims: int = 1):
    """Constrain the leading batch dim(s) to the (pod, data) mesh axes when
    lowering inside a mesh context; no-op otherwise (CPU FL runs).

    Without this, GSPMD propagates the embedding table's sharding through
    the gather and replicates the batch — measured 16x activation blow-up
    on the dry-run (see EXPERIMENTS.md §Dry-run).
    """
    try:
        import os
        from jax.sharding import PartitionSpec as _P, get_abstract_mesh
        mesh = get_abstract_mesh()
        if mesh is None or not mesh.axis_names:
            return x
        batch_axes = tuple(os.environ.get("REPRO_BATCH_AXES",
                                          "pod,data").split(","))
        axes = tuple(a for a in batch_axes if a in mesh.axis_names)
        while axes:
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if x.shape[0] % n == 0:
                break
            axes = axes[:-1]
        if not axes:
            return x
        spec = _P(axes, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, in_dim: int, out_dim: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(rng, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(rng, vocab: int, dim: int, dtype):
    return (jax.random.normal(rng, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_init(cfg: ModelConfig, dim: Optional[int] = None):
    dim = dim or cfg.d_model
    if cfg.norm_type == "layer":
        return {"scale": jnp.ones((dim,), cfg.param_dtype),
                "bias": jnp.zeros((dim,), cfg.param_dtype)}
    return {"scale": jnp.zeros((dim,), cfg.param_dtype)}  # gemma-style (1+scale)


def norm_apply(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layer":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * (1.0 + p["scale"].astype(jnp.float32))
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float, fraction: float = 1.0):
    """x: (..., S, H, D) with D even; positions: (..., S).

    ``fraction`` < 1 rotates the first ``fraction * D`` dims (half-split
    within them, frequencies over that width) and passes the rest
    through, as Nemotron's partial rotary does."""
    d = x.shape[-1]
    rot = int(d * fraction)
    if rot != d:
        if rot <= 0 or rot % 2:
            raise ValueError(f"rope_fraction {fraction} of head dim {d} "
                             f"gives {rot} rotary dims; need a positive "
                             f"even number")
        return jnp.concatenate([rope(x[..., :rot], positions, theta),
                                x[..., rot:]], axis=-1)
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    ang = ang[..., None, :]                                   # (..., S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(rng, cfg: ModelConfig, d_ff: Optional[int] = None, d_model: Optional[int] = None):
    d_ff = d_ff or cfg.d_ff
    dm = d_model or cfg.d_model
    r = jax.random.split(rng, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {"w_gate": dense_init(r[0], dm, d_ff, cfg.param_dtype),
                "w_up": dense_init(r[1], dm, d_ff, cfg.param_dtype),
                "w_down": dense_init(r[2], d_ff, dm, cfg.param_dtype)}
    p = {"w_up": dense_init(r[0], dm, d_ff, cfg.param_dtype),
         "w_down": dense_init(r[1], d_ff, dm, cfg.param_dtype)}
    if cfg.mlp_bias:
        p["b_up"] = jnp.zeros((d_ff,), cfg.param_dtype)
        p["b_down"] = jnp.zeros((dm,), cfg.param_dtype)
    return p


def mlp_apply(p, x, cfg: ModelConfig):
    if cfg.mlp_type in ("swiglu", "geglu"):
        g = x @ p["w_gate"]
        act = jax.nn.silu(g) if cfg.mlp_type == "swiglu" else jax.nn.gelu(g)
        return (act * (x @ p["w_up"])) @ p["w_down"]
    h = x @ p["w_up"]
    if cfg.mlp_bias:
        h = h + p["b_up"]
    if cfg.mlp_type == "relu2":                     # nemotron squared-ReLU
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    out = h @ p["w_down"]
    return out + p["b_down"] if cfg.mlp_bias else out


# ---------------------------------------------------------------------------
# blockwise causal attention (pure JAX, exact FLOPs, no S x S tensor)
# ---------------------------------------------------------------------------


def _softcap(scores, cap: Optional[float]):
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _scores_mask(qpos, kpos, window, causal=True):
    mask = kpos[None, :] >= 0                       # padding slots carry kpos=-1
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    return mask


def _attend_block(q, k, v, qpos, kpos, scale, softcap, window, kv_chunk=2048,
                  causal=True):
    """q: (B,Cq,H,D) k/v: (B,L,KVH,D) -> (B,Cq,H,Dv). fp32 online softmax.

    When the kv prefix is long, an inner lax.scan over kv chunks keeps the
    score tensor at (B,KVH,G,Cq,kv_chunk) — the flash-attention memory
    pattern, expressed in pure JAX so it lowers on any backend.
    """
    b, cq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    dv = v.shape[-1]
    qg = q.reshape(b, cq, kvh, g, d)
    L = k.shape[1]

    if L <= 2 * kv_chunk:
        scores = jnp.einsum("bqkgd,blkd->bkgql", qg, k).astype(jnp.float32) * scale
        scores = _softcap(scores, softcap)
        mask = _scores_mask(qpos, kpos, window, causal)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        out = jnp.einsum("bkgql,blkd->bqkgd", w, v)
        return out.reshape(b, cq, h, dv)

    n = (L + kv_chunk - 1) // kv_chunk
    pad = n * kv_chunk - L
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pad), constant_values=-1)
    ks = k.reshape(b, n, kv_chunk, kvh, d).swapaxes(0, 1)
    vs = v.reshape(b, n, kv_chunk, kvh, dv).swapaxes(0, 1)
    kps = kpos.reshape(n, kv_chunk)

    m0 = jnp.full((b, kvh, g, cq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, cq), jnp.float32)
    a0 = jnp.zeros((b, cq, kvh, g, dv), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, kp = xs
        s = jnp.einsum("bqkgd,blkd->bkgql", qg, kb).astype(jnp.float32) * scale
        s = _softcap(s, softcap)
        mask = _scores_mask(qpos, kp, window, causal)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgql,blkd->bqkgd", p.astype(vb.dtype), vb)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, kps))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out.astype(v.dtype).reshape(b, cq, h, dv)


def bidir_attention(q, k, v, *, softcap=None, scale=None, kv_chunk=2048):
    """Full bidirectional attention (encoder). q:(B,Sq,H,D) k/v:(B,Sk,KVH,D)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qpos = jnp.arange(q.shape[1])
    kpos = jnp.arange(k.shape[1])
    return _attend_block(q, k, v, qpos, kpos, scale, softcap, None,
                         kv_chunk=kv_chunk, causal=False)


def blockwise_attention(q, k, v, *, window: Optional[int], softcap: Optional[float],
                        q_chunk: int, scale: Optional[float] = None):
    """Causal (optionally windowed) attention.

    q: (B, S, H, Dq), k: (B, S, KVH, Dq), v: (B, S, KVH, Dv).
    Static python loop over q chunks; chunk i attends to the exact causal
    (or windowed) kv prefix with *static* slice bounds, so HLO FLOPs equal
    the true ~S^2/2 (or S*W) cost.
    """
    b, s, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    c = min(q_chunk, s)
    n = (s + c - 1) // c
    outs = []
    for i in range(n):
        q0, q1 = i * c, min((i + 1) * c, s)
        k1 = q1
        k0 = 0 if window is None else max(0, q1 - window - (q1 - q0))
        qpos = jnp.arange(q0, q1)
        kpos = jnp.arange(k0, k1)
        outs.append(_attend_block(q[:, q0:q1], k[:, k0:k1], v[:, k0:k1],
                                  qpos, kpos, scale, softcap, window))
    return jnp.concatenate(outs, axis=1) if n > 1 else outs[0]


def decode_attention(q, k_cache, v_cache, index, *, window: Optional[int],
                     softcap: Optional[float], scale: Optional[float] = None):
    """Single-token attention over a (possibly rolling) cache.

    q: (B, 1, H, D); caches: (B, S_buf, KVH, D); index: scalar int32 = number
    of tokens written so far (absolute). Slots hold absolute positions
    ``slot_pos``; with a rolling buffer slot j holds position
    index-1 - ((write-1 - j) mod S_buf) — but masking only needs validity +
    window, both derivable from index.
    """
    b, _, h, d = q.shape
    s_buf = k_cache.shape[1]
    kvh = k_cache.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, 1, kvh, g, d)
    scores = jnp.einsum("bqkgd,blkd->bkgql", qg, k_cache).astype(jnp.float32) * scale
    scores = _softcap(scores, softcap)
    slot = jnp.arange(s_buf)
    write = (index - 1) % s_buf                     # slot of newest token
    age = (write - slot) % s_buf                    # 0 = newest
    valid = age < jnp.minimum(index, s_buf)
    if window is not None:
        valid &= age < window
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgql,blkd->bqkgd", w, v_cache)
    return out.reshape(b, 1, h, -1)


# ---------------------------------------------------------------------------
# GQA attention layer (params + cache plumbing)
# ---------------------------------------------------------------------------


def attn_init(rng, cfg: ModelConfig, kv_dim: Optional[int] = None):
    dm = cfg.d_model
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    r = jax.random.split(rng, 4)
    kd = kv_dim or dm  # cross-attention reads from encoder width
    p = {"wq": dense_init(r[0], dm, h * hd, cfg.param_dtype),
         "wk": dense_init(r[1], kd, kvh * hd, cfg.param_dtype),
         "wv": dense_init(r[2], kd, kvh * hd, cfg.param_dtype),
         "wo": dense_init(r[3], h * hd, dm, cfg.param_dtype)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), cfg.param_dtype)
        p["bk"] = jnp.zeros((kvh * hd,), cfg.param_dtype)
        p["bv"] = jnp.zeros((kvh * hd,), cfg.param_dtype)
    return p


def _qkv(p, x, cfg: ModelConfig, src=None):
    b, s, _ = x.shape
    src = x if src is None else src
    q = x @ p["wq"]
    k = src @ p["wk"]
    v = src @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, src.shape[1], cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, src.shape[1], cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def attn_apply_full(p, x, positions, cfg: ModelConfig, *, window=None):
    """Training / prefill forward (no cache in, optionally cache out)."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    with jax.named_scope("rope"):
        q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    out = blockwise_attention(q, k, v, window=window, softcap=cfg.attn_softcap,
                              q_chunk=cfg.q_chunk)
    return out.reshape(b, s, -1) @ p["wo"], (k, v)


def attn_apply_decode(p, x, cache, cfg: ModelConfig, *, window=None):
    """One-token decode. cache = {"k","v": (B,S_buf,KVH,D), "index": ()}"""
    b = x.shape[0]
    q, k, v = _qkv(p, x, cfg)
    idx = cache["index"]
    pos = jnp.full((b, 1), idx, jnp.int32)
    q = rope(q, pos, cfg.rope_theta, cfg.rope_fraction)
    k = rope(k, pos, cfg.rope_theta, cfg.rope_fraction)
    s_buf = cache["k"].shape[1]
    slot = idx % s_buf
    k_cache = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                           (0, slot, 0, 0))
    out = decode_attention(q, k_cache, v_cache, idx + 1, window=window,
                           softcap=cfg.attn_softcap)
    new_cache = {"k": k_cache, "v": v_cache, "index": idx + 1}
    return out.reshape(b, 1, -1) @ p["wo"], new_cache


def attn_cache_init(cfg: ModelConfig, batch: int, s_buf: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    return {"k": jnp.zeros((batch, s_buf, cfg.num_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, s_buf, cfg.num_kv_heads, cfg.head_dim), dtype),
            "index": jnp.zeros((), jnp.int32)}


def attn_cache_from_full(k, v, s_buf: int):
    """Build a decode cache from prefill K/V (keep the trailing window)."""
    s = k.shape[1]
    if s >= s_buf:
        # newest token ends at slot (s-1) % s_buf to stay consistent with
        # the rolling-write convention used in attn_apply_decode.
        tail_k, tail_v = k[:, s - s_buf:], v[:, s - s_buf:]
        shift = s % s_buf
        tail_k = jnp.roll(tail_k, shift, axis=1)
        tail_v = jnp.roll(tail_v, shift, axis=1)
        return {"k": tail_k, "v": tail_v, "index": jnp.asarray(s, jnp.int32)}
    pad = [(0, 0), (0, s_buf - s), (0, 0), (0, 0)]
    return {"k": jnp.pad(k, pad), "v": jnp.pad(v, pad),
            "index": jnp.asarray(s, jnp.int32)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(rng, cfg: ModelConfig):
    m = cfg.mla
    h = cfg.num_heads
    r = jax.random.split(rng, 7)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": dense_init(r[0], cfg.d_model, m.q_lora_rank, cfg.param_dtype),
        "q_norm": norm_init(cfg, m.q_lora_rank),
        "w_uq": dense_init(r[1], m.q_lora_rank, h * qk_head, cfg.param_dtype),
        "w_dkv": dense_init(r[2], cfg.d_model, m.kv_lora_rank, cfg.param_dtype),
        "kv_norm": norm_init(cfg, m.kv_lora_rank),
        "w_uk": dense_init(r[3], m.kv_lora_rank, h * m.qk_nope_head_dim, cfg.param_dtype),
        "w_uv": dense_init(r[4], m.kv_lora_rank, h * m.v_head_dim, cfg.param_dtype),
        "w_kr": dense_init(r[5], cfg.d_model, m.qk_rope_head_dim, cfg.param_dtype),
        "wo": dense_init(r[6], h * m.v_head_dim, cfg.d_model, cfg.param_dtype),
    }


def _mla_q(p, x, positions, cfg: ModelConfig):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q_lat = norm_apply(p["q_norm"], x @ p["w_dq"], cfg)
    q = (q_lat @ p["w_uq"]).reshape(b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_apply_full(p, x, positions, cfg: ModelConfig):
    """Train/prefill: expand latents to per-head K/V, blockwise attention."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope = _mla_q(p, x, positions, cfg)
    c_kv = norm_apply(p["kv_norm"], x @ p["w_dkv"], cfg)          # (B,S,r_kv)
    k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, m.qk_nope_head_dim)
    vv = (c_kv @ p["w_uv"]).reshape(b, s, h, m.v_head_dim)
    k_rope = rope((x @ p["w_kr"])[:, :, None, :], positions, cfg.rope_theta)
    k_rope = jnp.broadcast_to(k_rope, (b, s, h, m.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    out = blockwise_attention(q, k, vv, window=None, softcap=cfg.attn_softcap,
                              q_chunk=cfg.q_chunk, scale=scale)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, (c_kv, k_rope[:, :, 0, :])


def mla_apply_decode(p, x, cache, cfg: ModelConfig, *, window=None):
    """Absorbed-matmul MLA decode: scores/ctx live in the latent space, so
    per-step FLOPs are O(S * r_kv) instead of O(S * H * d) — DeepSeek-V3's
    actual serving trick, and the reason the cache is only r_kv + d_rope
    wide."""
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    idx = cache["index"]
    pos = jnp.full((b, 1), idx, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, pos, cfg)                        # (B,1,H,*)
    c_new = norm_apply(p["kv_norm"], x @ p["w_dkv"], cfg)          # (B,1,r)
    kr_new = rope((x @ p["w_kr"])[:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]
    s_buf = cache["c_kv"].shape[1]
    slot = idx % s_buf
    c_kv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new.astype(cache["c_kv"].dtype),
                                        (0, slot, 0))
    k_rope = jax.lax.dynamic_update_slice(cache["k_rope"], kr_new.astype(cache["k_rope"].dtype),
                                          (0, slot, 0))
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)             # absorb W_UK
    scores = (jnp.einsum("bqhr,bsr->bhqs", q_lat, c_kv)
              + jnp.einsum("bqhd,bsd->bhqs", q_rope, k_rope)).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    scores = _softcap(scores, cfg.attn_softcap)
    slot_ids = jnp.arange(s_buf)
    write = idx % s_buf
    age = (write - slot_ids) % s_buf
    valid = age < jnp.minimum(idx + 1, s_buf)
    if window is not None:
        valid &= age < window
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
    ctx_lat = jnp.einsum("bhqs,bsr->bqhr", w, c_kv)                # (B,1,H,r)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    ctx = jnp.einsum("bqhr,rhd->bqhd", ctx_lat, w_uv)              # absorb W_UV
    out = ctx.reshape(b, 1, -1) @ p["wo"]
    return out, {"c_kv": c_kv, "k_rope": k_rope, "index": idx + 1}


def mla_cache_init(cfg: ModelConfig, batch: int, s_buf: int, dtype=None):
    m = cfg.mla
    dtype = dtype or cfg.compute_dtype
    return {"c_kv": jnp.zeros((batch, s_buf, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, s_buf, m.qk_rope_head_dim), dtype),
            "index": jnp.zeros((), jnp.int32)}
