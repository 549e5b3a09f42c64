"""Model zoo: a uniform functional API over every assigned architecture.

``build(cfg)`` returns a ``Model`` whose methods are pure functions:

    init(rng) -> params
    train_loss(params, batch) -> (loss, metrics)
    prefill(params, batch) -> (last_logits, decode_caches)
    decode_step(params, caches, tokens) -> (logits, caches)
    init_cache(batch_size, ctx_len, long=False) -> caches

Batch dict keys (ShapeDtypeStruct-compatible, see launch/specs.py):
    tokens (B, S) int32; targets (B, S) int32; loss_mask (B, S) f32 [optional]
    patch_embeds (B, P, E_f)   — vlm frontend stub
    src_embeds (B, S_src, E_f) — audio frontend stub (enc-dec)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T


# ---------------------------------------------------------------------------
# chunked cross-entropy (never materialises (B, S, V) at once)
# ---------------------------------------------------------------------------


def _ce_chunk_size(batch: int, vocab: int, seq: int) -> int:
    budget = 2 ** 33  # ~8 GiB of fp32 logits globally per chunk
    c = max(16, int(budget / max(1, batch * vocab * 4)))
    c = min(c, seq, 1024)
    while seq % c:
        c -= 1
    return max(c, 1)


def chunked_ce_loss(x, w_unembed, targets, mask, softcap=None):
    """x: (B,S,D), w_unembed: (D,V), targets: (B,S) -> scalar mean CE."""
    b, s, d = x.shape
    v = w_unembed.shape[1]
    c = _ce_chunk_size(b, v, s)
    n = s // c
    xs = x.reshape(b, n, c, d).swapaxes(0, 1)
    ts = targets.reshape(b, n, c).swapaxes(0, 1)
    ms = mask.reshape(b, n, c).swapaxes(0, 1)

    # remat: without it the scan saves every chunk's logits for the
    # backward pass, defeating the point of chunking (measured 4 GB/device
    # on the dry-run for 256k-vocab archs).
    @jax.checkpoint
    def body(carry, inp):
        tot, cnt = carry
        xc, tc, mc = inp
        logits = (xc @ w_unembed).astype(jnp.float32)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum((lse - ll) * mc)
        cnt = cnt + jnp.sum(mc)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)),
                                 (xs, ts, ms))
    return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------


def io_init(rng, cfg: ModelConfig):
    r = jax.random.split(rng, 4)
    p = {"embed": L.embed_init(r[0], cfg.vocab_size, cfg.d_model, cfg.param_dtype),
         "final_norm": L.norm_init(cfg)}
    if not cfg.tie_embeddings:
        p["head"] = L.dense_init(r[1], cfg.d_model, cfg.vocab_size, cfg.param_dtype)
    if cfg.learned_pos_emb:
        p["pos_embed"] = (jax.random.normal(r[2], (cfg.learned_pos_emb, cfg.d_model),
                                            jnp.float32) * 0.02).astype(cfg.param_dtype)
    if cfg.frontend is not None:
        p["frontend_proj"] = L.dense_init(r[3], cfg.frontend.embed_dim,
                                          cfg.d_model, cfg.param_dtype)
    return p


def embed_tokens(p, tokens, cfg: ModelConfig, positions=None):
    x = jnp.take(p["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    if cfg.learned_pos_emb:
        pos = positions if positions is not None else jnp.arange(tokens.shape[-1])
        x = x + jnp.take(p["pos_embed"], pos, axis=0).astype(cfg.compute_dtype)
    return x


def unembed_matrix(p, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return p["embed"].T
    return p["head"]


def logits_fn(p, x, cfg: ModelConfig):
    logits = (x @ unembed_matrix(p, cfg)).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


# ---------------------------------------------------------------------------
# decoder-only model (dense / moe / ssm / hybrid / vlm)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Any]
    train_loss: Callable[..., Any]
    prefill: Callable[..., Any]
    decode_step: Callable[..., Any]
    init_cache: Callable[..., Any]
    param_count: Callable[[], Dict[str, int]]


def _decoder_model(cfg: ModelConfig) -> Model:
    n_prefix_tok = cfg.frontend.num_prefix_tokens if cfg.frontend else 0

    def init(rng):
        r1, r2 = jax.random.split(rng)
        return {"io": io_init(r1, cfg), "stack": T.stack_init(r2, cfg)}

    def _embed_batch(params, batch):
        tokens = batch["tokens"]
        x = embed_tokens(params["io"], tokens, cfg)
        if cfg.frontend is not None and "patch_embeds" in batch:
            patches = (batch["patch_embeds"].astype(cfg.compute_dtype)
                       @ params["io"]["frontend_proj"])
            x = jnp.concatenate([patches, x], axis=1)
        x = L.shard_batch(x)
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        return x, positions

    def train_loss(params, batch):
        x, positions = _embed_batch(params, batch)
        x, _, aux = T.stack_apply_full(params["stack"], x, positions, cfg,
                                       want_cache=False, remat=True)
        with jax.named_scope("head_loss"):
            x = L.norm_apply(params["io"]["final_norm"], x, cfg)
            if n_prefix_tok:
                x = x[:, n_prefix_tok:]
            targets = batch["targets"]
            mask = batch.get("loss_mask",
                             jnp.ones(targets.shape, jnp.float32))
            w = unembed_matrix(params["io"], cfg).astype(cfg.compute_dtype)
            ce = chunked_ce_loss(x, w, targets, mask, cfg.final_softcap)
        return ce + aux, {"ce": ce, "aux": aux}

    def prefill(params, batch, use_decode_window: bool = False,
                max_new_tokens: int = 0):
        x, positions = _embed_batch(params, batch)
        ctx_len = x.shape[1]
        x, caches, _ = T.stack_apply_full(params["stack"], x, positions, cfg,
                                          want_cache=True, remat=False)
        x = L.norm_apply(params["io"]["final_norm"], x, cfg)
        logits = logits_fn(params["io"], x[:, -1:], cfg)
        caches = T.caches_from_prefill(cfg, caches, ctx_len, use_decode_window,
                                       max_new_tokens)
        return logits, caches

    def decode_step(params, caches, tokens):
        """tokens: (B, 1) -> logits (B, 1, V), new caches."""
        x = embed_tokens(params["io"], tokens, cfg,
                         positions=_decode_positions(caches, cfg))
        x = L.shard_batch(x)
        x, caches = T.stack_apply_decode(params["stack"], x, caches, cfg)
        x = L.norm_apply(params["io"]["final_norm"], x, cfg)
        return logits_fn(params["io"], x, cfg), caches

    def init_cache(batch_size, ctx_len, long: bool = False):
        return T.stack_cache_init(cfg, batch_size, ctx_len,
                                  use_decode_window=long)

    def param_count():
        params = jax.eval_shape(init, jax.random.PRNGKey(0))
        total = sum(math.prod(l.shape) for l in jax.tree.leaves(params))
        active = total
        if cfg.moe is not None:
            from repro.models.moe import moe_param_count
            per_layer = moe_param_count(cfg)
            n_moe = sum(1 for i in range(cfg.num_layers)
                        if T.block_spec(cfg, i).use_moe)
            active = total - n_moe * (per_layer["total"] - per_layer["active"])
        return {"total": total, "active": active}

    return Model(cfg, init, train_loss, prefill, decode_step, init_cache,
                 param_count)


def _decode_positions(caches, cfg: ModelConfig):
    """Absolute position of the new token = any attn cache's index."""
    def find(tree):
        if isinstance(tree, dict):
            if "index" in tree:
                return tree["index"]
            for v in tree.values():
                r = find(v)
                if r is not None:
                    return r
        elif isinstance(tree, (list, tuple)):
            for v in tree:
                r = find(v)
                if r is not None:
                    return r
        return None

    idx = find(caches)
    if idx is None:
        return None  # pure-recurrent model: positions unused
    if idx.ndim > 0:            # scan-stacked per-unit indices (all equal)
        idx = idx.reshape(-1)[0]
    return idx[None, None]


def build(cfg: ModelConfig) -> Model:
    if cfg.encdec:
        from repro.models.encdec import encdec_model
        return encdec_model(cfg)
    return _decoder_model(cfg)
