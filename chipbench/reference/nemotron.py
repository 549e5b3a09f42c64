"""Plain reference of the Nemotron decoder (Minitron-8B,
``configs/minitron-8b-tp8.json``).

Straightforward ``jax.numpy`` like ``reference/decoder.py``, whose
precision kinds (``precision``), freezing masks (``trainable_mask``),
matrix product and rotary it shares; it imports nothing of the system
under test, and its parameter tree has the system's nested keys.

Equations (per layer ``l``, pre-norm residual; arXiv:2407.14679 and the
published ``nvidia/Minitron-8B-Base`` config, ``model_type: nemotron``)::

    h  = LN(x);  q, k, v = h Wq, h Wk, h Wv   (GQA: H query heads share
                                              KV heads, H / KV per group)
    q, k = rope(q), rope(k)                   on the first
                                              ``rope_fraction * hd`` dims
                                              of each head, half-split
                                              within them; the rest pass
    x += softmax(q k^T / sqrt(hd) + causal) v Wo
    h  = LN(x);  x += relu(h W_up)^2 W_down   (no biases)
    logits = LN_f(x) W_head                   (untied head)
    loss   = mean over tokens of logsumexp(logits) - logits[target]

LayerNorm has a bias and eps ``norm_eps`` (1e-5), in float32 whatever
the compute type. Departures from the published model:

* LayerNorm1p scales by ``1 + gamma`` with gamma starting at 0; here the
  scale itself is the parameter, starting at 1 (``harness/weights.py``).
  The function is the same; only weight decay differs (it pulls the
  scale to 0, not to 1), in the system and in ``reference/fl.py`` alike.
* One chip's share of an 8-way tensor-parallel layer: the configuration
  holds 6 of 48 query heads, 1 of 8 KV heads, 2,048 of 16,384 MLP
  columns and 32,000 of 256,000 vocabulary rows. Each share's partial
  attention and MLP output goes on with no all-reduce, and the loss is
  over the slice, as in the program.
* So that a 4,096-token sequence fits the chip beside the optimizer
  state, attention is computed per block of ``Q_BLOCK`` queries against
  their whole causal prefix (each block's softmax is exact), and each
  layer's activations are recomputed for the backward pass
  (``jax.checkpoint``) instead of kept.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from reference import decoder

precision = decoder.precision
trainable_mask = decoder.trainable_mask

#: queries per attention block (a block's scores: H x Q_BLOCK x prefix)
Q_BLOCK = 1024


def _layer_norm(x, p, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def _partial_rope(x, theta: float, fraction: float):
    """x: (B, S, H, D): rotary over the first ``fraction * D`` dims."""
    rot = int(x.shape[-1] * fraction)
    return jnp.concatenate([decoder._rope(x[..., :rot], theta),
                            x[..., rot:]], axis=-1)


def _attention(p, h, cfg: Dict, prec: decoder.Precision):
    b, s, _ = h.shape
    nh, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    mm = decoder._einsum
    q = mm("bsd,de->bse", h, p["wq"], prec).reshape(b, s, nh, hd)
    k = mm("bsd,de->bse", h, p["wk"], prec).reshape(b, s, kvh, hd)
    v = mm("bsd,de->bse", h, p["wv"], prec).reshape(b, s, kvh, hd)
    frac = cfg.get("rope_fraction", 1.0)
    q = _partial_rope(q, cfg["rope_theta"], frac)
    k = _partial_rope(k, cfg["rope_theta"], frac)
    qg = q.reshape(b, s, kvh, nh // kvh, hd)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        scores = mm("bqkgd,blkd->bkgql", qg[:, q0:q1], k[:, :q1],
                    prec).astype(jnp.float32) / math.sqrt(hd)
        causal = np.tril(np.ones((q1 - q0, q1), bool), k=q0)
        scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
        outs.append(mm("bkgql,blkd->bqkgd", w, v[:, :q1], prec))
    out = jnp.concatenate(outs, axis=1).reshape(b, s, nh * hd)
    return mm("bse,ed->bsd", out, p["wo"], prec)


def _mlp(p, h, cfg: Dict, prec: decoder.Precision):
    if cfg["mlp_type"] != "relu2" or cfg.get("mlp_bias", True):
        raise ValueError("the Nemotron reference has the bias-free "
                         "squared-ReLU MLP only")
    u = decoder._einsum("bsd,df->bsf", h, p["w_up"], prec)
    a = jnp.square(jnp.maximum(u, 0))
    return decoder._einsum("bsf,fd->bsd", a, p["w_down"], prec)


def loss(params, tokens, targets, cfg: Dict, prec: decoder.Precision):
    """Mean next-token cross entropy of one batch: tokens, targets (B, S)."""
    if cfg["tie_embeddings"] or cfg.get("learned_pos_emb"):
        raise ValueError("Nemotron has an untied head and no position table")
    eps = cfg["norm_eps"]
    p = jax.tree.map(lambda a: a.astype(prec.compute), params)
    io = p["io"]
    x = io["embed"][tokens]
    units = p["stack"]["units"]["b0"]

    @jax.checkpoint
    def layer_fn(x, lp):
        x = x + _attention(lp["attn"], _layer_norm(x, lp["ln1"], eps), cfg,
                           prec)
        return x + _mlp(lp["ffn"], _layer_norm(x, lp["ln2"], eps), cfg, prec)

    for layer in range(cfg["num_layers"]):
        x = layer_fn(x, jax.tree.map(lambda a, i=layer: a[i], units))
    x = _layer_norm(x, io["final_norm"], eps)
    logits = decoder._einsum("bsd,dv->bsv", x, io["head"],
                             prec).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
