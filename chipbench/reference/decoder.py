"""Plain reference of the decoder-only language model the configurations
in ``chipbench/configs`` describe.

Straightforward ``jax.numpy``: an unrolled loop over layers, full
``S x S`` causal attention with a mask, no kernels, no chunking, no
rematerialisation. It imports nothing of the system under test. Its
parameter tree uses the same nested keys as the system's, so that the
check can compare leaf by leaf, and the weights come from the harness's
seeded generator (``harness/weights.py``), never from the system.

Equations (per layer ``l``, pre-norm residual)::

    h  = LN(x);  q, k, v = h Wq, h Wk, h Wv        (GQA: kv heads shared)
    q, k = rope(q), rope(k)                         (half-split rotary)
    x += softmax(q k^T / sqrt(hd) + causal) v Wo
    h  = LN(x);  x += act(h W_up + b_up) W_down + b_down
    logits = LN_f(x) W_out,   W_out = E^T (tied) or the head
    loss   = mean over tokens of logsumexp(logits) - logits[target]

``act`` is GELU (tanh form, as GPT-2). A learned position table, where
the configuration has one, is added to the token embedding; rotary
positions apply to every configuration, as the system's attention
applies them. LayerNorm uses eps 1e-6 and, like softmax, runs in float32
whatever the compute type.

``Precision`` selects how it computes. The reference proper stores the
parameters in the type the configuration states and computes everything
in float32, matrix products at ``HIGHEST``. The control that the check
must reject is one precision step lower. Where the configuration states
float32, that is bfloat16: parameters stored and updated in bfloat16
(Adam moments stay float32), activations in bfloat16, matrix products at
``DEFAULT``. On a TPU the system's float32 already multiplies at
``DEFAULT``, i.e. with operands rounded to bfloat16, so bfloat16
activations over float32 master weights (``bf16_compute``) are not a step
below it; ``calibrate.py`` reads that variant, and float32 at ``DEFAULT``
(``default``), beside the control.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6


class Precision(NamedTuple):
    param: Any           # storage type of the parameters
    compute: Any         # type of activations (and of the pass's weights)
    matmul: str          # "highest" | "default"


#: the precision one step below each configured one (the control)
LOWER = {"float32": Precision(jnp.bfloat16, jnp.bfloat16, "default")}
#: other precisions the limits were read against (``calibrate.py``)
READ = {"bf16_compute": Precision(jnp.float32, jnp.bfloat16, "default"),
        "default": Precision(jnp.float32, jnp.float32, "default")}


def precision(cfg: Dict, kind: str = "reference") -> Precision:
    """``reference``: stored as configured, computed in float32;
    ``control``: one step below the configured precision."""
    if kind == "control":
        return LOWER[cfg["param_dtype"]]
    if kind in READ:
        return READ[kind]
    return Precision(jnp.dtype(cfg["param_dtype"]), jnp.float32, "highest")


_PRECISION = {"highest": jax.lax.Precision.HIGHEST,
              "default": jax.lax.Precision.DEFAULT}


def _einsum(spec, a, b, prec: Precision):
    return jnp.einsum(spec, a, b, precision=_PRECISION[prec.matmul])


def _layer_norm(x, p):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + LN_EPS)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def _rope(x, theta: float):
    """x: (B, S, H, D), rotary over the two halves of D."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(s, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _gelu_tanh(u):
    return 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (u + 0.044715 * u ** 3)))


def _attention(p, h, cfg, prec: Precision):
    b, s, _ = h.shape
    nh, kvh, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = _einsum("bsd,de->bse", h, p["wq"], prec).reshape(b, s, nh, hd)
    k = _einsum("bsd,de->bse", h, p["wk"], prec).reshape(b, s, kvh, hd)
    v = _einsum("bsd,de->bse", h, p["wv"], prec).reshape(b, s, kvh, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    g = nh // kvh
    qg = q.reshape(b, s, kvh, g, hd)
    scores = _einsum("bqkgd,blkd->bkgql", qg, k, prec).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(h.dtype)
    out = _einsum("bkgql,blkd->bqkgd", w, v, prec).reshape(b, s, nh * hd)
    return _einsum("bse,ed->bsd", out, p["wo"], prec)


def _mlp(p, h, cfg, prec: Precision):
    if cfg["mlp_type"] != "gelu":
        raise ValueError(f"reference has no MLP {cfg['mlp_type']!r}")
    u = _einsum("bsd,df->bsf", h, p["w_up"], prec) + p["b_up"]
    a = _gelu_tanh(u)
    return _einsum("bsf,fd->bsd", a, p["w_down"], prec) + p["b_down"]


def loss(params, tokens, targets, cfg: Dict, prec: Precision):
    """Mean next-token cross entropy of one batch: tokens, targets (B, S)."""
    c = prec.compute
    p = jax.tree.map(lambda a: a.astype(c), params)
    io = p["io"]
    x = io["embed"][tokens]
    if cfg.get("learned_pos_emb"):
        x = x + io["pos_embed"][: tokens.shape[1]]
    units = p["stack"]["units"]["b0"]
    for layer in range(cfg["num_layers"]):
        lp = jax.tree.map(lambda a, i=layer: a[i], units)
        x = x + _attention(lp["attn"], _layer_norm(x, lp["ln1"]), cfg, prec)
        x = x + _mlp(lp["ffn"], _layer_norm(x, lp["ln2"]), cfg, prec)
    x = _layer_norm(x, io["final_norm"])
    w_out = io["embed"].T if cfg["tie_embeddings"] else io["head"]
    logits = _einsum("bsd,dv->bsv", x, w_out, prec).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def trainable_mask(shapes, cfg: Dict, k: int):
    """Freezing depth ``k``: the top ``k`` layers, the final norm and
    the output head train; the embeddings (and a learned position
    table) train only when every layer does. Layer masks are per layer
    along the stacked axis, shaped to broadcast; the rest are scalars.
    Float32 NumPy, like the masks the system builds."""
    n = cfg["num_layers"]
    k = max(1, min(k, n))
    layer_on = (np.arange(n) >= n - k).astype(np.float32)

    def unit(leaf):
        return layer_on.reshape((n,) + (1,) * (len(leaf.shape) - 1))

    io = {}
    for key, sub in shapes["io"].items():
        on = 1.0 if key not in ("embed", "pos_embed") or k >= n else 0.0
        io[key] = jax.tree.map(lambda _l, v=on: np.float32(v), sub)
    return {"io": io,
            "stack": {"units": jax.tree.map(unit, shapes["stack"]["units"])}}
