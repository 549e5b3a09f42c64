"""Pallas-TPU flash attention (causal / sliding-window / softcap, GQA).

TPU adaptation of the standard flash pattern: the MXU consumes
(BLK_Q x D) x (D x BLK_K) tiles from VMEM; the online-softmax running
stats (m, l) and the output accumulator live in VMEM scratch and persist
across the minor-most grid axis (the kv-block axis), which TPU iterates
sequentially per (batch, head, q-block) — so no HBM traffic for the
accumulator. Causal skipping uses @pl.when: blocks strictly above the
diagonal do no work (they still occupy grid slots; the q-chunked exact
slicing used by the pure-JAX path in models/layers.py is the compile-time
alternative).

Layout: (B, H, S, D) — the ops.py wrapper transposes from the model's
(B, S, H, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLK_Q = 128
DEFAULT_BLK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale, causal, window, softcap, blk_q, blk_k, n_k):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * blk_q
    k_start = ik * blk_k

    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # (blk_q, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (blk_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * corr[:, None]
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the diagonal (and, with a window,
        # blocks entirely below it): no MXU work, no stat updates.
        run = k_start <= q_start + blk_q - 1
        if window is not None:
            run &= k_start + blk_k - 1 > q_start - window
        pl.when(run)(_body)
    else:
        _body()

    @pl.when(ik == n_k - 1)
    def _final():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "scale", "blk_q", "blk_k",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=None, softcap=None,
                         scale=None, blk_q=DEFAULT_BLK_Q, blk_k=DEFAULT_BLK_K,
                         interpret: bool):
    """q: (B,H,Sq,D); k,v: (B,KVH,Sk,D) -> (B,H,Sq,D)."""
    b, h, sq, d = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    blk_q = min(blk_q, sq)
    blk_k = min(blk_k, sk)
    assert sq % blk_q == 0 and sk % blk_k == 0, "pad seq to block multiple"
    n_q, n_k = sq // blk_q, sk // blk_k
    grid = (b, h, n_q, n_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, blk_q=blk_q, blk_k=blk_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda ib, ih, iq, ik, g=g: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda ib, ih, iq, ik, g=g: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, d), jnp.float32),   # output accumulator
            pltpu.VMEM((blk_q,), jnp.float32),     # running max m
            pltpu.VMEM((blk_q,), jnp.float32),     # running denom l
        ],
        interpret=interpret,
    )(q, k, v)
