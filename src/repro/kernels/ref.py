"""Pure-jnp oracles for every Pallas kernel (the correctness reference)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# blockwise symmetric mid-tread quantization (the CAFL-L wire format)
# ---------------------------------------------------------------------------


def quantize_blocks_ref(x2d, bits: int):
    """x2d: (n_blocks, block) fp -> (codes int8, scales fp32).

    Mid-tread uniform quantizer: scale = absmax / (L-1) with
    L = 2^(bits-1); code = clip(rint(x / scale), -(L-1), L-1);
    dequant = code * scale. Zero-preserving: an exact-zero input maps
    to code 0 and dequantizes to exactly 0.0 — a mid-rise code would
    bias it to +0.5*scale, which destroys wire sparsity (every
    coordinate a top-k sparsifier zeroes out would come back nonzero).
    """
    L = 2 ** (bits - 1)
    absmax = jnp.max(jnp.abs(x2d.astype(jnp.float32)), axis=1, keepdims=True)
    # explicit fp32 reciprocal multiply: XLA may or may not fold a
    # constant division into one depending on context, and the 1-ulp
    # scale difference flips codes at half-integer boundaries — this
    # keeps ref and Pallas bit-identical
    scale = absmax * jnp.float32(1.0 / (L - 1))
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.rint(x2d.astype(jnp.float32) / safe), -(L - 1),
                     L - 1)
    return codes.astype(jnp.int8), scale[:, 0]


def dequantize_blocks_ref(codes, scales):
    # code 0 -> exactly 0.0; all-zero blocks (scale 0) stay zero for free
    return codes.astype(jnp.float32) * scales[:, None]


def topk_mask_ref(absx, k: int):
    """absx: (n_blocks, block) -> bool mask keeping exactly ``k`` per
    row, largest magnitudes first, ties broken toward the lower index.

    Branch- and sort-free: rank_i = #{j : a_j > a_i} + #{j < i : a_j ==
    a_i}; keep rank < k. O(block^2) plain comparisons, so this is the
    oracle; the Pallas kernel selects the same set by bisection
    (``kernels.wire._topk_keep``), so the two paths agree bit-for-bit.
    """
    rows, block = absx.shape
    if k >= block:
        return jnp.ones((rows, block), bool)
    a_i = absx[:, :, None]                      # (rows, i, 1)
    a_j = absx[:, None, :]                      # (rows, 1, j)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    ahead = (a_j > a_i) | ((a_j == a_i) & (j_idx < i_idx)[None])
    rank = jnp.sum(ahead.astype(jnp.int32), axis=2)
    return rank < k


def quantize_topk_blocks_ref(x2d, bits: int, k: int):
    """Fused quantize + per-block top-k sparsify:
    (n_blocks, block) fp -> (codes int8, scales f32, mask int8).

    The scale is the *dense* absmax (top-k keeps the largest-magnitude
    entry, so sparsifying never changes it); dropped coordinates get
    code 0, which the mid-tread dequantizer maps to exactly 0.0 — the
    sparse wire tuple needs no separate dequantize path.
    """
    x = x2d.astype(jnp.float32)
    codes, scales = quantize_blocks_ref(x, bits)
    keep = topk_mask_ref(jnp.abs(x), k)
    codes = jnp.where(keep, codes, jnp.int8(0))
    return codes.astype(jnp.int8), scales, keep.astype(jnp.int8)


def quantize_dequantize_blocks_ref(blocks, bits: int, topk=None):
    """(n_blocks, block) f32 -> wire round-trip, (n_blocks, block) f32."""
    if topk is not None and topk < blocks.shape[1]:
        codes, scales, _ = quantize_topk_blocks_ref(blocks, bits, topk)
    else:
        codes, scales = quantize_blocks_ref(blocks, bits)
    return dequantize_blocks_ref(codes, scales)


def quantize_dequantize_ref(x, bits: int, block: int = 256, topk=None):
    """Arbitrary-shape tensor -> wire round-trip, same shape/dtype."""
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    deq = quantize_dequantize_blocks_ref(flat.reshape(-1, block), bits, topk)
    return deq.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# fixed-point masked sum (secure-aggregation cohort fold)
# ---------------------------------------------------------------------------

#: Column sums of 16-bit digits stay exact in uint32 up to this many
#: clients per fold (sum <= C * 0xffff < 2^32).
MASKED_SUM_MAX_CLIENTS = 1 << 16


def masked_sum_ref(hi, lo):
    """(C, n) uint32 limb pairs -> ((n,), (n,)) summed mod 2^64.

    TPU (and jnp without x64) has no uint64, so the uint64 modular-mask
    algebra ``MaskedSumAggregator`` runs is carried as (hi, lo) uint32
    limb pairs, and the cohort fold uses radix-2^16 column reduction:
    split each limb into two 16-bit digits, column-sum every digit
    (exact in uint32 for C <= 2^16 clients), then ripple the carries.
    One bandwidth-bound pass over the stacked cohort instead of C
    sequential accumulations.
    """
    assert hi.shape == lo.shape and hi.shape[0] <= MASKED_SUM_MAX_CLIENTS
    hi = hi.astype(jnp.uint32)
    lo = lo.astype(jnp.uint32)
    mask16 = jnp.uint32(0xFFFF)
    s0 = jnp.sum(lo & mask16, axis=0, dtype=jnp.uint32)
    s1 = jnp.sum(lo >> 16, axis=0, dtype=jnp.uint32)
    s2 = jnp.sum(hi & mask16, axis=0, dtype=jnp.uint32)
    s3 = jnp.sum(hi >> 16, axis=0, dtype=jnp.uint32)
    d0 = s0 & mask16
    t1 = s1 + (s0 >> 16)
    d1 = t1 & mask16
    t2 = s2 + (t1 >> 16)
    d2 = t2 & mask16
    t3 = s3 + (t2 >> 16)          # carry past bit 64 drops: mod 2^64
    d3 = t3 & mask16
    return d2 | (d3 << 16), d0 | (d1 << 16)


# ---------------------------------------------------------------------------
# flash attention (causal, optional window + softcap), fp32 math
# ---------------------------------------------------------------------------


def flash_attention_ref(q, k, v, *, causal: bool = True, window=None,
                        softcap=None, scale=None):
    """q: (B,Sq,H,D), k/v: (B,Sk,KVH,D) -> (B,Sq,H,D). Naive O(S^2) oracle."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, kvh, g, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bqkgd,blkd->bkgql", qg, kf) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgql,blkd->bqkgd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)
