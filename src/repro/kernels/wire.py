"""Pallas-TPU wire-path kernels — the CAFL-L communication hot path
fused end to end: quantize -> per-block top-k sparsify -> fixed-point
masked sum -> dequantize.

Three kernels (each with a pure-jnp twin in ``kernels/ref.py`` and
backend dispatch in ``kernels/ops.py``):

``quantize_topk_blocks``
    Fused blockwise mid-tread quantization + exactly-k magnitude
    sparsification emitting the sparse wire tuple ``(codes int8,
    scales f32, mask int8)``. The scale is the dense absmax (top-k
    keeps the largest entry), dropped coordinates get code 0, and the
    zero-preserving mid-tread dequantizer maps code 0 to exactly 0.0 —
    so the dense dequantize epilogue serves the sparse format too.
    Selection bisects each block for its k-th largest magnitude and
    the last tie that fits (``_topk_keep``): compares and lane
    reductions only, no sort, no scatter, and exactly the set the
    pairwise-rank oracle ``ref.topk_mask_ref`` keeps, so the paths
    agree bit-for-bit.

``masked_sum_limbs``
    The secure-aggregation cohort fold: sums C clients' uint64
    fixed-point masked vectors mod 2^64 in one bandwidth-bound pass.
    TPU has no 64-bit integers, so values arrive as (hi, lo) uint32
    limb pairs and the kernel does radix-2^16 column reduction —
    split each limb into two 16-bit digits, column-sum (exact in
    uint32 for C <= 2^16, summed in int32 since Mosaic has no unsigned
    reductions), ripple carries. Modular sums are
    associative, so the result is bit-exact vs the sequential NumPy
    oracle in ``MaskedSumAggregator``.

``dequantize_blocks`` (re-exported from ``kernels/quantize``)
    The dequantize epilogue: ``codes * scale`` per block. Shared by
    the dense and sparse formats because code 0 -> 0.0 exactly.

Validated against the twins in interpret mode on CPU
(tests/test_wire_kernels.py) and compiled for TPU v5e
(tests/test_chip_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize import ROWS_PER_TILE, _quantize_tile, _tile_specs
from repro.kernels.quantize import dequantize_blocks  # noqa: F401  (epilogue)

#: Column tile of the masked-sum kernel: 512 uint32 lanes = 2 KiB per
#: limb row in VMEM, a multiple of the 128-lane register width.
LIMB_TILE = 512


# ---------------------------------------------------------------------------
# (a) fused quantize + per-block top-k sparsify
# ---------------------------------------------------------------------------


def _row_count(pred):
    """(ROWS, block) bool -> (ROWS, 1) int32 count of True per row."""
    return jnp.sum(pred.astype(jnp.int32), axis=1, keepdims=True)


def _topk_keep(absx, k: int):
    """(ROWS, block) non-negative f32 -> bool mask keeping exactly ``k``
    per row, largest first, ties toward the lower index.

    Selects the same set as ``ref.topk_mask_ref`` (rank < k under the
    order (magnitude desc, index asc)) without its rank-3 pairwise
    compare, which Mosaic cannot lay out: a bisection over the bits of
    the magnitude finds the k-th largest value ``t`` per row, then a
    bisection over the index finds the last tied entry that still fits.
    Non-negative floats order like their int32 bit patterns, so every
    step is an exact integer compare plus a lane reduction —
    (31 + log2(block)) passes over the tile instead of ``block``.
    """
    rows, block = absx.shape
    # the oracle's float compares see denormals as zero (the TPU and
    # XLA:CPU flush them), so they key as zero here too
    tiny = jnp.float32(jnp.finfo(jnp.float32).tiny)
    key = jnp.where(absx < tiny, 0,
                    jax.lax.bitcast_convert_type(absx, jnp.int32))
    # t = the largest value with count(key >= t) >= k: the k-th largest
    t = jnp.zeros((rows, 1), jnp.int32)
    for b in range(30, -1, -1):
        cand = t | (1 << b)
        t = jnp.where(_row_count(key >= cand) >= k, cand, t)
    above = key > t
    tied = key == t
    need = k - _row_count(above)                          # >= 1 ties kept
    # last = index of the need-th tied entry: the largest value with
    # fewer than ``need`` tied entries strictly before it
    idx = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    last = jnp.zeros((rows, 1), jnp.int32)
    for b in range((block - 1).bit_length() - 1, -1, -1):
        cand = last | (1 << b)
        last = jnp.where(_row_count(tied & (idx < cand)) < need, cand, last)
    return above | (tied & (idx <= last))


def _quantize_topk_kernel(x_ref, codes_ref, scales_ref, mask_ref, *,
                          bits: int, k: int):
    x = x_ref[...].astype(jnp.float32)                    # (ROWS, block)
    # the scale is the dense absmax: top-k keeps the largest entry
    codes, scale = _quantize_tile(x, bits)
    keep = _topk_keep(jnp.abs(x), k)
    codes_ref[...] = jnp.where(keep, codes, 0.0).astype(jnp.int8)
    scales_ref[...] = scale
    mask_ref[...] = keep.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("bits", "k", "interpret"))
def quantize_topk_blocks(x2d, bits: int, k: int, *, interpret: bool):
    """x2d: (n_blocks, block) -> (codes int8, scales f32 (n_blocks,),
    mask int8)."""
    n, block = x2d.shape
    assert n % ROWS_PER_TILE == 0, "pad n_blocks to ROWS_PER_TILE"
    tile, col = _tile_specs(block)
    codes, scales, mask = pl.pallas_call(
        functools.partial(_quantize_topk_kernel, bits=bits, k=k),
        grid=(n // ROWS_PER_TILE,),
        in_specs=[tile],
        out_specs=[tile, col, tile],
        out_shape=[jax.ShapeDtypeStruct((n, block), jnp.int8),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n, block), jnp.int8)],
        interpret=interpret,
    )(x2d)
    return codes, scales.reshape(n), mask


# ---------------------------------------------------------------------------
# (b) fixed-point masked sum over a stacked cohort
# ---------------------------------------------------------------------------


def _digit_sum(digits):
    """(C, TILE) uint32 16-bit digits -> (1, TILE) column sum mod 2^32.

    Mosaic has no unsigned reductions; two's-complement int32 addition
    wraps to the same bits, so the sum runs in int32 and is bitcast
    back (exact for C <= 2^16 clients either way).
    """
    s = jnp.sum(jax.lax.bitcast_convert_type(digits, jnp.int32), axis=0,
                keepdims=True)
    return jax.lax.bitcast_convert_type(s, jnp.uint32)


def _masked_sum_kernel(hi_ref, lo_ref, hi_out, lo_out):
    hi = hi_ref[...]                                      # (C, TILE) uint32
    lo = lo_ref[...]
    mask16 = jnp.uint32(0xFFFF)
    # radix-2^16 column reduction: 16-bit digit sums are exact in
    # uint32 for C <= 2^16 clients, then ripple the carries
    s0 = _digit_sum(lo & mask16)
    s1 = _digit_sum(lo >> 16)
    s2 = _digit_sum(hi & mask16)
    s3 = _digit_sum(hi >> 16)
    d0 = s0 & mask16
    t1 = s1 + (s0 >> 16)
    d1 = t1 & mask16
    t2 = s2 + (t1 >> 16)
    d2 = t2 & mask16
    t3 = s3 + (t2 >> 16)          # carry past bit 64 drops: mod 2^64
    d3 = t3 & mask16
    hi_out[...] = d2 | (d3 << 16)
    lo_out[...] = d0 | (d1 << 16)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_sum_limbs(hi, lo, *, interpret: bool):
    """(C, n) uint32 limb pairs -> ((n,), (n,)) cohort sum mod 2^64."""
    c, n = hi.shape
    assert hi.shape == lo.shape
    assert n % LIMB_TILE == 0, "pad columns to LIMB_TILE"
    # outputs are (1, n) rows: a rank-1 tile's layout disagrees with
    # XLA's, a one-row block spanning the array's leading dim does not
    row = pl.BlockSpec((1, LIMB_TILE), lambda i: (0, i))
    hi_s, lo_s = pl.pallas_call(
        _masked_sum_kernel,
        grid=(n // LIMB_TILE,),
        in_specs=[pl.BlockSpec((c, LIMB_TILE), lambda i: (0, i)),
                  pl.BlockSpec((c, LIMB_TILE), lambda i: (0, i))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.uint32),
                   jax.ShapeDtypeStruct((1, n), jnp.uint32)],
        interpret=interpret,
    )(hi, lo)
    return hi_s.reshape(n), lo_s.reshape(n)
