"""Pallas-TPU blockwise quantization kernel — the CAFL-L communication
hot spot (every round quantizes the full update tree at q>0).

Wire format: 1-D blocks of ``block`` values; per-block fp32 absmax scale;
zero-preserving mid-tread codes (see kernels/ref.py — code 0 dequantizes
to exactly 0.0, which the top-k sparse wire format in kernels/wire.py
relies on). Tiling: ROWS_PER_TILE blocks x block
values per kernel invocation — (8, 256) fp32 = 8 KiB in VMEM, lane-dim
256 is a multiple of 128 so loads/stores are register-aligned; the
reduction (absmax) runs along the minor axis on the VPU.

Scales travel through the kernels as an ``(n_blocks, 1)`` column: each
tile's absmax is already an ``(ROWS_PER_TILE, 1)`` column, and Mosaic
takes a 2-D block whose minor dim spans the array, where a rank-1
block of 8 would have to be a multiple of 128. The ``ops`` wrappers
give callers the flat ``(n_blocks,)`` scales.

Validated against ref.quantize_blocks_ref in interpret mode on CPU
(tests/test_kernels_quantize.py) and compiled for TPU v5e
(tests/test_chip_compile.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS_PER_TILE = 8


def _quantize_tile(x, bits: int):
    """(ROWS, block) f32 -> (codes f32, scale (ROWS, 1) f32): the
    mid-tread quantizer shared by the dense and top-k kernels."""
    L = 2 ** (bits - 1)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)   # (ROWS, 1)
    # reciprocal multiply, not division: bit-identical to the ref twin
    # (see ref.quantize_blocks_ref)
    scale = absmax * jnp.float32(1.0 / (L - 1))
    safe = jnp.where(scale > 0, scale, 1.0)
    # mid-tread: rint keeps exact zeros at code 0 (zero-preserving)
    codes = jnp.clip(jnp.rint(x / safe), -(L - 1), L - 1)
    return codes, scale


def _quantize_kernel(x_ref, codes_ref, scales_ref, *, bits: int):
    codes, scale = _quantize_tile(x_ref[...].astype(jnp.float32), bits)
    codes_ref[...] = codes.astype(jnp.int8)
    scales_ref[...] = scale


def _dequantize_kernel(codes_ref, scales_ref, out_ref):
    codes = codes_ref[...].astype(jnp.float32)
    # code 0 -> exactly 0.0; all-zero blocks (scale 0) stay zero for free
    out_ref[...] = codes * scales_ref[...]


def _tile_specs(block: int):
    """BlockSpecs of one (ROWS_PER_TILE, block) tile and its scale column."""
    return (pl.BlockSpec((ROWS_PER_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS_PER_TILE, 1), lambda i: (i, 0)))


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quantize_blocks(x2d, bits: int, *, interpret: bool):
    """x2d: (n_blocks, block) -> (codes int8, scales f32 (n_blocks,))."""
    n, block = x2d.shape
    assert n % ROWS_PER_TILE == 0, "pad n_blocks to ROWS_PER_TILE"
    tile, col = _tile_specs(block)
    codes, scales = pl.pallas_call(
        functools.partial(_quantize_kernel, bits=bits),
        grid=(n // ROWS_PER_TILE,),
        in_specs=[tile],
        out_specs=[tile, col],
        out_shape=[jax.ShapeDtypeStruct((n, block), jnp.int8),
                   jax.ShapeDtypeStruct((n, 1), jnp.float32)],
        interpret=interpret,
    )(x2d)
    return codes, scales.reshape(n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_blocks(codes, scales, *, interpret: bool):
    """(n_blocks, block) int8 codes x (n_blocks,) f32 scales -> f32."""
    n, block = codes.shape
    assert n % ROWS_PER_TILE == 0, "pad n_blocks to ROWS_PER_TILE"
    tile, col = _tile_specs(block)
    return pl.pallas_call(
        _dequantize_kernel,
        grid=(n // ROWS_PER_TILE,),
        in_specs=[tile, col],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n, block), jnp.float32),
        interpret=interpret,
    )(codes, scales.reshape(n, 1))
