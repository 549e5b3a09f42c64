"""jit'd public wrappers around the Pallas kernels, with backend dispatch.

On TPU the compiled kernels run, and a kernel the chip's compiler
refuses is an error: nothing falls back to the reference there. Off
the TPU the hot paths use the pure-jnp twins in ``kernels/ref.py``
(which XLA fuses well on CPU). ``FORCE_BACKEND`` pins either path;
``"pallas"`` off the TPU runs the kernel bodies in interpret mode, which
is how the CPU tests pin kernel-vs-ref bit equality. This module is the
one place that chooses ``interpret``: every kernel takes it as a
required keyword. While the profiler records, each wire-kernel launch
(a quantize, a dequantize, or a ref twin's whole round trip, whether
over one tensor or over a knob group's packed blocks) adds one to the
round's ``wire_calls`` counter (``repro.spans``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.kernels import ref
from repro.kernels import quantize as qk
from repro.kernels import wire as wk
from repro.kernels import flash_attention as fak

FORCE_BACKEND: Optional[str] = None   # None | "pallas" | "ref"


def _use_pallas() -> bool:
    if FORCE_BACKEND == "pallas":
        return True
    if FORCE_BACKEND == "ref":
        return False
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Interpret the kernel bodies everywhere but on the TPU."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bits", "block", "topk"))
def _qdq_ref(x, bits: int, block: int, topk):
    return ref.quantize_dequantize_ref(x, bits, block, topk=topk)


def _pallas_quantize(blocks, bits: int, topk: Optional[int]):
    """(n_blocks, block) f32, whole ``ROWS_PER_TILE`` tiles -> Pallas
    wire tuple ``(codes, scales, mask | None)``: one kernel launch."""
    spans.count("wire_calls")
    if topk is not None and topk < blocks.shape[1]:
        return wk.quantize_topk_blocks(blocks, bits, topk,
                                       interpret=_interpret())
    codes, scales = qk.quantize_blocks(blocks, bits, interpret=_interpret())
    return codes, scales, None


def _pallas_wire(flat, bits: int, block: int, topk: Optional[int]):
    """Flat f32 -> Pallas wire tuple ``(codes, scales, mask | None)``
    over the input zero-padded to whole ``block * ROWS_PER_TILE`` tiles."""
    pad = (-flat.shape[0]) % (block * qk.ROWS_PER_TILE)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return _pallas_quantize(flat.reshape(-1, block), bits, topk)


def quantize_dequantize(x, *, bits: int, block: int = 256,
                        topk: Optional[int] = None):
    """Wire round-trip (quantize then dequantize), any shape.

    ``topk`` keeps only the k largest-magnitude codes per block (the
    sparse wire format); dropped coordinates round-trip to exactly 0.0.
    """
    if not _use_pallas():
        spans.count("wire_calls")
        return _qdq_ref(x, bits, block, topk)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1).astype(jnp.float32)
    codes, scales, _ = _pallas_wire(flat, bits, block, topk)
    spans.count("wire_calls")
    deq = qk.dequantize_blocks(codes, scales, interpret=_interpret())
    return deq.reshape(-1)[:flat.shape[0]].reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("bits", "topk"))
def _qdq_blocks_ref(blocks, bits: int, topk):
    return ref.quantize_dequantize_blocks_ref(blocks, bits, topk=topk)


def quantize_dequantize_blocks(blocks, *, bits: int,
                               topk: Optional[int] = None):
    """Wire round-trip of blocks already laid out for the kernels:
    (n_blocks, block) f32 with ``n_blocks`` a whole number of
    ``ROWS_PER_TILE`` tiles -> the dequantized (n_blocks, block) f32.

    Every block is quantized on its own, so one call over many tensors'
    blocks gives each block the bits a call per tensor gives it. Two
    launches on the Pallas path (quantize, or the top-k quantize, then
    dequantize, each its own jitted program), one ref-twin program
    elsewhere.
    """
    if not _use_pallas():
        spans.count("wire_calls")
        return _qdq_blocks_ref(blocks, bits, topk)
    codes, scales, _ = _pallas_quantize(blocks, bits, topk)
    spans.count("wire_calls")
    return qk.dequantize_blocks(codes, scales, interpret=_interpret())


_dequantize_blocks_ref_jit = jax.jit(ref.dequantize_blocks_ref)


def dequantize_blocks(codes, scales):
    """Decode wire blocks: (n_blocks, block) int8 codes x per-block f32
    scales -> (n_blocks, block) f32 (code 0 -> exactly 0.0).

    The server-side half of the wire round-trip, dispatched like every
    other kernel: the Pallas ``quantize.dequantize_blocks`` kernel on
    TPU, the pure-jnp ``dequantize_blocks_ref`` twin elsewhere.
    """
    spans.count("wire_calls")
    if not _use_pallas():
        return _dequantize_blocks_ref_jit(codes, scales)
    n = codes.shape[0]
    pad = (-n) % qk.ROWS_PER_TILE
    if pad:
        codes = jnp.pad(codes, ((0, pad), (0, 0)))
        scales = jnp.pad(scales, (0, pad))
    return qk.dequantize_blocks(codes, scales, interpret=_interpret())[:n]


@functools.partial(jax.jit, static_argnames=("bits", "topk"))
def _quantize_wire_ref(blocks, bits: int, topk):
    if topk is not None:
        return ref.quantize_topk_blocks_ref(blocks, bits, topk)
    codes, scales = ref.quantize_blocks_ref(blocks, bits)
    return codes, scales, None


def quantize_wire(x, *, bits: int, block: int = 256,
                  topk: Optional[int] = None):
    """Quantize a tensor into the wire tuple actually shipped.

    -> ``(codes int8 (n_blocks, block), scales f32 (n_blocks,),
    mask int8 (n_blocks, block) | None, n_valid)`` with exactly
    ``n_blocks = ceil(n / block)`` on every backend: the Pallas path
    pads to ``block * ROWS_PER_TILE`` tiles internally but the pad
    blocks are stripped before return, so ``core.compression.wire_bytes``
    and the tuple's nbytes agree. ``mask`` is None for the dense format.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    n_blocks = -(-n // block) if n else 0
    if n == 0:
        return (jnp.zeros((0, block), jnp.int8), jnp.zeros((0,), jnp.float32),
                None if topk is None or topk >= block else
                jnp.zeros((0, block), jnp.int8), 0)
    if topk is not None and topk >= block:
        topk = None
    if _use_pallas():
        codes, scales, mask = _pallas_wire(flat, bits, block, topk)
        return (codes[:n_blocks], scales[:n_blocks],
                None if mask is None else mask[:n_blocks], n)
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    spans.count("wire_calls")
    codes, scales, mask = _quantize_wire_ref(blocks, bits, topk)
    return codes, scales, mask, n


# ---------------------------------------------------------------------------
# fixed-point masked sum (secure-aggregation cohort fold)
# ---------------------------------------------------------------------------

MASKED_SUM_MAX_CLIENTS = ref.MASKED_SUM_MAX_CLIENTS


def split_limbs(u64: np.ndarray):
    """NumPy uint64 (C, n) -> ((C, n) hi, (C, n) lo) uint32 limb pairs."""
    u64 = np.ascontiguousarray(u64, dtype=np.uint64)
    return ((u64 >> np.uint64(32)).astype(np.uint32),
            (u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def merge_limbs(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """(hi, lo) uint32 -> NumPy uint64, elementwise."""
    return ((np.asarray(hi, dtype=np.uint64) << np.uint64(32))
            | np.asarray(lo, dtype=np.uint64))


_masked_sum_ref_jit = jax.jit(ref.masked_sum_ref)


def masked_sum(hi, lo):
    """Sum C clients' uint64 vectors mod 2^64, carried as uint32 limbs.

    hi/lo: (C, n) uint32 -> ((n,) hi, (n,) lo) uint32. Bit-exact on
    every backend (modular sums are associative); the Pallas kernel
    does it in one bandwidth-bound pass over the stacked cohort.
    """
    hi = jnp.asarray(hi, dtype=jnp.uint32)
    lo = jnp.asarray(lo, dtype=jnp.uint32)
    c, n = hi.shape
    if c > MASKED_SUM_MAX_CLIENTS:
        raise ValueError(
            f"masked_sum supports at most {MASKED_SUM_MAX_CLIENTS} clients "
            f"per fold, got {c}")
    if not _use_pallas():
        return _masked_sum_ref_jit(hi, lo)
    pad = (-n) % wk.LIMB_TILE
    if pad:
        hi = jnp.pad(hi, ((0, 0), (0, pad)))
        lo = jnp.pad(lo, ((0, 0), (0, pad)))
    hi_s, lo_s = wk.masked_sum_limbs(hi, lo, interpret=_interpret())
    return hi_s[:n], lo_s[:n]


def masked_sum_u64(vals: np.ndarray) -> np.ndarray:
    """Host-level cohort fold: (C, n) uint64 -> (n,) sum mod 2^64.

    The ``MaskedSumAggregator`` flush path. One fused pass over the
    stacked cohort on every backend: the Pallas limb kernel on TPU,
    a single NumPy ``add.reduce`` (uint64 wraps mod 2^64 natively) on
    CPU where 32-bit limb emulation can't win. ``FORCE_BACKEND``
    pins the limb paths for bit-compat validation.
    """
    vals = np.ascontiguousarray(vals, dtype=np.uint64)
    c = vals.shape[0]
    if c > MASKED_SUM_MAX_CLIENTS:
        raise ValueError(
            f"masked_sum supports at most {MASKED_SUM_MAX_CLIENTS} clients "
            f"per fold, got {c}")
    if FORCE_BACKEND is None and jax.default_backend() != "tpu":
        return np.add.reduce(vals, axis=0)
    hi, lo = split_limbs(vals)
    hi_s, lo_s = masked_sum(hi, lo)
    return merge_limbs(np.asarray(hi_s), np.asarray(lo_s))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None):
    """Model layout (B, S, H, D); dispatches Pallas (TPU) vs reference."""
    if not _use_pallas():
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       softcap=softcap, scale=scale)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = fak.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                   softcap=softcap, scale=scale,
                                   interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# trace-analysis entry points (repro.analysis.trace)
# ---------------------------------------------------------------------------


def _wire_build(bits: int, topk: Optional[int]):
    def build():
        x = jax.ShapeDtypeStruct((1 << 16,), jnp.float32)

        def fn(t):
            return quantize_wire(t, bits=bits, topk=topk)

        return fn, (x,)
    return build


def _masked_sum_build():
    hi = jax.ShapeDtypeStruct((8, 4096), jnp.uint32)
    lo = jax.ShapeDtypeStruct((8, 4096), jnp.uint32)
    return masked_sum, (hi, lo)


def trace_entry_points() -> list:
    """Declared traceable surfaces: the wire pipeline at both formats
    plus the secure-aggregation cohort fold (all pure uint32/f32 —
    TRACE001 proves no 64-bit promotion sneaks onto the wire path)."""
    from repro.analysis.trace.registry import EntryPoint
    path = "src/repro/kernels/ops.py"
    return [
        EntryPoint(name="kernels.wire_dense", path=path, line=149,
                   build=_wire_build(8, None),
                   note="dense int8 wire tuple, 64k params"),
        EntryPoint(name="kernels.wire_topk", path=path, line=149,
                   build=_wire_build(2, 64),
                   note="2-bit top-64 sparse wire tuple, 64k params"),
        EntryPoint(name="kernels.masked_sum", path=path, line=205,
                   build=_masked_sum_build,
                   note="uint64-as-limbs cohort fold, C=8, n=4096"),
    ]
