"""Update compression for the communication knob ``q``.

q=0: fp32 (4 B/param) — no-op.
q=1: blockwise int8 absmax quantization (1 B/param + fp32 scale / block).
q=2: blockwise 2-bit quantization (0.25 B/param + fp32 scale / block).

``topk`` adds the sparse wire format on top of either quantized level:
only the ``topk`` largest-magnitude codes per block ship, as
(packed codes, 1-bit/coordinate keep-bitmask, per-block fp32 scale) —
the knob surface the Constraint API's ``wire_mb`` constraint steers.

The FL loop calls ``compress_decompress`` (the server immediately
dequantizes, so we model the *wire* format and keep the math in fp32).
``repro.kernels.ops`` picks the backend: on TPU the quantize/top-k path
runs the compiled Pallas kernels (``repro.kernels.quantize`` /
``repro.kernels.wire``), and a kernel failure there is an error; off
the TPU it runs their pure-jnp twins, which are bit-identical.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import numpy as np


def compress_decompress(tree: Any, q: int, block: int = 256,
                        topk: Optional[int] = None) -> Any:
    if q == 0:
        return tree
    from repro.kernels import ops
    bits = 8 if q == 1 else 2
    return jax.tree.map(
        lambda l: ops.quantize_dequantize(l, bits=bits, block=block,
                                          topk=topk), tree)


#: dyadic scale-out factor: integer *bit* counts -> bytes; exact in
#: float (power of two), so the rewrite below is bit-identical to the
#: old per-block float formulas
_BYTES_PER_BIT = 0.125


def to_mb(bytes_: float) -> float:
    """The one float-division reporting edge for byte counts (exact
    integer accounting everywhere upstream; see analysis rule REPRO003)."""
    return bytes_ / 1e6


def wire_bytes(tree: Any, q: int, block: int = 256,
               topk: Optional[int] = None) -> float:
    """Exact bytes of the shipped wire tuple.

    Matches ``kernels.ops.quantize_wire`` output leaf by leaf: each
    leaf ships ``ceil(n / block)`` blocks (the tail block is padded
    within itself; no ``ROWS_PER_TILE`` pad blocks — the kernel path
    strips those before return). Dense format: ``block`` codes at
    ``bits`` each + one fp32 scale per block. Top-k format: ``topk``
    packed codes + a 1-bit/coordinate keep-bitmask + the scale.

    Counted in integer bits, scaled out once — exact accounting.
    """
    leaves = jax.tree.leaves(tree)
    n = sum(int(np.prod(l.shape)) for l in leaves)
    if q == 0:
        return n * 32 * _BYTES_PER_BIT
    bits = 8 if q == 1 else 2
    n_blocks = sum(-(-int(np.prod(l.shape)) // block) for l in leaves)
    if topk is not None and topk < block:
        code_bits = n_blocks * (topk * bits + block)
    else:
        code_bits = n_blocks * block * bits
    return (code_bits + 32 * n_blocks) * _BYTES_PER_BIT


def wire_mb(tree: Any, q: int, block: int = 256,
            topk: Optional[int] = None) -> float:
    return to_mb(wire_bytes(tree, q, block, topk))


def compression_error(tree: Any, q: int, block: int = 256,
                      topk: Optional[int] = None) -> Dict[str, float]:
    """Relative L2 error introduced by the wire format (diagnostics)."""
    if q == 0:
        return {"rel_l2": 0.0}
    deq = compress_decompress(tree, q, block, topk)
    num = 0.0
    den = 0.0
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(deq)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        num += float(np.sum((a - b) ** 2))
        den += float(np.sum(a ** 2))
    return {"rel_l2": float(np.sqrt(num / max(den, 1e-30)))}
