"""Update compression for the communication knob ``q``.

q=0: fp32 (4 B/param) — no-op.
q=1: blockwise int8 absmax quantization (1 B/param + fp32 scale / block).
q=2: blockwise 2-bit quantization (0.25 B/param + fp32 scale / block).

``topk`` adds the sparse wire format on top of either quantized level:
only the ``topk`` largest-magnitude codes per block ship, as
(packed codes, 1-bit/coordinate keep-bitmask, per-block fp32 scale) —
the knob surface the Constraint API's ``wire_mb`` constraint steers.

The sequential client path calls ``compress_decompress`` per update
tree, one round trip per leaf (the server immediately dequantizes, so
we model the *wire* format and keep the math in fp32). The batched
executor ships a knob group's stacked ``(C, ...)`` deltas at once:
``compress_decompress_stacked`` packs every client's row of every leaf
into one block array and makes one round trip over it, and
``unpack_stacked`` (``unstack_masked`` at q=0) hands back the C
per-client trees under the freeze mask, each one program. Blocks never
straddle a leaf or a client, so both paths ship the same bits.
``repro.kernels.ops`` picks the backend: on TPU the quantize/top-k path
runs the compiled Pallas kernels (``repro.kernels.quantize`` /
``repro.kernels.wire``), and a kernel failure there is an error; off
the TPU it runs their pure-jnp twins, which are bit-identical.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import freezing
from repro.kernels.quantize import ROWS_PER_TILE


def _bits(q: int) -> int:
    return 8 if q == 1 else 2


def compress_decompress(tree: Any, q: int, block: int = 256,
                        topk: Optional[int] = None) -> Any:
    if q == 0:
        return tree
    from repro.kernels import ops
    bits = _bits(q)
    return jax.tree.map(
        lambda l: ops.quantize_dequantize(l, bits=bits, block=block,
                                          topk=topk), tree)


@functools.partial(jax.jit, static_argnames=("block",))
def pack_stacked(stacked: Any, block: int = 256):
    """Stacked ``(C, ...)`` leaves -> one ``(n_blocks, block)`` f32 array.

    Each client's row of each leaf is flattened and zero-padded to
    whole blocks within itself, as ``compress_decompress`` pads a leaf;
    the leaves follow one another (clients in order within a leaf), and
    the whole is zero-padded to whole ``ROWS_PER_TILE`` tiles.
    """
    parts = []
    for leaf in jax.tree.leaves(stacked):
        c, n = leaf.shape[0], math.prod(leaf.shape[1:])
        rows = leaf.reshape(c, n).astype(jnp.float32)
        pad = (-n) % block
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad)))
        parts.append(rows.reshape(-1, block))
    blocks = jnp.concatenate(parts)
    pad = (-blocks.shape[0]) % ROWS_PER_TILE
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0)))
    return blocks


def _split_masked(stacked: Any, mask: Any) -> List[Any]:
    """Stacked ``(C, ...)`` tree -> C per-client trees, freeze-masked."""
    c = jax.tree.leaves(stacked)[0].shape[0]
    return [freezing.apply_mask(jax.tree.map(lambda l, i=i: l[i], stacked),
                                mask) for i in range(c)]


@jax.jit
def unstack_masked(stacked: Any, mask: Any) -> List[Any]:
    """The q=0 wire: stacked ``(C, ...)`` deltas -> the C per-client
    trees under the freeze mask, as one program."""
    return _split_masked(stacked, mask)


@functools.partial(jax.jit, static_argnames=("treedef", "shapes", "block"))
def _unpack(blocks, mask, treedef, shapes, block: int):
    leaves, off = [], 0
    for shape in shapes:
        c, n = shape[0], math.prod(shape[1:])
        nb = -(-n // block)
        rows = blocks[off:off + c * nb].reshape(c, nb * block)
        leaves.append(rows[:, :n].reshape(shape))
        off += c * nb
    return _split_masked(jax.tree.unflatten(treedef, leaves), mask)


def unpack_stacked(blocks, mask: Any, like: Any,
                   block: int = 256) -> List[Any]:
    """Inverse of ``pack_stacked``: the round-tripped blocks of the
    stacked tree ``like`` (shapes are all it reads) -> the C per-client
    f32 trees under the freeze mask, as one program."""
    leaves, treedef = jax.tree.flatten(like)
    return _unpack(blocks, mask, treedef=treedef,
                   shapes=tuple(tuple(l.shape) for l in leaves), block=block)


def compress_decompress_stacked(stacked: Any, q: int, block: int = 256,
                                topk: Optional[int] = None):
    """Wire round trip of a knob group's stacked f32 deltas, q > 0:
    ``pack_stacked``, then one ``ops.quantize_dequantize_blocks`` call.
    -> the dequantized blocks, for ``unpack_stacked``.

    Consumes ``stacked``. The device allocates a program's outputs as
    the program is queued and frees a buffer once the programs that
    read it have run, so queued back to back the stages would hold the
    stacked deltas, the packed blocks, the codes and the dequantized
    blocks at once. Waiting for the pack and for the round trip keeps
    at most the packed blocks, the codes and one other cohort copy.
    """
    from repro.kernels import ops
    packed = pack_stacked(stacked, block=block)
    for leaf in jax.tree.leaves(stacked):
        leaf.delete()
    packed.block_until_ready()
    deq = ops.quantize_dequantize_blocks(packed, bits=_bits(q), topk=topk)
    del packed
    return deq.block_until_ready()


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
    bits = _bits(q)
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
