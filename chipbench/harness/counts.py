"""Operations and bytes the algorithm needs, counted from shapes and
knobs, never from the compiled program, so that a change to how the
work is split into calls or fused cannot move them.

Model FLOPs of one client's LocalTrain (``s`` steps of ``ga``
microbatches of ``b`` sequences of ``S`` tokens), for a decoder of
``L`` layers::

    per-token matmuls of a layer  2 (d H hd + 2 d KV hd + H hd d + m d F)
                                  m = 3 for a gated MLP, else 2
    causal attention of a layer   2 H hd S (S + 1) per sequence
                                  (q k^T and p v over the causal half)
    output head                   2 d V per token
    forward                       every layer and the head
    backward                      twice the forward of the head and of
                                  the top ``k`` layers (gradients of
                                  activations and of weights); nothing
                                  below the lowest trainable layer

Embedding lookups, norms, biases and the optimizer are not counted, nor
is recomputation.

Wire bytes of one client at ``q > 0``: every trainable element is read
as float32 (4 B), written as a code of ``bits`` bits, with one float32
scale per 256 elements, read back as code and scale, and written out as
float32: ``n (8 + bits/4 + 1/32)`` bytes. Frozen elements need no wire.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

BLOCK = 256
BITS = {1: 8, 2: 2}


def layer_matmul_flops_per_token(m: Dict) -> int:
    d, h, kv, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    mlp = 3 if m["mlp_type"] in ("swiglu", "geglu") else 2
    return 2 * (d * h * hd + 2 * d * kv * hd + h * hd * d + mlp * d * f)


def attention_flops_per_seq(m: Dict, seq: int) -> int:
    return 2 * m["num_heads"] * m["head_dim"] * seq * (seq + 1)


def head_flops_per_token(m: Dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def seq_flops(m: Dict, seq: int, k: int) -> int:
    """Forward and required backward of one sequence at freezing depth k."""
    n = m["num_layers"]
    k = max(1, min(k, n))
    layer = seq * layer_matmul_flops_per_token(m) + attention_flops_per_seq(
        m, seq)
    head = seq * head_flops_per_token(m)
    return (n * layer + head) + 2 * (k * layer + head)


def client_flops(m: Dict, seq: int, knobs: Tuple) -> int:
    k, s, b, _q, ga = knobs
    return s * ga * b * seq_flops(m, seq, k)


def round_flops(m: Dict, seq: int, cohort: Iterable[Tuple]) -> int:
    return sum(client_flops(m, seq, kn) for kn in cohort)


def round_tokens(seq: int, cohort: Iterable[Tuple]) -> int:
    return sum(s * ga * b * seq for (_k, s, b, _q, ga) in cohort)


def trainable_elements(shapes, mask) -> float:
    """Elements the mask trains: a per-layer mask counts its share."""
    import jax
    total = 0.0
    for leaf, m in zip(jax.tree.leaves(shapes), jax.tree.leaves(mask)):
        m_arr = np.asarray(m, np.float64)
        total += float(m_arr.mean()) * int(np.prod(leaf.shape))
    return total


def wire_bytes(elements: float, q: int) -> float:
    if q == 0:
        return 0.0
    bits = BITS[q]
    return elements * (8 + bits / 4 + 4 * 2 / BLOCK)


def param_count(shapes) -> int:
    import jax
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
