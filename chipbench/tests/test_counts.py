"""FLOP and wire-byte counters against hand counts."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import counts
from reference import decoder

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["model"]


def _shapes(name):
    from repro.configs.base import ModelConfig
    from repro.models import build
    model = build(ModelConfig(**_config(name)))
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def _charlm_params_by_hand():
    d, f, layers, vocab, positions = 256, 1024, 6, 128, 512
    layer = 4 * d * d + 2 * d * f + f + d + 4 * d   # q k v o, MLP, 2 LN
    return layers * layer + vocab * d + positions * d + 2 * d


def test_param_count():
    shapes = _shapes("charlm-shakespeare")
    assert counts.param_count(shapes) == _charlm_params_by_hand() == 4_896_768
    with open(os.path.join(BENCH, "configs", "charlm-shakespeare.json")) as f:
        assert json.load(f)["assumed"]["param_count"] == 4_896_768


def _charlm_hand(seq, k):
    """charlm by hand: d=256, 8 heads of 32, d_ff=1024, 6 layers, V=128."""
    per_token_layer = 2 * (256 * 256 + 2 * 256 * 256 + 256 * 256
                           + 2 * 256 * 1024)          # 1,572,864
    attn = 2 * 8 * 32 * seq * (seq + 1)               # causal q k^T, p v
    layer = seq * per_token_layer + attn
    head = seq * 2 * 256 * 128
    forward = 6 * layer + head
    backward = 2 * (k * layer + head)
    return forward + backward


@pytest.mark.parametrize("k", [6, 2])
def test_charlm_flops_by_hand(k):
    m = _config("charlm-shakespeare")
    assert counts.seq_flops(m, 32, k) == _charlm_hand(32, k)
    # s * ga * b sequences per client, summed over the cohort
    kn = (k, 40, 32, 0, 1)
    assert counts.round_flops(m, 32, [kn] * 6) == 6 * 40 * 32 * _charlm_hand(32, k)
    assert counts.round_tokens(32, [kn] * 6) == 6 * 40 * 32 * 32


def test_backward_only_above_the_freeze():
    m = _config("charlm-shakespeare")
    full, two = counts.seq_flops(m, 32, 6), counts.seq_flops(m, 32, 2)
    layer = 32 * 1_572_864 + 2 * 8 * 32 * 32 * 33
    assert full - two == 2 * 4 * layer


def test_wire_bytes_do_not_depend_on_the_split():
    shapes = _shapes("charlm-shakespeare")
    cfg = _config("charlm-shakespeare")
    mask = decoder.trainable_mask(shapes, cfg, 5)
    per_leaf = sum(counts.wire_bytes(counts.trainable_elements(
        {"x": l}, {"x": m}), 2) for l, m in zip(jax.tree.leaves(shapes),
                                                  jax.tree.leaves(mask)))
    n = counts.trainable_elements(shapes, mask)
    flat = counts.wire_bytes(n, 2)
    assert per_leaf == pytest.approx(flat, rel=1e-12)
    assert flat == pytest.approx(n * (8 + 2 / 4 + 1 / 32), rel=1e-12)
    # trainable at k=5: five of six layers and the final norm; the
    # token and position tables are frozen
    layers = 4_896_768 - 128 * 256 - 512 * 256 - 2 * 256
    assert n == layers * 5 // 6 + 2 * 256


@pytest.mark.parametrize("bits, q", [(8, 1), (2, 2)])
def test_wire_bytes_same_for_ref_twin_and_kernel_path(bits, q):
    """The count reads shapes only: the shipped tuples of the kernel path
    (interpret mode here) and of the ref twin have the same shapes, and
    their codes and scales are never fewer bytes than counted."""
    from repro.kernels import ops
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 700)),
                    jnp.float32)
    shipped = {}
    for backend in ("ref", "pallas"):
        ops.FORCE_BACKEND = backend
        try:
            codes, scales, _m, n = ops.quantize_wire(x, bits=bits)
        finally:
            ops.FORCE_BACKEND = None
        shipped[backend] = (codes.shape, scales.shape, n)
    assert shipped["ref"] == shipped["pallas"]
    codes_shape, scales_shape, n = shipped["ref"]
    need_out = counts.wire_bytes(n, q) / 2 - 4 * n   # codes + scales
    assert np.prod(codes_shape) + 4 * np.prod(scales_shape) >= need_out
