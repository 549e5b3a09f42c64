"""Pallas quantization kernel vs pure-jnp oracle: shape/dtype sweeps in
interpret mode (assignment requirement), both dispatch backends
(``FORCE_BACKEND in {"ref", "pallas"}``) over every shape class the FL
trees produce (scalars, odd tails, non-tile-multiples), mid-tread
quantization-error bounds, the ``qdq(0) == 0`` zero-preservation
regression, and hypothesis property tests (skipped when hypothesis is
not installed — the backend/shape sweeps still run)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # pragma: no cover - property tests skip
    given = None

from repro.kernels import ops, ref
from repro.kernels.quantize import ROWS_PER_TILE, dequantize_blocks, quantize_blocks

#: shapes covering every class the FL trees produce: scalars, short
#: vectors, odd tails (n % block != 0), and padded tails that are a
#: block multiple but not a block*ROWS_PER_TILE tile multiple
SHAPES = [(), (1,), (37,), (3, 129), (5, 7, 11), (2048, 3),
          (256 * ROWS_PER_TILE + 17,), (3 * 256,)]


@pytest.fixture(params=["ref", "pallas"])
def backend(request, monkeypatch):
    monkeypatch.setattr(ops, "FORCE_BACKEND", request.param)
    return request.param


@pytest.mark.parametrize("bits", [8, 2])
@pytest.mark.parametrize("n_blocks,block", [(8, 256), (16, 128), (32, 512)])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_kernel_matches_ref_blocks(bits, n_blocks, block, dtype, rng):
    x = jnp.asarray(rng.normal(size=(n_blocks, block)).astype(np.float32),
                    dtype=dtype).astype(jnp.float32)
    codes_k, scales_k = quantize_blocks(x, bits, interpret=True)
    codes_r, scales_r = ref.quantize_blocks_ref(x, bits)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))
    np.testing.assert_allclose(np.asarray(scales_k), np.asarray(scales_r),
                               rtol=1e-6)
    deq_k = dequantize_blocks(codes_k, scales_k, interpret=True)
    deq_r = ref.dequantize_blocks_ref(codes_r, scales_r)
    np.testing.assert_allclose(np.asarray(deq_k), np.asarray(deq_r), rtol=1e-6)


@pytest.mark.parametrize("bits", [8, 2])
def test_quantization_error_bound(bits, backend, rng):
    """Mid-tread quantizer error is at most scale/2 = absmax/(2(L-1))."""
    x = jnp.asarray(rng.normal(size=(2048,)).astype(np.float32))
    y = ops.quantize_dequantize(x, bits=bits, block=256)
    err = np.abs(np.asarray(y - x))
    blocks = np.asarray(x).reshape(-1, 256)
    absmax = np.abs(blocks).max(axis=1, keepdims=True)
    bound = np.repeat(absmax / (2 ** (bits - 1) - 1) / 2, 256,
                      axis=1).reshape(-1)
    assert np.all(err <= bound + 1e-6)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_arbitrary_shapes_roundtrip(shape, backend, rng):
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    y = ops.quantize_dequantize(x, bits=8)
    assert y.shape == x.shape and y.dtype == x.dtype
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(y - x))) <= amax / 254 * (1 + 1e-3) + 1e-6


@pytest.mark.parametrize("bits", [8, 2])
def test_qdq_zero_is_exactly_zero(bits, backend):
    """Regression: the mid-rise code had no zero level, so exact-zero
    inputs came back as +0.5*scale. Mid-tread must return exact zeros —
    both for all-zero blocks and for zeros embedded among nonzeros
    (what a top-k sparsifier or freezing mask produces)."""
    z = ops.quantize_dequantize(jnp.zeros((1024,), jnp.float32), bits=bits)
    np.testing.assert_array_equal(np.asarray(z), 0.0)
    x = np.linspace(-1.0, 1.0, 512, dtype=np.float32)
    x[::3] = 0.0                      # exact zeros inside nonzero blocks
    y = np.asarray(ops.quantize_dequantize(jnp.asarray(x), bits=bits))
    np.testing.assert_array_equal(y[::3], 0.0)


@pytest.mark.parametrize("topk", [None, 32])
def test_pallas_and_ref_backends_agree(topk, rng):
    x = jnp.asarray(rng.normal(size=(4096 + 37,)).astype(np.float32))
    old = ops.FORCE_BACKEND
    try:
        ops.FORCE_BACKEND = "pallas"
        a = ops.quantize_dequantize(x, bits=8, topk=topk)
        ops.FORCE_BACKEND = "ref"
        b = ops.quantize_dequantize(x, bits=8, topk=topk)
    finally:
        ops.FORCE_BACKEND = old
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


if given is not None:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 4), st.integers(1, 513), st.sampled_from([2, 8]),
           st.floats(0.01, 100.0))
    def test_property_error_bound_and_shape(rows, cols, bits, scale):
        """Property: round-trip preserves shape, error bounded by half
        the mid-tread step per block, idempotent on already-quantized
        data."""
        rng = np.random.default_rng(rows * 1000 + cols)
        x = jnp.asarray((rng.normal(size=(rows, cols)) * scale)
                        .astype(np.float32))
        y = ops.quantize_dequantize(x, bits=bits, block=256)
        assert y.shape == x.shape
        assert np.all(np.isfinite(np.asarray(y)))
        amax = float(jnp.max(jnp.abs(x)))
        bound = amax / (2 ** (bits - 1) - 1) / 2
        # relative slack: scale and code*scale round in fp32
        assert float(jnp.max(jnp.abs(y - x))) <= bound * (1 + 1e-3) + 1e-5
        # idempotence: quantizing the dequantized signal is (nearly) stable
        z = ops.quantize_dequantize(y, bits=bits, block=256)
        assert float(jnp.max(jnp.abs(z - y))) <= 2 * bound * (1 + 1e-3) + 1e-5


@pytest.mark.parametrize("n_blocks", [1, ROWS_PER_TILE + 3])
def test_ops_dequantize_blocks_any_block_count(n_blocks, backend, rng):
    """The dispatch pads to whole kernel tiles and strips the pad: the
    wire tuple's ceil(n / block) blocks decode on either backend."""
    x = jnp.asarray(rng.normal(size=(n_blocks * 256,)).astype(np.float32))
    codes, scales, _, _ = ops.quantize_wire(x, bits=8)
    got = np.asarray(ops.dequantize_blocks(codes, scales))
    assert got.shape == (n_blocks, 256)
    np.testing.assert_array_equal(
        got, np.asarray(ref.dequantize_blocks_ref(codes, scales)))
