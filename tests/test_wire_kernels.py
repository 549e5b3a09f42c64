"""The Pallas wire-path pipeline (repro.kernels.wire + ops): fused
quantize/top-k semantics and ref<->pallas bit-compatibility, the
fixed-point masked-sum kernel vs the NumPy uint64 oracle, and the
wire-accounting pin — ``compression.wire_bytes`` must price exactly
the tuple ``ops.quantize_wire`` ships."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression
from repro.kernels import ops, ref
from repro.kernels import wire as wk
from repro.kernels.quantize import ROWS_PER_TILE

BLOCK = 256


@pytest.fixture(params=["ref", "pallas"])
def backend(request, monkeypatch):
    monkeypatch.setattr(ops, "FORCE_BACKEND", request.param)
    return request.param


# ---------------------------------------------------------------------------
# fused quantize + top-k
# ---------------------------------------------------------------------------


def test_topk_mask_semantics(rng):
    """Exactly k survivors per block, and they are the k largest
    magnitudes with ties broken toward the lower index — i.e. the mask
    matches a stable argsort oracle."""
    absx = np.abs(rng.normal(size=(16, BLOCK)).astype(np.float32))
    absx[3, :10] = absx[3, 10]        # ties inside a block
    absx[7] = 0.0                     # fully degenerate block
    for k in (1, 32, BLOCK - 1, BLOCK):
        keep = np.asarray(ref.topk_mask_ref(jnp.asarray(absx), k))
        assert keep.sum(axis=1).tolist() == [k] * 16
        # stable argsort on (-magnitude, index): the canonical oracle
        order = np.argsort(-absx, axis=1, kind="stable")
        for r in range(16):
            want = np.zeros(BLOCK, bool)
            want[order[r, :k]] = True
            np.testing.assert_array_equal(keep[r], want, err_msg=f"row {r}")


def test_quantize_topk_kernel_matches_ref(rng):
    x = jnp.asarray(rng.normal(size=(ROWS_PER_TILE * 2, BLOCK))
                    .astype(np.float32))
    for bits, k in ((8, 32), (2, 8), (8, 1)):
        ck, sk, mk = wk.quantize_topk_blocks(x, bits, k, interpret=True)
        cr, sr, mr = ref.quantize_topk_blocks_ref(x, bits, k)
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
        np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
        np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)


@pytest.mark.parametrize("k", [1, 7, 64, BLOCK - 1])
def test_quantize_topk_kernel_ties_match_ref(k, rng):
    """The kernel selects by bisection, the oracle by pairwise rank:
    they keep the same set on tie-heavy blocks — few distinct
    magnitudes, +/- pairs of one magnitude, all-zero and constant
    blocks, and magnitudes spanning denormals to near-overflow."""
    x = rng.integers(-3, 4, size=(ROWS_PER_TILE * 2, BLOCK)) \
        .astype(np.float32)
    x[1] = 0.0
    x[2] = 5.0
    x[3, 1::2] = -x[3, ::2]
    x[4] = rng.choice(np.float32([1e-45, 1e-38, 1.0, 3e38]), size=BLOCK)
    x = jnp.asarray(x)
    ck, sk, mk = wk.quantize_topk_blocks(x, 2, k, interpret=True)
    cr, sr, mr = ref.quantize_topk_blocks_ref(x, 2, k)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    assert np.asarray(mk).sum(axis=1).tolist() == [k] * x.shape[0]


def test_sparse_roundtrip_properties(backend, rng):
    """Dropped coordinates come back exactly 0.0, survivors obey the
    dense mid-tread bound (the scale is the dense absmax), and k=block
    degrades to the dense format."""
    x = np.asarray(rng.normal(size=(1000,)).astype(np.float32))
    y = np.asarray(ops.quantize_dequantize(jnp.asarray(x), bits=8, topk=32))
    blocks = np.pad(x, (0, 24)).reshape(-1, BLOCK)
    absmax = np.abs(blocks).max(axis=1)
    kept = 0
    for b in range(blocks.shape[0]):
        yb = np.pad(y, (0, 24)).reshape(-1, BLOCK)[b]
        nz = yb != 0.0
        kept += int(nz.sum())
        assert np.all(np.abs(yb[nz] - blocks[b][nz])
                      <= absmax[b] / 254 * (1 + 1e-3) + 1e-6)
    assert kept <= 32 * blocks.shape[0]
    dense = np.asarray(ops.quantize_dequantize(jnp.asarray(x), bits=8))
    full = np.asarray(ops.quantize_dequantize(jnp.asarray(x), bits=8,
                                              topk=BLOCK))
    np.testing.assert_array_equal(full, dense)


# ---------------------------------------------------------------------------
# fixed-point masked sum
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("clients", [1, 2, 5, 33])
def test_masked_sum_matches_uint64_oracle(clients, backend, rng):
    """The limb fold is exact mod 2^64 for any cohort size, on both
    dispatch backends, against NumPy's native wrapping uint64 sum."""
    vals = rng.integers(0, 2 ** 64, size=(clients, 1000), dtype=np.uint64)
    want = np.add.reduce(vals, axis=0)
    hi, lo = ops.split_limbs(vals)
    hi_s, lo_s = ops.masked_sum(hi, lo)
    got = ops.merge_limbs(np.asarray(hi_s), np.asarray(lo_s))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ops.masked_sum_u64(vals), want)


def test_masked_sum_u64_default_cpu_path(rng):
    """The un-forced host-level fold (NumPy one-pass on CPU) agrees
    with the forced limb backends bit-for-bit."""
    vals = rng.integers(0, 2 ** 64, size=(7, 513), dtype=np.uint64)
    old = ops.FORCE_BACKEND
    try:
        ops.FORCE_BACKEND = None
        a = ops.masked_sum_u64(vals)
        ops.FORCE_BACKEND = "pallas"
        b = ops.masked_sum_u64(vals)
        ops.FORCE_BACKEND = "ref"
        c = ops.masked_sum_u64(vals)
    finally:
        ops.FORCE_BACKEND = old
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_masked_sum_rejects_oversized_cohort():
    vals = np.zeros((2, 4), np.uint64)
    hi, lo = ops.split_limbs(vals)
    ops.masked_sum(hi, lo)            # fine at 2 clients
    with pytest.raises(ValueError, match="clients"):
        ops.masked_sum(np.zeros((ops.MASKED_SUM_MAX_CLIENTS + 1, 1),
                                np.uint32),
                       np.zeros((ops.MASKED_SUM_MAX_CLIENTS + 1, 1),
                                np.uint32))


def test_split_merge_limbs_roundtrip(rng):
    vals = rng.integers(0, 2 ** 64, size=(3, 97), dtype=np.uint64)
    hi, lo = ops.split_limbs(vals)
    assert hi.dtype == lo.dtype == np.uint32
    np.testing.assert_array_equal(ops.merge_limbs(hi, lo), vals)


# ---------------------------------------------------------------------------
# wire accounting: wire_bytes prices exactly what quantize_wire ships
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 255, 256, 1000, BLOCK * ROWS_PER_TILE,
                               BLOCK * ROWS_PER_TILE + 17])
@pytest.mark.parametrize("topk", [None, 32])
def test_wire_bytes_matches_quantize_wire_tuple(n, topk, backend, rng):
    """Regression: the accounting used ceil(n/block) scale blocks while
    the Pallas path shipped ROWS_PER_TILE-padded tuples. Both backends
    must now emit exactly ceil(n/block) blocks, and wire_bytes must
    equal the modeled size of that tuple (packed codes + 1-bit mask for
    top-k + fp32 scales)."""
    x = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    codes, scales, mask, n_valid = ops.quantize_wire(x, bits=8, topk=topk)
    n_blocks = -(-n // BLOCK)
    assert n_valid == n
    assert codes.shape == (n_blocks, BLOCK)
    assert scales.shape == (n_blocks,)
    if topk is None:
        assert mask is None
        modeled = codes.size * 1 + scales.size * 4       # int8 + fp32
    else:
        assert mask.shape == (n_blocks, BLOCK)
        # shipped: topk packed int8 codes + 1-bit mask + fp32 scale
        modeled = n_blocks * (topk * 1 + BLOCK / 8) + scales.size * 4
    assert compression.wire_bytes(x, q=1, topk=topk) == modeled


def test_wire_bytes_2bit_packing(rng):
    """q=2 models 2-bit code packing: a quarter of the int8 payload."""
    x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    n_blocks = -(-1000 // BLOCK)
    assert compression.wire_bytes(x, q=2) == \
        n_blocks * BLOCK * 2 / 8 + n_blocks * 4
    assert compression.wire_bytes(x, q=2, topk=32) == \
        n_blocks * (32 * 2 / 8 + BLOCK / 8) + n_blocks * 4
    # q=0 ships raw fp32, no scales
    assert compression.wire_bytes(x, q=0) == 4000


def test_quantize_wire_empty_and_scalar(backend):
    codes, scales, mask, n = ops.quantize_wire(jnp.zeros((0,)), bits=8)
    assert n == 0 and codes.shape == (0, BLOCK) and scales.shape == (0,)
    codes, scales, mask, n = ops.quantize_wire(jnp.asarray(1.5), bits=8)
    assert n == 1 and codes.shape == (1, BLOCK)
    assert int(np.asarray(codes)[0, 0]) == 127