"""The batched executor's wire over a knob group's stacked deltas.

``executor._compress`` packs every client's row of every leaf into one
block array, makes one wire round trip over it and unpacks the C
per-client trees under the freeze mask (``compression.pack_stacked``,
``compress_decompress_stacked``, ``unpack_stacked``; ``unstack_masked``
at q=0). Blocks never straddle a leaf or a client, so what it ships is
what the per-client, per-leaf path (``compress_decompress`` then
``freezing.apply_mask``) ships, bit for bit, on the ref twins and on
the Pallas kernels in interpret mode, with one launch per kernel per
group."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compression, freezing
from repro.core.client import _masked_wire_mb
from repro.fl import executor, spans
from repro.kernels import ops
from repro.kernels.quantize import ROWS_PER_TILE

#: leaf shapes without the client axis: sizes off the multiples of 256
#: and of 2048 (a kernel tile), one a whole tile, a leaf below one
#: block and a scalar
SHAPES = {"embed": (3, 100), "flat": (2048 + 5,), "small": (7,),
          "scale": (), "units": (4, 16, 32), "pair": (2, 300)}


def _mask():
    """Partly frozen, with per-unit singleton dims where a unit stack
    is frozen unit by unit."""
    return {"embed": jnp.float32(1.0), "flat": jnp.float32(1.0),
            "small": jnp.float32(0.0), "scale": jnp.float32(1.0),
            "units": jnp.asarray(np.array([0, 1, 1, 0], np.float32)
                                 .reshape(4, 1, 1)),
            "pair": jnp.asarray(np.array([1, 0], np.float32).reshape(2, 1))}


def _stacked(c, seed):
    rng = np.random.default_rng(seed)
    tree = {k: rng.standard_normal((c,) + s).astype(np.float32)
            for k, s in SHAPES.items()}
    tree["flat"][:, :256] = 0.0          # an all-zero block: scale 0
    return tree


@pytest.fixture
def launches(monkeypatch):
    """Counts what ``spans.count`` is given, profiler or not."""
    seen = {}

    def count(name, n=1):
        seen[name] = seen.get(name, 0) + n

    monkeypatch.setattr(spans, "count", count)
    return seen


@pytest.mark.parametrize("c", [1, 3])
@pytest.mark.parametrize("topk", [None, 64])
@pytest.mark.parametrize("q", [0, 1, 2])
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_stacked_wire_ships_the_per_leaf_bits(backend, q, topk, c,
                                              monkeypatch, launches):
    monkeypatch.setattr(ops, "FORCE_BACKEND", backend)
    host = _stacked(c, seed=17 + 10 * q + c)
    mask = _mask()
    want = [freezing.apply_mask(compression.compress_decompress(
        {k: jnp.asarray(v[i]) for k, v in host.items()}, q, topk=topk), mask)
        for i in range(c)]
    launches.clear()
    got = executor._compress({k: jnp.asarray(v) for k, v in host.items()},
                             mask, q, topk=topk)
    assert len(got) == c
    for g, w in zip(got, want):
        assert jax.tree.structure(g) == jax.tree.structure(w)
        for key in SHAPES:
            assert g[key].shape == w[key].shape
            assert g[key].dtype == w[key].dtype
            np.testing.assert_array_equal(np.asarray(g[key]),
                                          np.asarray(w[key]), err_msg=key)
        assert _masked_wire_mb(g, mask, q, topk=topk) == _masked_wire_mb(
            w, mask, q, topk=topk)
    per_group = 0 if q == 0 else (2 if backend == "pallas" else 1)
    assert launches.get("wire_calls", 0) == per_group


def test_pack_keeps_blocks_within_leaf_and_client():
    host = _stacked(2, seed=3)
    blocks = np.asarray(compression.pack_stacked(
        {k: jnp.asarray(v) for k, v in host.items()}))
    rows = []
    for key in sorted(host):                 # a dict's leaf order
        for row in host[key].reshape(2, -1):
            pad = (-row.size) % 256
            rows.append(np.pad(row, (0, pad)).reshape(-1, 256))
    want = np.concatenate(rows)
    assert blocks.shape[0] % ROWS_PER_TILE == 0
    assert blocks.shape[0] - want.shape[0] < ROWS_PER_TILE
    np.testing.assert_array_equal(blocks[:want.shape[0]], want)
    assert not blocks[want.shape[0]:].any()


@pytest.mark.parametrize("q", [0, 2])
def test_stacked_wire_consumes_its_input_only_at_q(q):
    """At q > 0 the stacked deltas are released once packed, so the
    cohort is not held three times over; at q=0 nothing is packed."""
    raw = {k: jnp.asarray(v) for k, v in _stacked(2, seed=5).items()}
    executor._compress(raw, _mask(), q)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(raw)) == (q > 0)
