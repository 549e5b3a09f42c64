"""The main path compiles for one TPU v5e chip.

Ahead-of-time compiles against a described ``v5e:2x2`` topology (no
chip attached): the chip's own compiler refuses what interpret mode
accepts — misaligned blocks, unsigned reductions, layouts Mosaic cannot
build — so the wire kernels are compiled at the char-LM's largest leaf
and at a whole cohort's packed blocks (the batched executor ships a
knob group in one launch per kernel), and the batched executor's round
program at the paper's configuration from ``jax.eval_shape`` shapes.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, and
every test worker imports this file. Where no topology can be described
the fixture skips.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import quantize as qk
from repro.kernels import wire as wk

BLOCK = 256
TOPK = 64
COHORT = 6


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def paper():
    """The paper's char-LM (configs/charlm_shakespeare.py), shapes only."""
    from repro.configs import get_config, get_fl_config
    from repro.models import build
    model = build(get_config("charlm-shakespeare"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, get_fl_config(), params


@pytest.fixture(scope="module")
def leaf_blocks(paper):
    """Wire blocks of the largest leaf, padded to whole kernel tiles."""
    _, _, params = paper
    n = max(math.prod(l.shape) for l in jax.tree.leaves(params))
    tile = BLOCK * qk.ROWS_PER_TILE
    return -(-n // tile) * tile // BLOCK


@pytest.fixture(scope="module")
def cohort(one_chip):
    """The stacked deltas of a 6-client cohort of the paper's char-LM at
    its stated widths (d=256, MLP 4 x 256: 4,896,768 parameters), on
    the chip, and the wire blocks they pack into."""
    from repro.configs import get_config
    from repro.core import compression
    from repro.models import build
    cfg = get_config("charlm-shakespeare").replace(
        d_model=256, head_dim=32, d_ff=1024)
    params = jax.eval_shape(build(cfg).init, jax.random.PRNGKey(0))
    assert sum(math.prod(l.shape)
               for l in jax.tree.leaves(params)) == 4_896_768
    stacked = jax.tree.map(
        lambda l: _sds((COHORT,) + l.shape, jnp.float32, one_chip), params)
    blocks = jax.eval_shape(compression.pack_stacked, stacked)
    return stacked, blocks.shape[0]


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bits", [8, 2])
def test_quantize_blocks_compiles(bits, one_chip, leaf_blocks):
    x = _sds((leaf_blocks, BLOCK), jnp.float32, one_chip)
    text = _compiled_text(
        lambda t: qk.quantize_blocks(t, bits, interpret=False), x)
    assert "tpu_custom_call" in text


def test_dequantize_blocks_compiles(one_chip, leaf_blocks):
    codes = _sds((leaf_blocks, BLOCK), jnp.int8, one_chip)
    scales = _sds((leaf_blocks,), jnp.float32, one_chip)
    text = _compiled_text(
        lambda c, s: qk.dequantize_blocks(c, s, interpret=False),
        codes, scales)
    assert "tpu_custom_call" in text


def test_quantize_topk_blocks_compiles(one_chip, leaf_blocks):
    x = _sds((leaf_blocks, BLOCK), jnp.float32, one_chip)
    text = _compiled_text(
        lambda t: wk.quantize_topk_blocks(t, 2, TOPK, interpret=False), x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bits", [8, 2])
def test_cohort_wire_kernels_compile(bits, one_chip, cohort):
    """Quantize and dequantize over a whole cohort's packed blocks,
    about 115k blocks of 256: one launch each per knob group."""
    _, rows = cohort
    assert rows % qk.ROWS_PER_TILE == 0 and 110_000 < rows < 120_000
    x = _sds((rows, BLOCK), jnp.float32, one_chip)
    text = _compiled_text(
        lambda t: qk.quantize_blocks(t, bits, interpret=False), x)
    assert "tpu_custom_call" in text
    codes = _sds((rows, BLOCK), jnp.int8, one_chip)
    scales = _sds((rows,), jnp.float32, one_chip)
    text = _compiled_text(
        lambda c, s: qk.dequantize_blocks(c, s, interpret=False),
        codes, scales)
    assert "tpu_custom_call" in text


def test_cohort_pack_and_unpack_compile(one_chip, cohort):
    """The programs around the kernels: pack the stacked deltas, and
    unpack the round-tripped blocks into the masked per-client trees."""
    from repro.core import compression
    stacked, rows = cohort
    packed = compression.pack_stacked.lower(stacked).compile()
    assert packed.out_info.shape == (rows, BLOCK)
    mask = jax.tree.map(lambda l: _sds((), jnp.float32, one_chip), stacked)
    leaves, treedef = jax.tree.flatten(stacked)
    unpack = compression._unpack.lower(
        _sds((rows, BLOCK), jnp.float32, one_chip), mask, treedef=treedef,
        shapes=tuple(l.shape for l in leaves), block=BLOCK).compile()
    assert len(unpack.out_info) == COHORT


def test_masked_sum_limbs_compiles(one_chip, leaf_blocks):
    n = -(-leaf_blocks * BLOCK // wk.LIMB_TILE) * wk.LIMB_TILE
    hi = _sds((COHORT, n), jnp.uint32, one_chip)
    lo = _sds((COHORT, n), jnp.uint32, one_chip)
    text = _compiled_text(
        lambda h, l: wk.masked_sum_limbs(h, l, interpret=False), hi, lo)
    assert "tpu_custom_call" in text


def test_batched_round_compiles(one_chip, paper):
    """One batched round at the paper's FedAvg operating point: the
    6-client cohort, s=40 local steps of b=32 x seq_len=32, all layers
    trainable."""
    from repro.core.client import ClientRunner
    from repro.fl.executor import BatchedExecutor
    model, fl, params = paper
    runner = ClientRunner(model, fl, data=None, resources=None)
    mask, _ = runner.mask_for(params, fl.k_base)
    on_chip = lambda t: jax.tree.map(
        lambda l: _sds(l.shape, l.dtype, one_chip), t)
    batch = _sds((fl.clients_per_round, fl.s_base, 1, fl.b_base,
                  fl.seq_len), jnp.int32, one_chip)
    compiled = BatchedExecutor(runner)._batched.lower(
        on_chip(params), on_chip(mask),
        {"tokens": batch, "targets": batch}).compile()
    deltas, losses = compiled.out_info
    assert losses.shape == (fl.clients_per_round,)
    assert jax.tree.structure(deltas) == jax.tree.structure(params)
