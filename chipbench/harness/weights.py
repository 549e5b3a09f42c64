"""Seeded weights, made on the device in one jitted call.

The tree's structure and types come from the system's own
``jax.eval_shape`` of its model (shapes only); the values come from the
seed alone, by leaf name: LayerNorm scales 1, biases 0, embedding and
position tables N(0, 0.02), every other matrix N(0, 1/fan_in) with
``fan_in`` the second-to-last axis. The check hands the same call's
output to the reference, so both start from the same weights.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ZERO = ("bias", "b_up", "b_down", "bq", "bk", "bv")
TABLES = ("embed", "pos_embed")


def key_of(seed: int):
    """A PRNG key for any whole-number seed, including ones wider than
    32 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def maker(shapes):
    """-> jitted ``seed_key -> weights`` for a tree of ShapeDtypeStructs."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = []
        for i, (path, sds) in enumerate(flat):
            name = _name(path)
            if name == "scale":
                v = jnp.ones(sds.shape, jnp.float32)
            elif name in ZERO:
                v = jnp.zeros(sds.shape, jnp.float32)
            else:
                std = (0.02 if name in TABLES
                       else 1.0 / math.sqrt(sds.shape[-2]))
                v = jax.random.normal(jax.random.fold_in(key, i), sds.shape,
                                      jnp.float32) * std
            leaves.append(v.astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)


def make_weights(shapes, seed: int):
    return maker(shapes)(key_of(seed))
