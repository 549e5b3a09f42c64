"""The compiled aggregation fold (``repro.core.aggregation``): it matches
a float64 weighted mean, is exact for a single report at weight 1, and
compiles once for every cohort size. Its ``aggregate_calls`` counter (C
fold steps and one server update a round) is checked in a traced engine
run by ``tests/test_spans.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.runtime import RecompileWatcher, no_transfers
from repro.core import aggregation


def _deltas(n: int, seed: int = 0):
    """``n`` deltas with an f32 matrix, an f32 vector and a bf16 leaf;
    entries positive so a relative tolerance is meaningful."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        out.append({
            "w": jnp.asarray(rng.uniform(0.5, 1.5, (8, 16)), jnp.float32),
            "b": jnp.asarray(rng.uniform(0.5, 1.5, (16,)), jnp.float32),
            "h": jnp.asarray(rng.uniform(0.5, 1.5, (4, 8)), jnp.bfloat16)})
    return out


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("n", [1, 2, 6])
def test_fold_matches_float64_weighted_mean(n, weighted):
    deltas = _deltas(n, seed=n)
    weights = [float(i + 1) for i in range(n)] if weighted else None
    out = aggregation.aggregate(deltas, weights)
    w = np.asarray(weights if weighted else [1.0] * n, np.float64)
    w = w / w.sum()
    for k in ("w", "b", "h"):
        assert out[k].dtype == jnp.float32
        ref = sum(wi * np.asarray(d[k].astype(jnp.float32), np.float64)
                  for wi, d in zip(w, deltas))
        np.testing.assert_allclose(np.asarray(out[k], np.float64), ref,
                                   rtol=1e-6)


def test_single_report_at_weight_one_is_exact():
    (delta,) = _deltas(1, seed=7)
    delta = {k: v for k, v in delta.items() if v.dtype == jnp.float32}
    out = aggregation.aggregate([delta], [3.0])
    for k, v in delta.items():
        assert np.array_equal(np.asarray(out[k]), np.asarray(v))


def test_warm_fold_compiles_nothing_for_any_cohort_size():
    """The fold step is one program for every C: warmed at C = 2, folds
    over 1 to 6 reports and the server update compile nothing and make
    no implicit transfer."""
    rng = np.random.default_rng(3)
    deltas = [{"u": jnp.asarray(rng.normal(size=(24, 5)), jnp.float32),
               "v": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
              for _ in range(6)]
    params = jax.tree.map(jnp.ones_like, deltas[0])
    aggregation.apply_delta(params, aggregation.aggregate(deltas[:2]))
    watch = RecompileWatcher()
    with watch, no_transfers():
        for n in range(1, 7):
            delta = aggregation.aggregate(deltas[:n], [1.0 + i
                                                       for i in range(n)])
            params = aggregation.apply_delta(params, delta)
        jax.block_until_ready(params)
        assert watch.mark("steady") == 0
