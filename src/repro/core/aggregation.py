"""Server-side delta combination (Algorithm 1, line 15).

The paper aggregates the *participating* clients' deltas with a plain
mean: w <- w + (1/|S_t|) sum_i dw_i. Passing ``weights`` gives the
|D_i|-weighted FedAvg variant (Eq. 1).

Weight normalization lives in one place — ``normalize_weights`` — so
every caller (``FedAvg(weighted=True)``, ``ServerOpt``'s inner combine,
the ``repro.fl.aggregator`` policies, dropout renormalization over
survivors) shares the same renormalization semantics: whatever subset
of clients is present, their weights are rescaled to sum to 1.

The combine is a left fold over the reports in the order given, each
step one compiled program over the whole tree: ``d_0 * w_0`` first,
then ``acc + d_i * w_i`` once per further report. The step is the same
program for any cohort size, so dropout, late arrivals and partial
buffers recompile nothing, and no ``(C, ...)`` stack of the cohort is
ever built (server memory stays O(P)). XLA may contract ``acc + d * w``
into a fused multiply-add, so the result can differ from an unfused
eager fold by one ulp per leaf element; it is deterministic for a given
backend and report order.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans


def normalize_weights(weights: Optional[Sequence[float]], n: int
                      ) -> List[float]:
    """The shared renormalization path: ``None`` -> uniform 1/n; else
    weights rescaled to sum to 1 over the clients that are present."""
    assert n > 0
    if weights is None:
        return [1.0 / n] * n
    assert len(weights) == n
    tot = sum(weights)
    assert tot > 0, "aggregation weights must have positive mass"
    return [x / tot for x in weights]


@jax.jit
def _first_term(delta, w):
    return jax.tree.map(lambda d: d.astype(jnp.float32) * w, delta)


@functools.partial(jax.jit, donate_argnums=0)
def _fold_term(acc, delta, w):
    # ``acc`` is always this module's own intermediate, so its buffers
    # are reused for the new accumulator
    return jax.tree.map(lambda a, d: a + d.astype(jnp.float32) * w,
                        acc, delta)


def aggregate(deltas: Sequence, weights: Optional[List[float]] = None):
    n = len(deltas)
    assert n > 0
    w = normalize_weights(weights, n)
    # The programs below allocate their outputs as they are queued.
    # Waiting for the reports first lets whatever produced them (a
    # cohort's stacked deltas, say) be freed before that, so the fold's
    # outputs never stand beside the producer's inputs.
    jax.block_until_ready(deltas)
    # One explicit transfer of the 0-d f32 weights: handing Python or
    # NumPy scalars to the programs would be an implicit host->device
    # transfer per call, which the steady-state transfer-guard pin
    # (repro.analysis.runtime) disallows.
    w_dev = jax.device_put([np.float32(x) for x in w])
    acc = _first_term(deltas[0], w_dev[0])
    spans.count("aggregate_calls")
    for delta, wi in zip(deltas[1:], w_dev[1:]):
        acc = _fold_term(acc, delta, wi)
        spans.count("aggregate_calls")
    return acc


@jax.jit
def _apply(params, delta):
    return jax.tree.map(lambda p, d: (p.astype(jnp.float32)
                                      + d.astype(jnp.float32)).astype(p.dtype),
                        params, delta)


def apply_delta(params, delta):
    """The server update ``params + delta`` in one program, each leaf
    computed in f32 and returned in its parameter's dtype. ``params``
    is not donated: the engine and its callbacks may still hold it."""
    out = _apply(params, delta)
    spans.count("aggregate_calls")
    return out
