"""Client executors: how one round's LocalTrain workload actually runs.

``SequentialExecutor`` keeps the seed semantics: a Python loop over
clients driving ``ClientRunner.train_client`` (one jitted grad step per
microbatch, one host sync per client).

``BatchedExecutor`` groups clients that received the same knobs (same
shapes), pre-samples every microbatch, and runs the whole group's local
training as ONE jitted call: ``vmap`` over clients of a
``lax.scan`` over local steps of a ``lax.scan`` over grad-accum
microbatches. That removes the per-client Python dispatch and every
intermediate host sync. The group's wire round trip then runs over the
stacked deltas on the device too: one pack, one quantize and one
dequantize for the whole group, and one program that hands back the
per-client trees. Under the profiler its host work is spans inside the
engine's ``execute`` (``repro.spans``), each once per knob group:
``stage``, ``local_train_wait``, ``wire`` (pack and round trip; none
at q=0), ``unstack`` (per-client trees and freeze mask) and
``wire_bytes``; ``localtrain_calls`` counts the launches.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client import (ClientResult, ClientRunner,
                               _masked_wire_mb, apply_masked_update)
from repro.core.policy import Knobs
from repro import spans
from repro.fl.device import ClientInfo

Assignment = Tuple[ClientInfo, Knobs]


class ClientExecutor:
    """Protocol: run one round of LocalTrain for the sampled clients."""

    def run_round(self, params, assignments: Sequence[Assignment]
                  ) -> List[ClientResult]:
        raise NotImplementedError


class SequentialExecutor(ClientExecutor):
    """Seed semantics: clients one after another through the shared
    jitted step cache."""

    def __init__(self, runner: ClientRunner):
        self.runner = runner

    def run_round(self, params, assignments):
        return [self.runner.train_client(ci.client_id, params, kn)
                for ci, kn in assignments]


class BatchedExecutor(ClientExecutor):
    """Same-knob clients stacked and trained in a single jitted
    vmap-of-scan call. Numerically matches the sequential path up to
    float reassociation (same batches, same update math)."""

    def __init__(self, runner: ClientRunner):
        self.runner = runner
        self._batched = jax.jit(jax.vmap(self._one_client,
                                         in_axes=(None, None, 0)))

    def _one_client(self, params, mask, batches):
        """LocalTrain for one client; ``batches`` leaves are shaped
        (s, grad_accum, b, seq). vmapped over a leading client axis."""
        opt = self.runner.opt
        ga = jax.tree.leaves(batches)[0].shape[1]
        loss_fn = self.runner.model.train_loss

        def local_step(carry, micros):
            w, opt_state = carry
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), w)

            def accum(c, mb):
                gsum, lsum = c
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    w, mb)
                gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                    gsum, grads)
                return (gsum, lsum + loss.astype(jnp.float32)), None

            (gsum, lsum), _ = jax.lax.scan(
                accum, (zeros, jnp.zeros((), jnp.float32)), micros)
            grads = jax.tree.map(lambda g: g / ga, gsum)
            with jax.named_scope("masked_update"):
                w, opt_state = apply_masked_update(opt, w, opt_state, grads,
                                                   mask)
            return (w, opt_state), lsum / ga

        opt_state = opt.init(params)
        (w, _), losses = jax.lax.scan(local_step, (params, opt_state), batches)
        delta = jax.tree.map(lambda a, b_: a.astype(jnp.float32)
                             - b_.astype(jnp.float32), w, params)
        return delta, jnp.mean(losses)

    def _stack_batches(self, cids: Sequence[int], kn: Knobs):
        """Pre-sample every microbatch for the group, in the same
        (client, step, micro) order the sequential path draws them, and
        stack to leaves of shape (C, s, grad_accum, b, seq)."""
        per_key: Dict[str, list] = {}
        for cid in cids:
            rows: Dict[str, list] = {}
            for _ in range(kn.s):
                for _ in range(kn.grad_accum):
                    batch = self.runner.data.batch(cid, kn.b,
                                                   self.runner.fl.seq_len)
                    for key, arr in batch.items():
                        rows.setdefault(key, []).append(arr)
            for key, arrs in rows.items():
                stacked = np.stack(arrs).reshape(
                    (kn.s, kn.grad_accum) + arrs[0].shape)
                per_key.setdefault(key, []).append(stacked)
        return {key: jnp.asarray(np.stack(arrs))
                for key, arrs in per_key.items()}

    def run_round(self, params, assignments):
        # group client indices by knobs; same knobs => same shapes
        groups: Dict[Knobs, List[int]] = {}
        for idx, (_, kn) in enumerate(assignments):
            groups.setdefault(kn, []).append(idx)

        topk = self.runner.fl.wire_topk
        results: List[ClientResult] = [None] * len(assignments)  # type: ignore
        for kn, idxs in groups.items():
            cids = [assignments[i][0].client_id for i in idxs]
            mask, active = self.runner.mask_for(params, kn.k)
            with spans.span("stage"):
                batches = self._stack_batches(cids, kn)
            spans.count("localtrain_calls")
            deltas, losses = self._batched(params, mask, batches)
            with spans.span("local_train_wait"):
                losses = np.asarray(losses)
            shipped = _compress(deltas, mask, kn.q, topk=topk)
            del deltas
            with spans.span("wire_bytes"):
                # mask, q and topk are the group's: one count serves all
                wire_mb = _masked_wire_mb(shipped[0], mask, kn.q, topk=topk)
            for row, i in enumerate(idxs):
                results[i] = ClientResult(
                    client_id=cids[row], delta=shipped[row],
                    params_active=active, train_loss=float(losses[row]),
                    wire_mb_actual=wire_mb)
        return results


def _compress(raw, mask, q: int, topk=None):
    """Wire round trip of one knob group's stacked fp32 deltas (the
    batched path computes w - params on device; only the q/topk knobs
    remain) -> the C per-client trees as shipped, freeze-masked.
    Consumes ``raw`` at q > 0 (``compress_decompress_stacked``)."""
    from repro.core import compression
    if q == 0:
        with spans.span("unstack"):
            return compression.unstack_masked(raw, mask)
    like = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                        raw)
    with spans.span("wire"):
        blocks = compression.compress_decompress_stacked(raw, q, topk=topk)
    with spans.span("unstack"):
        return compression.unpack_stacked(blocks, mask, like)


# ---------------------------------------------------------------------------
# trace-analysis entry points (repro.analysis.trace)
# ---------------------------------------------------------------------------


def _batched_round_build():
    from repro.analysis.trace.registry import (TRACE_MODEL,
                                               charlm_trace_setup)
    runner, params, _ = charlm_trace_setup(b=4)
    ex = BatchedExecutor(runner)
    mask, _ = runner.mask_for(params, 0)
    seq = TRACE_MODEL["seq_len"]
    batches = {
        "tokens": jax.ShapeDtypeStruct((2, 2, 1, 4, seq), jnp.int32),
        "targets": jax.ShapeDtypeStruct((2, 2, 1, 4, seq), jnp.int32),
    }
    return ex._batched, (params, mask, batches)


def trace_entry_points() -> List[object]:
    """Declared traceable surface: the one jitted call a batched round
    makes (vmap over clients of scan over steps of scan over micros)."""
    from repro.analysis.trace.registry import EntryPoint
    return [EntryPoint(
        name="fl.executor_batched_round", path="src/repro/fl/executor.py",
        line=65, build=_batched_round_build,
        note="vmap(C=2) of scan(s=2) of scan(ga=1), b=4")]


EXECUTORS = {
    "sequential": SequentialExecutor,
    "batched": BatchedExecutor,
}


def make_executor(name: str, runner: ClientRunner) -> ClientExecutor:
    try:
        return EXECUTORS[name](runner)
    except KeyError:
        raise ValueError(f"unknown executor {name!r}; "
                         f"options: {sorted(EXECUTORS)}") from None
